"""Fused ternary {-1, 0, +1} matvec Pallas kernel (T-MAC's ``tl2`` layout).

``y = x @ Ŵ`` with ``Ŵ = alpha ∘ t``, ``t ∈ {-1, 0, +1}``, consumed
**directly in packed form**: the ternary codes are stored as TWO bit planes —
plane 0 the *sign* bit (1 → +1), plane 1 the *mask* bit (1 → nonzero) — in
the shared physical layout (``core/packing.py``: 8 codes per byte along k,
LSB-first), plus ONE per-(group, column) magnitude plane ``alpha``. Each grid
step unpacks a ``(2, bk/8, bo)`` byte block with VPU shift/mask ops,
reconstructs ``t = (2·sign − 1) · mask`` in registers, applies the group
magnitudes, and feeds the MXU — the decoded block never exists in HBM (the
paper's "no dequantization overhead" requirement, §III, at 2 stored bits +
one scale per group: the sub-2-bit regime T-MAC serves BitNet-class models
in at memory-bandwidth speed).

Ternary is *masked BCQ*: ``t = 0.5·b1 + 0.5·b2`` with ``b1 = sign | ~mask``
and ``b2 = sign & mask`` — the equivalence ``core/formats.py::TernaryFormat``
exploits to hand self-speculation a nested 1-plane BCQ draft (``truncate``).
This kernel is the direct 2-plane decode; the drafts run through ``bcq_mm``.

Grid, accumulator and dimension semantics mirror ``bcq_mm.py``: a float32
VMEM ``scratch_shapes`` accumulator persists across the sequential k steps,
the HBM output block is written once on the last k step, and the o dimension
is ``parallel`` while k is ``arbitrary`` (DESIGN.md §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 512
DEFAULT_BLOCK_O = 256
PLANES = 2  # sign + mask — fixed; the policy's q is not a free axis here


def vmem_bytes(*, B: int, block_k: int, block_o: int, q: int, g: int) -> int:
    """Per-grid-step VMEM estimate (``kernels/introspect.py``): bcq_mm's
    pipeline shape with 2 packed planes, the single alpha plane, and the
    unpacked sign/mask bits + decoded ternary block the body materialises.
    ``q`` is accepted for the estimator protocol but the layout pins it to 2
    packed planes / 1 scale plane."""
    from repro.kernels.introspect import scales_block_rows

    del q  # ternary stores exactly 2 planes regardless of the policy's q
    groups = scales_block_rows(block_k, g)
    io = 2 * (
        B * block_k * 4  # x block, f32
        + PLANES * (block_k // 8) * block_o  # packed sign+mask planes, uint8
        + 1 * groups * block_o * 4  # alpha block (<= f32)
        + B * block_o * 4  # out block, f32
    )
    body = (
        PLANES * block_k * block_o * 4  # unpacked sign/mask bits
        + 2 * block_k * block_o * 4  # decoded t + scaled w_eff
        + B * block_o * 4  # acc scratch
    )
    return io + body


def _decode_ternary_block(packed: jax.Array, compute_dtype) -> jax.Array:
    """uint8 (2, bk/8, bo) sign+mask planes → t ∈ {-1, 0, +1} (bk, bo)."""
    _, kc, bo = packed.shape
    # widen to int32 first: Mosaic has no uint8 -> float cast
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 8, 1), 2)
    bits = (packed.astype(jnp.int32)[:, :, None, :] >> shifts) & 1  # (2, kc, 8, bo)
    planes = bits.reshape(PLANES, kc * 8, bo).astype(compute_dtype)
    sign = 2.0 * planes[0] - 1.0
    return sign * planes[1]  # mask=0 zeroes the code


def _ternary_mm_kernel(
    x_ref, packed_ref, scales_ref, out_ref, acc_ref, *, g: int, bk: int, compute_dtype
):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = _decode_ternary_block(packed_ref[...], compute_dtype)  # (bk, bo)
    alpha = scales_ref[...].astype(compute_dtype)[0]  # (bk//g or 1, bo)
    bk_, bo = t.shape

    if g <= bk:
        # alpha block carries bk//g groups — expand each over its g rows
        w_eff = (t.reshape(bk // g, g, bo) * alpha[:, None, :]).reshape(bk, bo)
    else:
        # whole k-block lies inside one scale group: alpha rows are (1, bo)
        w_eff = t * alpha

    x = x_ref[...].astype(compute_dtype)
    acc_ref[...] += jnp.dot(x, w_eff, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def ternary_mm_call(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
    *,
    g: int,
    block_k: int,
    block_o: int,
    interpret: bool,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Unjitted pallas_call core (fused multi-projection dispatch reuses it
    via ``ops.qmatmul`` — the fused layout is plain output-dim concatenation)."""
    from repro.kernels.bcq_mm import _validate_tiling

    B, k = x.shape
    planes, kc, o = packed.shape
    if planes != PLANES:
        raise ValueError(
            f"ternary packed tensor must carry exactly {PLANES} planes "
            f"(sign + mask), got {planes}"
        )
    _validate_tiling(k, o, kc, g, block_k, block_o)

    grid = (o // block_o, k // block_k)
    if g <= block_k:
        scales_spec = pl.BlockSpec(
            (1, block_k // g, block_o), lambda io, ik: (0, ik, io)
        )
    else:
        scales_spec = pl.BlockSpec(
            (1, 1, block_o), lambda io, ik: (0, ik // (g // block_k), io)
        )

    kernel = functools.partial(
        _ternary_mm_kernel, g=g, bk=block_k, compute_dtype=compute_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, block_k), lambda io, ik: (0, ik)),
            pl.BlockSpec((PLANES, block_k // 8, block_o), lambda io, ik: (0, ik, io)),
            scales_spec,
        ],
        out_specs=pl.BlockSpec((B, block_o), lambda io, ik: (0, io)),
        out_shape=jax.ShapeDtypeStruct((B, o), jnp.float32),
        scratch_shapes=[pltpu.VMEM((B, block_o), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, packed, scales)


@functools.partial(
    jax.jit, static_argnames=("g", "block_k", "block_o", "interpret", "compute_dtype")
)
def ternary_mm(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
    *,
    g: int,
    block_k: int = DEFAULT_BLOCK_K,
    block_o: int = DEFAULT_BLOCK_O,
    interpret: bool = False,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """x (B, k) @ ternary[(2, k/8, o) sign+mask planes, (1, k/g, o) alpha] → (B, o) f32.

    Constraints are :func:`repro.kernels.bcq_mm.bcq_mm`'s: k % block_k == 0,
    o % block_o == 0, g % 8 == 0 and (block_k % g == 0 or g % block_k == 0).
    ``ops.qmatmul`` pads inputs so callers never see these.
    """
    return ternary_mm_call(
        x,
        packed,
        scales,
        g=g,
        block_k=block_k,
        block_o=block_o,
        interpret=interpret,
        compute_dtype=compute_dtype,
    )


from repro.kernels.introspect import register_vmem_estimator  # noqa: E402

register_vmem_estimator("ternary_mm", vmem_bytes)
