"""Fused group-wise uniform int-q matmul Pallas kernel (FineQuant-style).

``y = x @ Ŵ`` with ``Ŵ = s ∘ C + z`` consumed **directly in packed form**:
``C`` are unsigned ``q``-bit magnitude codes stored as ``q`` bit planes (the
same physical layout as the BCQ sign planes — ``core/packing.py::pack_codes``)
and ``(s, z)`` are per-(group, column) affine scale/zero parameters. Each grid
step unpacks a ``(q, bk/8, bo)`` byte block to bits with VPU shift/mask ops,
reassembles the codes as ``Σ_i 2^i·bit_i``, applies the group affine in VMEM
registers, and feeds the MXU — the dequantized block never exists in HBM
(the same "no dequantization overhead" requirement the BCQ kernel satisfies,
paper §III; contrast ``kernels/dequant_mm.py``, the explicit baseline).

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


def vmem_bytes(*, B: int, block_k: int, block_o: int, q: int, g: int) -> int:
    """Per-grid-step VMEM estimate (``kernels/introspect.py``): bcq_mm's
    pipeline shape with the (2, groups, bo) affine scale/zero block and the
    unpacked bit planes + reassembled code block the body materialises."""
    from repro.kernels.introspect import scales_block_rows

    groups = scales_block_rows(block_k, g)
    io = 2 * (
        B * block_k * 4  # x block, f32
        + q * (block_k // 8) * block_o  # packed bit planes, uint8
        + 2 * groups * block_o * 4  # (scale, zero) block (<= f32)
        + B * block_o * 4  # out block, f32
    )
    body = (
        q * block_k * block_o * 4  # unpacked bit planes
        + 2 * block_k * block_o * 4  # reassembled codes + affine w_eff
        + B * block_o * 4  # acc scratch
    )
    return io + body


def _unpack_codes_block(packed: jax.Array, compute_dtype) -> jax.Array:
    """uint8 (q, bk/8, bo) bit planes → codes (bk, bo) in compute_dtype."""
    q, kc, bo = packed.shape
    # widen to int32 first: Mosaic has no uint8 -> float cast
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 8, 1), 2)
    bits = (packed.astype(jnp.int32)[:, :, None, :] >> shifts) & 1  # (q, kc, 8, bo)
    planes = bits.reshape(q, kc * 8, bo).astype(compute_dtype)
    # q is static (<= 8): unroll the weighted plane sum with Python scalar
    # weights 2^i — Pallas kernels may not capture array constants
    codes = planes[0]
    for i in range(1, q):
        codes = codes + planes[i] * (2.0**i)
    return codes  # (bk, bo)


def _uniform_mm_kernel(
    x_ref, packed_ref, scales_ref, out_ref, acc_ref, *, g: int, bk: int, compute_dtype
):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_codes_block(packed_ref[...], compute_dtype)  # (bk, bo)
    scales = scales_ref[...].astype(compute_dtype)  # (2, bk//g or 1, bo)
    s, z = scales[0], scales[1]
    bk_, bo = codes.shape

    if g <= bk:
        # scales block carries bk//g groups — expand each over its g rows
        w = codes.reshape(bk // g, g, bo) * s[:, None, :] + z[:, None, :]
        w_eff = w.reshape(bk, bo)
    else:
        # whole k-block lies inside one scale group: s/z rows are (1, bo)
        w_eff = codes * s + z

    x = x_ref[...].astype(compute_dtype)
    acc_ref[...] += jnp.dot(x, w_eff, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def uniform_mm_call(
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
    q, kc, o = packed.shape
    _validate_tiling(k, o, kc, g, block_k, block_o)

    grid = (o // block_o, k // block_k)
    if g <= block_k:
        scales_spec = pl.BlockSpec(
            (2, block_k // g, block_o), lambda io, ik: (0, ik, io)
        )
    else:
        scales_spec = pl.BlockSpec(
            (2, 1, block_o), lambda io, ik: (0, ik // (g // block_k), io)
        )

    kernel = functools.partial(
        _uniform_mm_kernel, g=g, bk=block_k, compute_dtype=compute_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, block_k), lambda io, ik: (0, ik)),
            pl.BlockSpec((q, block_k // 8, block_o), lambda io, ik: (0, ik, io)),
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
def uniform_mm(
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
    """x (B, k) @ uniform[(q, k/8, o) bit planes, (2, k/g, o) scale/zero] → (B, o) f32.

    Constraints are :func:`repro.kernels.bcq_mm.bcq_mm`'s: k % block_k == 0,
    o % block_o == 0, g % 8 == 0 and (block_k % g == 0 or g % block_k == 0).
    ``ops.qmatmul`` pads inputs so callers never see these.
    """
    return uniform_mm_call(
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

register_vmem_estimator("uniform_mm", vmem_bytes)
