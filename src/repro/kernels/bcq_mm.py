"""Fused BCQ matmul Pallas kernel — the TPU-native LUT-GEMM variant.

``y = x @ Ŵ`` with ``Ŵ = Σ_i alpha_i ∘ b_i`` consumed **directly in packed
form**: each grid step unpacks a ``(q, bk/8, bo)`` byte block to ±1 signs with
VPU shift/mask ops, applies group scales in VMEM registers, and feeds the MXU —
the dequantized block never exists in HBM (paper's "no dequantization overhead"
requirement, §III).

Why this beats a literal LUT port on TPU (DESIGN.md §2): the paper's LUT
replaces *bit-level arithmetic* that GPUs do poorly; TPUs unpack bits for free
on the VPU while a per-byte LUT *gather* is the expensive part. Both are
implemented (see ``lutgemm.py``) and compared in benchmarks.

Grid: ``(o_blocks, k_blocks)`` with k fastest. Partial sums live in a float32
VMEM ``scratch_shapes`` accumulator that persists across the sequential k
steps; the HBM output block is written exactly once, on the last k step
(DESIGN.md §2 — the deterministic replacement for the paper's atomicAdd,
without the ``out_ref`` read-modify-write HBM round-trip per k step that the
first version paid). The o dimension is declared ``parallel`` so Mosaic may
split output blocks across cores; k is ``arbitrary`` (sequential, carries the
accumulator).
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
    """Per-grid-step VMEM estimate for this kernel's BlockSpecs (see
    ``kernels/introspect.py``): double-buffered x/packed/scales/out blocks,
    the f32 accumulator scratch, and the unpacked sign planes + effective
    weight block the body materialises."""
    from repro.kernels.introspect import scales_block_rows

    groups = scales_block_rows(block_k, g)
    io = 2 * (
        B * block_k * 4  # x block, f32
        + q * (block_k // 8) * block_o  # packed block, uint8
        + q * groups * block_o * 4  # scales block (<= f32)
        + B * block_o * 4  # out block, f32
    )
    body = (
        q * block_k * block_o * 4  # unpacked ±1 signs
        + block_k * block_o * 4  # w_eff
        + B * block_o * 4  # acc scratch
    )
    return io + body


def _unpack_block(packed: jax.Array, compute_dtype) -> jax.Array:
    """uint8 (q, bk/8, bo) → ±1 (q, bk, bo) in compute_dtype (VPU shift/mask).

    The bytes are widened to int32 first: Mosaic has no uint8 → float cast."""
    q, kc, bo = packed.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 8, 1), 2)
    bits = (packed.astype(jnp.int32)[:, :, None, :] >> shifts) & 1  # (q, kc, 8, bo)
    signs = 2.0 * bits.astype(compute_dtype) - 1.0
    return signs.reshape(q, kc * 8, bo)


def _bcq_mm_kernel(
    x_ref, packed_ref, scales_ref, out_ref, acc_ref, *, g: int, bk: int, compute_dtype
):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    signs = _unpack_block(packed_ref[...], compute_dtype)  # (q, bk, bo)
    scales = scales_ref[...].astype(compute_dtype)  # (q, bk//g or 1, bo)
    q, _, bo = signs.shape

    if g <= bk:
        # scales block carries bk//g groups — expand each over its g rows
        w = (signs.reshape(q, bk // g, g, bo) * scales[:, :, None, :]).sum(0)
        w_eff = w.reshape(bk, bo)
    else:
        # whole k-block lies inside one scale group: scales block is (q, 1, bo)
        w_eff = (signs * scales).sum(0)

    x = x_ref[...].astype(compute_dtype)
    acc_ref[...] += jnp.dot(x, w_eff, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _validate_tiling(k, o, kc, g, block_k, block_o, mu=8):
    """Shared tiling constraints for the BCQ Pallas kernels."""
    if kc * mu != k:
        raise ValueError(f"packed k dim {kc}*{mu} != x k dim {k}")
    if k % block_k or o % block_o:
        raise ValueError(f"(k={k}, o={o}) must be divisible by ({block_k}, {block_o})")
    if g % mu or not (block_k % g == 0 or g % block_k == 0):
        raise ValueError(f"g={g} incompatible with block_k={block_k}")


def bcq_mm_call(
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
    """Unjitted pallas_call core, shared with the fused multi-projection
    wrapper (``bcq_mm_fused.py``) so both dispatch the identical kernel."""
    B, k = x.shape
    q, kc, o = packed.shape
    _validate_tiling(k, o, kc, g, block_k, block_o)

    grid = (o // block_o, k // block_k)
    if g <= block_k:
        scales_spec = pl.BlockSpec(
            (q, block_k // g, block_o), lambda io, ik: (0, ik, io)
        )
    else:
        scales_spec = pl.BlockSpec(
            (q, 1, block_o), lambda io, ik: (0, ik // (g // block_k), io)
        )

    kernel = functools.partial(
        _bcq_mm_kernel, g=g, bk=block_k, compute_dtype=compute_dtype
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
def bcq_mm(
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
    """x (B, k) @ BCQ[(q, k/8, o) packed, (q, k/g, o) scales] → (B, o) f32.

    Constraints (enforced): k % block_k == 0, o % block_o == 0, g % 8 == 0 and
    (block_k % g == 0 or g % block_k == 0). ``ops.quantized_matmul`` pads inputs
    so callers never see these.
    """
    return bcq_mm_call(
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

register_vmem_estimator("bcq_mm", vmem_bytes)
