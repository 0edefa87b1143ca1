"""Arbitrary-codebook matvec Pallas kernel (FLUTE-style LUT generalization).

``y = x @ Ŵ`` with ``Ŵ[r, c] = T[code[r, c], group(r), c]`` consumed
**directly in packed form**: ``code`` are unsigned ``q``-bit centroid indices
stored as ``q`` bit planes (the shared physical layout —
``core/packing.py::pack_codes``, identical bytes to the uniform int-q
planes) and ``T`` is a per-(group, column) table of ``2^q`` learned scalar
centroids (k-means, or the fixed NF4 grid). This is the paper's LUT
mechanism generalized exactly as FLUTE does: where ``lutgemm.py``'s VMEM
table holds the ``2^mu`` partial dots of activation chunks against *sign
patterns*, here the table is the codebook itself — the index planes are the
LUT keys, a vectorised ``take_along_axis`` is the retrieve, and the MXU
contracts the decoded block against the activations. The centroid table
rides the scales BlockSpec into VMEM (``2^q · groups · bo`` floats per grid
step — priced by ``vmem_bytes`` below and budget-gated by
``kernels/introspect.py``), so the dense weight never exists in HBM.

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
DEFAULT_BLOCK_O = 128


def vmem_bytes(*, B: int, block_k: int, block_o: int, q: int, g: int) -> int:
    """Per-grid-step VMEM estimate (``kernels/introspect.py``): bcq_mm's
    input/output pipeline with the ``(2^q, groups, bo)`` centroid table in
    place of scale planes, plus the unpacked index planes, the reassembled
    codes and the gathered weight block the body materialises — the
    ``2^q``-proportional table term is what caps ``block_o`` differently
    from the sign-plane kernels (the autotuner rationale)."""
    from repro.kernels.introspect import scales_block_rows

    groups = scales_block_rows(block_k, g)
    io = 2 * (
        B * block_k * 4  # x block, f32
        + q * (block_k // 8) * block_o  # packed index planes, uint8
        + (1 << q) * groups * block_o * 4  # centroid table block (<= f32)
        + B * block_o * 4  # out block, f32
    )
    body = (
        q * block_k * block_o * 4  # unpacked index bit planes
        + block_k * block_o * 4  # reassembled int32 codes
        + block_k * block_o * 4  # gathered (decoded) weight block
        + B * block_o * 4  # acc scratch
    )
    return io + body


def _unpack_indices_block(packed: jax.Array) -> jax.Array:
    """uint8 (q, bk/8, bo) bit planes → int32 centroid indices (bk, bo)."""
    q, kc, bo = packed.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint8, (1, 1, 8, 1), 2)
    bits = (packed[:, :, None, :] >> shifts) & jnp.uint8(1)  # (q, kc, 8, bo)
    planes = bits.reshape(q, kc * 8, bo).astype(jnp.int32)
    # q is static (<= 8): unroll the weighted plane sum with Python int
    # weights 2^i — Pallas kernels may not capture array constants
    codes = planes[0]
    for i in range(1, q):
        codes = codes + planes[i] * (1 << i)
    return codes  # (bk, bo)


def _codebook_mm_kernel(
    x_ref, packed_ref, scales_ref, out_ref, acc_ref, *, g: int, bk: int, compute_dtype
):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_indices_block(packed_ref[...])  # (bk, bo) int32
    table = scales_ref[...].astype(compute_dtype)  # (2^q, bk//g or 1, bo)
    n_cent, gb, bo = table.shape

    # LUT retrieve: per (group, column) codebook, per-element index — group
    # the code rows, move the centroid axis inboard, gather along it. gb is
    # the number of whole scale groups this k-block spans (>= 1: when
    # g > block_k the whole block lies inside one group).
    rows_per_group = bk // gb
    cent = jnp.swapaxes(table, 0, 1)  # (gb, 2^q, bo)
    idx = codes.reshape(gb, rows_per_group, bo)
    w_eff = jnp.take_along_axis(cent, idx, axis=1).reshape(bk, bo)

    x = x_ref[...].astype(compute_dtype)
    acc_ref[...] += jnp.dot(x, w_eff, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def codebook_mm_call(
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
    n_cent = scales.shape[0]
    if n_cent != (1 << q):
        raise ValueError(
            f"codebook table carries {n_cent} centroids but the packed tensor "
            f"has q={q} index planes (expected {1 << q})"
        )
    _validate_tiling(k, o, kc, g, block_k, block_o)

    grid = (o // block_o, k // block_k)
    if g <= block_k:
        scales_spec = pl.BlockSpec(
            (n_cent, block_k // g, block_o), lambda io, ik: (0, ik, io)
        )
    else:
        scales_spec = pl.BlockSpec(
            (n_cent, 1, block_o), lambda io, ik: (0, ik // (g // block_k), io)
        )

    kernel = functools.partial(
        _codebook_mm_kernel, g=g, bk=block_k, compute_dtype=compute_dtype
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
def codebook_mm(
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
    """x (B, k) @ codebook[(q, k/8, o) index planes, (2^q, k/g, o) centroids] → (B, o) f32.

    Constraints are :func:`repro.kernels.bcq_mm.bcq_mm`'s: k % block_k == 0,
    o % block_o == 0, g % 8 == 0 and (block_k % g == 0 or g % block_k == 0).
    ``ops.qmatmul`` pads inputs so callers never see these.
    """
    return codebook_mm_call(
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

register_vmem_estimator("codebook_mm", vmem_bytes)
