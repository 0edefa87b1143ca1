"""LUT-GEMM Pallas kernel — the paper-faithful lookup-table algorithm (§III.B-C).

Per k-block of the reduction dimension:

1. **LUT build** (paper Table II): all ``2^mu`` partial dot products of every
   length-``mu=8`` activation sub-vector against every sign pattern, computed as
   ONE small MXU matmul ``x_chunks (B·bk/8, 8) @ P^T (8, 256)`` — the TPU
   replacement for the GPU thread-block shared-memory fill. The LUT lives in
   VMEM (~16 MB/core — the paper's shared-memory capacity argument holds with
   ~2 orders of magnitude more headroom than a GPU SM's shared memory; the
   per-grid-step budget is machine-checked via ``vmem_bytes`` below and
   ``kernels/introspect.py``).
2. **Retrieve** — packed weight bytes are the LUT keys; a vectorised
   ``take_along_axis`` replaces per-thread gathers. NOTE: this lowers to a
   dynamic-gather on TPU, which is VPU-serviced (no MXU) — the reason the
   unpack-and-MXU variant (``bcq_mm.py``) usually wins on TPU; see the
   benchmark comparison and DESIGN.md §2.
3. **Scale & accumulate** — partial sums are reduced over ``g/8`` byte-chunks
   per scale group, multiplied by the group scales, summed over the q bit
   planes, and accumulated into a float32 VMEM scratch accumulator that lives
   across the sequential k steps (deterministic stand-in for the paper's
   atomicAdd); the HBM output block is written once, on the last k step
   (DESIGN.md §2). The o grid dimension is declared ``parallel``, k
   ``arbitrary``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 512
DEFAULT_BLOCK_O = 128
MU = 8


def vmem_bytes(*, B: int, block_k: int, block_o: int, q: int, g: int) -> int:
    """Per-grid-step VMEM estimate (``kernels/introspect.py``): the bcq_mm
    input/output pipeline plus this kernel's LUT table and the gathered
    per-plane partial products — the terms that cap ``block_k`` differently
    from the unpack kernel (the autotuner rationale)."""
    from repro.kernels.introspect import scales_block_rows

    C = block_k // MU
    groups = scales_block_rows(block_k, g)
    io = 2 * (
        B * block_k * 4  # x block, f32
        + q * C * block_o  # packed block (LUT keys), uint8
        + q * groups * block_o * 4  # scales block (<= f32)
        + B * block_o * 4  # out block, f32
    )
    body = (
        B * C * (1 << MU) * 4  # the LUT: all 2^mu partial dots per chunk
        + B * q * C * block_o * 4  # gathered partial products
        + B * block_o * 4  # acc scratch
    )
    return io + body


def _sign_patterns(dtype) -> jax.Array:
    """(256, 8) constant: patterns[key, j] = +1 if bit j (LSB-first) of key set."""
    keys = jax.lax.broadcasted_iota(jnp.int32, (1 << MU, MU), 0)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1 << MU, MU), 1)
    return (2 * ((keys >> shifts) & 1) - 1).astype(dtype)


def _lutgemm_kernel(x_ref, packed_ref, scales_ref, out_ref, acc_ref, *, g: int, bk: int):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    B = x_ref.shape[0]
    C = bk // MU  # byte-chunks in this k-block

    # 1. LUT build on the MXU: (B*C, mu) @ (mu, 256) → (B, C, 256)
    x = x_ref[...].astype(jnp.float32)
    lut = jnp.dot(
        x.reshape(B * C, MU), _sign_patterns(jnp.float32).T,
        preferred_element_type=jnp.float32,
    ).reshape(B, C, 1 << MU)

    # 2. retrieve partial products by byte key: (B, q, C, bo)
    keys = packed_ref[...].astype(jnp.int32)  # (q, C, bo)
    partial = jnp.take_along_axis(
        lut[:, None, :, :, None],  # (B, 1, C, 256, 1)
        keys[None, :, :, None, :],  # (1, q, C, 1,  bo)
        axis=3,
    )[:, :, :, 0, :]

    # 3. group-scale and reduce
    scales = scales_ref[...].astype(jnp.float32)  # (q, bk//g or 1, bo)
    q, _, bo = keys.shape
    if g <= bk:
        cpg = g // MU  # byte-chunks per scale group
        grouped = partial.reshape(B, q, C // cpg, cpg, bo).sum(axis=3)
        acc = jnp.einsum("bqGo,qGo->bo", grouped, scales)
    else:
        acc = jnp.einsum("bqco,qo->bo", partial, scales[:, 0, :])
    acc_ref[...] += acc

    @pl.when(ik == nk - 1)
    def _finalize():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("g", "block_k", "block_o", "interpret"))
def lutgemm(
    x: jax.Array,
    packed: jax.Array,
    scales: jax.Array,
    *,
    g: int,
    block_k: int = DEFAULT_BLOCK_K,
    block_o: int = DEFAULT_BLOCK_O,
    interpret: bool = False,
) -> jax.Array:
    """Paper-faithful LUT-GEMM: x (B, k) @ BCQ weights → (B, o) f32.

    Same contract and constraints as :func:`repro.kernels.bcq_mm.bcq_mm`,
    plus ``g % 8 == 0`` (a scale group may not split a LUT key byte).
    """
    B, k = x.shape
    q, kc, o = packed.shape
    if kc * MU != k:
        raise ValueError(f"packed k dim {kc}*{MU} != x k dim {k}")
    if k % block_k or o % block_o:
        raise ValueError(f"(k={k}, o={o}) must be divisible by ({block_k}, {block_o})")
    if g % MU or not (block_k % g == 0 or g % block_k == 0):
        raise ValueError(f"g={g} incompatible with block_k={block_k}")

    grid = (o // block_o, k // block_k)
    if g <= block_k:
        scales_spec = pl.BlockSpec(
            (q, block_k // g, block_o), lambda io, ik: (0, ik, io)
        )
    else:
        scales_spec = pl.BlockSpec(
            (q, 1, block_o), lambda io, ik: (0, ik // (g // block_k), io)
        )

    kernel = functools.partial(_lutgemm_kernel, g=g, bk=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, block_k), lambda io, ik: (0, ik)),
            pl.BlockSpec((q, block_k // MU, block_o), lambda io, ik: (0, ik, io)),
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


from repro.kernels.introspect import register_vmem_estimator  # noqa: E402

register_vmem_estimator("lutgemm", vmem_bytes)
