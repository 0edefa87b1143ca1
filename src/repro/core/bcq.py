"""Group-wise binary-coding quantization (BCQ) — paper §III.A.

A weight matrix ``W`` of shape ``(k, o)`` (used as ``y = x @ W``) is approximated

    W[k, o]  ≈  Σ_{i=1..q}  alpha_i[k // g, o] · b_i[k, o]

with ``b_i ∈ {-1, +1}`` and a scaling factor ``alpha`` shared by ``g`` consecutive
weights along the **reduction** dimension ``k`` (the paper's row dimension — its
``B`` is ``(m × n)`` acting on ``x ∈ R^n``; we store the transpose so that
activations contract on the leading weight axis, the JAX convention).

Solvers
-------
``quantize_bcq_greedy``   residual greedy (Guo et al., "network sketching"): exact
                          for q=1, good init otherwise.
``quantize_bcq``          greedy init + the alternating iterative solver the paper
                          uses (Xu et al. [20]): alternate a per-group least-squares
                          refit of ``alpha`` with an exhaustive 2^q re-selection of
                          the binary codes. Monotone non-increasing error.

Shapes
------
binary  : int8  ``(q, k, o)`` in {-1, +1}
scales  : f32   ``(q, G, o)`` with ``G = k // g``

Eq. (3) of the paper gives the space complexity these produce:
``S = O(m·n·q·(1 + 32/g))`` — see :func:`compression_ratio`.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def _check_args(k: int, q: int, g: int) -> None:
    if q < 1 or q > 8:
        raise ValueError(f"q must be in [1, 8], got {q}")
    if g < 8:
        raise ValueError(f"group size g must be >= 8 (paper §III.A), got {g}")
    if k % g != 0:
        raise ValueError(f"group size g={g} must divide the reduction dim k={k}")


def _sign(x: Array) -> Array:
    """sign with sign(0) := +1 so codes are always in {-1,+1}."""
    return jnp.where(x >= 0, 1.0, -1.0)


# ---------------------------------------------------------------------------
# Greedy solver (init)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("q", "g"))
def quantize_bcq_greedy(w: Array, q: int, g: int) -> Tuple[Array, Array]:
    """Residual-greedy BCQ. Returns ``(scales (q,G,o) f32, binary (q,k,o) int8)``.

    Per group, iteratively: ``b_i = sign(r)``, ``alpha_i = mean(|r|)`` (the optimal
    scale for that code), ``r -= alpha_i * b_i``.
    """
    k, o = w.shape
    _check_args(k, q, g)
    grouped = w.astype(jnp.float32).reshape(k // g, g, o)

    def step(r, _):
        b = _sign(r)
        alpha = jnp.mean(jnp.abs(r), axis=1)  # (G, o); == <b,r>/g for b=sign(r)
        r = r - alpha[:, None, :] * b
        return r, (alpha, b)

    _, (scales, binary) = jax.lax.scan(step, grouped, None, length=q)
    binary = binary.reshape(q, k, o).astype(jnp.int8)
    return scales, binary


# ---------------------------------------------------------------------------
# Alternating solver (paper's PTQ method, Xu et al. [20])
# ---------------------------------------------------------------------------


def _alpha_lstsq(w_g: Array, b_g: Array, ridge: float) -> Array:
    """Least-squares refit of scales given codes.

    w_g: (G, g, o) grouped weights; b_g: (q, G, g, o) codes.
    Solves per (G, o): min_alpha || w - B alpha ||^2 with B = codes as (g, q),
    via the ridge-regularised normal equations (codes can be collinear when the
    residual hits zero). Returns (q, G, o).
    """
    q = b_g.shape[0]
    btb = jnp.einsum("iago,jago->aoij", b_g, b_g)  # (G, o, q, q)
    btw = jnp.einsum("iago,ago->aoi", b_g, w_g)  # (G, o, q)
    eye = jnp.eye(q, dtype=btb.dtype)
    sol = jnp.linalg.solve(btb + ridge * eye, btw[..., None])[..., 0]  # (G, o, q)
    return jnp.moveaxis(sol, -1, 0)  # (q, G, o)


def _bits_step(w_g: Array, scales: Array) -> Array:
    """Exhaustive re-selection of codes given scales.

    Every weight independently picks the pattern c in {-1,+1}^q minimising
    (w - c·alpha)^2. 2^q candidates (q <= 8 → <= 256).

    w_g: (G, g, o); scales: (q, G, o). Returns codes (q, G, g, o).
    """
    q = scales.shape[0]
    n_pat = 1 << q
    idx = np.arange(n_pat)
    # patterns[p, i] in {-1,+1}; bit i of p (LSB-first)
    patterns = jnp.asarray(
        2.0 * ((idx[:, None] >> np.arange(q)[None, :]) & 1) - 1.0, dtype=w_g.dtype
    )  # (2^q, q)
    cand = jnp.einsum("pi,iao->pao", patterns, scales)  # (2^q, G, o)
    # distance of each weight to each candidate value: (G, g, o, 2^q)
    dist = jnp.abs(w_g[..., None] - jnp.moveaxis(cand, 0, -1)[:, None, :, :])
    best = jnp.argmin(dist, axis=-1)  # (G, g, o) int
    # plane i is bit i of the winning pattern, built plane-major: gathering
    # patterns[best] yields q minor-most, a layout that XLA then propagates
    # into the (q, k, o) code buffers (~40x padding on TPU)
    return jnp.stack(
        [2.0 * ((best >> i) & 1).astype(w_g.dtype) - 1.0 for i in range(q)]
    )  # (q, G, g, o)


@functools.partial(jax.jit, static_argnames=("q", "g", "iters", "col_chunk"))
def quantize_bcq(
    w: Array, q: int, g: int, iters: int = 10, col_chunk: int = 512
) -> Tuple[Array, Array]:
    """Greedy init + ``iters`` rounds of alternating optimisation.

    ``col_chunk`` bounds peak memory of the exhaustive bits-step
    (O(k · col_chunk · 2^q) floats) by scanning over output-column chunks.

    Returns ``(scales (q,G,o) f32, binary (q,k,o) int8)``.
    """
    k, o = w.shape
    _check_args(k, q, g)
    wf = w.astype(jnp.float32)

    col_chunk = min(col_chunk, o)
    if o % col_chunk != 0:
        # fall back to a divisor of o
        col_chunk = int(np.gcd(o, col_chunk)) or o

    def solve_chunk(w_chunk: Array) -> Tuple[Array, Array]:
        kk, oo = w_chunk.shape
        scales0, binary0 = quantize_bcq_greedy(w_chunk, q, g)
        w_g = w_chunk.reshape(kk // g, g, oo)

        def body(carry, _):
            scales, codes = carry
            scales = _alpha_lstsq(w_g, codes, ridge=1e-8)
            codes = _bits_step(w_g, scales)
            return (scales, codes), None

        codes0 = binary0.astype(jnp.float32).reshape(q, kk // g, g, oo)
        (scales, codes), _ = jax.lax.scan(body, (scales0, codes0), None, length=iters)
        binary = codes.reshape(q, kk, oo).astype(jnp.int8)
        return scales, binary

    chunks = jnp.moveaxis(wf.reshape(k, o // col_chunk, col_chunk), 1, 0)
    scales_c, binary_c = jax.lax.map(solve_chunk, chunks)
    scales = jnp.moveaxis(scales_c, 0, 2).reshape(q, k // g, o)
    binary = jnp.moveaxis(binary_c, 0, 2).reshape(q, k, o)
    return scales, binary


# ---------------------------------------------------------------------------
# Reconstruction / metrics
# ---------------------------------------------------------------------------


def dequantize(scales: Array, binary: Array, g: int) -> Array:
    """Reconstruct ``W ≈ Σ_i alpha_i ∘ b_i`` → (..., k, o) f32.

    Supports leading batch dims (stacked layers / experts): binary
    ``(..., q, k, o)``, scales ``(..., q, k//g, o)``.
    """
    *lead, q, k, o = binary.shape
    b = binary.astype(jnp.float32).reshape(*lead, q, k // g, g, o)
    w = jnp.einsum("...iago,...iao->...ago", b, scales.astype(jnp.float32))
    return w.reshape(*lead, k, o)


def bcq_error(w: Array, scales: Array, binary: Array, g: int) -> Array:
    """Relative Frobenius reconstruction error ||W - Ŵ|| / ||W||."""
    w_hat = dequantize(scales, binary, g)
    return jnp.linalg.norm(w.astype(jnp.float32) - w_hat) / (
        jnp.linalg.norm(w.astype(jnp.float32)) + 1e-12
    )


def compression_ratio(q: int, g: int, base_bits: int = 16, scale_bits: int = 16) -> float:
    """Paper Eq. (3): bits-per-weight of BCQ vs a ``base_bits`` dense format.

    BCQ stores q binary bits + (scale_bits / g) amortised scale bits per weight.
    The paper uses FP32 for both (base 32, scales 32); our TPU framework defaults
    to bf16 baselines and bf16 scales (their §VI halving note).
    """
    bcq_bits = q * (1.0 + scale_bits / g)
    return base_bits / bcq_bits
