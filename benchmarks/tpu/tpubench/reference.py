"""A plain float32 reference of the dense decoder the configurations describe,
and the comparison that decides ``correct``.

The block is the published one: pre-norm RMSNorm, grouped-query attention
with rotary embeddings (rotate-half layout, ``inv_freq = theta^(-2i/d)``) and
a causal mask, and a SwiGLU MLP; then a final RMSNorm and the output head.
Linear weights are BCQ codes: ``W[k, o] = sum_i scales[i, k // g, o] *
(2 * bit_i[k, o] - 1)``, with bit ``j`` of byte ``c`` of plane ``i`` holding
row ``8 c + j``. The reference unpacks them itself, from the arrays the
benchmark made from the seed, and imports nothing of the program.

It runs layer by layer over a block of sequences (prompt plus served tokens,
teacher-forced), with every matmul at ``precision=highest``, and reads the
logits only where a served token was chosen. The control runs the same
forward with every matmul operand rounded to float8 (e4m3, absmax-scaled per
matrix, or per eighth of the output head, for weights and per row for
activations): the next precision below the bfloat16 the configurations
state.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn


def unpack(packed: jax.Array, scales: jax.Array, g: int) -> jax.Array:
    """``(q, k/8, o)`` sign bytes and ``(q, k/g, o)`` scales -> ``(k, o)`` f32."""
    q, kc, o = packed.shape
    shifts = jnp.arange(8, dtype=jnp.int32)[None, None, :, None]
    w = jnp.zeros((kc * 8, o), jnp.float32)
    for i in range(q):
        bits = (packed[i].astype(jnp.int32)[:, None, :] >> shifts[0]) & 1
        sign = (2 * bits - 1).astype(jnp.float32).reshape(kc * 8, o)
        w = w + sign * jnp.repeat(scales[i].astype(jnp.float32), g, axis=0)
    return w


def _f8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 after absmax scaling over ``axis`` (None: the
    whole tensor), and back to f32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a: jax.Array, w: jax.Array, control: bool) -> jax.Array:
    """``a (..., k) @ w (k, o)`` in f32, or with float8 operands (control)."""
    if control:
        a, w = _f8(a, -1), _f8(w, None)
    return jnp.einsum("...k,ko->...o", a, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """``x (R, S, H, d)`` at positions ``0..S-1``, rotate-half layout."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # (S, d/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, control: bool, block: int):
    """Causal GQA. ``q (R, S, H, d)``, ``k, v (R, S, Hkv, d)``; queries in
    blocks of ``block`` rows so the scores stay small."""
    r, s, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(r, s, hkv, h // hkv, d)
    block = min(block, s)
    if control:
        qg, k, v = _f8(qg, -1), _f8(k, -1), _f8(v, -1)
    nb = s // block

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * block, block, axis=1)
        sc = jnp.einsum("rqhgd,rkhd->rhgqk", qb, k,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(d)
        qpos = i * block + jnp.arange(block)
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if control:
            p = _f8(p, -1)
        return jnp.einsum("rhgqk,rkhd->rqhgd", p, v, precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(one, jnp.arange(nb))  # (nb, r, block, hkv, g, d)
    return jnp.moveaxis(out, 0, 1).reshape(r, s, h * d)


def _forward(mc: dict, g: int, control: bool, block: int, w: dict, tokens, rows):
    """Final-normed hidden states of ``tokens (R, S)`` at ``rows (N, 2)``
    (sequence, position) and the logits there, ``(N, V)``."""
    h_q = mc["n_heads"] * mc["d_head"]
    h_kv = mc["n_kv_heads"] * mc["d_head"]
    eps, theta, dh = mc["norm_eps"], mc["rope_theta"], mc["d_head"]
    x = w["embed"][tokens].astype(jnp.float32)
    r, s, _ = x.shape

    def layer(x, lw):
        h = _rms(x, lw["ln1"], eps)
        qkv = _mm(h, unpack(lw["wqkv"]["packed"], lw["wqkv"]["scales"], g), control)
        q = qkv[..., :h_q].reshape(r, s, mc["n_heads"], dh)
        k = qkv[..., h_q:h_q + h_kv].reshape(r, s, mc["n_kv_heads"], dh)
        v = qkv[..., h_q + h_kv:].reshape(r, s, mc["n_kv_heads"], dh)
        a = _attention(_rope(q, theta), _rope(k, theta), v, control, block)
        x = x + _mm(a, unpack(lw["wo"]["packed"], lw["wo"]["scales"], g), control)
        h = _rms(x, lw["ln2"], eps)
        gu = _mm(h, unpack(lw["w_gate_up"]["packed"], lw["w_gate_up"]["scales"], g), control)
        f = gu.shape[-1] // 2
        act = jax.nn.silu(gu[..., :f]) * gu[..., f:]
        x = x + _mm(act, unpack(lw["w_down"]["packed"], lw["w_down"]["scales"], g), control)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    hsel = _rms(x[rows[:, 0], rows[:, 1]], w["final_norm"], eps)
    packed, scales = w["lm_head"]["packed"], w["lm_head"]["scales"]
    vocab = packed.shape[-1]
    nb = next(n for n in (8, 4, 2, 1) if vocab % n == 0)  # head in vocab blocks
    vb = vocab // nb

    def head(i):
        p = jax.lax.dynamic_slice_in_dim(packed, i * vb, vb, axis=-1)
        s = jax.lax.dynamic_slice_in_dim(scales, i * vb, vb, axis=-1)
        return _mm(hsel, unpack(p, s, g), control)

    out = jax.lax.map(head, jnp.arange(nb))  # (nb, N, vb)
    return jnp.moveaxis(out, 0, 1).reshape(hsel.shape[0], vocab)


_JIT: Dict[tuple, object] = {}


def logits(config: dict, weights: dict, tokens: np.ndarray, rows: np.ndarray,
           *, control: bool = False, block: int = 512) -> jax.Array:
    """Reference logits ``(N, V)`` of ``tokens (R, S)`` at ``rows (N, 2)``."""
    mc, g = config["model_config"], config["quant"]["g"]
    key = (config["name"], control, block)
    if key not in _JIT:
        _JIT[key] = jax.jit(lambda w, t, r: _forward(mc, g, control, block, w, t, r))
    return _JIT[key](weights, jnp.asarray(tokens), jnp.asarray(rows))


def pack_sequences(seqs: Sequence[Tuple[np.ndarray, np.ndarray]], n_seqs: int,
                   seq_len: int, n_rows: int):
    """Teacher-forcing inputs for ``(prompt, served)`` pairs at fixed shapes
    (one compilation per cell): ``tokens (n_seqs, seq_len)``, ``rows
    (n_rows, 2)`` where each served token was chosen (padded with row
    ``(0, 0)``), and ``served``, one per real row."""
    tokens = np.zeros((n_seqs, seq_len), np.int32)
    rows = np.zeros((n_rows, 2), np.int32)
    served, n = [], 0
    for i, (p, t) in enumerate(seqs):
        full = np.concatenate([p, t[:-1]])
        tokens[i, : len(full)] = full
        for j in range(len(t)):
            rows[n] = (i, len(p) - 1 + j)
            served.append(t[j])
            n += 1
    return tokens, rows, np.asarray(served, np.int32)


def gaps(ref_logits: jax.Array, chosen: np.ndarray) -> np.ndarray:
    """By how much each chosen token's reference logit lies below the
    reference's best, ``(N,)``."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(chosen)[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)


def compare(config: dict, weights: dict, seqs: List[Tuple[np.ndarray, np.ndarray]],
            shape: Tuple[int, int, int], *, control: bool = False) -> Dict[str, float]:
    """The widest gap of the served tokens, and with ``control=True`` also
    that of the tokens the float8 control puts first at the same positions
    (``control_max_gap``). ``shape`` is ``(sequences, sequence length,
    rows)`` of the padded inputs."""
    tokens, rows, served = pack_sequences(seqs, *shape)
    n = len(served)
    with jax.default_matmul_precision("highest"):
        ref = logits(config, weights, tokens, rows)[:n]
        out = {"max_gap": float(gaps(ref, served).max()), "tokens": n,
               "sequences": len(seqs),
               "top1_agree": float(np.mean(np.asarray(jnp.argmax(ref, -1)) == served))}
        if control:
            low = logits(config, weights, tokens, rows, control=True)[:n]
            out["control_max_gap"] = float(gaps(ref, np.asarray(jnp.argmax(low, -1))).max())
    return out
