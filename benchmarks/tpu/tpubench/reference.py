"""A plain float32 reference of the model a configuration describes, and the
comparison that decides ``correct``.

The forward itself is the configuration's architecture module's
(``tpubench.arch``); it builds on the pieces here. Linear weights are BCQ
codes: ``W[k, o] = sum_i scales[i, k // g, o] * (2 * bit_i[k, o] - 1)``, with
bit ``j`` of byte ``c`` of plane ``i`` holding row ``8 c + j``. The reference
unpacks them itself, from the arrays the benchmark made from the seed, and
imports nothing of the program.

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

from tpubench import arch

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


_JIT: Dict[tuple, object] = {}


def logits(config: dict, weights: dict, tokens: np.ndarray, rows: np.ndarray,
           *, control: bool = False, block: int = 512) -> jax.Array:
    """Reference logits ``(N, V)`` of ``tokens (R, S)`` at ``rows (N, 2)``."""
    mc, g = config["model_config"], config["quant"]["g"]
    key = (config["name"], control, block)
    if key not in _JIT:
        fwd = arch.of(config).forward
        _JIT[key] = jax.jit(lambda w, t, r: fwd(mc, g, control, block, w, t, r))
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
