"""Transformer building blocks (pure JAX, pytree params).

Every weight application goes through :func:`repro.kernels.ops.linear`, so any
leaf may be a dense array *or* a packed :class:`QuantizedTensor` of any
registered quantization format (``core/formats.py``: BCQ, uniform int-q, the
dequant baseline — dispatched per leaf through ``ops.qmatmul``) — the paper's
technique is a per-layer switch, not a separate model, and formats mix freely
within one forward (DESIGN.md §2.4).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.qtensor import QuantizedTensor
from repro.kernels.ops import linear, linear_fused
from repro.models.config import ModelConfig
from repro.parallel.ctx import constrain_decode_q, constrain_qkv, psum_partial

Array = jax.Array
NEG_INF = jnp.finfo(jnp.float32).min

# ---------------------------------------------------------------------------
# cache-rewind contract (speculative decoding, DESIGN.md §5)
#
# Speculative verification writes q'-draft tokens into the cache ahead of
# acceptance; rejected tokens must be rolled back. Every cache leaf falls in
# exactly one class, identified by its name:
#
# - POSITIONAL (k, v, k_scale, v_scale): writes land at absolute positions.
#   Rewind = reset the position counter; masked reads (`slot <= pos`, ring
#   band) guarantee rows beyond the counter are never attended, and the next
#   chunk overwrites them before they re-enter the valid range. Ring-window
#   buffers are the one exception: once wrapped, a write at position p
#   *clobbers* the live entry at p - s_max, so speculative chunks snapshot the
#   rows they will write and restore the rejected ones
#   (infer/speculative.py::snapshot_rows/restore_rows).
# - RECURRENT (h, conv, c, n, m): RG-LRU/xLSTM state folds every consumed
#   token irreversibly — it cannot be re-masked after the fact. Rewind
#   requires per-step snapshots: `forward(..., collect_states=True)` makes the
#   recurrent blocks return their state stacked over the chunk's time axis
#   (leading axis S), and rollback selects the entry at the commit index.
# - STATIC (k_img, v_img): projected image memory, never written during
#   decode; rewind is a no-op.
# ---------------------------------------------------------------------------

POSITIONAL_CACHE_LEAVES = frozenset({"k", "v", "k_scale", "v_scale"})
RECURRENT_CACHE_LEAVES = frozenset({"h", "conv", "c", "n", "m"})
STATIC_CACHE_LEAVES = frozenset({"k_img", "v_img"})


def _cache_leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


# ---------------------------------------------------------------------------
# prefix-cache install/commit ops (infer/prefix_cache.py, DESIGN.md §12)
#
# Prefix reuse applies the same leaf taxonomy as the rewind contract above,
# but at admission time instead of rollback time:
#
# - POSITIONAL: a committed prefix of length L is exactly rows [0, L) along
#   axis 2 (positions are absolute; a fresh cache has no wrap). Commit
#   gathers those rows; install writes them back into a fresh zeroed cache
#   and the suffix prefill resumes at pos=L.
# - RECURRENT: state folds tokens irreversibly, so a committed block carries
#   a snapshot of the state *at the block boundary* (captured from a
#   collect_states=True prefill); install overwrites the fresh zero state.
# - STATIC: projected image memory is prompt-independent — no-op (and the
#   prefix subsystem refuses VLM configs outright).
#
# All four functions keep the cache treedef: non-participating leaves become
# (0,)-shaped placeholders on gather (the snapshot_rows idiom) and pass
# through untouched on install.
# ---------------------------------------------------------------------------


def gather_prefix_rows(cache, start, n: int):
    """POSITIONAL leaves → their ``n`` rows starting at ``start`` along axis 2
    (``(repeat, B, n, ...)``); every other leaf → an empty placeholder.
    ``start`` may be traced; the caller guarantees ``start + n <= s_eff``
    (the ring cap), so the dynamic slice never clamps."""

    def visit(path, leaf):
        if _cache_leaf_name(path) not in POSITIONAL_CACHE_LEAVES:
            return jnp.zeros((0,), jnp.int8)
        return jax.lax.dynamic_slice_in_dim(leaf, start, n, axis=2)

    return jax.tree_util.tree_map_with_path(visit, cache)


def install_prefix_rows(cache, rows):
    """Write gathered prefix rows into rows [0, L) of every POSITIONAL leaf
    of a *fresh* cache. ``rows`` may be zero-padded past the real prefix
    length: a fresh cache is all-zero there, so the padding writes are
    no-ops by value — which is what lets install shapes bucket without
    changing the cache contents."""

    def visit(path, leaf, rw):
        if _cache_leaf_name(path) not in POSITIONAL_CACHE_LEAVES:
            return leaf
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, rw.astype(leaf.dtype), 0, axis=2
        )

    return jax.tree_util.tree_map_with_path(visit, cache, rows)


def snapshot_recurrent(cache):
    """RECURRENT leaves verbatim, everything else an empty placeholder — the
    boundary-state payload a committed prefix block carries."""

    def visit(path, leaf):
        if _cache_leaf_name(path) not in RECURRENT_CACHE_LEAVES:
            return jnp.zeros((0,), jnp.int8)
        return leaf

    return jax.tree_util.tree_map_with_path(visit, cache)


def install_recurrent(cache, snap):
    """Overwrite RECURRENT leaves from a boundary snapshot; positional and
    static leaves pass through untouched."""

    def visit(path, leaf, sn):
        if _cache_leaf_name(path) not in RECURRENT_CACHE_LEAVES:
            return leaf
        return sn.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(visit, cache, snap)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dense_init(key, k: int, o: int, dtype) -> Array:
    scale = 1.0 / jnp.sqrt(k)
    return (jax.random.normal(key, (k, o), jnp.float32) * scale).astype(dtype)


def init_attention(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense_init(ks[0], cfg.d_model, cfg.q_dim, cfg.pdtype),
        "wk": _dense_init(ks[1], cfg.d_model, cfg.kv_dim, cfg.pdtype),
        "wv": _dense_init(ks[2], cfg.d_model, cfg.kv_dim, cfg.pdtype),
        "wo": _dense_init(ks[3], cfg.q_dim, cfg.d_model, cfg.pdtype),
    }


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(ks[0], cfg.d_model, d_ff, cfg.pdtype),
        "w_up": _dense_init(ks[1], cfg.d_model, d_ff, cfg.pdtype),
        "w_down": _dense_init(ks[2], d_ff, cfg.d_model, cfg.pdtype),
    }


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def rmsnorm(w: Array, x: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * w.astype(jnp.float32)).astype(x.dtype)


def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding. x: (B, S, H, Dh); positions: (B, S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _repeat_kv(kv: Array, n_rep: int) -> Array:
    """(B, S, Hkv, Dh) → (B, S, Hkv*n_rep, Dh) for GQA."""
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def _sdpa(q: Array, k: Array, v: Array, mask: Optional[Array]) -> Array:
    """GQA-native softmax attention. q: (B,Sq,H,Dh); k,v: (B,Sk,Hkv,Dh) with
    H = G·Hkv; mask broadcastable to (..,Sq,Sk) or None.

    - No K/V head replication is ever materialised: queries are grouped
      (B,Sq,Hkv,G,Dh) and contracted against the raw Hkv heads (the repeated
      broadcast cost 64 GB/step on decode_32k before this).
    - Inputs stay in their native (bf16) dtype; accumulation is f32 via
      preferred_element_type — no materialised f32 Q/K/V copies.
    """
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, dh)
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(dh))
    if mask is not None:
        logits = jnp.where(mask[..., None, :, :] if mask.ndim == 4 else mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def _local_attention_chunked(q: Array, k: Array, v: Array, window: int) -> Array:
    """Exact sliding-window causal attention in O(S·window).

    Standard chunking: split the sequence into window-sized chunks; each chunk
    attends to itself + the previous chunk under a banded causal mask.
    q, k, v: (B, S, H, Dh) with S % window == 0 (callers pad).
    """
    b, s, h, dh = q.shape
    w = window
    if s <= w:
        qi = jnp.arange(s)[:, None]
        ki = jnp.arange(s)[None, :]
        mask = (ki <= qi) & (ki > qi - w)
        return _sdpa(q, k, v, mask[None, None])
    if s % w:
        # pad at the end; padded keys are "future" for every real query → masked
        pad = w - s % w
        padded = [jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v)]
        return _local_attention_chunked(*padded, window)[:, :s]
    nc = s // w
    hkv = k.shape[2]
    g = h // hkv
    qc = q.reshape(b, nc, w, hkv, g, dh)
    kc = k.reshape(b, nc, w, hkv, dh)
    vc = v.reshape(b, nc, w, hkv, dh)
    # previous chunk (zeros before chunk 0)
    kprev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    kk = jnp.concatenate([kprev, kc], axis=2)  # (b, nc, 2w, hkv, dh)
    vv = jnp.concatenate([vprev, vc], axis=2)
    qi = jnp.arange(w)[:, None] + w  # query abs pos within the 2w key window
    ki = jnp.arange(2 * w)[None, :]
    mask = (ki <= qi) & (ki > qi - w)  # (w, 2w)
    first = jnp.arange(nc) == 0
    # chunk 0 must not see the zero-padded "previous" keys
    mask_c = mask[None] & ~(first[:, None, None] & (ki < w)[None])  # (nc, w, 2w)
    logits = jnp.einsum(
        "bnqhgd,bnkhd->bnhgqk", qc, kk, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(dh))
    logits = jnp.where(mask_c[None, :, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bnhgqk,bnkhd->bnqhgd", probs.astype(vv.dtype), vv,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, h, dh).astype(q.dtype)


Q_CHUNK = 2048  # query-block length for long-sequence causal attention


def _sdpa_qchunked(q: Array, k: Array, v: Array, chunk: int = Q_CHUNK) -> Array:
    """Causal attention scanned over query blocks: O(chunk·S) live logits.

    Full-S² logits at 32k seq are ~34 GB/device f32 — this bounds them to one
    (B, H, chunk, S) block at a time. Pure-XLA fallback for the TPU flash
    kernel; attention FLOPs remain full-S² masked (2× the causal-useful work —
    noted in the roofline methodology).
    """
    b, s, h, dh = q.shape
    if s % chunk:
        return _sdpa(q, k, v, causal_mask(s, s))
    nc = s // chunk
    qc = jnp.moveaxis(q.reshape(b, nc, chunk, h, dh), 1, 0)  # (nc, b, chunk, h, dh)

    kpos = jnp.arange(s)

    def body(_, inp):
        qblk, i = inp
        qpos = i * chunk + jnp.arange(chunk)
        mask = (kpos[None, :] <= qpos[:, None])[None, None]  # (1,1,chunk,s)
        out = _sdpa(qblk, k, v, mask)
        return None, out

    _, outs = jax.lax.scan(body, None, (qc, jnp.arange(nc)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, dh)


LONG_SEQ_THRESHOLD = 8192


def causal_mask(sq: int, sk: int, window: int = 0) -> Array:
    """(1,1,sq,sk) boolean; window>0 restricts to a local band."""
    qi = jnp.arange(sq)[:, None] + (sk - sq)
    ki = jnp.arange(sk)[None, :]
    m = ki <= qi
    if window:
        m &= ki > qi - window
    return m[None, None]


# ---------------------------------------------------------------------------
# attention block (self / local / cross)
# ---------------------------------------------------------------------------


def attention(
    p: dict,
    cfg: ModelConfig,
    x: Array,
    positions: Array,
    *,
    cache: Optional[dict] = None,
    layer: Optional[Array] = None,
    pos: Optional[Array] = None,
    window: int = 0,
    kv_override: Optional[Tuple[Array, Array]] = None,
    chunked: bool = False,
) -> Tuple[Array, Optional[dict]]:
    """GQA attention. Returns (out, new_cache).

    ``cache`` is the stage's *stacked* cache for this block (leaves
    ``(repeat, B, s_max, ...)``) and ``layer`` this block's index in it: the
    block writes only its new rows at ``[layer, b, pos]`` and reads its slab
    back from the updated stack (``_cache_write``), and ``new_cache`` is the
    updated stack.

    Modes
    -----
    train            cache=None                 full / chunked-local causal attn
    prefill          cache=empty, pos=0         as train, but also fills the cache
    decode           cache=filled, pos=cur_len  x is (B, 1, D), attends cache
    chunked decode   chunked=True, cache=filled x is (B, s, D) *mid-sequence*:
                     the s new tokens attend the whole cache + themselves
                     (speculative verify — DESIGN.md §5); `pos` may be a
                     scalar or a per-row (B,) array
    cross            kv_override=(k_mem, v_mem) attends provided memory, no cache
    """
    b, s, _ = x.shape
    n_rep = cfg.n_heads // cfg.n_kv_heads
    if "wqkv" in p:
        # decode fast path: output-fused QKV weights (models.fuse) — one kernel
        # pass / one activation read for all three projections (DESIGN.md §2.3)
        q, k, v = linear_fused(x, p["wqkv"], (cfg.q_dim, cfg.kv_dim, cfg.kv_dim))
    else:
        q = linear(x, p["wq"])
        k = v = None
    q = rope(q.reshape(b, s, cfg.n_heads, cfg.d_head), positions, cfg.rope_theta)

    if kv_override is not None:
        k_mem, v_mem = kv_override
        out = _sdpa(q, k_mem, v_mem, None)
        # wo is row-parallel under TP: local heads contract to a partial (B,S,D)
        return psum_partial(linear(out.reshape(b, s, cfg.q_dim), p["wo"])), cache

    if k is None:
        k = linear(x, p["wk"])
        v = linear(x, p["wv"])
    k = rope(k.reshape(b, s, cfg.n_kv_heads, cfg.d_head), positions, cfg.rope_theta)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.d_head)

    def _causal(qq, kk, vv):
        qq, kk, vv = constrain_qkv(qq, kk, vv)
        if s >= LONG_SEQ_THRESHOLD:
            return _sdpa_qchunked(qq, kk, vv)
        return _sdpa(qq, kk, vv, causal_mask(s, s))

    new_cache = None
    if cache is None:
        # train: no cache
        if window:
            out = _local_attention_chunked(q, k, v, window)
        else:
            out = _causal(q, k, v)
    elif s > 1 and not chunked:
        # prefill: compute attention over the fresh sequence, then write cache
        if window:
            out = _local_attention_chunked(q, k, v, window)
        else:
            out = _causal(q, k, v)
        new_cache = _cache_write(cache, layer, k, v, pos, window)
    elif s > 1:
        # chunked decode (speculative verify): s fresh tokens at absolute
        # positions pos..pos+s-1 against a *filled* cache. All s rows are
        # written first, then every token attends the cache under a per-token
        # positional mask — the same slot layout a step-by-step decode reads,
        # so the unwrapped case is compute-identical to s single-token steps.
        # Ring ring-buffers additionally re-expose the entries the chunk's own
        # writes clobbered (live positions p - s_max once wrapped) as appended
        # snapshot keys with their original validity band: the only mode that
        # reads cache rows before writing them, a gather of s rows per batch
        # row from the stack, made before the write.
        s_max_c = cache["k"].shape[2]
        pvec = pos if jnp.ndim(pos) == 1 else jnp.full((b,), pos, jnp.int32)
        snap = None
        if window:
            idx = (pvec[:, None] + jnp.arange(s)) % s_max_c  # (B, s) written slots
            snap_k = _gather_rows(cache["k"], layer, idx)
            snap_v = _gather_rows(cache["v"], layer, idx)
            if "k_scale" in cache:
                snap_k = _kv_dequantize(snap_k, _gather_rows(cache["k_scale"], layer, idx), x.dtype)
                snap_v = _kv_dequantize(snap_v, _gather_rows(cache["v_scale"], layer, idx), x.dtype)
            snap = (snap_k.astype(x.dtype), snap_v.astype(x.dtype))
        new_cache = _cache_write(cache, layer, k, v, pos, window)
        ck, cv = _layer_kv(new_cache, layer, x.dtype)
        q = constrain_decode_q(q)
        qpos = pvec[:, None] + jnp.arange(s)  # (B, s) per-token absolute pos
        slot = jnp.arange(s_max_c)
        if window:
            stored = _ring_positions(slot[None, None, :], (pvec + s)[:, None, None], s_max_c)
            valid = (
                (stored >= 0)
                & (stored <= qpos[..., None])
                & (stored > qpos[..., None] - window)
            )  # (B, s, s_max)
            # clobbered entries: written slot j previously held position
            # qpos_j - s_max (if the ring had wrapped); in-band for earlier
            # tokens of this same chunk
            op = qpos - s_max_c  # (B, s) original position of snapshot row j
            valid_snap = (op[:, None, :] >= 0) & (
                op[:, None, :] > qpos[..., None] - window
            )  # (B, s_q, s_snap)
            valid = jnp.concatenate([valid, valid_snap], axis=-1)
            ck = jnp.concatenate([ck, snap[0]], axis=1)
            cv = jnp.concatenate([cv, snap[1]], axis=1)
        else:
            valid = slot[None, None, :] <= qpos[..., None]  # (B, s, s_max)
        out = _sdpa(q, ck, cv, valid[:, None])  # mask (B, 1, s, n_keys)
    else:
        # decode: single new token against the cache. The cache is Dh-sharded
        # on `model`; constrain q to match so the score einsum is a local
        # partial followed by a tiny all-reduce of (B,1,D) partials — NOT a
        # whole-cache all-gather (was 64 GB/step).
        #
        # `pos` may be a scalar (whole batch at one position — Engine.generate)
        # or a (B,) array (slot-batched serving: each batch row is an
        # independent request at its own position — infer/scheduler). The
        # per-row mask values are identical to the scalar case, so a slotted
        # decode reproduces solo decodes bit-for-bit per row.
        new_cache = _cache_write(cache, layer, k, v, pos, window)
        ck, cv = _layer_kv(new_cache, layer, x.dtype)
        q = constrain_decode_q(q)
        s_max = ck.shape[1]
        slot = jnp.arange(s_max)
        if jnp.ndim(pos) == 0:
            if window:
                stored = _ring_positions(slot, pos + 1, s_max)
                valid = (stored >= 0) & (stored <= pos) & (stored > pos - window)
            else:
                valid = slot <= pos
            mask = valid[None, None, None, :]
        else:
            pb = pos[:, None]  # (B, 1)
            if window:
                stored = _ring_positions(slot[None, :], pb + 1, s_max)
                valid = (stored >= 0) & (stored <= pb) & (stored > pb - window)
            else:
                valid = slot[None, :] <= pb
            mask = valid[:, None, None, :]
        out = _sdpa(q, ck, cv, mask)
    # wo is row-parallel under TP (heads → q_dim local shards): psum the
    # partial sums; no-op outside a TP shard_map region
    out = psum_partial(linear(out.reshape(b, s, cfg.q_dim), p["wo"]))
    return out, new_cache


def _gather_rows(stack: Array, layer: Array, idx: Array) -> Array:
    """Per-row gather of one layer's cache rows: stack (R, B, s_max, ...),
    idx (B, n) → (B, n, ...)."""
    rows = jnp.arange(idx.shape[0])[:, None]
    return stack[layer, rows, idx]


def _layer_kv(stack: dict, layer: Array, dtype) -> Tuple[Array, Array]:
    """Layer ``layer``'s K/V slabs ``(B, s_max, Hkv, Dh)`` of the stacked
    cache, dequantized when the cache is int8."""
    sl = {
        n: jax.lax.dynamic_index_in_dim(a, layer, axis=0, keepdims=False)
        for n, a in stack.items()
    }
    ck, cv = sl["k"], sl["v"]
    if "k_scale" in sl:
        ck = _kv_dequantize(ck, sl["k_scale"], dtype)
        cv = _kv_dequantize(cv, sl["v_scale"], dtype)
    return ck, cv


def _kv_quantize(x: Array):
    """(B, s, Hkv, Dh) → int8 codes + per-(token, head) scale (beyond-paper
    int8 KV cache; vLLM-style dynamic per-vector scaling)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # (B, s, Hkv)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-8)[..., None] * 127.0),
        -127, 127,
    ).astype(jnp.int8)
    return q, scale


def _kv_dequantize(q: Array, scale: Array, dtype) -> Array:
    return (q.astype(jnp.float32) * (scale[..., None] / 127.0)).astype(dtype)


def _cache_write(
    cache: dict, layer: Array, k: Array, v: Array, pos: Array, window: int
) -> dict:
    """Write s new K/V rows at absolute position `pos` (ring buffer if local)
    into layer ``layer`` of the stage's stacked cache (leaves
    ``(repeat, B, s_max, ...)``); returns the updated stack.

    The stack is the layer scan's carry (``models/model.py::_apply_stage``),
    so a decode step writes only its new rows and XLA updates the carried
    buffer in place: no per-layer slab is sliced out before the write and
    none is restacked after it. The form of the row write decides that:

    - scalar ``pos``: one scalar-start ``dynamic_update_slice`` at
      ``(layer, 0, pos, 0, 0)``;
    - per-row ``(B,)`` ``pos`` (slot-batched serving decode, speculative
      verify): a ``fori_loop`` over batch rows, each a scalar-start
      ``dynamic_update_slice`` at ``(layer, b, pos[b], 0, 0)``. Do not vmap
      the write over the batch axis: XLA cannot do the batched update in
      place, and the compiled decode-slots program then copies the whole
      stack (8 whole-cache copies and 1.16x the cache as temp in
      ``tests/test_decode_inplace.py``'s dense case, CPU compile). A
      ``.at[layer, rows, pos].set`` scatter stayed in place there and in a
      TPU v5e compile;
    - ring-buffer writes of several rows that may wrap: a scatter (the
      speculative-verify chunk on a local-attention block; not on the
      serving hot path).

    Before, each layer's slab was sliced out of the stack as the scan's
    ``xs`` and the updated slab restacked as its ``ys``: three whole-cache
    passes per decode step (slice, restack, and the outer decode scan's
    copy back into its carry), 57% of the device time of Phi-4-mini decode
    at 20 slots x 2048 on a TPU v5e. Carried, its decode step fell from
    70.5 to 33.4 ms (PERF.md). An earlier carry variant was rejected because
    its body read a slice of the carry and then updated it; here attention
    reads the slab only *after* the write, which needs no defensive copy.
    """
    s_max = cache["k"].shape[2]
    s = k.shape[1]
    new = {"k": k, "v": v}
    if "k_scale" in cache:
        new["k"], new["k_scale"] = _kv_quantize(k)
        new["v"], new["v_scale"] = _kv_quantize(v)
    layer = jnp.asarray(layer, jnp.int32)
    zero = jnp.int32(0)

    def dus(buf, rows, row, start):
        # rows (n, s, ...) → buf[layer, row:row+n, start:start+s]
        idxs = (layer, row, start) + (zero,) * (buf.ndim - 3)
        return jax.lax.dynamic_update_slice(buf, rows[None].astype(buf.dtype), idxs)

    def scatter(idx):
        # idx (B, s) ring slots; rows may wrap, so no single start fits
        rows = jnp.arange(idx.shape[0])[:, None]
        return {
            n: cache[n].at[layer, rows, idx].set(new[n].astype(cache[n].dtype))
            for n in cache
        }

    if jnp.ndim(pos) == 1:
        # per-row writes (slot-batched serving / speculative chunks): each
        # batch row writes its s fresh rows at its own position
        if s >= s_max:
            raise ValueError(
                f"per-row cache writes need s({s}) < s_max({s_max}) "
                "(whole-window overwrite is a lockstep-prefill-only path)"
            )
        if window and s > 1:
            # per-row partial ring fill (speculative verify on a ring buffer)
            return scatter((pos[:, None] + jnp.arange(s)) % s_max)
        start_b = (pos % s_max if window else pos).astype(jnp.int32)

        def write_row(b, bufs):
            return {
                n: dus(bufs[n], jax.lax.dynamic_slice_in_dim(new[n], b, 1), b, start_b[b])
                for n in bufs
            }

        return jax.lax.fori_loop(0, k.shape[0], write_row, dict(cache))

    if s >= s_max:
        # keep only the last s_max tokens (local-attn prefill over a window)
        new = {n: a[:, s - s_max :] for n, a in new.items()}
        if window:
            # ring phase: slot = abs_pos % s_max → roll the linear order
            base = (pos + s - s_max) % s_max
            new = {n: jnp.roll(a, base, axis=1) for n, a in new.items()}
        return {n: dus(cache[n], new[n], zero, zero) for n in cache}
    if window and s > 1:
        # partial ring fill that may wrap — not used by any assigned shape
        idx = (pos + jnp.arange(s)) % s_max
        return scatter(jnp.broadcast_to(idx, (k.shape[0], s)))
    start = jnp.asarray((pos % s_max) if window else pos, jnp.int32)
    return {n: dus(cache[n], new[n], zero, start) for n in cache}


def _ring_positions(slot: Array, total: Array, s_max: int) -> Array:
    """Absolute position held by each ring slot after `total` writes."""
    r = total % s_max
    base = total - r
    return jnp.where(slot < r, base + slot, base - s_max + slot)


def mlp_swiglu(p: dict, x: Array) -> Array:
    if "w_gate_up" in p:
        # decode fast path: output-fused gate/up weights (models.fuse)
        w = p["w_gate_up"]
        d_ff = (w.o if isinstance(w, QuantizedTensor) else w.shape[-1]) // 2
        gate, up = linear_fused(x, w, (d_ff, d_ff))
    else:
        gate = linear(x, p["w_gate"])
        up = linear(x, p["w_up"])
    # w_down is row-parallel under TP (d_ff shards): psum the partials
    return psum_partial(linear(jax.nn.silu(gate) * up, p["w_down"]))
