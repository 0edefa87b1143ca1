"""Model assembly: init / forward for every architecture family.

Layers are stacked per *stage* (see ``config.py``) and applied with
``jax.lax.scan`` so the HLO stays one-superblock-sized regardless of depth —
essential for 100-layer dry-run compiles and for remat during training.

One ``forward()`` serves train, prefill and decode:
  train    cache=None                       → logits (B, S, V)
  prefill  cache=init_cache(...), pos=0     → logits (B, 1, V) [last position], cache
  decode   cache=filled, pos=cur_len        → logits (B, 1, V), cache
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ops import linear
from repro.models import layers as L
from repro.parallel.ctx import all_gather_cols, constrain_tokens
from repro.models import moe as M
from repro.models import recurrent as R
from repro.models.config import ModelConfig

Array = jax.Array

ATTN_BLOCKS = ("attn", "attn_moe", "local_attn", "cross")
KV_BLOCKS = ("attn", "attn_moe", "local_attn")  # write K/V rows into the stack


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, btype: str) -> dict:
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    p = {"ln1": jnp.ones((d,), cfg.pdtype)}
    if btype in ATTN_BLOCKS:
        p["attn"] = L.init_attention(ks[0], cfg)
        p["ln2"] = jnp.ones((d,), cfg.pdtype)
        if btype == "attn_moe":
            p["mlp"] = M.init_moe(ks[1], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg)
    elif btype == "rglru":
        p["mix"] = R.init_rglru(ks[0], cfg)
        p["ln2"] = jnp.ones((d,), cfg.pdtype)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif btype == "mlstm":
        p["mix"] = R.init_mlstm(ks[0], cfg)
    elif btype == "slstm":
        p["mix"] = R.init_slstm(ks[0], cfg)
    else:
        raise ValueError(f"unknown block type {btype}")
    return p


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, len(cfg.stages) + 3)
    stages = []
    for si, (pattern, repeat) in enumerate(cfg.stages):
        bkeys = jax.random.split(keys[si], len(pattern))
        stage_p = {}
        for bi, btype in enumerate(pattern):
            rkeys = jax.random.split(bkeys[bi], repeat)
            stage_p[f"b{bi}"] = jax.vmap(
                lambda k, bt=btype: init_block(k, cfg, bt)
            )(rkeys)
        stages.append(stage_p)
    params = {
        "stages": tuple(stages),
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype),
        "lm_head": L._dense_init(keys[-1], cfg.d_model, cfg.vocab, cfg.pdtype),
    }
    if cfg.input_kind == "tokens":
        params["embed"] = (
            jax.random.normal(keys[-2], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02
        ).astype(cfg.pdtype)
    return params


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


def init_block_cache(cfg: ModelConfig, btype: str, batch: int, s_max: int, dtype):
    hkv, dh, w = cfg.n_kv_heads, cfg.d_head, cfg.lru_width
    nh = cfg.n_heads
    if btype in ("attn", "attn_moe", "local_attn"):
        s_eff = min(s_max, cfg.window) if btype == "local_attn" else s_max
        shape = (batch, s_eff, hkv, dh)
        if cfg.kv_cache_dtype == "int8":
            # beyond-paper: the paper quantizes the weight stream; at batched
            # decode shapes the KV cache dominates HBM bytes — store it int8
            # with one dynamic scale per (token, head)
            return {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros((batch, s_eff, hkv), jnp.float32),
                "v_scale": jnp.zeros((batch, s_eff, hkv), jnp.float32),
            }
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if btype == "cross":
        shape = (batch, cfg.n_image_tokens, hkv, dh)
        return {"k_img": jnp.zeros(shape, dtype), "v_img": jnp.zeros(shape, dtype)}
    if btype == "rglru":
        return {
            "h": jnp.zeros((batch, w), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, w), dtype),
        }
    if btype == "mlstm":
        inner = int(cfg.d_model * cfg.mlstm_proj_factor)
        dhi = inner // nh
        return {
            "c": jnp.zeros((batch, nh, dhi, dhi), jnp.float32),
            "n": jnp.zeros((batch, nh, dhi), jnp.float32),
            "m": jnp.full((batch, nh), -jnp.inf, jnp.float32),
        }
    if btype == "slstm":
        dhd = cfg.d_model // nh
        z = jnp.zeros((batch, nh, dhd), jnp.float32)
        return {"h": z, "c": z, "n": z + 1e-6, "m": z - jnp.inf}
    raise ValueError(btype)


def init_cache(cfg: ModelConfig, batch: int, s_max: int, dtype=None) -> dict:
    """Stacked (per-stage, per-pattern-position) decoding state."""
    dtype = dtype or cfg.cdtype

    def stacked(btype, repeat):
        one = init_block_cache(cfg, btype, batch, s_max, dtype)
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (repeat,) + x.shape), one)

    stages = tuple(
        {f"b{bi}": stacked(bt, repeat) for bi, bt in enumerate(pattern)}
        for pattern, repeat in cfg.stages
    )
    return {"stages": stages}


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------


def apply_block(
    p: dict,
    cfg: ModelConfig,
    btype: str,
    h: Array,
    positions: Array,
    cache: Optional[dict],
    pos: Optional[Array],
    image_emb: Optional[Array],
    chunked: bool = False,
    collect: bool = False,
    layer: Optional[Array] = None,
) -> Tuple[Array, Optional[dict], Array]:
    """Returns (h, new_cache, aux_loss).

    For the self-attention blocks (``KV_BLOCKS``) ``cache`` is the stage's
    stacked cache for this block (leaves ``(repeat, B, s_max, ...)``) and
    ``layer`` the block's index in it: the block writes its new K/V rows
    into the stack and returns the updated stack. For every other block
    ``cache`` is this layer's own state (recurrent state or projected image
    memory) and ``new_cache`` its replacement.

    ``chunked``/``collect`` implement the speculative-verify contract
    (DESIGN.md §5): multi-token decode against a filled cache, with recurrent
    state returned as per-step snapshot stacks instead of finals."""
    aux = jnp.float32(0.0)
    if btype in ATTN_BLOCKS:
        window = cfg.window if btype == "local_attn" else 0
        kv_override = None
        new_cache = cache
        if btype == "cross":
            if cache is not None and pos is not None and image_emb is None:
                # decode: reuse cached projected image memory
                kv_override = (cache["k_img"], cache["v_img"])
            else:
                b, n, _ = image_emb.shape
                k_img = linear(image_emb, p["attn"]["wk"]).reshape(
                    b, n, cfg.n_kv_heads, cfg.d_head
                )
                v_img = linear(image_emb, p["attn"]["wv"]).reshape(
                    b, n, cfg.n_kv_heads, cfg.d_head
                )
                kv_override = (k_img, v_img)
                if cache is not None:
                    new_cache = {
                        "k_img": k_img.astype(cache["k_img"].dtype),
                        "v_img": v_img.astype(cache["v_img"].dtype),
                    }
            r, _ = L.attention(
                p["attn"], cfg, L.rmsnorm(p["ln1"], h), positions,
                kv_override=kv_override,
            )
        else:
            r, new_cache = L.attention(
                p["attn"], cfg, L.rmsnorm(p["ln1"], h), positions,
                cache=cache, layer=layer, pos=pos, window=window, chunked=chunked,
            )
        h = h + r
        x2 = L.rmsnorm(p["ln2"], h)
        if btype == "attn_moe":
            y, aux = M.moe_apply(p["mlp"], cfg, x2)
            h = h + y
        else:
            h = h + L.mlp_swiglu(p["mlp"], x2)
        return h, new_cache, aux

    if btype == "rglru":
        r, new_cache = R.rglru_block(
            p["mix"], cfg, L.rmsnorm(p["ln1"], h), cache, collect=collect
        )
        h = h + r
        h = h + L.mlp_swiglu(p["mlp"], L.rmsnorm(p["ln2"], h))
        return h, new_cache, aux

    fn = {"mlstm": R.mlstm_block, "slstm": R.slstm_block}[btype]
    r, new_cache = fn(p["mix"], cfg, L.rmsnorm(p["ln1"], h), cache, collect=collect)
    return h + r, new_cache, aux


def _apply_stage(
    stage_params: dict,
    cfg: ModelConfig,
    pattern: Tuple[str, ...],
    h: Array,
    positions: Array,
    stage_cache: Optional[dict],
    pos: Optional[Array],
    image_emb: Optional[Array],
    remat: bool,
    chunked: bool = False,
    collect: bool = False,
) -> Tuple[Array, Optional[dict], Array]:
    """Scan the stage's layers.

    The self-attention blocks' stacked K/V caches ride in the scan's carry
    beside the hidden state, and the layer index comes in beside the layer's
    params. Each such block writes only its new rows into the stack and
    reads its slab back after the write (``L._cache_write``), so a decode
    step moves its new K/V rows and nothing else, and the carried stack, the
    outer decode scan's carry and a donated slot state stay one buffer.
    Slicing each layer's slab out as the scan's ``xs`` and restacking it as
    its ``ys`` cost three whole-cache passes per decode step on the chip
    (PERF.md). The windowed chunked verify, the one mode that reads K/V rows
    before writing them, gathers those rows from the stack ahead of the
    write (``L.attention``).

    Every other block's state is small (recurrent state, projected image
    memory) and stays ``xs``/``ys``: in the carry, XLA fused the state's
    read into consumers on both sides of the in-place write and copied the
    stacked state twice per layer, and with ``collect`` a recurrent block
    returns per-step stacks shaped unlike its cache, which only ``ys`` can
    hold."""
    kv_names = {f"b{bi}" for bi, bt in enumerate(pattern) if bt in KV_BLOCKS}
    carried, sliced = {}, None
    if stage_cache is not None:
        carried = {n: c for n, c in stage_cache.items() if n in kv_names}
        sliced = {n: c for n, c in stage_cache.items() if n not in kv_names}

    def body(carry, xs):
        hh, aux, kv = carry
        layer_p, layer_c, layer = xs
        kv, new_c = dict(kv), {}
        for bi, btype in enumerate(pattern):
            name = f"b{bi}"
            if name in kv:
                c_in = kv[name]
            else:
                c_in = None if layer_c is None else layer_c[name]
            hh, c_out, a = apply_block(
                layer_p[name], cfg, btype, hh, positions, c_in, pos, image_emb,
                chunked=chunked, collect=collect, layer=layer,
            )
            aux = aux + a
            if name in kv:
                kv[name] = c_out
            elif layer_c is not None:
                new_c[name] = c_out if c_out is not None else c_in
        return (hh, aux, kv), new_c

    if remat:
        body = jax.checkpoint(body)

    repeat = jax.tree.leaves(stage_params)[0].shape[0]
    (h, aux, kv), new_sliced = jax.lax.scan(
        body,
        (h, jnp.float32(0.0), carried),
        (stage_params, sliced, jnp.arange(repeat, dtype=jnp.int32)),
    )
    new_cache = None if stage_cache is None else {**kv, **new_sliced}
    return h, new_cache, aux


def forward(
    cfg: ModelConfig,
    params: dict,
    *,
    tokens: Optional[Array] = None,
    embeddings: Optional[Array] = None,
    image_emb: Optional[Array] = None,
    positions: Optional[Array] = None,
    cache: Optional[dict] = None,
    pos: Optional[Array] = None,
    logits_mode: str = "all",  # "all" | "last"
    remat: bool = False,
    chunked_decode: bool = False,
    collect_states: bool = False,
) -> Tuple[Array, Optional[dict], Array]:
    """Run the decoder. Returns (logits f32, new_cache or None, aux_loss).

    ``chunked_decode=True`` feeds ``s > 1`` fresh tokens *mid-sequence*
    against a filled cache (speculative verify): every token attends the
    whole cache plus its intra-chunk predecessors under per-token positional
    masks, instead of the fresh-sequence-only prefill attention.
    ``collect_states=True`` additionally makes recurrent blocks return their
    state stacked over the chunk's time axis (leading ``S`` after the layer
    axis) so a rollback can select the snapshot at the commit index — the
    cache-rewind contract of DESIGN.md §5 / models/layers.py."""
    if tokens is not None:
        h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)
        b, s = tokens.shape
    else:
        h = embeddings.astype(cfg.cdtype)
        b, s, _ = embeddings.shape
    if positions is None:
        if pos is None:
            base = jnp.zeros((b, 1), jnp.int32)
        elif jnp.ndim(pos) == 1:
            base = pos[:, None]  # per-slot positions (serving decode)
        else:
            base = jnp.full((b, 1), pos)
        positions = base + jnp.arange(s)[None, :]

    new_stages = []
    aux_total = jnp.float32(0.0)
    h = constrain_tokens(h)
    for si, (pattern, _) in enumerate(cfg.stages):
        sc = None if cache is None else cache["stages"][si]
        h, nsc, aux = _apply_stage(
            params["stages"][si], cfg, pattern, h, positions, sc, pos, image_emb,
            remat, chunked=chunked_decode, collect=collect_states,
        )
        h = constrain_tokens(h)  # re-anchor: keep batch on dp at stage edges
        aux_total = aux_total + aux
        new_stages.append(nsc)

    h = L.rmsnorm(params["final_norm"], h)
    if logits_mode == "last":
        h = h[:, -1:]
    # lm_head is column-parallel under TP (vocab shards): gather the full
    # (B, S, V) logits so sampling sees every token; no-op otherwise
    logits = all_gather_cols(linear(h, params["lm_head"], out_dtype=jnp.float32))
    new_cache = None if cache is None else {"stages": tuple(new_stages)}
    return logits, new_cache, aux_total
