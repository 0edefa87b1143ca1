"""The dense decoder: every layer a pre-norm RMSNorm, grouped-query attention
with rotary embeddings, and a SwiGLU MLP; then a final RMSNorm and the output
head.

Weights: the layout on the device is the serving one, QKV and gate/up
concatenated along the output dim, each linear stacked over the layers.
:func:`program_params` wraps the same buffers in the program's pytree and
checks it against the program's own serving layout, so no byte is copied.

Reference: rotary embeddings in the rotate-half layout, ``inv_freq =
theta^(-2i/d)``, and a causal mask, run layer by layer over a block of
sequences with the matmuls of ``tpubench.reference``.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpubench.arch import HEAD
from tpubench.reference import _f8, _mm, unpack
from tpubench.weights import EMBED_STD, _bcq_leaf


def dims(mc: dict) -> Dict[str, int]:
    q_dim = mc["n_heads"] * mc["d_head"]
    kv_dim = mc["n_kv_heads"] * mc["d_head"]
    return dict(D=mc["d_model"], F=mc["d_ff"], V=mc["vocab"], L=mc["n_layers"],
                Q=q_dim, KV=kv_dim)


def linears(mc: dict) -> List[Tuple[str, int, int, int]]:
    """``(name, k, o, count)`` of every quantized linear, in the serving
    layout (fused QKV and gate/up)."""
    d = dims(mc)
    return [
        ("wqkv", d["D"], d["Q"] + 2 * d["KV"], d["L"]),
        ("wo", d["Q"], d["D"], d["L"]),
        ("w_gate_up", d["D"], 2 * d["F"], d["L"]),
        ("w_down", d["F"], d["D"], d["L"]),
        (HEAD, d["D"], d["V"], 1),
    ]


def attention_layers(mc: dict) -> int:
    return mc["n_layers"]


def weights_fn(config: dict):
    """``key -> weights``: the whole model, for one jitted call."""
    mc, qc = config["model_config"], config["quant"]
    d = dims(mc)
    q, g = qc["q"], qc["g"]
    sdt = jnp.dtype(qc["scale_dtype"])
    pdt = jnp.dtype(mc["param_dtype"])

    def fill(key):
        keys = jax.random.split(key, 8)
        layers = {
            "ln1": jnp.ones((d["L"], d["D"]), pdt),
            "ln2": jnp.ones((d["L"], d["D"]), pdt),
        }
        out = {"final_norm": jnp.ones((d["D"],), pdt)}
        for i, (name, k, o, count) in enumerate(linears(mc)):
            per_layer = name != HEAD
            lead = (count,) if per_layer else ()
            leaf = _bcq_leaf(keys[i], lead, k, o, q, g, sdt)
            (layers if per_layer else out)[name] = leaf
        out["layers"] = layers
        out["embed"] = (jax.random.normal(keys[7], (d["V"], d["D"]), jnp.float32)
                        * EMBED_STD).astype(pdt)
        return out

    return fill


def program_params(weights: dict, config: dict):
    """The same buffers as the program's serving pytree (``Engine(...,
    fuse=False)``), checked leaf by leaf against the layout the program would
    build itself (``quantize_params`` then ``fuse_decode_projections``)."""
    from repro.core.qtensor import QuantizedTensor
    from repro.models import fuse_decode_projections, init_params
    from repro.models.config import ModelConfig
    from repro.quant import QuantPolicy, quantized_structs

    mc, qc = config["model_config"], config["quant"]
    cfg = ModelConfig(**mc)
    shapes = {name: (k, o) for name, k, o, _ in linears(mc)}

    def qt(leaf, name):
        k, o = shapes[name]
        return QuantizedTensor(packed=leaf["packed"], scales=leaf["scales"],
                               g=qc["g"], k=k, o=o, fmt=qc["fmt"])

    lw = weights["layers"]
    block = {
        "ln1": lw["ln1"],
        "attn": {"wqkv": qt(lw["wqkv"], "wqkv"), "wo": qt(lw["wo"], "wo")},
        "ln2": lw["ln2"],
        "mlp": {"w_gate_up": qt(lw["w_gate_up"], "w_gate_up"),
                "w_down": qt(lw["w_down"], "w_down")},
    }
    params = {"stages": ({"b0": block},), "final_norm": weights["final_norm"],
              HEAD: qt(weights[HEAD], HEAD), "embed": weights["embed"]}

    policy = QuantPolicy(q=qc["q"], g=qc["g"], fmt=qc["fmt"],
                         scale_dtype=qc["scale_dtype"])
    dense = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    want = jax.eval_shape(lambda p: fuse_decode_projections(cfg, p),
                          quantized_structs(dense, policy))
    got_def, want_def = jax.tree.structure(params), jax.tree.structure(want)
    if got_def != want_def:
        raise ValueError(f"weight tree differs from the program's serving "
                         f"layout:\n{got_def}\nvs\n{want_def}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight leaf {a.shape} {a.dtype} differs from the "
                             f"program's {b.shape} {b.dtype}")
    return cfg, params


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


def forward(mc: dict, g: int, control: bool, block: int, w: dict, tokens, rows):
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
    packed, scales = w[HEAD]["packed"], w[HEAD]["scales"]
    vocab = packed.shape[-1]
    nb = next(n for n in (8, 4, 2, 1) if vocab % n == 0)  # head in vocab blocks
    vb = vocab // nb

    def head(i):
        p = jax.lax.dynamic_slice_in_dim(packed, i * vb, vb, axis=-1)
        s = jax.lax.dynamic_slice_in_dim(scales, i * vb, vb, axis=-1)
        return _mm(hsel, unpack(p, s, g), control)

    out = jax.lax.map(head, jnp.arange(nb))  # (nb, N, vb)
    return jnp.moveaxis(out, 0, 1).reshape(hsel.shape[0], vocab)
