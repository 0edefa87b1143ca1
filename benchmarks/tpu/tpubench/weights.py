"""Packed BCQ weights made on the device from the run's seed.

A deployment loads a quantized checkpoint; it does not run the BCQ solver. So
the benchmark fills the packed leaves directly: sign planes uniform at random
and per-group scales of the magnitude the repo's alternating solver gives on
Gaussian weights of std ``1/sqrt(k)`` (measured with ``core.bcq.quantize_bcq``
at q=3, g=128, 4 iterations: plane means 0.973, 0.565, 0.282 and standard
deviations 0.074, 0.057, 0.037, in units of ``1/sqrt(k)``).

The weights live in a plain dict that belongs to the benchmark (the layout the
float32 reference reads). The layout on the device is the serving one: QKV
and gate/up concatenated along the output dim, sign planes packed 8 to a byte
along k, least significant bit first. :func:`program_params` wraps the same
buffers in the program's pytree and checks it against the program's own
serving layout, so no byte is copied.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SCALE_MEAN = (0.973, 0.565, 0.282)  # per plane, x 1/sqrt(k)
SCALE_STD = (0.074, 0.057, 0.037)
EMBED_STD = 0.02


def dims(mc: dict) -> Dict[str, int]:
    q_dim = mc["n_heads"] * mc["d_head"]
    kv_dim = mc["n_kv_heads"] * mc["d_head"]
    return dict(D=mc["d_model"], F=mc["d_ff"], V=mc["vocab"], L=mc["n_layers"],
                Q=q_dim, KV=kv_dim)


def linears(mc: dict) -> List[Tuple[str, int, int, bool]]:
    """``(name, k, o, per_layer)`` of every quantized linear, in the serving
    layout (fused QKV and gate/up)."""
    d = dims(mc)
    return [
        ("wqkv", d["D"], d["Q"] + 2 * d["KV"], True),
        ("wo", d["Q"], d["D"], True),
        ("w_gate_up", d["D"], 2 * d["F"], True),
        ("w_down", d["F"], d["D"], True),
        ("lm_head", d["D"], d["V"], False),
    ]


def jax_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole-number seed (also past 32 bits)."""
    state = np.random.SeedSequence([int(seed), stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(state) & 0x7FFFFFFF)


def _bcq_leaf(key, lead: Tuple[int, ...], k: int, o: int, q: int, g: int, sdt):
    def one(kk):
        kb, ks = jax.random.split(kk)
        packed = jax.random.bits(kb, (q, k // 8, o), jnp.uint8)
        z = jax.random.normal(ks, (q, k // g, o), jnp.float32)
        mean = jnp.asarray(SCALE_MEAN[:q], jnp.float32)[:, None, None]
        std = jnp.asarray(SCALE_STD[:q], jnp.float32)[:, None, None]
        scales = jnp.abs(mean + std * z) / np.sqrt(k)
        return packed, scales.astype(sdt)

    if not lead:
        packed, scales = one(key)
    else:
        packed, scales = jax.lax.map(one, jax.random.split(key, lead[0]))
    return {"packed": packed, "scales": scales}


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
        for i, (name, k, o, per_layer) in enumerate(linears(mc)):
            lead = (d["L"],) if per_layer else ()
            leaf = _bcq_leaf(keys[i], lead, k, o, q, g, sdt)
            (layers if per_layer else out)[name] = leaf
        out["layers"] = layers
        out["embed"] = (jax.random.normal(keys[7], (d["V"], d["D"]), jnp.float32)
                        * EMBED_STD).astype(pdt)
        return out

    return fill


def make_weights(config: dict, seed: int) -> dict:
    """The whole model's weights, in one jitted call on the default device."""
    return jax.jit(weights_fn(config))(jax_key(seed, 0))


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
              "lm_head": qt(weights["lm_head"], "lm_head"), "embed": weights["embed"]}

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


def nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))
