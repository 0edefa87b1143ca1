"""Tensor-parallel quantized serving: shard_map the BCQ decode stack.

The paper's multi-GPU latency model (§V.C) assumes the quantized GEMV shards
cleanly: group-wise BCQ keeps every scale next to the k-rows it scales, so a
weight split along either logical dim carries its packed planes *and* its
group scales with it and each device runs the same LUT/BCQ kernel on a
smaller problem — no dequantize-then-reshard step. This module turns that
into the serving topology (DESIGN.md §7):

- **column-parallel** (``wq``/``wk``/``wv``/``wqkv``, ``w_gate``/``w_up``/
  ``w_gate_up``, ``lm_head``): output dim over ``model``. Each device
  projects its own attention heads / FFN columns / vocab slice from the
  replicated activation — zero collectives. Fused multi-projection leaves
  (``wqkv``, ``w_gate_up``) need a **column re-layout first**: their output
  dim is ``[q | k | v]`` concatenated, and naively slicing ``o_total`` into
  N chunks would hand device 0 all of Q and device N-1 all of V. The fuser's
  ``o_total`` is split per-projection and re-interleaved so shard ``d`` holds
  ``[q_d | k_d | v_d]`` (:func:`relayout_fused_for_tp`) and the local
  ``linear_fused`` split keeps working with local dims.
- **row-parallel** (``wo``, ``w_down``): reduction dim over ``model``; local
  matmuls produce partial sums that ``psum`` back to the replicated residual
  stream (`models/layers.py::psum_partial`). Group scales shard with their
  groups, which requires ``(k / g) % tp == 0`` — checked loudly, below.
- **KV caches**: kv-head dim over ``model`` (``cache_specs(layout="heads")``)
  matching the column-parallel projections' local heads. Attention is then
  fully head-local; rope stays local too (it rotates ``(i, i + Dh/2)`` pairs
  *within* each head, which Dh-sharding would split across devices).
- **replicated**: norms, embeddings (token gather stays local), per-slot
  counters/PRNG/logits buffers, activations between blocks.

Collective count per decode step: one ``psum`` per attention block (after
``wo``), one per MLP (after ``w_down``), plus one ``all_gather`` of the
vocab-sharded logits — 2·L + 1 small (B, 1, D)-sized collectives, never a
weight or cache gather.

Divisibility is **strict**: :func:`tp_param_specs` raises a ``ValueError``
naming the leaf and the offending dims instead of quietly replicating (the
``_maybe`` fallback of the generic GSPMD rules) — under ``shard_map`` a
silently replicated weight would be consumed as if it were a local shard and
produce garbage, and a quietly-served replicated weight defeats the whole
point of sharding. Packed/scales spec derivation comes from the registered
format (``QuantFormat.tp_specs`` — DESIGN.md §2.4, subsuming the old
BCQ-only ``qt_specs_like`` group-divisibility logic); this module only
refuses to proceed when derivation had to drop an axis.

Entry point: :func:`shard_model` → ``(sharded_params, TPContext)``; the
engine calls ``TPContext.forward`` everywhere it used ``models.forward``
(`infer/engine.py::Engine(mesh=...)`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.formats import get_format
from repro.core.qtensor import QuantizedTensor
from repro.models.config import ModelConfig
from repro.parallel.compat import auto_mesh, mesh_axis_names_sizes, shard_map
from repro.parallel.ctx import tp_shard_region
from repro.parallel.sharding import MeshAxes, cache_specs

# leaves that split along the output dim (heads / FFN columns / vocab)
_COLUMN_PARALLEL = frozenset(
    {"wq", "wk", "wv", "wqkv", "w_gate", "w_up", "w_gate_up", "lm_head"}
)
# leaves that split along the reduction dim (partial sums psum'd back)
_ROW_PARALLEL = frozenset({"wo", "w_down"})
# block types the shard_map decode path supports. MoE is excluded for the
# same reason as slot serving (expert capacity couples batch rows — DESIGN.md
# §4); recurrent state mixes the full width inside the per-step scan, which
# would put a collective in every timestep (the measured slstm pathology in
# sharding._slstm_specs).
_TP_BLOCKS = frozenset({"attn", "local_attn", "cross"})


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _path_str(path) -> str:
    return "/".join(
        str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p))))
        for p in path
    )


def make_tp_mesh(tp: int, axis: str = "model"):
    """A 1-D ``(tp,)`` decode mesh over the first ``tp`` visible devices."""
    n_dev = len(jax.devices())
    if n_dev < tp:
        raise RuntimeError(
            f"--tp {tp} needs {tp} XLA devices but only {n_dev} are visible; "
            f"on a CPU host set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={tp} before the first jax call"
        )
    return auto_mesh((tp,), (axis,))


# ---------------------------------------------------------------------------
# strict TP spec derivation (walks the ACTUAL — possibly fused — param tree)
# ---------------------------------------------------------------------------


def _require_div(dim: int, n: int, where: str, what: str, hint: str = "") -> None:
    if dim % n:
        raise ValueError(
            f"TP: cannot shard {where}: {what}={dim} is not divisible by the "
            f"model mesh axis size {n}{'; ' + hint if hint else ''} "
            "(refusing to serve a silently replicated weight)"
        )


def _qt_spec(path, qt: QuantizedTensor, ax: MeshAxes, kind: str) -> QuantizedTensor:
    n = ax.model_size
    where = _path_str(path)
    lead = qt.packed.ndim - 3  # layer-stack dims
    if kind == "col":
        _require_div(qt.o, n, where, f"output dim o (k={qt.k}, o={qt.o})")
        dense = P(*([None] * lead), None, ax.model)
    else:  # row
        _require_div(
            qt.packed.shape[-2], n, where,
            f"packed k/8 dim {qt.packed.shape[-2]} (k={qt.k})",
        )
        _require_div(
            qt.scales.shape[-2], n, where,
            f"group-scale k/g dim {qt.scales.shape[-2]} (k={qt.k}, g={qt.g})",
            hint=f"pick a group size dividing k/tp, i.e. g | {qt.k // n}",
        )
        dense = P(*([None] * lead), ax.model, None)
    # the format owns packed/scales spec derivation (QuantFormat.tp_specs —
    # group scales shard WITH their k-row groups, axes dropped if indivisible)
    spec = get_format(qt.fmt).tp_specs(dense, qt, ax)
    # belt-and-braces: qt_specs_like must not have dropped a required axis
    for plane, s in (("packed", spec.packed), ("scales", spec.scales)):
        if ax.model not in tuple(s):
            raise ValueError(
                f"TP: {qt.fmt!r} tp_specs replicated the {plane} plane of {where} "
                f"({dict(packed=qt.packed.shape, scales=qt.scales.shape)[plane]})"
                " — the dims above should have caught this"
            )
    return spec


def tp_param_specs(cfg: ModelConfig, params, ax: MeshAxes):
    """PartitionSpec tree for an actual (possibly decode-fused) param tree.

    Column/row assignment is by leaf name; everything else (norms, embed,
    biases) replicates. Raises — naming the leaf and dims — whenever a dim
    that must shard does not divide the model axis."""
    n = ax.model_size

    def visit(path, leaf):
        name = _leaf_name(path)
        where = _path_str(path)
        if isinstance(leaf, QuantizedTensor):
            if name in _COLUMN_PARALLEL:
                return _qt_spec(path, leaf, ax, "col")
            if name in _ROW_PARALLEL:
                return _qt_spec(path, leaf, ax, "row")
            return QuantizedTensor(
                packed=P(*([None] * leaf.packed.ndim)),
                scales=P(*([None] * leaf.scales.ndim)),
                g=leaf.g, k=leaf.k, o=leaf.o, fmt=leaf.fmt,
            )
        if name in _COLUMN_PARALLEL:
            _require_div(leaf.shape[-1], n, where, f"output dim {leaf.shape[-1]}")
            return P(*([None] * (leaf.ndim - 2)), None, ax.model)
        if name in _ROW_PARALLEL:
            _require_div(leaf.shape[-2], n, where, f"reduction dim {leaf.shape[-2]}")
            return P(*([None] * (leaf.ndim - 2)), ax.model, None)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(
        visit, params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )


# ---------------------------------------------------------------------------
# fused-leaf column re-layout
# ---------------------------------------------------------------------------


def _interleave_perm(out_dims: Sequence[int], n: int) -> np.ndarray:
    """Column permutation turning ``[p0 | p1 | ...]`` (projections whole) into
    ``[p0_0 p1_0 ... | p0_1 p1_1 ...]`` (device shards whole)."""
    starts, off = [], 0
    for d in out_dims:
        starts.append(off)
        off += d
    idx = []
    for dev in range(n):
        for st, d in zip(starts, out_dims):
            loc = d // n
            idx.extend(range(st + dev * loc, st + (dev + 1) * loc))
    return np.asarray(idx, np.int64)  # staticcheck: host-sync(host-built permutation index, no device values)


def _permute_cols(leaf, out_dims: Tuple[int, ...], n: int, where: str):
    for d in out_dims:
        _require_div(
            d, n, where,
            f"fused projection output dim {d} (of o_total split {out_dims})",
        )
    idx = _interleave_perm(out_dims, n)
    if isinstance(leaf, QuantizedTensor):
        return QuantizedTensor(
            packed=leaf.packed[..., idx], scales=leaf.scales[..., idx],
            g=leaf.g, k=leaf.k, o=leaf.o, fmt=leaf.fmt,
        )
    return leaf[..., idx]


def relayout_fused_for_tp(cfg: ModelConfig, params, n: int):
    """Re-interleave fused ``wqkv`` / ``w_gate_up`` output columns so each of
    the ``n`` contiguous shards holds that device's slice of EVERY projection
    (the local ``linear_fused`` split then uses local per-projection dims).

    Identity for ``n == 1`` and for unfused trees."""
    if n == 1:
        return params
    stages = []
    for si, (pattern, _) in enumerate(cfg.stages):
        stage_p = dict(params["stages"][si])
        for bi, _btype in enumerate(pattern):
            bp = dict(stage_p[f"b{bi}"])
            attn = bp.get("attn")
            if isinstance(attn, dict) and "wqkv" in attn:
                attn = dict(attn)
                attn["wqkv"] = _permute_cols(
                    attn["wqkv"], (cfg.q_dim, cfg.kv_dim, cfg.kv_dim), n,
                    f"stages/{si}/b{bi}/attn/wqkv",
                )
                bp["attn"] = attn
            mlp = bp.get("mlp")
            if isinstance(mlp, dict) and "w_gate_up" in mlp:
                mlp = dict(mlp)
                w = mlp["w_gate_up"]
                o = w.o if isinstance(w, QuantizedTensor) else w.shape[-1]
                mlp["w_gate_up"] = _permute_cols(
                    w, (o // 2, o // 2), n, f"stages/{si}/b{bi}/mlp/w_gate_up"
                )
                bp["mlp"] = mlp
            stage_p[f"b{bi}"] = bp
        stages.append(stage_p)
    return dict(params, stages=tuple(stages))


# ---------------------------------------------------------------------------
# the sharded-forward context
# ---------------------------------------------------------------------------


def _relocalize(params):
    """Fix QuantizedTensor static (k, o) to the per-device shard shapes.

    shard_map hands the body local ``packed``/``scales`` slices but the pytree
    statics still say the global shape; the kernels size their grids and
    output slicing from the statics, so rebuild them from the local planes
    (the format owns the packed-rows → k relation: ``QuantFormat.relocalize``)."""

    def fix(leaf):
        if isinstance(leaf, QuantizedTensor):
            return get_format(leaf.fmt).relocalize(leaf)
        return leaf

    return jax.tree.map(fix, params, is_leaf=lambda x: isinstance(x, QuantizedTensor))


class TPContext:
    """Per-engine tensor-parallel state: the mesh, the spec trees, the local
    view of the config, and the shard_map'd ``forward``."""

    def __init__(self, cfg: ModelConfig, mesh, axis: str = "model"):
        names, sizes = mesh_axis_names_sizes(mesh)
        if axis not in names:
            raise ValueError(f"mesh {names} has no {axis!r} axis")
        self.mesh = mesh
        self.axis_name = axis
        self.ax = MeshAxes((), None, axis, tuple(zip(names, sizes)))
        self.n = self.ax.model_size
        blocks = {bt for pattern, _ in cfg.stages for bt in pattern}
        bad = sorted(blocks - _TP_BLOCKS)
        if bad:
            raise NotImplementedError(
                f"tensor-parallel serving supports attention-family blocks "
                f"{sorted(_TP_BLOCKS)}; config {cfg.name!r} has {bad} "
                "(MoE couples batch rows through expert capacity; recurrent "
                "blocks would put a collective inside every scan timestep)"
            )
        if cfg.n_heads % self.n or cfg.n_kv_heads % self.n:
            raise ValueError(
                f"TP: config {cfg.name!r} heads (n_heads={cfg.n_heads}, "
                f"n_kv_heads={cfg.n_kv_heads}) not divisible by tp={self.n}"
            )
        self.cfg = cfg
        # the body computes with per-device head counts; d_head/q_dim/kv_dim
        # follow (q_dim = n_heads·d_head), everything else stays global
        self.cfg_local = dataclasses.replace(
            cfg, n_heads=cfg.n_heads // self.n, n_kv_heads=cfg.n_kv_heads // self.n
        )
        self.param_spec_tree = None  # set by shard_model
        self._cache_spec_tree = cache_specs(cfg, self.ax, 1, layout="heads")

    # -- placement ----------------------------------------------------------

    def _put(self, tree, specs):
        # QuantizedTensor nodes are placed as units (packed/scales each get
        # their spec) rather than flattened through jax.tree.map: a draft
        # tree's statics may legitimately differ from the spec tree's —
        # cross-format truncation re-tags leaves (ternary drafts are BCQ)
        # and slices the plane axis, while the plane/group dim specs still
        # apply verbatim.
        def put(x, s):
            if isinstance(x, QuantizedTensor):
                return QuantizedTensor(
                    packed=jax.device_put(
                        x.packed, NamedSharding(self.mesh, s.packed)
                    ),
                    scales=jax.device_put(
                        x.scales, NamedSharding(self.mesh, s.scales)
                    ),
                    g=x.g,
                    k=x.k,
                    o=x.o,
                    fmt=x.fmt,
                )
            return jax.device_put(x, NamedSharding(self.mesh, s))

        return jax.tree.map(
            put, tree, specs, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        )

    def place_params(self, params):
        """(Re-)commit a param tree to its TP sharding. Used for the params
        themselves and for ``truncate_params`` draft views — plane truncation
        slices the q axis (and may re-tag the format: ternary drafts are
        1-plane BCQ), never the sharded dim, so the spec tree of the full
        tree applies verbatim."""
        if self.param_spec_tree is None:
            raise RuntimeError("shard_model has not placed the params yet")
        return self._put(params, self.param_spec_tree)

    def _specs_like(self, params):
        """The param spec tree with QuantizedTensor statics re-tagged to match
        ``params``. Draft trees from cross-format truncation carry different
        static metadata than the target tree the specs were built from
        (ternary drafts are 1-plane BCQ) — the dim specs apply verbatim, but
        pytree-structure-sensitive consumers (shard_map in_specs) need the
        aux data to agree."""

        def fix(s, p):
            if isinstance(s, QuantizedTensor):
                return QuantizedTensor(
                    packed=s.packed, scales=s.scales, g=p.g, k=p.k, o=p.o,
                    fmt=p.fmt,
                )
            return s

        return jax.tree.map(
            fix, self.param_spec_tree, params,
            is_leaf=lambda x: isinstance(x, QuantizedTensor),
        )

    def shard_cache(self, cache):
        """Place a fresh ``init_cache`` tree with kv-heads over ``model``."""
        return self._put(cache, self.cache_spec_tree(cache))

    def cache_spec_tree(self, cache):
        # structure mirrors init_cache for this cfg; batch stays replicated
        # under the decode mesh (slots are requests, not shards)
        return self._cache_spec_tree

    # -- the sharded forward -------------------------------------------------

    def forward(
        self,
        params,
        *,
        tokens=None,
        embeddings=None,
        image_emb=None,
        cache=None,
        pos=None,
        logits_mode: str = "all",
        chunked_decode: bool = False,
        collect_states: bool = False,
    ):
        """Drop-in for ``functools.partial(models.forward, cfg)`` on the
        decode/serve paths: one shard_map region per forward, params/cache
        consumed as local shards, logits returned replicated (gathered)."""
        from repro.models.model import forward as _forward

        if cache is None:
            raise ValueError("TPContext.forward serves decode paths: pass a cache")
        arr_kw = {
            k: v
            for k, v in dict(
                tokens=tokens, embeddings=embeddings, image_emb=image_emb, pos=pos
            ).items()
            if v is not None
        }
        names = tuple(arr_kw)
        cspecs = self.cache_spec_tree(cache)
        cfg_local, axis = self.cfg_local, self.axis_name

        def body(params, cache, *arrs):
            params = _relocalize(params)
            with tp_shard_region(axis):
                return _forward(
                    cfg_local, params, cache=cache, logits_mode=logits_mode,
                    chunked_decode=chunked_decode, collect_states=collect_states,
                    **dict(zip(names, arrs)),
                )

        rep = lambda x: P(*([None] * jax.numpy.ndim(x)))
        fn = shard_map(
            body,
            mesh=self.mesh,
            in_specs=(self._specs_like(params), cspecs)
            + tuple(rep(v) for v in arr_kw.values()),
            out_specs=(P(None, None, None), cspecs, P()),
            check_vma=False,
        )
        return fn(params, cache, *arr_kw.values())


def shard_model(cfg: ModelConfig, params, mesh, *, axis: str = "model"):
    """Place a (possibly decode-fused) param tree tensor-parallel on ``mesh``.

    Returns ``(sharded_params, TPContext)``. Fused leaves are column-
    re-interleaved first so plain output-dim sharding hands each device its
    slice of every projection; QuantizedTensor leaves get packed/scales specs
    via ``qt_specs_like`` off the dense weight's spec. Any dim that must shard
    but does not divide the mesh axis raises (leaf + dims in the message)."""
    tpc = TPContext(cfg, mesh, axis=axis)
    params = relayout_fused_for_tp(cfg, params, tpc.n)
    specs = tp_param_specs(cfg, params, tpc.ax)
    tpc.param_spec_tree = specs
    return tpc._put(params, specs), tpc
