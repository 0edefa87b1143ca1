"""Pallas/VMEM budget pass: every schedule this repo can resolve must fit.

Two scopes:

1. **persisted autotune tables** — every entry of the checked-in
   ``autotune_table.json`` (and the user cache, if present) re-validated
   through ``kernels/autotune.py::validate_entry``: parseable key, positive
   block pair, the kernels' divisibility contract, and — for real-hardware
   backends — the impl's registered per-grid-step VMEM estimate under the
   ``kernels/introspect.py`` budget. (``autotune.py`` also enforces this at
   load time; the pass exists so CI fails on a bad *checked-in* table even
   if no code path loads it.)
2. **config sweep** — for every (registered config × quantized format × tp)
   cell, every QuantizedTensor leaf's matmul shape (global and per-device
   local), padded exactly as ``core/formats.py::_pallas_matvec`` pads it
   (B→sublane, o→lane block), resolved through ``autotune.get_blocks``
   with measurement off — i.e. the schedule serving would actually pick on
   a table miss — then priced against the budget for each of the format's
   kernels. This is the "would the real model's shapes compile on TPU"
   gate that no CPU test exercises.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax

from repro.analysis.staticcheck import PassResult, Violation
from repro.core.qtensor import QuantizedTensor
from repro.kernels import autotune, introspect

_SUBLANE = 8
_DECODE_B = 1  # decode batch before sublane padding


def validate_tables() -> Tuple[int, List[Violation]]:
    checked, violations = 0, []
    paths = [autotune._TABLE_PATH, autotune._user_cache_path()]
    for path in filter(None, paths):
        try:
            table = autotune._load_table(path)
        except ValueError as e:
            violations.append(Violation("vmem/table", path, str(e)))
            continue
        for key, blocks in table.items():
            checked += 1
            try:
                autotune.validate_entry(key, blocks, path=path)
            except ValueError as e:
                violations.append(Violation("vmem/table", path, str(e)))
    return checked, violations


def _leaf_shapes(arch: str, fmt: str, tp: int):
    """(k, o, q, g, leaf path) for every quantized matmul the cell runs,
    global and — for sharded leaves — per-device local."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.parallel.sharding import MeshAxes
    from repro.parallel.tp import _COLUMN_PARALLEL, _ROW_PARALLEL
    from repro.quant.quantize import QuantPolicy, quantized_structs

    cfg = get_config(arch)
    structs = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    structs = quantized_structs(structs, QuantPolicy(3, g=128, fmt=fmt))
    is_qt = lambda x: isinstance(x, QuantizedTensor)
    flat, _ = jax.tree_util.tree_flatten_with_path(structs, is_leaf=is_qt)
    out = []
    for path, leaf in flat:
        if not isinstance(leaf, QuantizedTensor):
            continue
        name = str(getattr(path[-1], "key", path[-1]))
        where = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((leaf.k, leaf.o, leaf.q, leaf.g, where))
        if tp > 1 and name in _COLUMN_PARALLEL and leaf.o % tp == 0:
            out.append((leaf.k, leaf.o // tp, leaf.q, leaf.g, f"{where} (local)"))
        if tp > 1 and name in _ROW_PARALLEL and leaf.k % (leaf.g * tp) == 0:
            out.append((leaf.k // tp, leaf.o, leaf.q, leaf.g, f"{where} (local)"))
    return out


def sweep_configs(
    *,
    archs: Optional[Sequence[str]] = None,
    fmts: Optional[Sequence[str]] = None,
    tps: Sequence[int] = (1, 2, 4),
) -> Tuple[int, List[Violation], List[str]]:
    from repro.configs import ARCH_IDS
    from repro.core.formats import get_format

    checked, violations, skips = 0, [], []
    budget = introspect.vmem_budget()
    fmts = [
        f
        for f in (fmts or ("bcq", "uniform", "dequant", "codebook", "ternary"))
        if f != "dense"
    ]
    for arch in archs or ARCH_IDS:
        for fmt in fmts:
            impls = get_format(fmt).impls
            for tp in tps:
                cell = f"{arch}/{fmt}/tp{tp}"
                try:
                    shapes = _leaf_shapes(arch, fmt, tp)
                except (NotImplementedError, ValueError) as e:
                    skips.append(f"{cell}: {str(e).splitlines()[0]}")
                    continue
                seen = set()
                for k, o, q, g, where in shapes:
                    o_pad = autotune.padded_o(o)
                    B = _DECODE_B + (-_DECODE_B % _SUBLANE)
                    sig = (k, o_pad, q, g)
                    if sig in seen:
                        continue
                    seen.add(sig)
                    for impl in impls:
                        checked += 1
                        bk, bo = autotune.get_blocks(
                            B=B, k=k, o=o_pad, q=q, g=g, impl=impl,
                            interpret=False, allow_measure=False,
                        )
                        if not bk or not bo:
                            violations.append(
                                Violation(
                                    "vmem/sweep", cell,
                                    f"{impl} has no valid tiling for leaf "
                                    f"{where} (k={k}, o={o_pad}, g={g})",
                                )
                            )
                            continue
                        try:
                            need = introspect.vmem_bytes(
                                impl, B=B, block_k=bk, block_o=bo, q=q, g=g
                            )
                        except KeyError:
                            violations.append(
                                Violation(
                                    "vmem/sweep", cell,
                                    f"{impl} has no registered VMEM estimator "
                                    "(kernels/introspect.py) — its schedules "
                                    "cannot be budget-checked",
                                )
                            )
                            continue
                        if need > budget:
                            violations.append(
                                Violation(
                                    "vmem/sweep", cell,
                                    f"{impl} blocks ({bk}, {bo}) for leaf {where} "
                                    f"(k={k}, o={o_pad}, q={q}, g={g}) need "
                                    f"~{need} B VMEM/grid-step, over the "
                                    f"{budget} B budget",
                                )
                            )
    return checked, violations, skips


def run(
    *,
    archs: Optional[Sequence[str]] = None,
    fmts: Optional[Sequence[str]] = None,
    tps: Sequence[int] = (1, 2, 4),
) -> PassResult:
    n_table, v_table = validate_tables()
    n_sweep, v_sweep, skips = sweep_configs(archs=archs, fmts=fmts, tps=tps)
    result = PassResult("vmem", checked=n_table + n_sweep, skipped=skips)
    result.violations.extend(v_table)
    result.violations.extend(v_sweep)
    return result
