"""Dtype-flow pass: packed planes stay integer until the Pallas kernel.

The paper's central requirement (§III, "no dequantization overhead") is a
dataflow property of the program: the ``uint8`` packed bit planes must flow
from the ``QuantizedTensor`` leaves into ``pallas_call`` **still integer-
typed**. A ``convert_element_type`` to f32/bf16 on a packed operand outside
a kernel means some code path materialised (part of) the dense weight in
HBM — numerically identical, memory-traffic catastrophic.

The check is classic forward taint propagation over the decode-step jaxpr
(traced under ``impl_mode("deploy")`` so the program under test is the
Pallas deployment, not the CPU ref oracle whose dequantize is the point):

- **sources** — top-level invars with ``uint8`` avals (the packed planes
  are this repo's only uint8 leaves; caches are int8, tokens int32);
- **propagation** — any eqn with a tainted operand taints its
  integer-dtype outputs; higher-order prims (pjit/scan/while/cond/
  shard_map/remat/custom_*) map taint positionally through their
  sub-jaxprs, scan/while carries to a fixpoint;
- **sinks** — ``pallas_call`` consumes taint (its outputs are activations;
  inside the kernel integer→float is exactly the fused dequant-in-VMEM the
  design prescribes);
- **violations** — a tainted operand reaching any eqn with a floating
  output outside a kernel, reported with the eqn, its source line, and the
  originating leaf (recovered from the harness shape index).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

from jax.extend import core as jcore
import jax.numpy as jnp
from jax._src import source_info_util

from repro.analysis.staticcheck import PassResult, Violation
from repro.analysis.staticcheck.harness import TraceCell

_SINK_PRIMS = frozenset({"pallas_call"})


def _is_float(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and jnp.issubdtype(dtype, jnp.floating)


def _is_int(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and jnp.issubdtype(dtype, jnp.integer)


def _src(eqn) -> str:
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    return f"{frame.file_name}:{frame.start_line}" if frame else "?"


@dataclasses.dataclass
class _Analysis:
    where: str
    violations: List[Violation]

    def flag(self, eqn, origin: str) -> None:
        out_dtypes = sorted(
            {str(v.aval.dtype) for v in eqn.outvars if _is_float(v.aval)}
        )
        self.violations.append(
            Violation(
                "dtypeflow", self.where,
                f"packed plane from {origin} reaches floating "
                f"({'/'.join(out_dtypes)}) output via {eqn.primitive.name} "
                f"at {_src(eqn)} outside any Pallas kernel — the dense "
                "weight is being materialised in HBM",
            )
        )


def _sub_jaxpr(obj):
    if isinstance(obj, jcore.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, jcore.Jaxpr):
        return obj
    return None


def _propagate(jaxpr, taint_in: List[Optional[str]], an: _Analysis) -> List[Optional[str]]:
    """Run taint (origin-name or None per var) through one jaxpr's eqns;
    returns per-outvar taint. ``taint_in`` aligns with ``jaxpr.invars``."""
    taint: Dict[object, str] = {}
    for var, t in zip(jaxpr.invars, taint_in):
        if t is not None:
            taint[var] = t

    def tget(atom) -> Optional[str]:
        if isinstance(atom, jcore.Literal):
            return None  # constants are never packed planes
        return taint.get(atom)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        in_taints = [tget(v) for v in eqn.invars]
        origin = next((t for t in in_taints if t is not None), None)

        if name in _SINK_PRIMS:
            continue  # kernel entry: taint consumed, outputs are activations

        sub = None
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = _sub_jaxpr(eqn.params.get(key))
            if sub is not None:
                break

        if name in ("scan", "while"):
            out_taints = _loop_taint(eqn, in_taints, an)
        elif name == "cond":
            out_taints = [None] * len(eqn.outvars)
            for br in eqn.params.get("branches", ()):
                sj = _sub_jaxpr(br)
                if sj is None:
                    continue
                br_out = _propagate(sj, in_taints[1:], an)
                out_taints = [a or b for a, b in zip(out_taints, br_out)]
        elif sub is not None and len(sub.invars) == len(eqn.invars):
            out_taints = _propagate(sub, in_taints, an)
            if len(out_taints) != len(eqn.outvars):
                out_taints = [origin] * len(eqn.outvars)
        elif origin is None:
            continue
        else:
            # first-order eqn with a tainted operand: integer outputs stay
            # tainted; a floating output is the violation this pass exists for
            out_taints = []
            flagged = False
            for outvar in eqn.outvars:
                if _is_float(outvar.aval):
                    if not flagged:
                        an.flag(eqn, origin)
                        flagged = True
                    out_taints.append(None)
                elif _is_int(outvar.aval):
                    out_taints.append(origin)
                else:
                    out_taints.append(None)  # bool/etc: comparisons launder

        for outvar, t in zip(eqn.outvars, out_taints):
            if t is not None:
                taint[outvar] = t
    return [tget(v) for v in jaxpr.outvars]


def _loop_taint(eqn, in_taints: List[Optional[str]], an: _Analysis) -> List[Optional[str]]:
    """Fixpoint taint for scan/while carries (a carry slot tainted on any
    iteration is tainted on all)."""
    name = eqn.primitive.name
    if name == "scan":
        body = _sub_jaxpr(eqn.params["jaxpr"])
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        consts, carry, xs = (
            in_taints[:nc], in_taints[nc : nc + ncar], in_taints[nc + ncar :]
        )
        quiet = _Analysis(an.where, [])  # only the converged pass reports
        for _ in range(len(carry) + 1):
            body_out = _propagate(body, consts + carry + xs, quiet)
            new_carry = [a or b for a, b in zip(carry, body_out[:ncar])]
            if new_carry == carry:
                break
            carry = new_carry
        body_out = _propagate(body, consts + carry + xs, an)
        return body_out[:ncar] + body_out[ncar:]
    # while: invars = cond_consts + body_consts + carry
    cond_j = _sub_jaxpr(eqn.params["cond_jaxpr"])
    body_j = _sub_jaxpr(eqn.params["body_jaxpr"])
    cn = eqn.params.get("cond_nconsts", 0)
    bn = eqn.params.get("body_nconsts", 0)
    cconsts = in_taints[:cn]
    bconsts = in_taints[cn : cn + bn]
    carry = in_taints[cn + bn :]
    quiet = _Analysis(an.where, [])
    for _ in range(len(carry) + 1):
        body_out = _propagate(body_j, bconsts + carry, quiet)
        new_carry = [a or b for a, b in zip(carry, body_out)]
        if new_carry == carry:
            break
        carry = new_carry
    _propagate(cond_j, cconsts + carry, an)
    return _propagate(body_j, bconsts + carry, an)


def analyze(closed: jcore.ClosedJaxpr, cell_id: str, shape_index=None) -> List[Violation]:
    """Taint-check one traced program. Sources = uint8 top-level invars;
    origins are named via the harness shape index when available."""
    jaxpr = closed.jaxpr
    shape_index = shape_index or {}
    taint_in: List[Optional[str]] = []
    for var in jaxpr.invars:
        aval = var.aval
        if getattr(aval, "dtype", None) is not None and str(aval.dtype) == "uint8":
            shape = tuple(aval.shape)
            taint_in.append(
                shape_index.get(shape, f"uint8 leaf {shape}")
            )
        else:
            taint_in.append(None)
    an = _Analysis(cell_id, [])
    _propagate(jaxpr, taint_in, an)
    # de-duplicate: the same offending eqn inside a scanned layer body would
    # otherwise repeat per origin leaf
    seen, unique = set(), []
    for v in an.violations:
        key = (v.where, v.message)
        if key not in seen:
            seen.add(key)
            unique.append(v)
    return unique


def run(cells: Sequence[TraceCell]) -> PassResult:
    result = PassResult("dtypeflow", checked=0)
    for cell in cells:
        if cell.fmt == "dense":
            continue  # no packed planes to track
        result.checked += 1
        result.violations.extend(analyze(cell.closed, cell.cell_id, cell.shape_index))
    return result
