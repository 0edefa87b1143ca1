"""Recursive jaxpr traversal shared by every trace-level pass.

``jax.make_jaxpr`` of the decode step yields a *static* program: the L-layer
stack is one ``scan`` eqn whose body appears once, TP regions are one
``shard_map`` eqn, Pallas kernels are opaque ``pallas_call`` eqns. The
walker flattens that nesting into a stream of :class:`EqnSite` records that
carry (a) the call-stack of enclosing higher-order primitives, (b) the
*dynamic repeat count* — the product of enclosing ``scan`` lengths — so a
census over the static program can assert dynamic counts (a psum inside the
L-step layer scan counts L times), and (c) user-source provenance for error
messages.

``pallas_call`` sub-jaxprs are NOT descended by default: the kernel body is
a different machine model (refs, grids) and its eqns would pollute
whole-program invariants like "no float cast of a packed operand" — the
kernel is exactly where integer planes legitimately become floats.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Tuple

from jax.extend import core as jcore
from jax._src import source_info_util

# higher-order primitive params that hold sub-jaxprs to descend into
_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr", "fun_jaxpr")


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One equation plus where it sits in the traced program."""

    eqn: jcore.JaxprEqn
    stack: Tuple[str, ...]  # enclosing higher-order prims, outermost first
    repeats: int  # product of enclosing scan lengths (dynamic multiplier)

    @property
    def prim(self) -> str:
        return self.eqn.primitive.name

    def source(self) -> str:
        """``file:line`` of the user frame that staged this eqn (or '?')."""
        frame = source_info_util.user_frame(self.eqn.source_info.traceback)
        if frame is None:
            return "?"
        return f"{frame.file_name}:{frame.start_line}"

    def describe(self) -> str:
        ctx = ">".join(self.stack) or "<top>"
        return f"{self.prim} at {self.source()} (in {ctx}, x{self.repeats})"


def _as_jaxpr(obj):
    """Raw ``Jaxpr`` from a sub-jaxpr param (raw or Closed), else None."""
    if isinstance(obj, jcore.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, jcore.Jaxpr):
        return obj
    return None


def walk(
    jaxpr, *, descend_pallas: bool = False, _stack: Tuple[str, ...] = (), _repeats: int = 1
) -> Iterator[EqnSite]:
    """Yield an :class:`EqnSite` for every eqn, recursing into sub-jaxprs.

    ``scan`` descent multiplies ``repeats`` by the scan ``length`` param;
    ``while`` bodies keep repeats unchanged (trip counts are dynamic — any
    per-iteration invariant must already hold for the body once).
    """
    inner = _as_jaxpr(jaxpr)
    if inner is None:
        raise TypeError(f"walk expects a (Closed)Jaxpr, got {type(jaxpr)!r}")
    for eqn in inner.eqns:
        yield EqnSite(eqn, _stack, _repeats)
        name = eqn.primitive.name
        if name == "pallas_call" and not descend_pallas:
            continue
        mult = _repeats
        if name == "scan":
            length = eqn.params.get("length")
            if isinstance(length, int):
                mult = _repeats * length
        for key in _SUBJAXPR_KEYS:
            sub = _as_jaxpr(eqn.params.get(key))
            if sub is not None:
                yield from walk(
                    sub, descend_pallas=descend_pallas,
                    _stack=_stack + (name,), _repeats=mult,
                )
        branches = eqn.params.get("branches")
        if branches:
            for br in branches:
                sub = _as_jaxpr(br)
                if sub is not None:
                    yield from walk(
                        sub, descend_pallas=descend_pallas,
                        _stack=_stack + (name,), _repeats=mult,
                    )


def aval_shape_dtype(var) -> Optional[Tuple[Tuple[int, ...], str]]:
    """(shape, dtype-name) of a jaxpr atom's aval, or None for literals
    without array avals."""
    aval = getattr(var, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return None
    return tuple(shape), str(dtype)
