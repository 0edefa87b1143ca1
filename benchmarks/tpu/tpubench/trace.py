"""Reduce a profiler trace to device busy time, program and kernel time, and
idle gaps attributed to what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events; everything else works on those events, so the reduction is tested on
a small recorded trace (``tests/data``) without a chip.

- Device ops: the ``XLA Ops`` line of the first TPU device plane.
- Programs: its ``XLA Modules`` line (one event per program execution, named
  after the jitted function).
- Host spans: events on host threads whose names start with one of
  ``HOST_PREFIXES`` (the engine's ``TraceAnnotation`` scopes and the
  benchmark's own).

Busy time is the union of the device op intervals inside the window; an op
belongs to a program when it starts inside one of that program's executions.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

HOST_PREFIXES = ("engine/", "bench/")
_WALK = 256  # spans looked back over for the one open at a gap
WINDOW_SPAN = "bench/window"


@dataclasses.dataclass
class Ev:
    name: str
    start: int  # ns
    dur: int  # ns
    label: str = ""  # what op patterns match: kind and framework name

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: List[Ev]
    modules: List[Ev]
    host: List[Ev]
    t0: int
    t1: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        """From ``{"ops", "modules", "host": [[name, start, dur, label]],
        "t0", "t1"}``, the form of the recorded test trace."""
        def evs(xs):
            return [Ev(n, s, t, lab) for n, s, t, lab in xs]
        return cls(evs(d["ops"]), evs(d["modules"]), evs(d["host"]), d["t0"], d["t1"])


def _stat_label(ev) -> str:
    """The op's kind and, where the trace has it, its framework name (the
    jitted scopes it was traced in): never its operands, which name other
    ops."""
    parts = [op_kind(ev.name)]
    try:
        parts += [str(v) for k, v in ev.stats if k == "tf_op"]
    except Exception:  # noqa: BLE001 - stats are optional
        pass
    return " ".join(parts)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    tpus = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: int(re.sub(r"\D", "", p.name.split(":")[-1]) or 0))
    if not tpus:
        raise ValueError(f"no TPU device plane in the trace: {[p.name for p in pd.planes]}")
    ops, modules, host = [], [], []
    for line in tpus[0].lines:
        if line.name == "XLA Ops":
            ops = [Ev(e.name, int(e.start_ns), int(e.duration_ns), _stat_label(e))
                   for e in line.events]
        elif line.name == "XLA Modules":
            modules = [Ev(e.name, int(e.start_ns), int(e.duration_ns), e.name)
                       for e in line.events]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIXES):
                    host.append(Ev(e.name, int(e.start_ns), int(e.duration_ns), e.name))
    win = [e for e in host if e.name == WINDOW_SPAN]
    if win:
        t0, t1 = win[0].start, win[0].end
    else:
        every = ops + modules
        t0, t1 = min(e.start for e in every), max(e.end for e in every)
    return Trace(ops=ops, modules=modules, host=host, t0=t0, t1=t1)


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def _clip(evs: Iterable[Ev], t0: int, t1: int) -> List[Tuple[int, int]]:
    out = []
    for e in evs:
        s, t = max(e.start, t0), min(e.end, t1)
        if t > s:
            out.append((s, t))
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_s(tr: Trace) -> float:
    return sum(t - s for s, t in union(_clip(tr.ops, tr.t0, tr.t1))) / 1e9


def programs(tr: Trace, pattern: str) -> List[Ev]:
    """Executions inside the window of programs whose name matches."""
    rx = re.compile(pattern)
    return [e for e in tr.modules if rx.search(e.name) and tr.t0 <= e.start < tr.t1]


def program_s(tr: Trace, pattern: str) -> float:
    return sum(e.dur for e in programs(tr, pattern)) / 1e9


def ops_in(tr: Trace, op_pattern: str, program_pattern: Optional[str] = None) -> List[Ev]:
    """Device ops whose label matches, optionally only those that start
    inside an execution of a matching program."""
    rx = re.compile(op_pattern)
    ops = [e for e in tr.ops if rx.search(e.label) and tr.t0 <= e.start < tr.t1]
    if program_pattern is None:
        return ops
    spans = sorted((p.start, p.end) for p in programs(tr, program_pattern))
    import bisect

    starts = [s for s, _ in spans]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < spans[i][1]:
            out.append(e)
    return out


def ops_s(tr: Trace, op_pattern: str, program_pattern: Optional[str] = None) -> float:
    return sum(e.dur for e in ops_in(tr, op_pattern, program_pattern)) / 1e9


def op_kind(name: str) -> str:
    """``%bcq_mm.38 = f32[...] custom-call(...)`` -> ``bcq_mm``."""
    return re.sub(r"\.\d+$", "", name.split(" = ")[0].strip().lstrip("%"))


def leaves(ops: List[Ev]) -> List[Ev]:
    """The ops that contain no other op: a ``while`` or ``call`` op spans the
    ops of its body on the same line."""
    ops = sorted(ops, key=lambda e: (e.start, -e.dur))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt.start < e.end and nxt.end <= e.end)]


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The device ops that took most time in the window, summed by kind
    (the HLO op's name without its number); ops that contain others count
    through their contents."""
    acc: Dict[str, int] = {}
    for s, t, e in ((max(e.start, tr.t0), min(e.end, tr.t1), e) for e in leaves(tr.ops)):
        if t > s:
            kind = op_kind(e.name)
            acc[kind] = acc.get(kind, 0) + (t - s)
    return [[n, v / 1e9] for n, v in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> List[List]:
    """Device idle time in the window, summed by the innermost host span
    open at each gap's midpoint (``host idle`` where none was). Host spans
    nest, so the innermost open one is the latest-starting that still
    covers the midpoint."""
    import bisect

    busy = union(_clip(tr.ops, tr.t0, tr.t1))
    gaps, prev = [], tr.t0
    for s, t in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, t)
    if tr.t1 > prev:
        gaps.append((prev, tr.t1))
    host = sorted((e for e in tr.host if e.name != WINDOW_SPAN), key=lambda e: e.start)
    starts = [h.start for h in host]
    acc: Dict[str, int] = {}
    for s, t in gaps:
        mid = (s + t) // 2
        name = "host idle"
        i = bisect.bisect_right(starts, mid) - 1
        for j in range(i, max(i - _WALK, -1), -1):  # siblings before the parent
            if host[j].end > mid:
                name = host[j].name
                break
        acc[name] = acc.get(name, 0) + (t - s)
    return [[n, v / 1e9] for n, v in sorted(acc.items(), key=lambda x: -x[1])[:k]]
