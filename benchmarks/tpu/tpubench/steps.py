"""Reductions over the scheduler's step spans and the engine's work counters
(DESIGN.md §11.1, §11.4), for a later metric reader to import unchanged.

The spans come from two places: a device trace loaded with the ``sched/``
host spans kept (:func:`load`), and a ring ``Tracer``'s events. A program
that records no such span or counter gives ``None`` (or an empty list),
never an error.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Iterable, List, Tuple

from tpubench import trace as tr

PHASES = "sched/"
STEP = "sched/step"
CHUNK = "sched/decode_chunk"


def host_spans(planes, prefixes: Tuple[str, ...]) -> List[tr.Ev]:
    """The events on the host planes of a ``ProfileData`` whose names start
    with one of ``prefixes``."""
    return [tr.Ev(e.name, int(e.start_ns), int(e.duration_ns), e.name)
            for plane in planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(prefixes)]


def load(trace_dir: str, prefixes: Tuple[str, ...] = (PHASES,)) -> tr.Trace:
    """``trace.load``, with the host spans under ``prefixes`` (none of its
    own ``HOST_PREFIXES``) kept as well."""
    from jax.profiler import ProfileData

    trc = tr.load(trace_dir)
    pd = ProfileData.from_file(tr.find_xplane(trace_dir))
    return dataclasses.replace(trc, host=trc.host + host_spans(pd.planes, prefixes))


def prefill_pad_pct(counters: dict):
    """Share of the prefill rows computed that were bucket padding, from
    deltas of ``Engine.counters``."""
    rows = counters.get("prefill_rows", 0)
    if not rows:
        return None
    return 100.0 * (rows - counters["prefill_tokens"]) / rows


def _overlap_ns(a, b) -> int:
    """Total overlap of two sorted lists of disjoint ``(start, end)``."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, t - s)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_ms_per_chunk(trc: tr.Trace):
    """Device idle time inside ``sched/step`` spans within the traced span,
    in ms per ``sched/decode_chunk`` span that starts there. Idle is the
    complement of the union of device ops, as in ``device.idle_pct``."""
    steps = tr.union(tr._clip((e for e in trc.host if e.name == STEP), trc.t0, trc.t1))
    chunks = sum(1 for e in trc.host if e.name == CHUNK and trc.t0 <= e.start < trc.t1)
    if not steps or not chunks:
        return None
    busy = tr.union(tr._clip(trc.ops, trc.t0, trc.t1))
    idle = sum(t - s for s, t in steps) - _overlap_ns(steps, busy)
    return idle / 1e6 / chunks


def _ring_spans(events: Iterable[tuple]) -> List[tuple]:
    """``(name, ts, dur, args)`` of a ring tracer's ``sched/*`` spans."""
    return [(name, ts, dur, args) for ph, name, _, _, ts, dur, args in events
            if ph == "X" and name.startswith(PHASES)]


def slow_steps(events, t0: float, t1: float, k: int = 3) -> List[dict]:
    """The ``k`` longest ``sched/step`` spans of a ring tracer's events that
    start in ``[t0, t1)``, longest first: when (seconds from ``t0``), how
    long, the step's args, and the seconds of each ``sched/*`` phase in it."""
    spans = _ring_spans(events)
    longest = sorted((s for s in spans if s[0] == STEP and t0 <= s[1] < t1),
                     key=lambda s: -s[2])[:k]
    out = []
    for _, ts, dur, args in longest:
        phases: dict = {}
        for name, s, d, _ in spans:
            if name != STEP and ts <= s and s + d <= ts + dur + 1e-9:
                phases[name] = phases.get(name, 0.0) + d
        out.append({"at": ts - t0, "s": dur, **(args or {}), "phases": phases})
    return out


def step_summary(events, t0: float, t1: float) -> dict:
    """The ``sched/step`` spans of a ring tracer's events that start in
    ``[t0, t1)``: how many, their median seconds, and the mean seconds per
    step of each ``sched/*`` phase."""
    spans = [s for s in _ring_spans(events) if t0 <= s[1] < t1]
    durs = sorted(d for name, _, d, _ in spans if name == STEP)
    if not durs:
        return {"n": 0}
    total: dict = {}
    for name, _, d, _ in spans:
        if name != STEP:
            total[name] = total.get(name, 0.0) + d
    return {"n": len(durs), "median_s": statistics.median(durs),
            "phase_mean_s": {k: v / len(durs) for k, v in total.items()}}
