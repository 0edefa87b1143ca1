"""The serving loop the window drives: open-loop arrivals into
``repro.infer.Scheduler`` over an ``Engine``.

Adapted from ``launch/serve.py::drive_continuous``, with a measured window:
requests arrive on their schedule from ``-preroll`` (so the window opens on a
loaded server), the window is ``[0, seconds)``, and after it closes the loop
serves on, arrivals included, until every request that arrived in the window
has its first token (or ``drain_max_s`` has passed). Each request is timed
from its scheduled arrival, so a late generator or a stall counts against the
server. Token times are taken when the scheduler hands the tokens over at a
chunk boundary.

A traced run counts only ``[0, count_s)`` and keeps its profiler running
until every request that arrived in that span has its first token, so the
profiler's write-out stalls no request the per-layer metrics count.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np


@dataclasses.dataclass
class Record:
    arrival: float  # scheduled, seconds from the window's start
    plen: int
    max_new: int
    submitted: Optional[float] = None
    rid: Optional[int] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: Optional[np.ndarray] = None  # set when finished
    state: str = "queued"
    admitted: Optional[float] = None  # the scheduler began its prefill

    @property
    def first(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


@dataclasses.dataclass
class Window:
    """What the loop saw. Times are seconds from the window's start."""

    seconds: float
    count_s: float  # the counted span is [0, count_s)
    records: List[Record]
    opened_at: float  # the step boundary at which the window opened
    closed_at: float  # the step boundary at which the counted span closed
    counters: Dict[str, float]  # scheduler counter deltas over the counted span
    prefill_chunks: List[tuple]  # (pos, n_tokens, prompt_len) of each chunk in it
    compiles_in_window: int
    compile_s_in_window: float
    loop_start: float  # perf_counter at the first scheduled arrival
    drained: bool
    backlog: tuple  # requests queued or mid-prefill at the window's open and close
    slow_steps: List[dict]  # the window's longest scheduler steps, longest first

    def in_window(self) -> List[Record]:
        return [r for r in self.records if 0.0 <= r.arrival < self.seconds]

    def counted(self) -> List[Record]:
        return [r for r in self.records if 0.0 <= r.arrival < self.count_s]


class _CompileCounter:
    """Counts backend compilations while armed (JAX's monitoring events)."""

    def __init__(self):
        self.armed = False
        self.n = 0
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.armed and "backend_compile" in event:
            self.n += 1
            self.s += duration


# JAX's monitoring listeners are process-wide and cannot be taken back, so one
# counter is registered per process and re-armed by each serve() call.
_COMPILES = None
SLOW_STEPS = 3  # scheduler steps kept for the run's notes


def serve(engine, arrivals, server: dict, seconds: float, *,
          on_open: Callable[[], None] = lambda: None,
          on_close: Callable[[], None] = lambda: None,
          on_settled: Callable[[], None] = lambda: None,
          count_s: Optional[float] = None, drain_max_s: float = 60.0) -> Window:
    """Serve ``arrivals``; the window is ``[0, seconds)``. Counters, prefill
    chunks and ``on_open``/``on_close`` span ``[0, count_s)`` (default: the
    window), so a profiler can cover the first part of it; ``on_settled``
    runs once every request that arrived in that span has its first token
    (or has failed), and at the latest when the loop ends."""
    from repro.infer import Request, Scheduler

    global _COMPILES
    if _COMPILES is None:
        _COMPILES = _CompileCounter()
    _COMPILES.n, _COMPILES.s = 0, 0.0
    records = [Record(a.at, int(a.prompt.size), a.max_new_tokens) for a in arrivals]
    by_rid: Dict[int, Record] = {}
    clock = time.perf_counter
    t0 = 0.0

    def on_tokens(rid, toks):
        rec = by_rid[rid]
        now = clock() - t0
        rec.token_times.extend([now] * len(toks))

    sched = Scheduler(engine, n_slots=server["n_slots"], chunk=server["decode_chunk"],
                      prefill_chunk=server["prefill_chunk"], max_queue=None,
                      on_tokens=on_tokens, clock=clock)

    window_open = False
    prefill_chunks: List[tuple] = []
    advance = engine.advance_admission

    def counted_advance(pending, budget=None):
        pos = pending.pos
        n = advance(pending, budget)
        if window_open:
            prefill_chunks.append((pos, n, pending.plen))
        return n

    engine.advance_admission = counted_advance

    def snapshot():
        return {"decode_steps": sched.decode_steps, "steps_active": sched.steps_active,
                "retries": sched.counters["retries"],
                "backlog": len(sched.queue) + len(sched._pending)}

    preroll = max(0.0, -min(a.at for a in arrivals))
    loop_start = clock()
    t0 = loop_start + preroll
    i, n = 0, len(arrivals)
    opened_at = closed_at = None
    snap0 = snap1 = None
    window = [r for r in records if 0.0 <= r.arrival < seconds]
    count_s = seconds if count_s is None else min(count_s, seconds)
    counted = [r for r in window if r.arrival < count_s]
    settled = drained = False
    steps: List[dict] = []
    try:
        while True:
            now = clock() - t0
            if opened_at is None and now >= 0.0:
                jax.block_until_ready(sched.slots)
                on_open()
                snap0, window_open = snapshot(), True
                _COMPILES.armed = True
                opened_at = clock() - t0
            if opened_at is not None and closed_at is None and now >= count_s:
                jax.block_until_ready(sched.slots)
                window_open = _COMPILES.armed = False
                snap1 = snapshot()
                closed_at = clock() - t0
                on_close()
            if closed_at is not None and not settled and all(
                    r.first is not None or r.state != "queued" for r in counted):
                settled = True
                on_settled()
            if closed_at is not None and now >= seconds:
                drained = all(r.first is not None or r.state not in ("queued",)
                              for r in window)
                if drained or now > seconds + drain_max_s:
                    break
            while i < n and arrivals[i].at <= now:
                a, rec = arrivals[i], records[i]
                rid = sched.submit(Request(prompt=a.prompt, max_new_tokens=a.max_new_tokens,
                                           temperature=0.0, seed=i))
                rec.rid, rec.submitted = rid, clock() - t0
                by_rid[rid] = rec
                i += 1
            if sched.idle:
                nxt = arrivals[i].at if i < n else seconds + drain_max_s
                if opened_at is None:
                    nxt = min(nxt, 0.0)
                elif closed_at is None:
                    nxt = min(nxt, count_s)
                elif now < seconds:
                    nxt = min(nxt, seconds)
                time.sleep(max(0.0, min(nxt - (clock() - t0), 0.05)))
                continue
            before = {"at": clock() - t0, "queued": len(sched.queue),
                      "prefilling": len(sched._pending), "decoding": sched.n_active}
            with jax.profiler.TraceAnnotation("bench/scheduler_step"):
                done = sched.step()
            if 0.0 <= before["at"] < seconds:
                before["s"] = clock() - t0 - before["at"]
                steps.append(before)
            for c in done:
                rec = by_rid[c.rid]
                rec.tokens, rec.state = c.new_tokens, "finished"
            for rid, out in sched.outcomes.items():
                rec = by_rid[rid]
                if out.state.terminal and rec.state not in ("finished", "failed"):
                    rec.state = "failed"
    finally:
        engine.advance_admission = advance
        _COMPILES.armed = False
        if not settled:
            on_settled()
    for rid, out in sched.outcomes.items():
        rec = by_rid[rid]
        rec.admitted = next((at - t0 for state, at in out.history
                             if state.name == "PREFILLING"), None)
        if rec.state == "queued" and out.state.name not in ("QUEUED",):
            rec.state = out.state.value
    backlog = (snap0.pop("backlog"), snap1.pop("backlog"))
    counters = {k: snap1[k] - snap0[k] for k in snap0}
    counters["n_slots"] = server["n_slots"]
    del sched
    return Window(seconds=seconds, count_s=count_s, records=records, opened_at=opened_at,
                  closed_at=closed_at, counters=counters, prefill_chunks=prefill_chunks,
                  compiles_in_window=_COMPILES.n, compile_s_in_window=_COMPILES.s,
                  loop_start=loop_start, drained=drained, backlog=backlog,
                  slow_steps=sorted(steps, key=lambda d: -d["s"])[:SLOW_STEPS])
