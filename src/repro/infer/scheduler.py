"""Continuous-batching scheduler over the slot-batched decode path.

The paper's headline number is decode-phase throughput on a *serving*
workload (§V: OPT-175B token generation): the LUT/BCQ kernels only pay off
end-to-end if the decode batch stays fed. One-shot ``Engine.generate`` runs a
fixed batch in lockstep — every request waits for the longest one, and the
batch drains as requests finish. This module keeps a fixed-width decode batch
full instead (Orca-style continuous batching):

- requests wait in a **bounded admission queue**;
- the decode batch has ``n_slots`` **slots**; a free slot is filled by
  prefilling the next queued request (batch-1) and scatter-installing its KV
  rows, position counter, PRNG key and sampling params into the slot
  (``Engine.admit_slot``);
- decode runs in **chunks** of ``chunk`` scanned steps over the whole batch
  (``Engine.decode_slots``); per-slot active masks let requests finish
  mid-chunk without stalling neighbours;
- a finished slot is freed and refilled at the next chunk boundary.

Correctness contract (tests/test_scheduler.py): the interleaving is
*invisible* — each request's tokens are identical to running it alone through
``Engine.generate(prompt, max_new_tokens, temperature=..., seed=...)``. This
holds because batch rows are fully independent in the model forward (per-slot
positions, per-slot cache rows, per-slot PRNG streams) and the batched
per-row compute is bitwise equal to the batch-1 compute. MoE families are the
documented exception: expert-capacity dropping couples batch rows, so
continuous batching there is throughput-correct but not token-identical.

**Request lifecycle** (DESIGN.md §9, ``infer/lifecycle.py``): every request
runs an explicit validated state machine — QUEUED → PREFILLING → DECODING →
{FINISHED, CANCELLED, TIMED_OUT, FAILED}, with SHED for deadline-aware queue
shedding and a loud :class:`~repro.infer.lifecycle.QueueFullError` when the
bounded admission queue rejects a submit. The hardening invariant
(tests/test_lifecycle.py) extends the §4 contract to the unhappy path:
**whatever happens to any subset of requests — cancellation, deadline
expiry, injected dispatch failures, NaN-poisoned rows — every surviving
request's tokens stay bit-identical to an undisturbed run.** The mechanisms:

- **cancellation** (:meth:`Scheduler.cancel`, thread-safe to *flag*): the
  slot is reclaimed at the next chunk boundary (``Engine.release_slot`` — the
  row goes inactive, the next admission overwrites its whole state row);
- **deadlines**: per-request TTFT and total wall-clock deadlines enforced at
  chunk boundaries against the scheduler's injectable ``clock``; queued
  requests whose deadline already expired are SHED before wasting a prefill;
- **NaN/inf logit guard**: a per-chunk (B,)-bool device check; a non-finite
  row is FAILED and quarantined (slot scrubbed + refilled) while neighbours
  decode on untouched;
- **bounded retry with backoff** around every engine dispatch; a prefill
  failure quarantines only the admitting request, exhausted decode-chunk
  retries fail the *active* tenants and rebuild the slot state so queued
  requests still complete;
- **fault injection** (``infer/faults.py``): all of the above is
  deterministically testable by threading a :class:`FaultPlan` through the
  dispatch points.

**Stop tokens**: per-request ``Request.stop_tokens`` finish a row early —
host-side truncation at the chunk boundary (the stop token is the last one
kept), the slot frees immediately, and the completion is token-identical to
a solo ``generate`` truncated at the same position.

**Tensor-parallel serving** (``Scheduler(Engine(cfg, params, mesh=...))``,
DESIGN.md §7): the scheduler is sharding-agnostic — slots, admission and
budgets live on the host exactly as below, while every engine dispatch it
drives (admission prefill, decode chunks, speculative chunks) consumes
TP-sharded weights and KV caches under ``shard_map``. Greedy completions
stay bit-identical to the single-device engine; sampled completions remain
solo-identical *within* the same sharded engine (tests/test_tp_serve.py).

**Speculative serving** (``Scheduler(engine, speculate=SpecConfig(...))``,
DESIGN.md §5): decode dispatches become draft-verify-accept chunks — each
chunk commits 1..gamma+1 tokens per row instead of exactly one. Requests opt
in per row (``Request.speculate``); opted-out rows run one plain target step
per chunk with their solo-identical PRNG stream. Greedy speculative rows are
token-identical to solo plain ``generate``; sampled speculative rows follow
the exact target distribution but a different stream for the same seed
(rejection sampling consumes randomness differently). The request's first
token is sampled at admission (it comes from the target's own prefill
logits), so a chunk always has a pending token to verify behind.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence

import numpy as np

from repro.infer.engine import Engine
from repro.infer.faults import FaultPlan
from repro.infer.lifecycle import (
    QueueFullError,
    RequestLifecycle,
    RequestState,
    latency_summary,
)
from repro.infer.spans import span
from repro.infer.speculative import SpecConfig


class DispatchError(RuntimeError):
    """An engine dispatch kept failing after bounded retries."""


@dataclasses.dataclass
class Request:
    """One generation request. `seed`/`temperature` are per-request: mixed
    greedy and sampled requests share a batch. ``speculate`` opts this request
    in/out of speculative decoding when the scheduler runs a speculative slot
    batch (None → the scheduler's default: in); it is ignored otherwise.

    ``stop_tokens`` ends the generation early at the first matching token
    (kept, then the slot frees at the next chunk boundary).
    ``ttft_deadline_s`` / ``deadline_s`` are wall-clock budgets measured from
    submit: miss the first-token deadline or the total deadline and the
    request is TIMED_OUT (or SHED while still queued) at the next chunk
    boundary instead of occupying a slot forever."""

    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    speculate: Optional[bool] = None
    stop_tokens: Optional[Sequence[int]] = None
    ttft_deadline_s: Optional[float] = None
    deadline_s: Optional[float] = None
    rid: Optional[int] = None  # assigned at submit() if None

    def __post_init__(self):
        arr = np.asarray(self.prompt)  # staticcheck: host-sync(request validation on host input)
        if arr.dtype.kind not in "iu":
            # silent float->int32 casting would truncate values the caller
            # never meant as token ids
            raise ValueError(
                f"prompt must be integer token ids, got dtype {arr.dtype}"
            )
        self.prompt = arr.astype(np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # temperature feeds `logits / temperature` on device: NaN/inf would
        # poison sampling silently (NaN fails every `<= 0` greedy check and
        # then divides the logits), negative values would invert the
        # distribution. Exactly 0.0 means greedy by convention.
        if not np.isfinite(self.temperature):
            raise ValueError(
                f"temperature must be finite, got {self.temperature!r}"
            )
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy), got {self.temperature!r}"
            )
        self.temperature = float(self.temperature)
        # seed feeds jax.random.PRNGKey, whose C-long conversion overflows
        # outside int64 — catch it here with the limit named instead of
        # letting an OverflowError surface mid-admission (and reject
        # non-integral seeds before they'd be silently truncated)
        if isinstance(self.seed, bool) or not isinstance(
            self.seed, (int, np.integer)
        ):
            raise ValueError(f"seed must be an integer, got {self.seed!r}")
        if not (-(2**63) <= int(self.seed) < 2**63):
            raise ValueError(
                f"seed must fit in int64 (PRNGKey range "
                f"[-2**63, 2**63)), got {self.seed}"
            )
        self.seed = int(self.seed)
        if self.stop_tokens is not None:
            toks = tuple(int(t) for t in self.stop_tokens)
            if any(
                isinstance(t, bool) or not isinstance(t, (int, np.integer))
                for t in self.stop_tokens
            ):
                raise ValueError(
                    f"stop_tokens must be integer token ids, got "
                    f"{self.stop_tokens!r}"
                )
            self.stop_tokens = toks
        for name in ("ttft_deadline_s", "deadline_s"):
            v = getattr(self, name)
            if v is not None and not (np.isfinite(v) and v > 0):
                raise ValueError(f"{name} must be a positive number, got {v!r}")


@dataclasses.dataclass
class Completion:
    rid: int
    prompt: np.ndarray  # (prompt_len,)
    new_tokens: np.ndarray  # (<= max_new_tokens,) — shorter iff stopped early
    admitted_at_step: int  # scheduler decode-step counter at admission
    finished_at_step: int
    stopped: bool = False  # True iff ended on a stop token before the budget

    @property
    def tokens(self) -> np.ndarray:
        """prompt + generation, the same layout GenerationResult.tokens uses."""
        return np.concatenate([self.prompt, self.new_tokens])


class _Tenant:
    __slots__ = ("req", "emitted", "admitted_at_step", "stop")

    def __init__(self, req: Request, admitted_at_step: int):
        self.req = req
        self.emitted: List[int] = []
        self.admitted_at_step = admitted_at_step
        self.stop = frozenset(req.stop_tokens or ())


class Scheduler:
    """Continuous-batching front-end for one :class:`Engine`.

    >>> sched = Scheduler(engine, n_slots=4)
    >>> sched.submit(Request(prompt, max_new_tokens=16))
    >>> done = sched.run()   # or: sched.step() in a serving loop

    Lifecycle/robustness knobs (all have serving-sane defaults):

    - ``prefill_chunk`` (tokens) switches admission to *chunked prefill*
      (DESIGN.md §12): a free slot claims a queued request immediately, but
      its prompt prefills at most ``prefill_chunk`` tokens per scheduler
      step, interleaved with the decode chunks of active streams — a long
      prompt admission never stalls running requests for its whole prefill.
      Chunk dispatches are padded to power-of-two buckets so they compile
      once per bucket, not once per prompt length. Requires
      ``engine.supports_chunked_prefill``; ``None`` (default) keeps the
      synchronous whole-prompt admission.
    - ``max_queue`` bounds the admission queue; a full queue rejects at
      ``submit`` with :class:`QueueFullError` (None = unbounded, for trusted
      batch drivers only).
    - ``retries``/``backoff_s``: bounded exponential-backoff retry around
      every engine dispatch.
    - ``nan_guard``: per-chunk non-finite-logit check; poisoned rows are
      FAILED and their slot scrubbed, neighbours untouched.
    - ``faults``: a :class:`FaultPlan` threaded through the dispatch points
      (deterministic fault injection; None in production).
    - ``clock``/``sleep``: injectable time sources — deadlines and backoff
      are wall-clock quantities, tests drive them with ``faults.StepClock``.
    - ``on_tokens(rid, tokens)``: streaming callback, fired at every chunk
      boundary with the request's newly visible (post-truncation) tokens.
    - ``on_event(record)``: fired at every terminal transition with the
      request's :class:`RequestLifecycle` (partial tokens attached).
    - ``tracer``: an optional :class:`repro.obs.trace.Tracer`; without
      one the scheduler records on its engine's ``tracer``, if any, so the
      step's spans and the engine's dispatches share one ring. Lifecycle
      spans (queued/prefill/decode per request) are replayed from the
      record's *stored* timestamps at the terminal transition — tracing
      consumes zero extra scheduler-clock readings, so StepClock-driven
      deadline behaviour is untouched. The step's phase spans
      (``sched/*``, see :meth:`step`) read the tracer's own clock
      (``time.monotonic`` unless injected), a separate timebase by design
      (DESIGN.md §11).
    - ``metrics``: an optional :class:`repro.obs.metrics.MetricsRegistry`.
      Every ``counters`` increment goes through one helper that also bumps
      the registry's ``serve_<key>_total`` series, so the exported metrics
      agree with :meth:`summary` by construction, plus queue-depth /
      slot-occupancy gauges and TTFT/TPOT/e2e histograms.

    Both hooks observe strictly *between* engine dispatches; instrumented
    serving is bit-identical to uninstrumented (tests/test_obs.py).

    Threading: the scheduler itself is single-threaded — drive ``submit``/
    ``step``/``run`` from one thread (the async server pumps it from a
    dedicated thread). :meth:`cancel` only *flags*; the flag is applied at
    the next chunk boundary, which makes it safe to call from notification
    contexts as long as submits/steps stay on the pump thread.
    """

    def __init__(
        self,
        engine: Engine,
        n_slots: int = 4,
        chunk: int = 8,
        speculate: Optional[SpecConfig] = None,
        *,
        prefill_chunk: Optional[int] = None,
        max_queue: Optional[int] = 64,
        retries: int = 2,
        backoff_s: float = 0.05,
        nan_guard: bool = True,
        faults: Optional[FaultPlan] = None,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
        on_tokens: Optional[Callable[[int, List[int]], None]] = None,
        on_event: Optional[Callable[[RequestLifecycle], None]] = None,
        tracer=None,
        metrics=None,
    ):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None for unbounded)")
        if retries < 0:
            raise ValueError("retries must be >= 0")
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(
                    "prefill_chunk must be >= 1 (or None for synchronous "
                    "whole-prompt admission)"
                )
            if not engine.supports_chunked_prefill:
                raise ValueError(
                    "chunked prefill is unsupported for this engine's "
                    "architecture (ring-buffer/recurrent models pad-clobber "
                    "— see Engine.supports_chunked_prefill); use "
                    "prefill_chunk=None"
                )
        self.prefill_chunk = prefill_chunk
        self.engine = engine
        self.n_slots = n_slots
        self.chunk = chunk
        self.speculate = speculate
        self.max_queue = max_queue
        self.retries = retries
        self.backoff_s = backoff_s
        self.nan_guard = nan_guard
        self.faults = faults
        self.on_tokens = on_tokens
        self.on_event = on_event
        self._clock = clock
        self._sleep = sleep
        self.slots = engine.init_slots(n_slots, speculate=speculate)
        self.queue: Deque[Request] = deque()
        self._tenants: List[Optional[_Tenant]] = [None] * n_slots
        # chunked-prefill admissions in flight: slot -> (Request, pending)
        self._pending: Dict[int, tuple] = {}
        self.outcomes: Dict[int, RequestLifecycle] = {}
        self._pending_cancel: Dict[int, str] = {}
        self.decode_steps = 0  # total chunked decode steps executed
        self.steps_active = 0  # sum over steps of active slots (utilisation)
        self.chunk_rows = 0  # spec mode: row-chunks dispatched (accept-rate est.)
        self.counters: Dict[str, int] = {
            "rejected_queue_full": 0,
            "shed": 0,
            "cancelled": 0,
            "timed_out": 0,
            "failed": 0,
            "nan_quarantined": 0,
            "retries": 0,
            "decode_dispatch_failures": 0,
            "stopped_early": 0,
        }
        self._chunk_ordinal = 0  # decode dispatches over the lifetime
        self._step_ordinal = 0  # step() calls over the lifetime
        self._rid_counter = itertools.count()
        self._used_rids = set()  # rids ever seen by THIS scheduler
        if tracer is None:
            tracer = getattr(engine, "tracer", None)
        self.tracer = tracer
        self.metrics = metrics
        pc = getattr(engine, "prefix_cache", None)
        if pc is not None:
            # prefix metrics/trace instants share the serve exporter unless
            # the cache already has its own
            pc.attach(metrics=metrics, tracer=tracer)
        if metrics is not None:
            metrics.gauge(
                "serve_slot_capacity", "configured decode-batch slots"
            ).set(n_slots)
            # pre-register the zero-valued series so a scrape before traffic
            # (and the counters_agree check) sees every family
            for key in self.counters:
                metrics.counter(
                    f"serve_{key}_total", f"requests/events: {key}"
                )
            metrics.counter("serve_submitted_total",
                            "submit() calls incl. queue-full rejections")
            metrics.counter("serve_finished_total",
                            "requests that reached FINISHED")
            metrics.counter("serve_tokens_total", "committed output tokens")
            metrics.counter("serve_decode_chunks_total",
                            "decode/speculative chunk dispatches")

    def _count(self, key: str, n: int = 1) -> None:
        """The one place ``counters`` increments happen: keeps the host-side
        dict and the exported ``serve_<key>_total`` series in lockstep."""
        self.counters[key] += n
        if self.metrics is not None:
            self.metrics.counter(f"serve_{key}_total").inc(n)

    def _observe_gauges(self) -> None:
        if self.metrics is None:
            return
        active = self.n_active
        self.metrics.gauge(
            "serve_queue_depth", "requests waiting for admission"
        ).set(len(self.queue))
        self.metrics.gauge(
            "serve_active_slots", "slots with a live tenant"
        ).set(active)
        self.metrics.gauge(
            "serve_batch_efficiency",
            "active slots / capacity at the last chunk boundary",
        ).set(active / self.n_slots)
        if self.speculate is not None and self.chunk_rows:
            self.metrics.gauge(
                "serve_spec_accept_rate",
                "estimated draft-token acceptance rate",
            ).set(self.spec_accept_rate)

    def _trace_lifecycle(self, rec: RequestLifecycle) -> None:
        """Replay one finished record as spans on its ``req:<rid>`` lane —
        all timestamps come from the record (taken by the scheduler clock as
        part of normal lifecycle bookkeeping), so tracing adds no readings."""
        lane = f"req:{rec.rid}"
        # phase spans: QUEUED from submit to the first transition, then each
        # history entry runs to the next (the terminal entry has zero width
        # and is emitted as an instant with the reason attached)
        t_prev, name_prev = rec.submitted_at, "queued"
        for state, at in rec.history:
            self.tracer.complete(name_prev, t_prev, at, cat="lifecycle",
                                 lane=lane, args={"rid": rec.rid})
            t_prev, name_prev = at, state.value
        self.tracer.instant(
            rec.state.value, ts=rec.finished_at, cat="lifecycle", lane=lane,
            args={"rid": rec.rid, "reason": rec.reason,
                  "n_tokens": rec.n_tokens},
        )

    def _observe_latency(self, rec: RequestLifecycle) -> None:
        """Terminal-time latency histograms (each record reaches a terminal
        state exactly once — terminal states are terminal — so these observe
        once per request)."""
        if rec.ttft is not None:
            self.metrics.histogram(
                "serve_ttft_seconds", "submit -> first token"
            ).observe(rec.ttft)
        if rec.tpot is not None:
            self.metrics.histogram(
                "serve_tpot_seconds", "mean time per token after the first"
            ).observe(rec.tpot)
        if rec.finished_at is not None:
            self.metrics.histogram(
                "serve_e2e_seconds", "submit -> terminal state"
            ).observe(rec.finished_at - rec.submitted_at)

    # -- queue ---------------------------------------------------------------

    def submit(self, req: Request) -> int:
        plen = int(req.prompt.size)
        headroom = 0 if self.speculate is None else self.speculate.gamma + 1
        if plen + req.max_new_tokens + headroom > self.engine.max_seq:
            raise ValueError(
                f"request needs {plen + req.max_new_tokens + headroom} cache "
                f"rows (incl. {headroom} speculation headroom), engine "
                f"max_seq={self.engine.max_seq}"
            )
        vocab = self.engine.cfg.vocab
        if req.prompt.min() < 0 or req.prompt.max() >= vocab:
            raise ValueError(
                f"prompt token ids must lie in [0, vocab={vocab}); got range "
                f"[{req.prompt.min()}, {req.prompt.max()}] — out-of-range ids "
                f"index garbage embedding rows device-side"
            )
        if self.metrics is not None:
            # counted after validation, before the queue-full check: the
            # accounting invariant is finished + cancelled + timed_out + shed
            # + failed + rejected_queue_full == submitted, with rejections on
            # both sides (a ValueError above is a malformed call, not a
            # request)
            self.metrics.counter("serve_submitted_total").inc()
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # loud reject-with-reason backpressure: the caller (or the async
            # server, which turns this into a per-client rejection) decides
            # whether to retry — the queue never grows without bound
            self._count("rejected_queue_full")
            if self.tracer is not None:
                self.tracer.instant(
                    "reject_queue_full", cat="admission", lane="scheduler",
                    args={"queued": len(self.queue)},
                )
            raise QueueFullError(
                f"admission queue full ({self.max_queue} waiting): request "
                f"rejected — resubmit later, shrink the burst, or raise "
                f"max_queue"
            )
        if req.rid is None:
            # skip values a caller-supplied rid already claimed: rids must be
            # unique per scheduler or `{c.rid: c for c in run()}` drops results
            req.rid = next(
                r for r in self._rid_counter if r not in self._used_rids
            )
        elif req.rid in self._used_rids:
            raise ValueError(
                f"rid {req.rid!r} already used in this scheduler (a Request "
                "submitted elsewhere keeps its assigned rid — pass a fresh "
                "Request or an explicit unique rid)"
            )
        self._used_rids.add(req.rid)
        rec = RequestLifecycle(rid=req.rid, submitted_at=self._clock())
        self.outcomes[req.rid] = rec
        self.queue.append(req)
        if self.tracer is not None:
            self.tracer.instant(
                "submit", ts=rec.submitted_at, cat="lifecycle",
                lane=f"req:{req.rid}",
                args={"rid": req.rid, "prompt_len": plen,
                      "max_new_tokens": req.max_new_tokens},
            )
        if self.metrics is not None:
            self._observe_gauges()
        return req.rid

    def cancel(self, rid: int, reason: str = "cancelled by client") -> bool:
        """Flag a request for cancellation; applied at the next chunk
        boundary (queued → removed before prefill, decoding → slot reclaimed
        with zero trace on surviving rows). Returns False if the rid is
        unknown or already terminal."""
        rec = self.outcomes.get(rid)
        if rec is None or rec.state.terminal:
            return False
        self._pending_cancel[rid] = reason
        return True

    @property
    def n_active(self) -> int:
        return sum(t is not None for t in self._tenants)

    @property
    def idle(self) -> bool:
        return not self.queue and self.n_active == 0 and not self._pending

    @property
    def spec_accept_rate(self) -> float:
        """Estimated draft-acceptance rate over all speculative dispatches:
        tokens per row-chunk is 1 + gamma * accept_rate (slight underestimate
        when rows finish mid-dispatch). 0.0 until a spec chunk has run."""
        if self.speculate is None or self.chunk_rows == 0:
            return 0.0
        tokens_per_row_chunk = self.steps_active / self.chunk_rows
        return max(0.0, (tokens_per_row_chunk - 1.0) / self.speculate.gamma)

    def summary(self) -> dict:
        """Lifecycle + latency summary: TTFT/TPOT percentiles over finished
        requests, terminal-state counts, and the robustness counters."""
        out = latency_summary(self.outcomes.values())
        out["counters"] = dict(self.counters)
        out["decode_steps"] = self.decode_steps
        return out

    # -- lifecycle internals -------------------------------------------------

    def _terminal(
        self,
        rec: RequestLifecycle,
        state: RequestState,
        reason: str,
        tokens: Optional[List[int]] = None,
    ) -> None:
        rec.transition(state, self._clock(), reason)
        rec.new_tokens = np.asarray(tokens or [], np.int32)  # staticcheck: host-sync(tokens already host-side)
        rec.n_tokens = int(rec.new_tokens.size)
        if self.tracer is not None:
            self._trace_lifecycle(rec)
        if self.metrics is not None:
            self._observe_latency(rec)
        if self.on_event is not None:
            self.on_event(rec)

    def _evict(self, slot: int, state: RequestState, reason: str) -> None:
        """Reclaim a slot mid-flight (cancel/timeout/quarantine): terminal
        transition with the partial tokens attached, then deactivate the row
        so it stops consuming decode steps. The next admission overwrites the
        row's entire state (slot-reset contract, DESIGN.md §4) — zero trace
        on surviving rows."""
        tenant = self._tenants[slot]
        assert tenant is not None
        self._terminal(
            self.outcomes[tenant.req.rid], state, reason, tokens=tenant.emitted
        )
        self._tenants[slot] = None
        self.slots = self.engine.release_slot(self.slots, slot)

    def _apply_cancels(self) -> None:
        if not self._pending_cancel:
            return
        keep: Deque[Request] = deque()
        for req in self.queue:
            reason = self._pending_cancel.pop(req.rid, None)
            if reason is None:
                keep.append(req)
            else:
                self._count("cancelled")
                self._terminal(
                    self.outcomes[req.rid], RequestState.CANCELLED, reason
                )
        self.queue = keep
        # chunked-prefill admissions cancel between their chunks: unpin the
        # prefix handle and drop the pending state — the slot row was never
        # written, so it is simply free again
        for slot, (req, pending) in list(self._pending.items()):
            reason = self._pending_cancel.pop(req.rid, None)
            if reason is not None:
                self._count("cancelled")
                self.engine.abort_admission(pending)
                del self._pending[slot]
                self._terminal(
                    self.outcomes[req.rid], RequestState.CANCELLED, reason
                )
        for slot, tenant in enumerate(self._tenants):
            if tenant is None:
                continue
            reason = self._pending_cancel.pop(tenant.req.rid, None)
            if reason is not None:
                self._count("cancelled")
                self._evict(slot, RequestState.CANCELLED, reason)
        self._pending_cancel.clear()  # unknown/raced rids: nothing to do

    def _enforce_deadlines(self) -> None:
        now = self._clock()
        # queued requests whose deadline already expired are shed before they
        # waste a prefill — deadline-aware queue shedding
        keep: Deque[Request] = deque()
        for req in self.queue:
            rec = self.outcomes[req.rid]
            waited = now - rec.submitted_at
            expired = None
            if req.ttft_deadline_s is not None and waited > req.ttft_deadline_s:
                expired = (
                    f"shed in queue: TTFT deadline {req.ttft_deadline_s}s "
                    f"expired after {waited:.3f}s waiting"
                )
            elif req.deadline_s is not None and waited > req.deadline_s:
                expired = (
                    f"shed in queue: deadline {req.deadline_s}s expired "
                    f"after {waited:.3f}s waiting"
                )
            if expired is None:
                keep.append(req)
            else:
                self._count("shed")
                self._terminal(rec, RequestState.SHED, expired)
        self.queue = keep
        # mid-prefill deadlines (chunked admissions span many steps): the
        # TTFT deadline always applies — no first token yet by definition
        for slot, (req, pending) in list(self._pending.items()):
            rec = self.outcomes[req.rid]
            age = now - rec.submitted_at
            expired = None
            if req.deadline_s is not None and age > req.deadline_s:
                expired = (
                    f"deadline {req.deadline_s}s exceeded mid-prefill "
                    f"({pending.pos}/{pending.plen} prompt tokens)"
                )
            elif req.ttft_deadline_s is not None and age > req.ttft_deadline_s:
                expired = (
                    f"TTFT deadline {req.ttft_deadline_s}s exceeded "
                    f"mid-prefill ({pending.pos}/{pending.plen} prompt tokens)"
                )
            if expired is not None:
                self._count("timed_out")
                self.engine.abort_admission(pending)
                del self._pending[slot]
                self._terminal(rec, RequestState.TIMED_OUT, expired)
        for slot, tenant in enumerate(self._tenants):
            if tenant is None:
                continue
            req = tenant.req
            rec = self.outcomes[req.rid]
            age = now - rec.submitted_at
            if req.deadline_s is not None and age > req.deadline_s:
                self._count("timed_out")
                self._evict(
                    slot,
                    RequestState.TIMED_OUT,
                    f"deadline {req.deadline_s}s exceeded after "
                    f"{len(tenant.emitted)} tokens",
                )
            elif (
                req.ttft_deadline_s is not None
                and rec.first_token_at is None
                and age > req.ttft_deadline_s
            ):
                self._count("timed_out")
                self._evict(
                    slot,
                    RequestState.TIMED_OUT,
                    f"TTFT deadline {req.ttft_deadline_s}s exceeded before "
                    f"first token",
                )

    def _with_retry(self, fn, what: str):
        """Bounded exponential-backoff retry around one engine dispatch.

        Sound for failures raised *before* the dispatch consumes its (donated)
        inputs — which is where FaultPlan injects and where argument/shape
        validation fails. A failure that killed the donated slot state anyway
        is caught one level up: exhausted decode retries rebuild the slot
        state from scratch."""
        delay = self.backoff_s
        last: Optional[Exception] = None
        for attempt in range(self.retries + 1):
            try:
                return fn()
            except Exception as e:  # noqa: BLE001 — retry then re-raise below
                last = e
                if attempt < self.retries:
                    self._count("retries")
                    if self.tracer is not None:
                        self.tracer.instant(
                            "retry", cat="fault", lane="scheduler",
                            args={"what": what, "attempt": attempt + 1,
                                  "error": repr(e)},
                        )
                    self._sleep(delay)
                    delay *= 2
        raise DispatchError(
            f"{what} failed after {self.retries + 1} attempt(s): {last!r}"
        ) from last

    # -- scheduling ----------------------------------------------------------

    def _record_tokens(self, tenant: _Tenant, new: List[int]) -> bool:
        """Append a chunk's newly emitted tokens to the tenant, honouring its
        stop set (truncation keeps the stop token). Fires the streaming
        callback with exactly the visible tokens. Returns True if a stop
        token ended the request."""
        rec = self.outcomes[tenant.req.rid]
        stopped = False
        if tenant.stop:
            for i, t in enumerate(new):
                if t in tenant.stop:
                    new = new[: i + 1]
                    stopped = True
                    break
        if new:
            if rec.first_token_at is None:
                rec.first_token_at = self._clock()
            tenant.emitted.extend(new)
            rec.n_tokens = len(tenant.emitted)
            if self.metrics is not None:
                self.metrics.counter("serve_tokens_total").inc(len(new))
            if self.on_tokens is not None:
                self.on_tokens(tenant.req.rid, list(new))
        return stopped

    def _finish(self, slot: int, *, stopped: bool) -> Completion:
        """FINISH a tenant: budget exhausted or stop token hit. Early stops
        release the slot (the device row is still active); budget exhaustion
        already deactivated the row on device."""
        tenant = self._tenants[slot]
        assert tenant is not None
        if stopped:
            self._count("stopped_early")
            self.slots = self.engine.release_slot(self.slots, slot)
        if self.metrics is not None:
            self.metrics.counter("serve_finished_total").inc()
        self._terminal(
            self.outcomes[tenant.req.rid],
            RequestState.FINISHED,
            "stop token" if stopped else "budget exhausted",
            tokens=tenant.emitted,
        )
        self._tenants[slot] = None  # freed; refilled next chunk boundary
        return Completion(
            rid=tenant.req.rid,
            prompt=tenant.req.prompt,
            new_tokens=np.asarray(tenant.emitted, np.int32),  # staticcheck: host-sync(emitted list is host-side)
            admitted_at_step=tenant.admitted_at_step,
            finished_at_step=self.decode_steps,
            stopped=stopped,
        )

    def _note_prefix(self, rec: RequestLifecycle, prompt_len: int) -> None:
        """Stamp prefix-cache hit stats onto the lifecycle record and emit
        the ``cache_hit`` trace instant (DESIGN.md §12 observability)."""
        h = self.engine.take_prefix_handle()
        if h is None:
            return
        rec.prefix_hit_tokens = h.length
        if h.length and self.tracer is not None:
            self.tracer.instant(
                "cache_hit", cat="prefix", lane="scheduler",
                args={"rid": rec.rid, "hit_tokens": h.length,
                      "prompt_len": prompt_len},
            )

    def _install_tenant(self, slot: int, req: Request) -> Optional[Completion]:
        """Post-admission bookkeeping shared by both admission modes: the
        DECODING transition, tenant install, and (spec mode) emitting the
        first token sampled at admission — which can complete a budget-1
        request right here."""
        rec = self.outcomes[req.rid]
        rec.transition(RequestState.DECODING, self._clock())
        self._note_prefix(rec, int(req.prompt.size))
        tenant = _Tenant(req, self.decode_steps)
        self._tenants[slot] = tenant
        if self.speculate is not None:
            t0 = int(np.asarray(self.slots["t_pend"][slot]))  # staticcheck: host-sync(per-admission fetch of the pre-sampled first token)
            stopped = self._record_tokens(tenant, [t0])
            if stopped or len(tenant.emitted) >= req.max_new_tokens:
                return self._finish(slot, stopped=stopped)
        return None

    def _admit_free_slots(self) -> List[Completion]:
        """Fill free slots from the queue. In speculative mode admission also
        emits the request's first token (sampled from its own prefill logits
        on device), so a budget-1 request can complete right here — returned
        so its slot frees up for the same admission round. A prefill dispatch
        that keeps failing quarantines only the admitting request; the slot
        stays free for the next queued request in the same round.

        With ``prefill_chunk`` set, admission is *chunked* instead
        (DESIGN.md §12): free slots claim queued requests, but each pending
        admission prefills at most ``prefill_chunk`` prompt tokens per step,
        so one long prompt never stalls the decode chunks of active streams.
        """
        if self.prefill_chunk is not None:
            return self._admit_chunked()
        done: List[Completion] = []
        for slot in range(self.n_slots):
            while self.queue and self._tenants[slot] is None:
                req = self.queue.popleft()
                rec = self.outcomes[req.rid]
                rec.transition(RequestState.PREFILLING, self._clock())

                def dispatch(req=req, slot=slot):
                    if self.faults is not None:
                        self.faults.on_prefill(req.rid)
                    return self.engine.admit_slot(
                        self.slots,
                        slot,
                        req.prompt,
                        max_new_tokens=req.max_new_tokens,
                        temperature=req.temperature,
                        seed=req.seed,
                        speculate=req.speculate is not False,
                    )

                try:
                    self.slots = self._with_retry(
                        dispatch, what=f"admission prefill (request {req.rid})"
                    )
                except DispatchError as e:
                    self._count("failed")
                    self._terminal(rec, RequestState.FAILED, str(e))
                    continue  # slot still free: try the next queued request
                rec.prefill_chunks = 1
                c = self._install_tenant(slot, req)
                if c is not None:
                    done.append(c)
        return done

    def _admit_chunked(self) -> List[Completion]:
        """Chunked admission: claim free slots (prefix lookup + install —
        host trie walk plus at most one row-install dispatch), then advance
        every pending admission by ``prefill_chunk`` prompt tokens. An
        admission that completes installs its tenant this same step; one
        that keeps failing is FAILED alone, its prefix pins released."""
        done: List[Completion] = []
        for slot in range(self.n_slots):
            if self._tenants[slot] is not None or slot in self._pending:
                continue
            if not self.queue:
                break
            req = self.queue.popleft()
            rec = self.outcomes[req.rid]
            rec.transition(RequestState.PREFILLING, self._clock())

            def begin(req=req):
                if self.faults is not None:
                    self.faults.on_prefill(req.rid)
                return self.engine.begin_admission(
                    req.prompt,
                    max_new_tokens=req.max_new_tokens,
                    temperature=req.temperature,
                    seed=req.seed,
                    speculate=req.speculate is not False,
                    chunked=True,
                )

            try:
                pending = self._with_retry(
                    begin, what=f"admission begin (request {req.rid})"
                )
            except DispatchError as e:
                self._count("failed")
                self._terminal(rec, RequestState.FAILED, str(e))
                continue
            self._pending[slot] = (req, pending)
        for slot, (req, pending) in sorted(self._pending.items()):
            rec = self.outcomes[req.rid]

            def advance(pending=pending):
                return self.engine.advance_admission(
                    pending, self.prefill_chunk
                )

            def fail(e: DispatchError) -> None:
                self.engine.abort_admission(pending)
                del self._pending[slot]
                self._count("failed")
                self._terminal(rec, RequestState.FAILED, str(e))

            try:
                self._with_retry(
                    advance, what=f"prefill chunk (request {req.rid})"
                )
            except DispatchError as e:
                fail(e)
                continue
            if not pending.done:
                continue

            def install(pending=pending, slot=slot):
                return self.engine.finish_admission(self.slots, slot, pending)

            try:
                self.slots = self._with_retry(
                    install, what=f"admission install (request {req.rid})"
                )
            except DispatchError as e:
                fail(e)
                continue
            del self._pending[slot]
            rec.prefill_chunks = pending.prefill_chunks
            c = self._install_tenant(slot, req)
            if c is not None:
                done.append(c)
        return done

    def _harvest(self, slot: int) -> Optional[Completion]:
        tenant = self._tenants[slot]
        if tenant is None or len(tenant.emitted) < tenant.req.max_new_tokens:
            return None
        assert len(tenant.emitted) == tenant.req.max_new_tokens, (
            "device active-mask emitted past the request budget"
        )
        return self._finish(slot, stopped=False)

    def _dispatch_decode(self):
        """One decode (or speculative) chunk with fault injection + bounded
        retry. Returns the (tokens, valid, slots) triple, or None after
        exhausted retries — in which case every *active* tenant is FAILED
        (they are the affected requests; their device state may be
        unrecoverable) and the slot state is rebuilt so queued requests still
        serve."""
        ordinal = self._chunk_ordinal
        self._chunk_ordinal += 1

        def dispatch():
            if self.faults is not None:
                self.faults.on_chunk(ordinal)
            if self.speculate is None:
                return self.engine.decode_slots(self.slots, self.chunk)
            return self.engine.spec_decode_slots(self.slots, self.chunk)

        try:
            return self._with_retry(dispatch, what=f"decode chunk {ordinal}")
        except DispatchError as e:
            self._count("decode_dispatch_failures")
            for slot, tenant in enumerate(self._tenants):
                if tenant is None:
                    continue
                self._count("failed")
                tenant_rec = self.outcomes[tenant.req.rid]
                self._terminal(
                    tenant_rec, RequestState.FAILED, str(e), tokens=tenant.emitted
                )
                self._tenants[slot] = None
            # the failed dispatch may have consumed (donated) the old slot
            # buffers — rebuild from scratch rather than risk dead buffers
            self.slots = self.engine.init_slots(
                self.n_slots, speculate=self.speculate
            )
            return None

    def _inject_and_guard_nan(self) -> None:
        """Post-chunk NaN handling: (a) FaultPlan poisons due rows (exactly
        what an upstream numerical fault leaves behind); (b) the guard fails
        and quarantines every non-finite row — slot scrubbed and refilled at
        the next boundary, neighbours untouched."""
        if self.faults is not None:
            for slot, tenant in enumerate(self._tenants):
                if tenant is not None and self.faults.poison_due(
                    tenant.req.rid, len(tenant.emitted)
                ):
                    self.slots = self.engine.poison_logit_row(self.slots, slot)
        if not self.nan_guard:
            return
        occupied = [s for s, t in enumerate(self._tenants) if t is not None]
        if not occupied:
            return
        finite = self.engine.finite_logit_rows(self.slots)
        for slot in occupied:
            if not finite[slot]:
                self._count("nan_quarantined")
                self._count("failed")
                if self.tracer is not None:
                    tenant = self._tenants[slot]
                    self.tracer.instant(
                        "nan_quarantine", cat="fault", lane="scheduler",
                        args={"slot": slot, "rid": tenant.req.rid},
                    )
                self._evict(
                    slot,
                    RequestState.FAILED,
                    "non-finite logits: row quarantined (slot scrubbed; "
                    "neighbours unaffected)",
                )

    def step(self) -> List[Completion]:
        """One chunk boundary: apply cancels, enforce deadlines, admit into
        free slots, run one decode chunk, harvest completions, guard NaNs.

        The step and each of its phases is a span (DESIGN.md §11.1):
        ``sched/step`` around ``sched/admit``, ``sched/decode_chunk``,
        ``sched/fetch``, ``sched/commit`` and ``sched/nan_guard``, in the
        ring tracer (on the tracer's own clock, never the scheduler's:
        tracing must not perturb StepClock-driven deadlines) and on the
        device trace's clock."""
        ordinal = self._step_ordinal
        self._step_ordinal += 1
        with span(
            self.tracer, "sched/step", lane="scheduler", ordinal=ordinal,
            queued=len(self.queue), prefilling=len(self._pending),
            decoding=self.n_active,
        ):
            return self._step()

    def _step(self) -> List[Completion]:
        done: List[Completion] = []
        with span(self.tracer, "sched/admit", lane="scheduler"):
            self._apply_cancels()
            self._enforce_deadlines()
            done.extend(self._admit_free_slots())
        if self.n_active == 0:
            self._observe_gauges()
            return done
        with span(
            self.tracer, "sched/decode_chunk", lane="scheduler",
            ordinal=self._chunk_ordinal, active=self.n_active,
            chunk=self.chunk, spec=self.speculate is not None,
        ):
            res = self._dispatch_decode()
        if self.metrics is not None:
            self.metrics.counter("serve_decode_chunks_total").inc()
        if res is None:
            self._observe_gauges()
            return done
        toks, valid, self.slots = res
        self.decode_steps += self.chunk
        if self.speculate is not None:
            self.chunk_rows += self.n_active * self.chunk
        # the chunk-boundary sync: the host waits here for the chunk to run
        with span(self.tracer, "sched/fetch", lane="scheduler") as sp:
            toks = np.asarray(toks)  # (B, chunk) / (B, chunk*(gamma+1))  # staticcheck: host-sync(the one documented per-chunk fetch)
            valid = np.asarray(valid)  # staticcheck: host-sync(the one documented per-chunk fetch)
            committed = int(valid.sum())  # staticcheck: host-sync(valid already fetched above)
            sp.annotate(tokens=committed)
        self.steps_active += committed
        with span(self.tracer, "sched/commit", lane="scheduler"):
            if self.speculate is not None and self.tracer is not None:
                self._trace_spec_subchunks(valid)
            if self.metrics is not None:
                self.metrics.histogram(
                    "serve_chunk_commit_tokens",
                    "tokens committed per decode chunk",
                    buckets=tuple(float(2**i) for i in range(10)),
                ).observe(committed)
            for slot, tenant in enumerate(self._tenants):
                if tenant is None:
                    continue
                stopped = self._record_tokens(
                    tenant, [int(t) for t in toks[slot][valid[slot]]]
                )
                if stopped:
                    done.append(self._finish(slot, stopped=True))
                else:
                    c = self._harvest(slot)
                    if c is not None:
                        done.append(c)
        with span(self.tracer, "sched/nan_guard", lane="scheduler"):
            self._inject_and_guard_nan()
        self._observe_gauges()
        return done

    def _trace_spec_subchunks(self, valid: np.ndarray) -> None:
        """Speculative draft/verify/rollback annotation. Draft + verify are
        fused into the one device dispatch that ``sched/decode_chunk``
        issues and ``sched/fetch`` waits on, so per-sub-chunk *timing* needs
        ``jax.profiler`` (DESIGN.md §11); what
        the host does know exactly — per row and sub-chunk, how many drafted
        tokens the verifier kept and how many rolled back — is emitted as
        ``spec_verify`` instants derived from the fetched valid mask."""
        gamma = self.speculate.gamma
        ordinal = self._chunk_ordinal - 1
        for slot, tenant in enumerate(self._tenants):
            if tenant is None:
                continue
            if tenant.req.speculate is False:
                continue  # plain rows have no draft to account for
            # valid row layout: chunk sub-chunks of (gamma accepted-draft
            # slots + 1 bonus/target token)
            sub = valid[slot].reshape(self.chunk, gamma + 1)
            for j in range(self.chunk):
                committed = int(sub[j].sum())  # staticcheck: host-sync(valid mask already fetched at the chunk boundary)
                if committed == 0:
                    continue  # row went inactive before this sub-chunk
                accepted = max(0, committed - 1)
                self.tracer.instant(
                    "spec_verify", cat="speculative",
                    lane=f"req:{tenant.req.rid}",
                    args={"chunk": ordinal, "sub": j, "drafted": gamma,
                          "accepted": accepted,
                          "rolled_back": gamma - accepted},
                )

    def run(self, max_chunks: int = 100_000) -> List[Completion]:
        """Drain the queue completely; returns completions in finish order.
        Requests that end CANCELLED/TIMED_OUT/FAILED/SHED do not produce a
        Completion — read their terminal records from ``outcomes`` (or stream
        them via ``on_event``)."""
        out: List[Completion] = []
        for _ in range(max_chunks):
            if self.idle:
                return out
            out.extend(self.step())
        raise RuntimeError(f"scheduler did not drain within {max_chunks} chunks")
