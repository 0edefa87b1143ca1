"""Observability layer (DESIGN.md §11): span tracer, metrics registry,
profiler hooks.

The contract under test is three-sided:

1. **Faithful**: a disturbed fault-injected serve run exports a valid
   Chrome/Perfetto trace from which every request's lifecycle is
   reconstructable, and the Prometheus export accounts for every submitted
   request with zero leaks (the ``counters_agree`` lockstep check).
2. **Invisible**: instrumented serving is bit-identical to uninstrumented —
   same tokens, same StepClock-driven deadline outcomes, no new compile-cache
   entries on the jitted decode programs.
3. **Cheap and host-only**: the per-span cost stays under the documented
   budget, ``repro.obs`` imports without jax, and the ``lint/obs-host-only``
   staticcheck rule keeps it that way structurally.
"""

import functools
import glob
import json
import math
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

import repro
from repro.analysis.staticcheck import lint
from repro.configs import get_config
from repro.data import MarkovCorpus
from repro.infer import (
    Engine,
    Request,
    RequestState,
    Scheduler,
    SpecConfig,
    StepClock,
)
from repro.infer.lifecycle import RequestLifecycle, latency_summary
from repro.models import init_params, reduced
from repro.obs import (
    Histogram,
    MetricsRegistry,
    Tracer,
    default_registry,
    parse_prometheus,
    prometheus_text,
    validate_chrome_trace,
)
from repro.obs.metrics import counters_agree, exponential_buckets
from repro.obs.trace import demo_serve, request_lifecycles

KEY = jax.random.PRNGKey(0)
MAX_SEQ = 64


def _cfg():
    return reduced(get_config("llama3.2-3b"), d_model=128, n_kv_heads=4, d_ff=256)


@functools.lru_cache(maxsize=None)
def _engine() -> Engine:
    return Engine(_cfg(), init_params(KEY, _cfg()), max_seq=MAX_SEQ)


def _requests(n=4, gen=6):
    """Fresh Request objects every call (rids are assigned at submit and are
    single-use per scheduler)."""
    cfg = _cfg()
    corpus = MarkovCorpus(cfg.vocab, seed=3)
    out = []
    for i in range(n):
        plen = 4 + (i % 3)
        prompt = corpus.sample(1, plen, seed=50 + i)[0, :plen].astype(np.int32)
        out.append(
            Request(prompt=prompt, max_new_tokens=gen,
                    temperature=[0.0, 0.8][i % 2], seed=20 + i)
        )
    return out


# ---------------------------------------------------------------------------
# tracer unit behaviour
# ---------------------------------------------------------------------------


def test_span_nesting_and_ordering_under_stepclock():
    """Nested spans under a deterministic clock: exact enter/exit stamps,
    completion-ordered ring, annotations and instants land on the span."""
    clk = StepClock(dt=1.0)
    tr = Tracer(capacity=64, clock=clk)
    with tr.span("outer", lane="L", a=1) as outer:
        with tr.span("inner", lane="L"):
            pass  # enter reads t=1, exit reads t=2
        tr.instant("mark", lane="L")  # t=3
        outer.annotate(b=2)
    # ring holds completion order: inner closed before outer
    evs = tr.events()
    assert [(e[0], e[1]) for e in evs] == [
        ("X", "inner"), ("i", "mark"), ("X", "outer")
    ]
    inner, mark, outer_ev = evs
    assert (inner[4], inner[5]) == (1.0, 1.0)  # ts=1, dur=2-1
    assert mark[4] == 3.0
    assert (outer_ev[4], outer_ev[5]) == (0.0, 4.0)  # ts=0, dur=4-0
    assert outer_ev[6] == {"a": 1, "b": 2}


def test_span_records_exception_and_reraises():
    tr = Tracer(clock=StepClock(dt=1.0))
    with pytest.raises(RuntimeError, match="boom"):
        with tr.span("failing", lane="L"):
            raise RuntimeError("boom")
    (ev,) = tr.events()
    assert ev[1] == "failing"
    assert ev[6]["error"] == "RuntimeError: boom"


def test_ring_eviction_bounds_memory():
    tr = Tracer(capacity=4, clock=StepClock(dt=1.0))
    for i in range(10):
        tr.instant(f"i{i}", lane="L")
    st = tr.stats()
    assert st == {"recorded": 10, "buffered": 4, "evicted": 6, "capacity": 4}
    assert [e[1] for e in tr.events()] == ["i6", "i7", "i8", "i9"]
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_disabled_tracer_never_reads_its_clock():
    """tracer=None and Tracer(enabled=False) must be true zeros: a counting
    clock proves no readings happen, and span() hands back a shared no-op."""
    reads = []

    def clock():
        reads.append(1)
        return 0.0

    tr = Tracer(clock=clock, enabled=False)
    with tr.span("x", lane="L") as sp:
        sp.annotate(a=1)
    tr.instant("y")
    tr.complete("z", 0.0, 1.0)
    assert reads == []
    assert tr.stats()["recorded"] == 0
    assert tr.span("a") is tr.span("b")  # the shared null handle


def test_chrome_export_schema_valid_and_lanes_labelled():
    clk = StepClock(dt=0.5)
    tr = Tracer(clock=clk)
    with tr.span("sched/decode_chunk", cat="scheduler", lane="scheduler", ordinal=0):
        pass
    tr.complete("queued", 0.25, 0.75, cat="lifecycle", lane="req:0")
    tr.instant("finished", lane="req:0", args={"rid": 0})
    trace = tr.to_chrome()
    assert validate_chrome_trace(trace) == []
    assert validate_chrome_trace(json.dumps(trace)) == []  # JSON round-trip
    events = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    assert min(e["ts"] for e in events) == 0.0  # rebased to the earliest event
    # lane -> tid metadata lets Perfetto label the rows
    names = {
        e["args"]["name"]
        for e in trace["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert names == {"scheduler", "req:0"}
    assert trace["otherData"]["recorded"] == 3
    assert Tracer().chrome_events() == []  # empty tracer exports cleanly


def test_validate_chrome_trace_rejects_garbage():
    assert validate_chrome_trace("not json{") != []
    assert validate_chrome_trace([1, 2]) != []
    assert validate_chrome_trace({}) == ["missing or non-list 'traceEvents'"]
    bad = {
        "traceEvents": [
            {"ph": "Q", "name": "x", "pid": 1, "tid": 1, "ts": 0},
            {"ph": "X", "name": "", "pid": 1, "tid": 1, "ts": 0, "dur": 1},
            {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": -1, "dur": -2},
            {"ph": "i", "name": "x", "pid": "1", "tid": 1, "ts": 0, "s": "q"},
            {"ph": "M", "name": "thread_name", "pid": 1, "tid": 1},
        ]
    }
    problems = validate_chrome_trace(bad)
    assert len(problems) >= 6
    assert any("ph='Q'" in p for p in problems)


def test_request_lifecycles_groups_and_sorts_by_lane():
    tr = Tracer(clock=StepClock(dt=1.0))
    tr.complete("decoding", 5.0, 9.0, lane="req:1")
    tr.complete("queued", 0.0, 5.0, lane="req:1")
    tr.complete("queued", 1.0, 2.0, lane="req:2")
    tr.instant("mark", lane="scheduler")  # non-request lane: excluded
    lanes = request_lifecycles(tr.to_chrome())
    assert set(lanes) == {"req:1", "req:2"}
    assert [e["name"] for e in lanes["req:1"]] == ["queued", "decoding"]


# ---------------------------------------------------------------------------
# metrics registry unit behaviour
# ---------------------------------------------------------------------------


def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match=">= 0"):
        c.inc(-1)
    g = reg.gauge("queue_depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0


def test_histogram_bucketing_quantiles_and_nonfinite():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.cumulative() == [(1.0, 1), (2.0, 2), (4.0, 3), (math.inf, 4)]
    assert h.count == 4 and h.sum == pytest.approx(105.0)
    assert h.quantile(0.5) == 2.0
    assert h.quantile(1.0) == math.inf
    h.observe(float("nan"))  # must not poison sum/count
    assert h.nonfinite == 1 and h.count == 4
    assert Histogram().quantile(0.5) is None  # empty -> None, never NaN
    with pytest.raises(ValueError):
        Histogram(bounds=(2.0, 1.0))
    with pytest.raises(ValueError):
        exponential_buckets(start=0.0)
    bs = exponential_buckets(start=1.0, factor=2.0, count=3)
    assert bs == (1.0, 2.0, 4.0)


def test_registry_identity_and_morph_guards():
    reg = MetricsRegistry()
    a = reg.counter("hits_total", fmt="bcq")
    assert reg.counter("hits_total", fmt="bcq") is a  # get-or-create identity
    b = reg.counter("hits_total", fmt="uniform")  # new label set = new series
    assert b is not a
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("hits_total")  # kind morph
    with pytest.raises(ValueError, match="one name, one label set"):
        reg.counter("hits_total", impl="ref")  # label-key morph
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")
    with pytest.raises(ValueError, match="invalid label name"):
        reg.counter("ok_total", **{"bad-label": "x"})


def test_registry_thread_safety_exact_totals():
    reg = MetricsRegistry()
    n_threads, per_thread = 8, 500

    def work():
        for i in range(per_thread):
            reg.counter("hits_total").inc()
            reg.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.05)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hits_total").value == n_threads * per_thread
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    assert h.count == n_threads * per_thread
    assert h.cumulative()[0] == (0.1, n_threads * per_thread)


def test_prometheus_text_round_trips_through_parser():
    reg = MetricsRegistry()
    reg.counter("dispatch_total", "dispatches", fmt="bcq", impl="lutgemm").inc(7)
    reg.gauge("depth", "queue depth").set(3)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = prometheus_text(reg)
    assert "# TYPE dispatch_total counter" in text
    assert "# HELP lat_seconds latency" in text
    samples = parse_prometheus(text)
    assert samples["dispatch_total"] == [({"fmt": "bcq", "impl": "lutgemm"}, 7.0)]
    assert samples["depth"] == [({}, 3.0)]
    buckets = {ls["le"]: v for ls, v in samples["lat_seconds_bucket"]}
    assert buckets == {"0.1": 1.0, "1": 1.0, "+Inf": 2.0}
    assert samples["lat_seconds_count"] == [({}, 2.0)]
    assert samples["lat_seconds_sum"][0][1] == pytest.approx(5.05)
    # one scrape must not carry duplicate metric families
    other = MetricsRegistry()
    other.counter("dispatch_total").inc()
    with pytest.raises(ValueError, match="more than one registry"):
        prometheus_text(reg, other)


def test_parse_prometheus_is_strict():
    assert parse_prometheus("x_total 1\nx_total{a=\"b\"} +Inf\n") == {
        "x_total": [({}, 1.0), ({"a": "b"}, math.inf)]
    }
    for bad in ("no value here and no digits",
                "name{unclosed=\"x\" 1",
                "x_total notanumber",
                "# BOGUS comment kind"):
        with pytest.raises(ValueError):
            parse_prometheus(bad)


# ---------------------------------------------------------------------------
# latency_summary percentile edge cases (the satellite bugfix)
# ---------------------------------------------------------------------------


def test_latency_summary_empty_has_explicit_nulls():
    m = latency_summary([])
    assert m["requests"] == 0 and m["finished"] == 0
    for block in (m["ttft_s"], m["tpot_s"]):
        assert block["p50"] is None and block["p99"] is None
        assert block["mean"] is None and block["n"] == 0
    json.dumps(m)  # nulls survive JSON; NaN would raise here


def test_latency_summary_single_token_and_no_first_token():
    fin = RequestLifecycle(rid=0, submitted_at=0.0)
    fin.transition(RequestState.PREFILLING, 1.0)
    fin.transition(RequestState.DECODING, 2.0)
    fin.first_token_at = 3.0
    fin.n_tokens = 1  # single-token completion: TTFT exists, TPOT undefined
    fin.transition(RequestState.FINISHED, 4.0)
    dead = RequestLifecycle(rid=1, submitted_at=0.0)
    dead.transition(RequestState.CANCELLED, 1.0)  # terminal, never emitted
    m = latency_summary([fin, dead])
    assert m["requests"] == 2 and m["finished"] == 1
    assert m["no_first_token"] == 1
    assert m["ttft_s"]["n"] == 1 and m["ttft_s"]["p50"] == pytest.approx(3.0)
    assert m["ttft_s"]["excluded"] == 0
    # the single-token request has no TPOT: excluded, not NaN and not dropped
    assert m["tpot_s"]["n"] == 0 and m["tpot_s"]["excluded"] == 1
    assert m["tpot_s"]["p50"] is None
    json.dumps(m)


# ---------------------------------------------------------------------------
# scheduler integration: faithful under faults, invisible to tokens
# ---------------------------------------------------------------------------


def test_disturbed_serve_trace_reconstructs_and_metrics_account():
    """The acceptance run: a fault-injected serve (client cancel + NaN
    quarantine + deadline shed) must export a valid Chrome trace that
    reconstructs every request's lifecycle, and a Prometheus scrape in which
    every submitted request is accounted for — finished + cancelled +
    timed_out + shed + failed + rejected == submitted, agreeing exactly with
    the scheduler's own counters."""
    sched, tracer, registry = demo_serve()
    assert tracer.stats()["evicted"] == 0  # the window held the whole run

    trace = tracer.to_chrome()
    assert validate_chrome_trace(trace) == []
    lanes = request_lifecycles(json.dumps(trace))
    for rid, rec in sched.outcomes.items():
        lane = lanes.get(f"req:{rid}")
        assert lane is not None, f"request {rid} missing from the trace"
        names = [e["name"] for e in lane]
        assert names[0] == "submit"
        assert names[-1] == rec.state.value  # terminal instant closes the lane
        if rec.state is RequestState.FINISHED:
            # the full phase chain is reconstructable from the trace alone
            assert {"queued", "prefilling", "decoding"} <= set(names)
        # timestamps in a lane are monotone (sorted view of a causal chain)
        ts = [e["ts"] for e in lane]
        assert ts == sorted(ts)

    # the disturbances actually happened and were annotated
    by_state = {r.state.value for r in sched.outcomes.values()}
    assert {"finished", "failed", "cancelled", "shed"} <= by_state
    event_names = [e[1] for e in tracer.events()]
    assert "nan_quarantine" in event_names
    assert "sched/decode_chunk" in event_names

    # zero-leak accounting, through the exact bytes a scraper would see
    samples = parse_prometheus(prometheus_text(registry))
    submitted = sum(v for _, v in samples["serve_submitted_total"])
    terminal = sum(
        sum(v for _, v in samples.get(f"serve_{k}_total", []))
        for k in ("finished", "cancelled", "timed_out", "shed", "failed",
                  "rejected_queue_full")
    )
    assert submitted == len(sched.outcomes) and submitted == terminal
    assert counters_agree(registry, sched.counters) == []
    # per-format kernel dispatch census rode along on the global registry
    fam = default_registry().snapshot().get("qmatmul_dispatch_total")
    assert fam is not None
    assert any(
        s["labels"].get("fmt") == "bcq" and s["value"] > 0 for s in fam["series"]
    )


def test_instrumented_serving_token_identical():
    eng = _engine()
    plain_sched = Scheduler(eng, n_slots=2, chunk=3)
    for r in _requests():
        plain_sched.submit(r)
    plain = {c.rid: c.new_tokens for c in plain_sched.run()}
    built = eng.programs_built()

    tr, reg = Tracer(), MetricsRegistry()
    sched = Scheduler(eng, n_slots=2, chunk=3, tracer=tr, metrics=reg)
    for r in _requests():
        sched.submit(r)
    instrumented = {c.rid: c.new_tokens for c in sched.run()}

    assert set(plain) == set(instrumented)
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], instrumented[rid])
    assert tr.stats()["recorded"] > 0  # it did actually trace
    # the scheduler's step spans add no compile-cache entries
    assert eng.programs_built() == built
    assert counters_agree(reg, sched.counters) == []
    total = sum(len(t) for t in plain.values())
    assert reg.counter("serve_tokens_total").value == total
    assert reg.gauge("serve_queue_depth").value == 0  # drained
    assert reg.histogram("serve_ttft_seconds").count == len(plain)


def test_tracing_does_not_perturb_stepclock_deadlines():
    """The tracer has its own clock precisely so recording spans never
    consumes scheduler clock readings — the deadline outcome of a
    StepClock-driven run must be identical with and without instrumentation."""
    eng = _engine()

    def run(instrumented):
        clk = StepClock(dt=0.05)
        kw = dict(clock=clk, sleep=clk.sleep)
        if instrumented:
            kw.update(tracer=Tracer(), metrics=MetricsRegistry())
        sched = Scheduler(eng, n_slots=1, chunk=2, **kw)
        reqs = _requests(n=3, gen=4)
        reqs[-1].deadline_s = 0.01  # sheds while earlier requests hold the slot
        for r in reqs:
            sched.submit(r)
        sched.run()
        return sched.summary()["by_state"], dict(sched.counters)

    assert run(False) == run(True)


def test_speculative_spans_account_for_draft_verify_rollback():
    from repro.quant import QuantPolicy, quantize_params

    cfg = _cfg()  # 128-dim: small enough to be fast, big enough to quantize
    params = quantize_params(
        init_params(KEY, cfg), QuantPolicy(q=3, g=32, iters=2)
    )
    tr, reg = Tracer(), MetricsRegistry()
    eng = Engine(cfg, params, max_seq=MAX_SEQ, tracer=tr)
    spec = SpecConfig(q_draft=2, gamma=2)
    sched = Scheduler(eng, n_slots=2, chunk=2, speculate=spec,
                      tracer=tr, metrics=reg)
    corpus = MarkovCorpus(cfg.vocab, seed=3)
    for i in range(2):
        prompt = corpus.sample(1, 5, seed=300 + i)[0, :5].astype(np.int32)
        sched.submit(Request(prompt=prompt, max_new_tokens=6))
    done = sched.run()
    assert len(done) == 2

    verifies = [e for e in tr.events() if e[1] == "spec_verify"]
    assert verifies, "speculative run emitted no spec_verify annotations"
    committed_in_chunks = 0
    for ev in verifies:
        args = ev[6]
        assert args["drafted"] == spec.gamma
        assert 0 <= args["accepted"] <= spec.gamma
        assert args["accepted"] + args["rolled_back"] == spec.gamma
        assert ev[3].startswith("req:")  # attributed to the request's lane
        committed_in_chunks += args["accepted"] + 1
    # every chunk-committed token is accounted for by exactly one sub-chunk
    assert committed_in_chunks == sched.steps_active
    assert reg.gauge("serve_spec_accept_rate").value == pytest.approx(
        sched.spec_accept_rate
    )
    assert "engine/spec_chunks" in {e[1] for e in tr.events()}


# ---------------------------------------------------------------------------
# profiler hooks stay outside jit: no retrace, no host callbacks, host-only
# ---------------------------------------------------------------------------


def test_instrumentation_adds_no_compile_cache_entries():
    """An engine with a tracer attached must compile exactly the same
    programs: two identical-shape generations leave each jitted entry with
    at most one compile-cache entry (the staticcheck trace-once contract),
    and the tokens match the uninstrumented engine bit-for-bit."""
    tr = Tracer()
    eng = Engine(_cfg(), init_params(KEY, _cfg()), max_seq=MAX_SEQ, tracer=tr)
    corpus = MarkovCorpus(_cfg().vocab, seed=3)
    p = corpus.sample(1, 5, seed=400)[0, :5].astype(np.int32)
    out = eng.generate(p[None], 6)
    eng.generate(corpus.sample(1, 5, seed=401)[0, :5].astype(np.int32)[None], 6)
    for name in ("_prefill", "_scan_decode"):
        size = getattr(eng, name)._cache_size()
        assert size <= 1, f"{name} retraced under instrumentation ({size})"
    # host-side spans were recorded around (not inside) the dispatches
    names = {e[1] for e in tr.events()}
    assert {"engine/prefill", "engine/scan_decode"} <= names
    solo = _engine().generate(p[None], 6)
    np.testing.assert_array_equal(out.tokens, solo.tokens)


# ---------------------------------------------------------------------------
# one span path, two sinks; the engine's work counters
# ---------------------------------------------------------------------------

SCHED_PHASES = ("sched/step", "sched/admit", "sched/decode_chunk",
                "sched/fetch", "sched/commit", "sched/nan_guard")


def _host_spans(profile_dir) -> dict:
    """``name -> [(thread, start_ns, end_ns)]`` of the host events of the
    ``jax.profiler`` capture under ``profile_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.setdefault(e.name, []).append(
                    ((plane.name, i), int(e.start_ns),
                     int(e.start_ns + e.duration_ns))
                )
    return out


def test_every_ring_span_lands_in_the_device_profile(tmp_path):
    """One span, two sinks: a chunked serve under ``jax.profiler.trace``
    puts each ``sched/*`` and ``engine/*`` span of the ring tracer into the
    capture's host events under the same name, once per ring span, with
    the fetch and the decode chunk nested in their step."""
    tr = Tracer()
    eng = Engine(_cfg(), init_params(KEY, _cfg()), max_seq=MAX_SEQ, tracer=tr)
    sched = Scheduler(eng, n_slots=2, chunk=3, prefill_chunk=4, tracer=tr)
    for r in _requests():
        sched.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        sched.run()

    ring = [e[1] for e in tr.events()
            if e[0] == "X" and e[1].startswith(("sched/", "engine/"))]
    assert set(SCHED_PHASES) | {"engine/prefill_chunk", "engine/admit",
                                "engine/scan_decode"} <= set(ring)
    host = _host_spans(str(tmp_path))
    for name in set(ring):
        assert len(host.get(name, [])) == ring.count(name), name
    steps = host["sched/step"]
    for name in ("sched/fetch", "sched/decode_chunk"):
        for thread, start, end in host[name]:
            assert any(t == thread and s <= start and end <= e
                       for t, s, e in steps), name


def test_scheduler_records_on_its_engines_tracer():
    """A scheduler given no tracer records on its engine's, so one ring
    holds the step's phases and the dispatches nested in them."""
    tr = Tracer()
    eng = Engine(_cfg(), init_params(KEY, _cfg()), max_seq=MAX_SEQ, tracer=tr)
    sched = Scheduler(eng, n_slots=2, chunk=3)
    assert sched.tracer is tr
    for r in _requests():
        sched.submit(r)
    sched.run()
    names = {e[1] for e in tr.events()}
    assert set(SCHED_PHASES) | {"engine/admit", "engine/scan_decode"} <= names
    assert Scheduler(_engine(), n_slots=1, chunk=1).tracer is None


@pytest.mark.parametrize("prefill_chunk", [None, 4])
def test_engine_counters_count_what_is_dispatched(prefill_chunk):
    """Whole-shot admission computes exactly the prompt rows; chunked
    admission computes each chunk's bucket (8 rows, the smallest, for
    chunks of at most 4 tokens here), so padding shows as rows over
    tokens."""
    eng = _engine()
    before = dict(eng.counters)
    sched = Scheduler(eng, n_slots=2, chunk=3, prefill_chunk=prefill_chunk)
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    sched.run()
    got = {k: eng.counters[k] - before[k] for k in before}

    plens = [int(r.prompt.size) for r in reqs]
    if prefill_chunk is None:
        rows = sum(plens)
    else:
        rows = 8 * sum(-(-p // prefill_chunk) for p in plens)
    assert got == {"prefill_tokens": sum(plens), "prefill_rows": rows}


def test_programs_built_rises_on_a_new_shape_only():
    """Whole-shot admission compiles its prefill per prompt length: a
    repeated length builds nothing, a new one builds its programs."""
    eng = Engine(_cfg(), init_params(KEY, _cfg()), max_seq=MAX_SEQ)
    corpus = MarkovCorpus(_cfg().vocab, seed=3)
    slots = eng.init_slots(2)

    def admit(plen, seed):
        prompt = corpus.sample(1, plen, seed=seed)[0, :plen].astype(np.int32)
        return eng.admit_slot(slots, 0, prompt, max_new_tokens=2)

    slots = admit(5, 1)
    built = eng.programs_built()
    assert built > 0
    slots = admit(5, 2)
    assert eng.programs_built() == built
    slots = admit(7, 3)
    assert eng.programs_built() > built


def test_obs_package_imports_without_jax():
    """repro.obs is host-side-only: importing it must not pull jax (the
    structural guarantee behind 'instrumentation cannot touch device
    state'). Run in a subprocess so this module's own jax import doesn't
    mask a regression."""
    src_dir = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env = dict(os.environ, PYTHONPATH=src_dir)
    code = (
        "import sys; import repro.obs; "
        "bad = sorted(m for m in sys.modules if m == 'jax' or "
        "m.startswith('jax.')); "
        "assert not bad, f'repro.obs pulled {bad[:3]}'"
    )
    subprocess.run([sys.executable, "-c", code], env=env, check=True)


def test_obs_host_only_lint_rule():
    bad = (
        "import jax\n"
        "from repro.kernels import ops\n"
        "try:\n"
        "    import repro.models\n"
        "except ImportError:\n"
        "    pass\n"
        "def demo():\n"
        "    import jax  # lazy: allowed\n"
    )
    hits = [v for v in lint.lint_source(bad, "obs/bad.py")
            if v.passname == "lint/obs-host-only"]
    assert sorted(int(v.where.split(":")[1]) for v in hits) == [1, 2, 4]
    good = "import json\ndef demo():\n    from repro.infer import Engine\n"
    assert lint.lint_source(good, "obs/good.py") == []
    # the rule is scoped to obs/ — the hot-path dirs legitimately import jax
    assert not [v for v in lint.lint_source("import jax\n", "infer/x.py")
                if v.passname == "lint/obs-host-only"]


def test_repo_lint_clean_including_obs_rule():
    """The instrumented stack stays lint-clean: every new host sync is
    declared, and the obs package never imports the jitted stack."""
    result = lint.run()
    assert result.checked > 0
    assert result.violations == [], "\n".join(str(v) for v in result.violations)


def test_tracer_overhead_within_budget():
    """DESIGN.md §11 budget: a recorded span costs two clock readings and a
    deque append — single-digit µs typical. Asserted against a 50x slack
    bound so a loaded CI host never flakes, while a pathological regression
    (formatting per record, lock convoy) still fails."""
    tr = Tracer(capacity=100_000)
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        with tr.span("bench", lane="bench", i=i):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert tr.stats()["recorded"] == n
    assert per_span < 100e-6, f"{per_span * 1e6:.1f} µs/span exceeds budget"


# ---------------------------------------------------------------------------
# server export surfaces
# ---------------------------------------------------------------------------


def _go(coro, timeout=120.0):
    import asyncio

    return asyncio.run(asyncio.wait_for(coro, timeout))


def test_session_exports_prometheus_and_trace():
    from repro.launch.server import ServeSession

    eng = _engine()
    (req,) = _requests(n=1, gen=5)

    async def run():
        async with ServeSession(eng, n_slots=2, chunk=3) as sess:
            stream = await sess.submit_stream(req)
            await stream.drain()
            return sess.metrics(), sess.prometheus(), sess.trace_json()

    m, text, trace = _go(run())
    assert "registry" in m and "tracer" in m
    assert m["tracer"]["recorded"] > 0
    samples = parse_prometheus(text)
    assert sum(v for _, v in samples["serve_submitted_total"]) == 1
    assert sum(v for _, v in samples["serve_finished_total"]) == 1
    assert "serve_slot_capacity" in samples
    assert validate_chrome_trace(trace) == []
    assert "req:0" in request_lifecycles(trace)

    dark = ServeSession(eng, observe=False)
    assert dark.tracer is None and dark.registry is None
    with pytest.raises(RuntimeError, match="no metrics registry"):
        dark.prometheus()
    with pytest.raises(RuntimeError, match="no tracer"):
        dark.trace_json()


def test_http_prometheus_and_trace_endpoints():
    aiohttp = pytest.importorskip("aiohttp")
    from repro.launch.server import ServeSession, bound_port, run_server

    eng = _engine()
    (req,) = _requests(n=1, gen=4)

    async def run():
        session = ServeSession(eng, n_slots=1, chunk=2)
        async with session:
            runner = await run_server(session, port=0)
            base = f"http://127.0.0.1:{bound_port(runner)}"
            try:
                stream = await session.submit_stream(req)
                await stream.drain()
                async with aiohttp.ClientSession() as client:
                    async with client.get(
                        f"{base}/v1/metrics", params={"format": "prometheus"}
                    ) as r:
                        assert r.status == 200
                        assert r.content_type == "text/plain"
                        text = await r.text()
                    async with client.get(f"{base}/v1/metrics") as r:
                        summary = await r.json()
                    async with client.get(f"{base}/v1/trace") as r:
                        trace = await r.json()
            finally:
                await runner.cleanup()
        return text, summary, trace

    text, summary, trace = _go(run(), timeout=180.0)
    samples = parse_prometheus(text)
    assert sum(v for _, v in samples["serve_finished_total"]) == 1
    # the scrape merges the process-global registry: kernel dispatch counts
    # ride along when any quantized model ran in this process (not asserted
    # present — this engine is dense)
    assert summary["by_state"] == {"finished": 1}
    assert "registry" in summary and "tracer" in summary
    assert validate_chrome_trace(trace) == []
    assert request_lifecycles(trace)  # at least the served request's lane
