"""``tpubench.steps``: the reductions over the scheduler's step spans and
the engine's counters, on synthetic input and on a CPU profile."""

import jax
import jax.numpy as jnp
import pytest

from tpubench import steps
from tpubench import trace as tr


def _trace(host):
    """Device ops busy over [0, 10), [30, 40), [60, 100) of a 100 ns span."""
    return tr.Trace.from_json({
        "ops": [["fusion.1", 0, 10, "fusion"], ["bcq_mm.1", 30, 10, "bcq_mm"],
                ["copy.1", 60, 40, "copy"]],
        "modules": [], "host": host, "t0": 0, "t1": 100})


def test_idle_per_chunk_counts_only_idle_inside_steps():
    trc = _trace([
        ["bench/window", 0, 100, ""],
        # step 1 covers 5-40: idle 10-30 inside it (20 ns)
        ["sched/step", 5, 35, ""], ["sched/decode_chunk", 8, 2, ""],
        ["sched/fetch", 12, 20, ""],
        # step 2 covers 50-70: idle 50-60 (10 ns); the gap 40-50 is outside
        # both steps and does not count
        ["sched/step", 50, 20, ""], ["sched/decode_chunk", 52, 3, ""],
    ])
    assert steps.idle_ms_per_chunk(trc) == pytest.approx((20 + 10) / 1e6 / 2)


def test_idle_per_chunk_reads_nothing_without_scheduler_spans():
    trc = _trace([["bench/window", 0, 100, ""], ["bench/scheduler_step", 5, 40, ""],
                  ["engine/scan_decode", 8, 2, ""]])
    assert steps.idle_ms_per_chunk(trc) is None


def test_prefill_pad_pct():
    assert steps.prefill_pad_pct({"prefill_rows": 1024, "prefill_tokens": 896}) == 12.5
    assert steps.prefill_pad_pct({"prefill_rows": 0, "prefill_tokens": 0}) is None
    assert steps.prefill_pad_pct({}) is None


def test_slow_steps_name_the_phase_that_held_them():
    X = "X"
    events = [  # (ph, name, cat, lane, ts, dur, args), in completion order
        (X, "sched/admit", "", "", 10.0, 0.1, None),
        (X, "sched/fetch", "", "", 10.2, 2.5, {"tokens": 8}),
        (X, "sched/step", "", "", 10.0, 3.0, {"ordinal": 4, "queued": 0}),
        (X, "sched/fetch", "", "", 13.1, 0.4, None),
        (X, "sched/step", "", "", 13.0, 0.6, {"ordinal": 5, "queued": 1}),
        (X, "sched/step", "", "", 2.0, 9.0, {"ordinal": 0}),  # before the window
        ("i", "submit", "", "", 11.0, None, None),
    ]
    (slow,) = steps.slow_steps(events, 5.0, 60.0, k=1)
    assert slow["at"] == pytest.approx(5.0) and slow["s"] == 3.0
    assert slow["ordinal"] == 4 and slow["queued"] == 0
    assert slow["phases"] == {"sched/admit": 0.1, "sched/fetch": 2.5}
    assert [s["ordinal"] for s in steps.slow_steps(events, 5.0, 60.0)] == [4, 5]
    summary = steps.step_summary(events, 5.0, 60.0)
    assert summary["n"] == 2 and summary["median_s"] == pytest.approx(1.8)
    assert summary["phase_mean_s"] == pytest.approx(
        {"sched/admit": 0.05, "sched/fetch": 1.45})
    assert steps.step_summary(events, 20.0, 60.0) == {"n": 0}
    assert steps.slow_steps([("i", "submit", "", "", 11.0, None, None)], 0.0, 60.0) == []


def test_host_spans_keep_only_the_prefixes_asked_for(tmp_path):
    """On a real capture: the annotations under the asked prefixes come
    back with their nesting, the others do not."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("sched/step"):
            with jax.profiler.TraceAnnotation("sched/fetch"):
                jax.block_until_ready(jnp.ones(4) + 1)
            with jax.profiler.TraceAnnotation("other/span"):
                pass
    pd = ProfileData.from_file(tr.find_xplane(str(tmp_path)))
    got = {e.name: e for e in steps.host_spans(pd.planes, ("sched/",))}
    assert set(got) == {"sched/step", "sched/fetch"}
    step, fetch = got["sched/step"], got["sched/fetch"]
    assert step.start <= fetch.start and fetch.end <= step.end
