"""``phases.py``: one serve of the tiny cell on the CPU with the ring tracer
attached."""

import jax
import pytest

import phases
from tpubench import spec

from conftest import DATA


@pytest.fixture(scope="module")
def tiny_run():
    cell = spec.Cell(
        name="tiny.chat", chips=1, config=spec.load_json(f"{DATA}/tiny-config.json"),
        traffic=spec.load_json(f"{DATA}/tiny-traffic.json"), end_to_end=[], per_layer=[])
    return phases.measure(cell, 2**31 + 41, 2.0, False, log=lambda m: None)


def test_engine_counters_agree_with_the_benchmarks_count(tiny_run):
    """The engine counts the prompt tokens the benchmark counts around each
    ``advance_admission`` call, and one bucket of rows per chunk."""
    out, w = tiny_run
    c = out["engine_counters"]
    assert w.prefill_chunks
    assert c["prefill_tokens"] == out["bench_prefill_tokens"] == sum(
        n for _, n, _ in w.prefill_chunks)
    max_seq = 256  # tiny-traffic.json: chunk buckets 8, 16, ..., 256
    buckets = [8 * 2**i for i in range(6)]
    assert c["prefill_rows"] == sum(
        next((b for b in buckets if b >= n and pos + b <= max_seq), n)
        for pos, n, _ in w.prefill_chunks)
    assert out["prefill_pad_pct"] == pytest.approx(
        100.0 * (c["prefill_rows"] - c["prefill_tokens"]) / c["prefill_rows"])
    assert out["programs_built_in_window"] >= 0


def test_the_slowest_steps_carry_their_phases(tiny_run):
    out, _ = tiny_run
    assert out["device"]["platform"] == jax.devices()[0].platform
    assert out["ring"]["evicted"] == 0
    steps = out["slow_steps"]
    assert steps and steps == sorted(steps, key=lambda s: -s["s"])
    for s in steps:
        assert "sched/admit" in s["phases"]
        assert sum(s["phases"].values()) <= s["s"]
        assert {"ordinal", "queued", "prefilling", "decoding"} <= set(s)
    assert out["steps_in_trace_span"]["n"] > 0
    assert out["span_cost_us"]["no_tracer"] > 0 and out["span_cost_us"]["ring"] > 0
