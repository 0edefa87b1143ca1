"""The trace reduction, on synthetic events and on a small trace recorded on
a TPU v5e (``data/trace_small.json``: the first part of a traced
``phi4-mini.chat`` window)."""

import json
import os

import pytest

from tpubench import trace as tr

from conftest import DATA


def ev(name, start, dur):
    return tr.Ev(name, start, dur, name)


@pytest.fixture
def synthetic():
    ops = [ev("fusion.1", 0, 10), ev("bcq_mm.1", 5, 10), ev("bcq_mm.2", 30, 10),
           ev("fusion.2", 60, 5)]
    modules = [ev("jit__scan_decode_slots(1)", 0, 20), ev("jit__prefill_chunk(2)", 25, 20)]
    host = [ev("bench/window", 0, 100), ev("bench/scheduler_step", 0, 50),
            ev("engine/scan_decode", 10, 10), ev("engine/prefill_chunk", 20, 30)]
    return tr.Trace(ops=ops, modules=modules, host=host, t0=0, t1=100)


def test_busy_is_the_union_of_op_intervals(synthetic):
    assert tr.union([(0, 10), (5, 15), (30, 40)]) == [(0, 15), (30, 40)]
    assert tr.busy_s(synthetic) == pytest.approx((15 + 10 + 5) / 1e9)


def test_ops_are_attributed_to_the_program_they_start_in(synthetic):
    assert tr.ops_s(synthetic, "bcq_mm", "_scan_decode_slots") == pytest.approx(10e-9)
    assert tr.ops_s(synthetic, "bcq_mm", "_prefill_chunk") == pytest.approx(10e-9)
    assert tr.ops_s(synthetic, "bcq_mm") == pytest.approx(20e-9)
    assert tr.program_s(synthetic, "_prefill_chunk") == pytest.approx(20e-9)


def test_idle_gaps_go_to_the_innermost_host_span(synthetic):
    gaps = dict(tr.idle_gaps(synthetic))
    # gaps: 15-30 (mid 22: engine/prefill_chunk), 40-60 (mid 50: no step
    # span covers 50 exactly, bench/scheduler_step ends at 50), 65-100
    assert gaps["engine/prefill_chunk"] == pytest.approx(15e-9)
    assert sum(gaps.values()) == pytest.approx(100e-9 - tr.busy_s(synthetic))


def test_top_ops_sums_by_name(synthetic):
    top = tr.top_ops(synthetic, k=2)
    assert top[0][0] == "bcq_mm" and top[0][1] == pytest.approx(20e-9)
    assert top[1][0] == "fusion" and top[1][1] == pytest.approx(15e-9)
    assert tr.op_kind("%bcq_mm.38 = f32[24,3072] custom-call(%pad.1)") == "bcq_mm"


RECORDED = os.path.join(DATA, "trace_small.json")


@pytest.fixture
def recorded():
    if not os.path.exists(RECORDED):
        pytest.skip("no recorded trace")
    with open(RECORDED) as f:
        return tr.Trace.from_json(json.load(f))


def test_recorded_trace_reduces_consistently(recorded):
    busy = tr.busy_s(recorded)
    assert 0 < busy <= recorded.window_s
    idle = sum(v for _, v in tr.idle_gaps(recorded, k=1000))
    assert busy + idle == pytest.approx(recorded.window_s, rel=1e-6)
    decode = tr.programs(recorded, "_scan_decode_slots")
    assert decode, "the decode program runs in the window"
    kernels = tr.ops_s(recorded, "bcq_mm", "_scan_decode_slots")
    assert 0 < kernels < sum(e.dur for e in decode) / 1e9


def test_containers_count_through_their_contents():
    ops = [ev("while.1", 0, 100), ev("bcq_mm.1", 10, 20), ev("fusion.1", 40, 10)]
    assert [e.name for e in tr.leaves(ops)] == ["bcq_mm.1", "fusion.1"]
