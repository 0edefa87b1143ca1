"""The outputs check, driven through a whole run on the CPU at a small size.

The control (the reference forward with float8 operands) must read above the
cell's limit while the program reads below it, and a run whose timed path
alters a served token where it is produced must come out not correct. The
harness's look for a chip is skipped: ``run_cell`` is called directly."""

import time

import jax
import pytest

import calibrate
from tpubench import cell as C
from tpubench import spec

from conftest import DATA


@pytest.fixture(scope="module")
def tiny():
    return spec.Cell(
        name="tiny.chat", chips=1, config=spec.load_json(f"{DATA}/tiny-config.json"),
        traffic=spec.load_json(f"{DATA}/tiny-traffic.json"),
        end_to_end=[spec.Metric(n, "u", "lower", "host_clock", None)
                    for n in ("output_tok_s", "ttft_p50_s", "tpot_ms", "setup_s")],
        per_layer=[])


def test_control_fails_the_limit_the_program_passes(tiny):
    limit = tiny.traffic["check"]["gap_limit"]
    readings = []
    calibrate.calibrate(tiny, [11, 12], 2.0, log=readings.append)
    import json

    for r in map(json.loads, readings):
        assert r["max_gap"] < limit < r["control_max_gap"], r


def _run(tiny, hook=None):
    return C.run_cell(tiny, 21, 2.0, False, t_start=time.perf_counter(),
                      device=jax.devices()[0], engine_hook=hook, log=lambda m: None)


def test_a_sound_run_is_correct(tiny):
    out = _run(tiny)
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {"output_tok_s", "ttft_p50_s", "tpot_ms", "setup_s"}


def test_a_token_altered_where_produced_is_caught(tiny):
    def alter_tokens(engine):
        decode = engine.decode_slots

        def altered(slots, n_steps):
            toks, valid, out = decode(slots, n_steps)
            return (toks + 1) % engine.cfg.vocab, valid, out

        engine.decode_slots = altered

    out = _run(tiny, alter_tokens)
    assert not out["correct"]
    assert out["check"]["served_token_gap"]["value"] > tiny.traffic["check"]["gap_limit"]


def test_a_traced_span_settles_before_the_profiler_stops(tiny):
    """With ``count_s`` short of the window, ``on_settled`` (where a traced
    run stops its profiler) comes after ``on_close`` and only once every
    request that arrived in ``[0, count_s)`` has its first token; each was
    admitted between its arrival and its first token."""
    from tpubench import serve

    _, engine, arrivals = C.setup(tiny, 31, 2.0, log=lambda m: None)
    seen = []
    w = serve.serve(engine, arrivals, tiny.traffic["server"], 2.0,
                    on_close=lambda: seen.append(("closed", time.perf_counter())),
                    on_settled=lambda: seen.append(("settled", time.perf_counter())),
                    count_s=1.0, drain_max_s=30.0)
    assert [k for k, _ in seen] == ["closed", "settled"]
    t0 = w.loop_start + max(0.0, -min(a.at for a in arrivals))
    counted = w.counted()
    assert counted and all(0.0 <= r.arrival < 1.0 for r in counted)
    assert all(r.first is not None and r.first <= seen[1][1] - t0 for r in counted)
    assert all(r.arrival <= r.admitted <= r.first for r in counted)
