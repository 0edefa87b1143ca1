"""``BENCHMARK.json`` against the rules every later PR is checked by."""

import json
import os
import re

import pytest

from tpubench import spec

BENCH = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# keys that name a width, which ``reduced`` may never list; the depth
# (``num_hidden_layers``) may be cut
WIDTH = re.compile(r"(hidden(?!_layers)|intermediate|latent|state|proj|head_dim|_dim$|_rank$|"
                   r"expansion|experts_per_tok|num_experts_per)")


def test_the_width_rule_tells_depth_from_width():
    assert all(WIDTH.search(k) for k in ("hidden_size", "intermediate_size", "head_dim",
                                         "kv_lora_rank", "num_experts_per_tok"))
    assert not any(WIDTH.search(k) for k in ("num_hidden_layers", "rms_norm_eps"))


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"][0] == "python3" and len(BENCH["command"]) <= 32
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    assert all(not w.startswith("/") and ".." not in w for w in BENCH["command"])
    assert 1 <= BENCH["run_seconds"] <= 51


def test_a_full_check_fits_its_time():
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entry_keys():
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert c["file"].startswith(tuple(BENCH["paths"]))
        assert all(NAME.match(k) and not WIDTH.search(k) for k in c["reduced"])
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs and len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
    metrics = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in metrics
        metrics.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_what_it_must():
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        e2e = {m.name for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m.moves in e2e, (w["name"], m.name)


def test_every_file_the_harness_finds_by_name_exists():
    for m in BENCH["per_layer"]:
        assert callable(spec.load_reader(m["name"]))
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert {"server", "arrivals", "prompt_len", "output_len", "check"} <= set(cell.traffic)
        srv = cell.traffic["server"]
        assert cell.traffic["prompt_len"]["max"] + cell.traffic["output_len"]["max"] <= srv["max_seq"]
        assert cell.traffic["check"]["gap_limit"] is not None


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files_build_and_state_their_cuts(c):
    from repro.models.config import ModelConfig

    f = spec.load_json(os.path.join(spec.ROOT, c["file"]))
    cfg = ModelConfig(**f["model_config"])
    assert cfg.d_model == f["hidden_size"] and cfg.d_ff == f["intermediate_size"]
    assert cfg.n_layers == f["num_hidden_layers"] and cfg.vocab == f["vocab_size"]
    assert cfg.d_head == f["head_dim"] and cfg.n_kv_heads == f["num_key_value_heads"]
    assert sorted(f["reduced"]) == sorted(c["reduced"]) == sorted(f["published"])
    assert f["source"] == c["source"]


def test_every_traced_span_holds_arrivals():
    """A traced run counts ``[0, trace_s)``; the scheduler and prefill
    readers need requests that arrive in it."""
    from tpubench import cell as C
    from tpubench import traffic

    for w in BENCH["workloads"]:
        tf = spec.load_cell(w["name"]).traffic
        span = tf.get("trace_s", C.TRACE_S)
        assert 0 < span <= BENCH["run_seconds"]
        arrivals = traffic.schedule(tf, 1000, 1, horizon_s=span)
        assert any(0.0 <= a.at < span for a in arrivals), w["name"]
