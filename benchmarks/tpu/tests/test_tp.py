"""A cell whose configuration states tensor parallelism, on four CPU devices.

It is served through the program's mesh path (``Engine(mesh=...)``, chunked
admission and the slot decode under ``shard_map``), its weights are made
split over the mesh with the values a one-chip run makes from the same seed,
and its served tokens pass the reference's check and match a one-chip run's.
The harness's look for a chip is skipped: ``run_cell`` is called directly."""

import json
import time

import jax
import numpy as np
import pytest

from tpubench import cell as C
from tpubench import serve, spec, weights

from conftest import DATA

TP4 = spec.load_json(f"{DATA}/tiny-tp4-config.json")
ONE = {k: v for k, v in TP4.items() if k != "parallel"}
SEED = 2**31 + 17
LIMIT = spec.load_json(f"{DATA}/tiny-traffic.json")["check"]["gap_limit"]

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")


def _cell(config, chips=1):
    return spec.Cell(
        name="tiny-tp4.chat", chips=chips, config=config,
        traffic=spec.load_json(f"{DATA}/tiny-traffic.json"),
        end_to_end=[spec.Metric(n, "u", "lower", "host_clock", None)
                    for n in ("output_tok_s", "setup_s")],
        per_layer=[])


def test_a_cell_whose_chips_differ_from_its_configurations_tp_is_refused(tmp_path):
    cfg = tmp_path / "tp4.json"
    cfg.write_text(json.dumps(TP4))
    bench = {"configs": [{"name": "tiny-tp4", "file": str(cfg)}],
             "workloads": [{"name": f"c{chips}", "config": "tiny-tp4", "chips": chips,
                            "traffic": "phi4-mini.chat"} for chips in (1, 4)],
             "end_to_end": [], "per_layer": []}
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    assert spec.load_cell("c4", str(path)).tp == 4
    with pytest.raises(ValueError, match="1 chip.*tensor parallelism over 4"):
        spec.load_cell("c1", str(path))
    assert _cell(ONE).tp == 1


def test_weights_split_over_the_mesh_hold_the_one_chip_values():
    mesh = weights.mesh(4)
    whole = weights.make_weights(TP4, SEED)
    split = weights.make_weights(TP4, SEED, mesh)
    assert jax.tree.structure(whole) == jax.tree.structure(split)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(split)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    layers, shard = split["layers"], lambda x: x.sharding.shard_shape(x.shape)
    assert shard(layers["wqkv"]["packed"])[-1] == layers["wqkv"]["packed"].shape[-1] // 4
    assert shard(layers["w_down"]["scales"])[-2] == layers["w_down"]["scales"].shape[-2] // 4
    assert shard(split["lm_head"]["packed"])[-1] == split["lm_head"]["packed"].shape[-1] // 4
    assert shard(split["embed"]) == split["embed"].shape


def test_a_tensor_parallel_cell_is_served_on_the_mesh_and_is_correct():
    cell = _cell(TP4, chips=4)
    out = C.run_cell(cell, SEED, 2.0, False, t_start=time.perf_counter(),
                     device=jax.devices()[0], log=lambda m: None)
    assert out["correct"], out["check"]
    assert out["check"]["served_token_gap"]["value"] <= LIMIT
    assert out["notes"]["reference_tokens"] > 0


def _served(config, seed):
    """Tokens of each finished request, by its index in the schedule, and
    what set-up handed back."""
    plain, engine, arrivals = C.setup(_cell(config), seed, 2.0, log=lambda m: None)
    w = serve.serve(engine, arrivals, _cell(config).traffic["server"], 2.0,
                    drain_max_s=30.0)
    done = {i: r.tokens for i, r in enumerate(w.records) if r.state == "finished"}
    return done, plain, engine, w


def _f32(config):
    """The configuration computing in float32: a row-parallel projection's
    partial sums then only reassociate in the all-reduce, where in bfloat16
    each chip rounds its partial sum first and near-tied tokens flip."""
    mc = dict(config["model_config"], param_dtype="float32", compute_dtype="float32",
              kv_cache_dtype="float32")
    return dict(config, model_config=mc)


def test_a_tensor_parallel_cell_serves_the_tokens_one_chip_serves():
    four, plain, engine, w = _served(_f32(TP4), SEED)
    assert plain is None  # the engine holds the only copy of the weights
    assert engine.mesh is not None and engine.mesh.devices.size == 4
    assert any(n < plen for _, n, plen in w.prefill_chunks)  # prompts took several chunks
    del engine
    one, plain, engine, _ = _served(_f32(ONE), SEED)
    assert plain is not None and engine.mesh is None
    common = sorted(set(four) & set(one))
    assert len(common) >= 5
    for i in common:
        np.testing.assert_array_equal(four[i], one[i], err_msg=f"request {i}")
