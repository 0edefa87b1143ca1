"""A configuration's architecture is found by name, and the harness takes the
weights, the program's tree, the reference and the work from that module
alone: a stand-in module (``data/archs/stub.py``) is loaded, counted and
served with no harness file changed."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpubench import arch, serve, spec, weights, work
from tpubench import cell as C
from tpubench import trace as tr
from tpubench.peaks import peaks

from conftest import DATA

TINY = spec.load_json(f"{DATA}/tiny-config.json")


@pytest.fixture
def stub(monkeypatch):
    monkeypatch.setattr(arch, "DIRS", arch.DIRS + [os.path.join(DATA, "archs")])
    config = dict(TINY, name="tiny-stub", arch="stub")
    mod = arch.of(config)
    mod.CALLS.clear()
    return config, mod


def test_a_configuration_without_arch_is_dense():
    assert arch.of(TINY) is arch.load("dense")
    assert "dense" in arch.found() and "stub" not in arch.found()


def test_an_unknown_arch_is_an_error_that_lists_the_modules():
    with pytest.raises(ValueError, match="nonesuch.*dense"):
        arch.of(dict(TINY, arch="nonesuch"))


def _weights_before(config: dict):
    """``key -> weights`` as the harness built dense weights before the
    architecture modules: the layer linears marked per layer, stacked over
    ``L``."""
    mc, qc = config["model_config"], config["quant"]
    D, F, V, L = mc["d_model"], mc["d_ff"], mc["vocab"], mc["n_layers"]
    Q, KV = mc["n_heads"] * mc["d_head"], mc["n_kv_heads"] * mc["d_head"]
    lins = [("wqkv", D, Q + 2 * KV, True), ("wo", Q, D, True),
            ("w_gate_up", D, 2 * F, True), ("w_down", F, D, True),
            ("lm_head", D, V, False)]
    sdt, pdt = jnp.dtype(qc["scale_dtype"]), jnp.dtype(mc["param_dtype"])

    def fill(key):
        keys = jax.random.split(key, 8)
        layers = {"ln1": jnp.ones((L, D), pdt), "ln2": jnp.ones((L, D), pdt)}
        out = {"final_norm": jnp.ones((D,), pdt)}
        for i, (name, k, o, per_layer) in enumerate(lins):
            lead = (L,) if per_layer else ()
            leaf = weights._bcq_leaf(keys[i], lead, k, o, qc["q"], qc["g"], sdt)
            (layers if per_layer else out)[name] = leaf
        out["layers"] = layers
        out["embed"] = (jax.random.normal(keys[7], (V, D), jnp.float32)
                        * weights.EMBED_STD).astype(pdt)
        return out

    return fill


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_dense_weights_are_bit_identical_to_the_harness_before(seed):
    got = weights.make_weights(TINY, seed)
    want = jax.jit(_weights_before(TINY))(weights.jax_key(seed, 0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_work_follows_the_modules_counts(stub):
    config, mod = stub
    mc, qc = config["model_config"], config["quant"]
    D, F, V, H = mc["d_model"], mc["d_ff"], mc["vocab"], mc["n_heads"] * mc["d_head"]
    assert mod.attention_layers(mc) < mc["n_layers"]
    assert work.linear_params(mod, mc) == 3 * D * 2 * D + F * D + 2 * F * D + D * V
    assert work.linear_params(mod, mc, head=False) == 3 * D * 2 * D + F * D + 2 * F * D
    flops, bytes_ = work.qmm_work(mod, mc, qc, passes=2, rows=5, head_rows=1)
    layers = [(D, 2 * D, 3), (D, F, 1), (F, D, 2)]
    assert flops == pytest.approx(sum(c * 2 * 5 * k * o for k, o, c in layers)
                                  + 2 * 1 * D * V)
    assert bytes_ == pytest.approx(
        sum(c * (2 * work.bcq_bytes(k, o, 3, 128) + 2 * 5 * (k + o)) for k, o, c in layers)
        + 2 * work.bcq_bytes(D, V, 3, 128) + 2 * 1 * (D + V))
    assert work.attention_flops(mod, mc, [10, 20]) == 4.0 * 1 * H * 30


def _run(mod, config):
    """A traced window's worth of synthetic events: one decode chunk of 1 s
    (its kernels 0.5 s) and one prefill chunk of 0.5 s (kernels 0.25 s)."""
    ms = 1_000_000

    def evs(xs):
        return [tr.Ev(n, s * ms, d * ms, n) for n, s, d in xs]

    trace = tr.Trace(ops=evs([("bcq_mm.1", 0, 500), ("bcq_mm.2", 1000, 250)]),
                     modules=evs([("jit__scan_decode_slots", 0, 1000),
                                  ("jit__prefill_chunk", 1000, 500)]),
                     host=[], t0=0, t1=2000 * ms)
    records = [serve.Record(arrival=0.0, plen=100, max_new=8,
                            token_times=[0.5 + 0.01 * j for j in range(8)])]
    window = serve.Window(seconds=2.0, count_s=2.0, records=records, opened_at=0.0,
                          closed_at=2.0, counters={"decode_steps": 8, "steps_active": 8,
                                                   "retries": 0, "n_slots": 4},
                          prefill_chunks=[(0, 100, 100)], compiles_in_window=0,
                          compile_s_in_window=0.0, loop_start=0.0, drained=True,
                          backlog=(0, 0), slow_steps=[])
    return C.Run(arch=mod, mc=config["model_config"], qc=config["quant"],
                 server={"decode_chunk": 8}, window=window, trace=trace,
                 peak=peaks("TPU v5 lite"))


def test_the_readers_follow_the_modules_counts(stub):
    config, mod = stub
    mc, qc = config["model_config"], config["quant"]
    D, V, H = mc["d_model"], mc["vocab"], mc["n_heads"] * mc["d_head"]
    run, peak = _run(mod, config), peaks("TPU v5 lite")
    read = {m: spec.load_reader(m)(run) for m in (
        "qmm.decode_roofline", "qmm.prefill_roofline",
        "step.decode_mfu_pct", "step.prefill_mfu_pct")}

    decode = work.roofline_s(*work.qmm_work(mod, mc, qc, passes=8, rows=8, head_rows=8), peak)
    prefill = work.roofline_s(*work.qmm_work(mod, mc, qc, passes=1, rows=100, head_rows=1),
                              peak)
    assert read["qmm.decode_roofline"] == pytest.approx(100 * decode / 0.5)
    assert read["qmm.prefill_roofline"] == pytest.approx(100 * prefill / 0.25)

    params = work.linear_params(mod, mc)
    layer_params = params - D * V
    attn = 4.0 * 1 * H  # per cached key, in the one attention layer
    want_decode = 2.0 * params * 8 + attn * sum(100 + j + 1 for j in range(8))
    want_prefill = 2.0 * layer_params * 100 + 2.0 * D * V + attn * sum(range(1, 101))
    assert read["step.decode_mfu_pct"] == pytest.approx(
        100 * want_decode / (1.0 * peak["bf16_flops"]))
    assert read["step.prefill_mfu_pct"] == pytest.approx(
        100 * want_prefill / (0.5 * peak["bf16_flops"]))
    # the dense module's counts give other numbers for the same run
    dense = _run(arch.load("dense"), config)
    assert spec.load_reader("step.decode_mfu_pct")(dense) != pytest.approx(
        read["step.decode_mfu_pct"])


def test_a_configuration_naming_the_stub_is_served(stub):
    config, mod = stub
    cell = spec.Cell(
        name="tiny-stub.chat", chips=1, config=config,
        traffic=spec.load_json(f"{DATA}/tiny-traffic.json"),
        end_to_end=[spec.Metric(n, "u", "lower", "host_clock", None)
                    for n in ("output_tok_s", "setup_s")],
        per_layer=[])
    out = C.run_cell(cell, 41, 2.0, False, t_start=time.perf_counter(),
                     device=jax.devices()[0], log=lambda m: None)
    assert out["correct"], out["check"]
    assert {"weights_fn", "program_params", "forward"} <= set(mod.CALLS)

