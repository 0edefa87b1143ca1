import pytest

from tpubench import arch, spec, work
from tpubench.peaks import peaks

PHI4 = spec.load_json(f"{spec.BENCH_DIR}/configs/phi4-mini-3.8b.json")
NEMO = spec.load_json(f"{spec.BENCH_DIR}/configs/mistral-nemo-12b.json")
DENSE = arch.load("dense")


def test_bcq_bytes_is_paper_eq3():
    # q bits per weight plus q bf16 scales per group of g
    assert work.bcq_bytes(4096, 4096, 3, 128) == 3 * 4096 * 4096 // 8 + 3 * 4096 * 32 * 2


def test_linear_params_of_phi4_mini():
    mc = PHI4["model_config"]
    per_layer = 3072 * (3072 + 2048) + 3072 * 3072 + 3072 * 16384 + 8192 * 3072
    assert work.linear_params(DENSE, mc, head=False) == 32 * per_layer
    assert work.linear_params(DENSE, mc) == 32 * per_layer + 3072 * 200064


def test_decode_step_is_bound_by_weight_bytes():
    mc, qc = PHI4["model_config"], PHI4["quant"]
    flops, bytes_ = work.qmm_work(DENSE, mc, qc, passes=1, rows=20, head_rows=20)
    weights = work.linear_params(DENSE, mc) * (3 / 8 + 3 * 2 / 128)
    acts = 20 * 2 * (32 * (3072 + 5120 + 3072 + 3072 + 3072 + 16384 + 8192 + 3072)
                     + 3072 + 200064)
    assert bytes_ == pytest.approx(weights + acts, rel=1e-6)
    t = work.roofline_s(flops, bytes_, peaks("TPU v5 lite"))
    assert t == pytest.approx(bytes_ / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")


# The work counts at the published shapes, as the harness computed them before
# the layers moved into architecture modules: exact, so the rooflines and MFU
# of the accepted cells move only with device time.
PINNED = {
    "phi4-mini-3.8b": (PHI4, 3835822080, 3221225472, (153432883200.0, 1684034560.0),
                       807665664.0, 825036374016.0),
    "mistral-nemo-12b": (NEMO, 11576279040, 10905190400, (463051161600.0, 5007155200.0),
                         1346109440.0, 1375060623360.0),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_work_at_published_shapes_is_pinned(name):
    config, params, layer_params, qmm, attn, attn_2k = PINNED[name]
    mc, qc = config["model_config"], config["quant"]
    mod = arch.of(config)
    assert mod is DENSE
    assert work.linear_params(mod, mc) == params
    assert work.linear_params(mod, mc, head=False) == layer_params
    assert work.qmm_work(mod, mc, qc, passes=1, rows=20, head_rows=20) == qmm
    assert work.attention_flops(mod, mc, [1, 2, 3, 2048]) == attn
    assert work.attention_flops(mod, mc, range(1, 2049)) == attn_2k
    assert work.head_flops(mod, mc, 3) == 2.0 * 3 * mc["d_model"] * mc["vocab"]
