import pytest

from tpubench import arch, spec, work
from tpubench.peaks import peaks

from conftest import DATA

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


LARGE = spec.load_json(f"{spec.BENCH_DIR}/configs/mistral-large-2-123b.json")
TINY_TP4 = spec.load_json(f"{DATA}/tiny-tp4-config.json")


@pytest.mark.parametrize("name", sorted(PINNED))
def test_work_of_one_chip_configurations_is_pinned_at_tp_1(name):
    config, params, layer_params, qmm, attn, attn_2k = PINNED[name]
    mc, qc = config["model_config"], config["quant"]
    assert work.linear_params(DENSE, mc, tp=1) == params
    assert work.linear_params(DENSE, mc, head=False, tp=1) == layer_params
    assert work.qmm_work(DENSE, mc, qc, passes=1, rows=20, head_rows=20, tp=1) == qmm
    assert work.attention_flops(DENSE, mc, [1, 2, 3, 2048], tp=1) == attn
    assert work.head_flops(DENSE, mc, 3, 1) == 2.0 * 3 * mc["d_model"] * mc["vocab"]


@pytest.mark.parametrize("config", [LARGE, TINY_TP4], ids=["mistral-large-2", "tiny-tp4"])
def test_a_chips_work_times_tp_is_the_whole_models(config):
    mc, qc, tp = config["model_config"], config["quant"], config["parallel"]["tp"]
    # packed bytes: every matrix once, no activations
    _, whole = work.qmm_work(DENSE, mc, qc, passes=3, rows=0, head_rows=0)
    _, chip = work.qmm_work(DENSE, mc, qc, passes=3, rows=0, head_rows=0, tp=tp)
    assert chip * tp == whole
    flops, _ = work.qmm_work(DENSE, mc, qc, passes=1, rows=32, head_rows=5)
    chip_flops, _ = work.qmm_work(DENSE, mc, qc, passes=1, rows=32, head_rows=5, tp=tp)
    assert chip_flops * tp == flops
    assert work.linear_params(DENSE, mc, tp=tp) * tp == work.linear_params(DENSE, mc)
    assert work.head_flops(DENSE, mc, 7, tp) * tp == work.head_flops(DENSE, mc, 7)
    ctx = [5, 100, 2048]
    assert work.attention_flops(DENSE, mc, ctx, tp) * tp == work.attention_flops(DENSE, mc, ctx)


def test_a_chips_share_follows_the_programs_split():
    mc = LARGE["model_config"]
    shares = {name: (k, o) for name, k, o, _ in work.linears(DENSE, mc, 4)}
    assert shares == {"wqkv": (12288, (12288 + 2 * 1024) // 4), "wo": (12288 // 4, 12288),
                      "w_gate_up": (12288, 2 * 28672 // 4), "w_down": (28672 // 4, 12288),
                      "lm_head": (12288, 32768 // 4)}
    # a column-parallel linear reads the whole activation, a row-parallel one
    # writes the whole output
    q, g = LARGE["quant"]["q"], LARGE["quant"]["g"]
    _, b = work.qmm_work(DENSE, mc, LARGE["quant"], passes=1, rows=1, head_rows=1, tp=4)
    want = sum(c * (work.bcq_bytes(k, o, q, g) + 2 * (k + o))
               for _, k, o, c in work.linears(DENSE, mc, 4))
    assert b == want
    assert work.attention_flops(DENSE, mc, [1], 4) == 4.0 * 44 * 24 * 128
