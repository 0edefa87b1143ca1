import pytest

from tpubench import spec, work
from tpubench.peaks import peaks

PHI4 = spec.load_json(f"{spec.BENCH_DIR}/configs/phi4-mini-3.8b.json")


def test_bcq_bytes_is_paper_eq3():
    # q bits per weight plus q bf16 scales per group of g
    assert work.bcq_bytes(4096, 4096, 3, 128) == 3 * 4096 * 4096 // 8 + 3 * 4096 * 32 * 2


def test_linear_params_of_phi4_mini():
    mc = PHI4["model_config"]
    per_layer = 3072 * (3072 + 2048) + 3072 * 3072 + 3072 * 16384 + 8192 * 3072
    assert work.linear_params(mc, head=False) == 32 * per_layer
    assert work.linear_params(mc) == 32 * per_layer + 3072 * 200064


def test_decode_step_is_bound_by_weight_bytes():
    mc, qc = PHI4["model_config"], PHI4["quant"]
    flops, bytes_ = work.qmm_work(mc, qc, passes=1, rows=20, head_rows=20)
    weights = work.linear_params(mc) * (3 / 8 + 3 * 2 / 128)
    acts = 20 * 2 * (32 * (3072 + 5120 + 3072 + 3072 + 3072 + 16384 + 8192 + 3072)
                     + 3072 + 200064)
    assert bytes_ == pytest.approx(weights + acts, rel=1e-6)
    t = work.roofline_s(flops, bytes_, peaks("TPU v5 lite"))
    assert t == pytest.approx(bytes_ / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
