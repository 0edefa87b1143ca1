"""Decode fast path: scanned engine vs step loop, fused projection kernels,
and the (block_k, block_o) autotuner (ISSUE 1 tentpole coverage)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import fuse_tensors, quantize_tensor
from repro.data import MarkovCorpus
from repro.infer import Engine
from repro.kernels import autotune, bcq_mm_fused, quantized_matmul, quantized_matmul_fused
from repro.models import forward, fuse_decode_projections, init_params, reduced
from repro.quant import QuantPolicy, quantize_params

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# scanned decode == step loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "arch", ["llama3.2-3b", "recurrentgemma-9b", "olmoe-1b-7b"]
)
def test_scan_decode_matches_step_loop_greedy(arch):
    """One lax.scan dispatch must reproduce the per-token loop bit-for-bit."""
    cfg = reduced(get_config(arch))
    params = init_params(KEY, cfg)
    prompts = MarkovCorpus(cfg.vocab, seed=3).sample(2, 8, seed=7).astype(np.int32)[:, :8]
    eng = Engine(cfg, params, max_seq=40)
    r_scan = eng.generate(prompts, 10, scan=True)
    r_step = eng.generate(prompts, 10, scan=False)
    np.testing.assert_array_equal(r_scan.tokens, r_step.tokens)
    assert r_scan.tokens.shape == (2, 18)


@pytest.mark.parametrize("arch", ["llama3.2-3b", "xlstm-125m"])
def test_scan_decode_matches_step_loop_sampled(arch):
    """Seeded categorical sampling: identical key-split order in both paths."""
    cfg = reduced(get_config(arch))
    params = init_params(KEY, cfg)
    prompts = MarkovCorpus(cfg.vocab, seed=1).sample(2, 8, seed=5).astype(np.int32)[:, :8]
    eng = Engine(cfg, params, max_seq=40)
    r_scan = eng.generate(prompts, 12, temperature=1.0, seed=11, scan=True)
    r_step = eng.generate(prompts, 12, temperature=1.0, seed=11, scan=False)
    np.testing.assert_array_equal(r_scan.tokens, r_step.tokens)
    # a different seed must actually change something (sampling is live)
    r_other = eng.generate(prompts, 12, temperature=1.0, seed=12, scan=True)
    assert not np.array_equal(r_scan.tokens, r_other.tokens)


def test_scan_decode_quantized_params():
    cfg = reduced(get_config("llama3.2-3b"))
    params = quantize_params(
        init_params(KEY, cfg), QuantPolicy(q=3, g=64, iters=2)
    )
    prompts = MarkovCorpus(cfg.vocab, seed=2).sample(2, 8, seed=9).astype(np.int32)[:, :8]
    eng = Engine(cfg, params, max_seq=40)
    r_scan = eng.generate(prompts, 8, scan=True)
    r_step = eng.generate(prompts, 8, scan=False)
    np.testing.assert_array_equal(r_scan.tokens, r_step.tokens)


def test_embedding_model_falls_back_to_step_loop():
    """scan=True must not break modality-stub models (host-side embed_fn)."""
    cfg = reduced(get_config("musicgen-medium"), d_model=64, n_layers=2)
    params = init_params(KEY, cfg)
    table = np.random.default_rng(1).standard_normal((cfg.vocab, 64)).astype(np.float32)
    eng = Engine(cfg, params, max_seq=32,
                 embed_fn=lambda toks: table[toks[:, 0]][:, None])
    emb = np.random.default_rng(0).standard_normal((1, 8, 64)).astype(np.float32)
    r = eng.generate(emb, 4, scan=True)
    assert r.steps == 4 and r.tokens.shape == (1, 4)


# ---------------------------------------------------------------------------
# fused multi-projection kernel
# ---------------------------------------------------------------------------


def _fused_case(rng, k, out_dims, q, g):
    ws = [jnp.asarray(rng.standard_normal((k, o)), jnp.float32) for o in out_dims]
    qts = [quantize_tensor(w, q, g, iters=1, scale_dtype=jnp.float32) for w in ws]
    x = jnp.asarray(rng.standard_normal((3, k)), jnp.float32)
    return x, qts, fuse_tensors(qts)


@pytest.mark.parametrize("impl", ["bcq_mm", "lutgemm"])
def test_fused_matches_per_projection(rng, impl):
    """One fused kernel pass == N separate quantized_matmul calls."""
    x, qts, fused = _fused_case(rng, 512, (256, 128, 128), q=3, g=64)
    outs = quantized_matmul_fused(
        x, fused, tuple(t.o for t in qts), impl=impl, interpret=True
    )
    for out, qt in zip(outs, qts):
        ref = quantized_matmul(x, qt, impl="ref")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
        )


def test_bcq_mm_fused_kernel_direct(rng):
    """The raw fused kernel splits the fused output at projection offsets."""
    x, qts, fused = _fused_case(rng, 512, (128, 128), q=2, g=128)
    outs = bcq_mm_fused(
        x, fused.packed, fused.scales, g=fused.g, out_dims=(128, 128),
        block_k=256, block_o=128, interpret=True,
    )
    assert [o.shape for o in outs] == [(3, 128), (3, 128)]
    whole = quantized_matmul(x, fused, impl="ref")
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(outs, -1)), np.asarray(whole),
        rtol=2e-4, atol=2e-4,
    )


def test_fuse_tensors_validation(rng):
    a = quantize_tensor(jnp.asarray(rng.standard_normal((128, 64)), jnp.float32), 2, 64)
    b = quantize_tensor(jnp.asarray(rng.standard_normal((128, 64)), jnp.float32), 3, 64)
    c = quantize_tensor(jnp.asarray(rng.standard_normal((256, 64)), jnp.float32), 2, 64)
    with pytest.raises(ValueError):
        fuse_tensors([a, b])  # q mismatch
    with pytest.raises(ValueError):
        fuse_tensors([a, c])  # k mismatch
    with pytest.raises(ValueError):
        quantized_matmul_fused(
            jnp.zeros((1, 128)), a, (32, 16), impl="ref"
        )  # out_dims don't sum to o


def test_fuse_decode_projections_preserves_forward():
    """Fused params tree computes identical logits (dense + quantized)."""
    cfg = reduced(get_config("llama3.2-3b"))
    params = init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    for tree in (params, quantize_params(params, QuantPolicy(q=2, g=64, iters=1,
                                                             method="greedy"))):
        base, _, _ = forward(cfg, tree, tokens=toks)
        fused_tree = fuse_decode_projections(cfg, tree)
        attn0 = fused_tree["stages"][0]["b0"]["attn"]
        assert "wqkv" in attn0 and "wq" not in attn0
        assert "w_gate_up" in fused_tree["stages"][0]["b0"]["mlp"]
        out, _, _ = forward(cfg, fused_tree, tokens=toks)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


def test_fuse_skips_cross_attention_kv():
    """VLM cross blocks must keep wk/wv (they project the image memory)."""
    cfg = reduced(get_config("llama-3.2-vision-90b"))
    params = fuse_decode_projections(cfg, init_params(KEY, cfg))
    pattern = cfg.stages[0][0]
    cross_bi = pattern.index("cross")
    cross_attn = params["stages"][0][f"b{cross_bi}"]["attn"]
    assert "wqkv" not in cross_attn and "wk" in cross_attn
    self_attn = params["stages"][0]["b0"]["attn"]
    assert "wqkv" in self_attn


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    # isolate both persistence layers: user cache AND the checked-in defaults;
    # re-enable measurement (conftest disables it suite-wide) regardless of
    # the ambient REPRO_AUTOTUNE so the opt-out env var can't redden the suite
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setattr(autotune, "_TABLE_PATH", str(tmp_path / "defaults.json"))
    autotune.clear_cache()
    yield autotune
    autotune.clear_cache()


def test_autotune_cache_roundtrip(tuner, tmp_path):
    """A measured winner persists to JSON and reloads in a fresh process state."""
    kw = dict(B=8, k=512, o=256, q=2, g=64, impl="bcq_mm", interpret=True)
    blocks = tuner.get_blocks(**kw)
    assert 512 % blocks[0] == 0 and 256 % blocks[1] == 0
    path = tmp_path / "autotune.json"
    assert path.exists()
    table = json.loads(path.read_text())
    key = tuner.make_key(8, 512, 256, 2, 64, "bcq_mm", tuner.backend_tag(True))
    assert tuple(table[key]) == blocks
    # fresh in-process state: served from the persisted table, no re-measure
    tuner.clear_cache()
    assert tuner.get_blocks(**kw, allow_measure=False) == blocks


def test_autotune_opt_out_uses_heuristic(tuner, monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    blocks = tuner.get_blocks(B=8, k=1024, o=512, q=2, g=128, impl="bcq_mm",
                              interpret=True)
    assert blocks == tuner.heuristic_blocks(1024, 512, 128)
    assert not (tmp_path / "autotune.json").exists()  # nothing measured/persisted


def test_autotune_unknown_shape_falls_back_safely(tuner):
    """No table entry + measurement disabled → legacy heuristic, never a raise."""
    bk, bo = tuner.get_blocks(B=8, k=768, o=640, q=2, g=96, impl="lutgemm",
                              interpret=True, allow_measure=False)
    assert bk and bo and 768 % bk == 0 and 640 % bo == 0
    assert bk % 96 == 0 or 96 % bk == 0  # g-compatible (irregular g=96 path)


def test_autotune_candidates_respect_group_size():
    bks, bos = autotune.candidate_blocks(768, 512, 96)
    assert all(c % 96 == 0 or 96 % c == 0 for c in bks)
    assert all(768 % c == 0 for c in bks)
    assert all(512 % c == 0 for c in bos)


@pytest.mark.parametrize(
    "k,o,g,B,want",
    [
        (3072, 5120, 128, 8, (1024, 512)),  # Phi-4-mini fused QKV, decode
        (3072, 200064, 128, 8, (1024, 128)),  # 200k-vocab head: 128 | o only
        (768, 3072, 128, 8, (768, 512)),  # TP-local wo: 6 scale rows → all of k
        (1024, 256, 64, 8, (1024, 256)),
    ],
)
def test_autotune_rule_yields_mosaic_tiles(k, o, g, B, want):
    """Every candidate obeys Mosaic's block rule: scale rows block_k//g a
    multiple of 8 or all k/g rows, block_o a multiple of 128 or all of o."""
    bks, bos = autotune.candidate_blocks(k, o, g)
    for bk in bks:
        rows = max(bk // g, 1)
        assert k % bk == 0 and (rows % 8 == 0 or rows == k // g)
    assert all(o % bo == 0 and (bo % 128 == 0 or bo == o) for bo in bos)
    assert autotune.heuristic_blocks(k, o, g, B=B, q=3, impl="bcq_mm") == want


def test_autotune_measure_raises_on_kernel_error(tuner, monkeypatch):
    """A candidate that fails is a kernel bug under the tiling rule: the
    sweep re-raises it instead of skipping to another candidate."""
    def broken(*args, **kw):
        raise RuntimeError("Mosaic refused the block")

    monkeypatch.setitem(
        tuner._MEASURE_KERNELS, "bcq_mm",
        (lambda: broken, tuner._MEASURE_KERNELS["bcq_mm"][1]),
    )
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        tuner.get_blocks(B=8, k=512, o=256, q=2, g=64, impl="bcq_mm",
                         interpret=True)


def test_autotune_reads_no_user_table_unless_named(tuner, monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE")
    assert tuner._user_cache_path() is None
    tuner._persist("bcq_mm/cpu/B8/k512/o256/q2/g64", (512, 256))  # no-op


def test_quantized_matmul_uses_autotuned_blocks(tuner, rng):
    """End-to-end: wrapper dispatch through the tuner still matches the oracle."""
    w = jnp.asarray(rng.standard_normal((768, 200)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((3, 768)), jnp.float32)
    qt = quantize_tensor(w, 3, 96, iters=1, scale_dtype=jnp.float32)
    y = quantized_matmul(x, qt, impl="bcq_mm", interpret=True)
    y_ref = quantized_matmul(x, qt, impl="ref")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)
