"""Self-speculative decoding (ISSUE 3 tentpole): nested BCQ truncation
properties, the greedy exactness invariant (speculative output token-identical
to plain decode for dense/BCQ/ring-window/recurrent configs), distribution
preservation under temperature sampling, cache rollback, and the speculative
continuous-batching scheduler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import quantize_tensor
from repro.core.qtensor import QuantizedTensor
from repro.data import MarkovCorpus
from repro.infer import Engine, Request, Scheduler, SpecConfig
from repro.infer import speculative as S
from repro.models import forward, init_cache, init_params, reduced
from repro.models import layers as L
from repro.quant import QuantPolicy, quantize_params, truncate_params

KEY = jax.random.PRNGKey(0)


def _quantizable(arch, **overrides):
    """A reduced config whose linears clear the quantizer's 128-dim floor."""
    base = dict(d_model=128, d_ff=256, vocab=512, n_kv_heads=2)
    base.update(overrides)
    return reduced(get_config(arch), **base)


# ---------------------------------------------------------------------------
# QuantizedTensor.truncate / truncate_params properties
# ---------------------------------------------------------------------------


def test_truncate_matches_greedy_prefix(rng):
    """The nested property: truncate(q') of a greedy q-bit solve is
    bit-identical to the greedy solver's own q'-bit output."""
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    full = quantize_tensor(w, q=4, g=64, method="greedy")
    for q_new in (1, 2, 3):
        nested = full.truncate(q_new)
        solo = quantize_tensor(w, q=q_new, g=64, method="greedy")
        np.testing.assert_array_equal(
            np.asarray(nested.packed), np.asarray(solo.packed)
        )
        np.testing.assert_array_equal(
            np.asarray(nested.scales), np.asarray(solo.scales)
        )
        assert (nested.q, nested.k, nested.o, nested.g) == (q_new, 256, 128, 64)


def test_truncate_error_monotone(rng):
    """Greedy planes are successive residual refinements: reconstruction
    error is monotone non-increasing in q'."""
    w = jnp.asarray(rng.standard_normal((256, 128)), jnp.float32)
    full = quantize_tensor(w, q=6, g=64, method="greedy", scale_dtype=jnp.float32)
    errs = [
        float(jnp.linalg.norm(full.truncate(qn).dequantize() - w))
        for qn in range(1, 7)
    ]
    for lo, hi in zip(errs[1:], errs[:-1]):
        assert lo <= hi + 1e-6, f"error not monotone: {errs}"


def test_truncate_validation(rng):
    w = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    qt = quantize_tensor(w, q=3, g=32, method="greedy")
    assert qt.truncate(3) is qt
    with pytest.raises(ValueError):
        qt.truncate(0)
    with pytest.raises(ValueError):
        qt.truncate(4)


def test_truncate_params_shares_unquantized():
    """truncate_params slices every QuantizedTensor leaf and shares all other
    leaves (norms/embeddings/dense linears) by reference."""
    cfg = _quantizable("llama3.2-3b")
    params = quantize_params(
        init_params(KEY, cfg), QuantPolicy(q=4, g=64, method="greedy")
    )
    draft = truncate_params(params, 2)
    is_qt = lambda x: isinstance(x, QuantizedTensor)
    full_leaves = jax.tree.leaves(params, is_leaf=is_qt)
    draft_leaves = jax.tree.leaves(draft, is_leaf=is_qt)
    n_qt = 0
    for f, d in zip(full_leaves, draft_leaves):
        if is_qt(f):
            n_qt += 1
            assert f.q == 4 and d.q == 2
            assert (d.k, d.o, d.g) == (f.k, f.o, f.g)
        else:
            assert d is f  # shared, not copied
    assert n_qt > 0
    # q_draft beyond a leaf's q clamps to the leaf's q
    same = truncate_params(params, 9)
    for f, d in zip(full_leaves, jax.tree.leaves(same, is_leaf=is_qt)):
        if is_qt(f):
            assert d.q == f.q


# ---------------------------------------------------------------------------
# greedy exactness invariant: speculative == plain, per family
# ---------------------------------------------------------------------------


def _prompts(cfg, b, plen, seed=7):
    c = MarkovCorpus(cfg.vocab, seed=3)
    return c.sample(b, plen, seed=seed).astype(np.int32)[:, :plen]


@pytest.mark.parametrize("quantized", [False, True], ids=["dense", "bcq_q4"])
def test_spec_greedy_identical_llama(quantized):
    """The big invariant on the attention family, batch>1: speculative greedy
    output is token-identical to plain greedy scanned decode. The quantized
    case uses a REAL nested draft (q'=2 of 4), so acceptance < 100% and the
    correction path is exercised."""
    cfg = _quantizable("llama3.2-3b")
    params = init_params(KEY, cfg)
    if quantized:
        params = quantize_params(params, QuantPolicy(q=4, g=64, method="greedy"))
    eng = Engine(cfg, params, max_seq=64)
    prompts = _prompts(cfg, 2, 8)
    plain = eng.generate(prompts, 16)
    spec = eng.generate(prompts, 16, speculate=SpecConfig(q_draft=2, gamma=4))
    np.testing.assert_array_equal(plain.tokens, spec.tokens)
    st = spec.spec_stats
    assert st["proposed"] > 0 and 0.0 <= st["accept_rate"] <= 1.0
    if not quantized:
        # dense draft IS the target: every proposal must be accepted
        assert st["accept_rate"] == 1.0


@pytest.mark.parametrize(
    "arch", ["recurrentgemma-9b", "xlstm-125m"],
    ids=["ring_window+rglru", "mlstm+slstm"],
)
def test_spec_greedy_identical_recurrent_and_window(arch):
    """Exactness through recurrent-state snapshots and ring-buffer restore.
    The hybrid config's window (16) is smaller than the decoded length, so
    the ring genuinely wraps and rejected writes clobber live entries —
    the rollback contract's hard case."""
    cfg = reduced(get_config(arch))
    eng = Engine(cfg, init_params(KEY, cfg), max_seq=48)
    prompts = _prompts(cfg, 2, 6)
    plain = eng.generate(prompts, 20)
    spec = eng.generate(prompts, 20, speculate=SpecConfig(q_draft=1, gamma=3))
    np.testing.assert_array_equal(plain.tokens, spec.tokens)


def test_spec_greedy_identical_low_acceptance():
    """q'=1 of a random-weight quantized model: acceptance near zero, so
    nearly every token comes from the correction path — still exact."""
    cfg = _quantizable("llama3.2-3b")
    params = quantize_params(
        init_params(KEY, cfg), QuantPolicy(q=4, g=64, iters=2)
    )
    eng = Engine(cfg, params, max_seq=64)
    prompts = _prompts(cfg, 1, 8)
    plain = eng.generate(prompts, 16)
    spec = eng.generate(prompts, 16, speculate=SpecConfig(q_draft=1, gamma=4))
    np.testing.assert_array_equal(plain.tokens, spec.tokens)
    assert spec.spec_stats["accept_rate"] < 0.9  # the draft really is worse


# ---------------------------------------------------------------------------
# temperature sampling preserves the target distribution
# ---------------------------------------------------------------------------


def test_spec_sampling_preserves_target_distribution():
    """Rejection sampling invariant on a toy vocab: the marginal of the token
    emitted AFTER the first speculative chunk matches plain sampling. Rows
    are iid samples (per-row PRNG streams over identical prompts), so one
    wide batch gives the statistics in two dispatches."""
    cfg = _quantizable("llama3.2-3b", vocab=16, d_model=128, d_ff=256)
    params = quantize_params(
        init_params(KEY, cfg), QuantPolicy(q=4, g=64, iters=2)
    )
    eng = Engine(cfg, params, max_seq=32)
    n = 1024
    prompts = np.tile(_prompts(cfg, 1, 6), (n, 1))
    # token index 1 of the generation = first token decided by draft/verify/
    # accept (index 0 comes directly from the prefill logits in both paths)
    plain = eng.generate(prompts, 2, temperature=1.0, seed=5)
    spec = eng.generate(
        prompts, 2, temperature=1.0, seed=5,
        speculate=SpecConfig(q_draft=1, gamma=2),
    )
    assert 0.0 < spec.spec_stats["accept_rate"] < 1.0  # both accept AND reject
    p_hist = np.bincount(plain.tokens[:, 7], minlength=cfg.vocab) / n
    s_hist = np.bincount(spec.tokens[:, 7], minlength=cfg.vocab) / n
    tv = 0.5 * np.abs(p_hist - s_hist).sum()
    assert tv < 0.10, f"total variation {tv:.3f} too large for n={n}"


# ---------------------------------------------------------------------------
# cache rollback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "arch", ["llama3.2-3b", "recurrentgemma-9b"], ids=["dense", "ring+rglru"]
)
def test_rejected_chunk_leaves_no_trace(arch):
    """Rollback unit test: decode a chunk of junk tokens through the chunked
    verify path, rewind it completely, and the next real decode step must
    produce logits identical to never having decoded the junk."""
    cfg = reduced(get_config(arch))
    params = init_params(KEY, cfg)
    prompts = _prompts(cfg, 2, 20)  # > window(16): hybrid ring already wrapped
    b, s = prompts.shape
    gamma = 3
    collect = S.has_recurrent_state(cfg)
    cache0 = init_cache(cfg, b, 40)
    _, cache0, _ = forward(
        cfg, params, tokens=jnp.asarray(prompts), cache=cache0,
        pos=jnp.int32(0), logits_mode="last",
    )
    pos = jnp.full((b,), s, jnp.int32)

    junk = jnp.asarray([[3, 5, 7, 11], [13, 2, 4, 8]], jnp.int32)
    snap = S.snapshot_rows(cache0, pos, gamma + 1)
    _, vcache, _ = forward(
        cfg, params, tokens=junk, cache=cache0, pos=pos, logits_mode="all",
        chunked_decode=True, collect_states=collect,
    )
    # full rejection: keep zero rows, recurrent state back to the chunk start
    restored = S.restore_rows(vcache, snap, pos, gamma + 1, jnp.zeros((b,), jnp.int32))
    restored = jax.tree_util.tree_map_with_path(
        lambda p, leaf, orig: (
            orig if S._leaf_name(p) in L.RECURRENT_CACHE_LEAVES else leaf
        ),
        restored, cache0,
    )

    tok = _prompts(cfg, b, s + 1, seed=9)[:, -1:]
    ref_logits, _, _ = forward(
        cfg, params, tokens=jnp.asarray(tok), cache=cache0, pos=pos,
        logits_mode="last",
    )
    got_logits, _, _ = forward(
        cfg, params, tokens=jnp.asarray(tok), cache=restored, pos=pos,
        logits_mode="last",
    )
    np.testing.assert_array_equal(np.asarray(ref_logits), np.asarray(got_logits))


def test_chunked_decode_matches_step_decode():
    """The verify forward itself: feeding s tokens chunked against a filled
    cache computes the same logits as s single-token decode steps."""
    for arch in ("llama3.2-3b", "recurrentgemma-9b", "xlstm-125m"):
        cfg = reduced(get_config(arch))
        params = init_params(KEY, cfg)
        toks = _prompts(cfg, 2, 26)
        b = 2
        prompt, rest = toks[:, :20], toks[:, 20:]
        cache = init_cache(cfg, b, 40)
        logits, cache, _ = forward(
            cfg, params, tokens=jnp.asarray(prompt), cache=cache,
            pos=jnp.int32(0), logits_mode="last",
        )
        step_cache = cache
        step_logits = []
        for t in range(rest.shape[1]):
            lg, step_cache, _ = forward(
                cfg, params, tokens=jnp.asarray(rest[:, t : t + 1]),
                cache=step_cache, pos=jnp.int32(20 + t), logits_mode="last",
            )
            step_logits.append(np.asarray(lg[:, 0]))
        chunk_logits, _, _ = forward(
            cfg, params, tokens=jnp.asarray(rest), cache=cache,
            pos=jnp.full((b,), 20, jnp.int32), logits_mode="all",
            chunked_decode=True,
        )
        np.testing.assert_allclose(
            np.asarray(chunk_logits), np.stack(step_logits, axis=1),
            rtol=2e-5, atol=2e-5,
            err_msg=f"{arch}: chunked decode diverged from step decode",
        )


@pytest.mark.parametrize(
    "case", ["local_attn", "recurrentgemma-9b"], ids=["ring", "ring+rglru"]
)
def test_chunked_verify_on_wrapped_ring_matches_step_decode(case):
    """Windowed chunked verify on a ring buffer that has wrapped: the chunk's
    own writes clobber rows its earlier tokens still attend, so the verify
    gathers those rows from the stacked cache before writing. Its logits and
    the cache it leaves must match s single-token decode steps."""
    if case == "local_attn":
        cfg = reduced(
            get_config("llama3.2-3b"), window=8, stages=((("local_attn",), 2),)
        )
    else:
        cfg = reduced(get_config(case))
    params = init_params(KEY, cfg)
    b, n_prompt, n_chunk = 2, 20, 6  # prompt > 2 windows: the ring has wrapped
    toks = _prompts(cfg, b, n_prompt + n_chunk)
    prompt, rest = toks[:, :n_prompt], toks[:, n_prompt:]
    _, cache, _ = forward(
        cfg, params, tokens=jnp.asarray(prompt), cache=init_cache(cfg, b, 40),
        pos=jnp.int32(0), logits_mode="last",
    )
    step_cache, step_logits = cache, []
    for t in range(n_chunk):
        lg, step_cache, _ = forward(
            cfg, params, tokens=jnp.asarray(rest[:, t : t + 1]),
            cache=step_cache, pos=jnp.full((b,), n_prompt + t, jnp.int32),
            logits_mode="last",
        )
        step_logits.append(np.asarray(lg[:, 0]))
    chunk_logits, chunk_cache, _ = forward(
        cfg, params, tokens=jnp.asarray(rest), cache=cache,
        pos=jnp.full((b,), n_prompt, jnp.int32), logits_mode="all",
        chunked_decode=True,
    )
    np.testing.assert_allclose(
        np.asarray(chunk_logits), np.stack(step_logits, axis=1),
        rtol=2e-5, atol=2e-5,
    )
    for got, want in zip(jax.tree.leaves(chunk_cache), jax.tree.leaves(step_cache)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )


# ---------------------------------------------------------------------------
# speculative continuous batching
# ---------------------------------------------------------------------------


def test_spec_scheduler_token_identical():
    """Speculative slots: greedy rows and per-request opt-outs (including a
    SAMPLED opt-out, whose PRNG stream must match plain decode bit-for-bit)
    are token-identical to solo plain generate; all budgets exact."""
    cfg = _quantizable("llama3.2-3b")
    params = quantize_params(
        init_params(KEY, cfg), QuantPolicy(q=4, g=64, method="greedy")
    )
    eng = Engine(cfg, params, max_seq=64)
    corpus = MarkovCorpus(cfg.vocab, seed=3)
    rng = np.random.default_rng(0)
    reqs = []
    for i, (temp, spec_in) in enumerate(
        [(0.0, True), (1.0, False), (0.0, True), (0.7, False), (0.0, False), (1.0, True)]
    ):
        plen = int(rng.integers(4, 10))
        reqs.append(
            Request(
                prompt=corpus.sample(1, plen, seed=100 + i)[0, :plen].astype(np.int32),
                max_new_tokens=int(rng.integers(3, 12)),
                temperature=temp,
                seed=10 + i,
                speculate=spec_in,
            )
        )

    sched = Scheduler(eng, n_slots=3, chunk=2, speculate=SpecConfig(q_draft=2, gamma=3))
    for r in reqs:
        sched.submit(r)
    done = {c.rid: c for c in sched.run()}
    assert len(done) == len(reqs)
    for r in reqs:
        assert done[r.rid].new_tokens.shape == (r.max_new_tokens,)
        if r.temperature == 0.0 or r.speculate is False:
            solo = eng.generate(
                r.prompt[None], r.max_new_tokens,
                temperature=r.temperature, seed=r.seed,
            )
            np.testing.assert_array_equal(
                solo.tokens[0, r.prompt.size :], done[r.rid].new_tokens,
                err_msg=f"request {r.rid} diverged from solo plain generate",
            )


def test_spec_scheduler_budget_one_completes_at_admission():
    """In spec mode the first token is emitted at admission: a budget-1
    request must complete immediately and free its slot for the same round."""
    cfg = _quantizable("llama3.2-3b")
    eng = Engine(cfg, init_params(KEY, cfg), max_seq=48)
    corpus = MarkovCorpus(cfg.vocab, seed=3)
    sched = Scheduler(eng, n_slots=1, chunk=2, speculate=SpecConfig(2, 2))
    p = corpus.sample(1, 5, seed=1)[0, :5].astype(np.int32)
    a = sched.submit(Request(prompt=p, max_new_tokens=1))
    b = sched.submit(Request(prompt=p, max_new_tokens=4))
    done = {c.rid: c for c in sched.run()}
    assert done[a].new_tokens.shape == (1,)
    assert done[b].new_tokens.shape == (4,)
    solo = eng.generate(p[None], 4)
    np.testing.assert_array_equal(solo.tokens[0, 5:6], done[a].new_tokens)
    np.testing.assert_array_equal(solo.tokens[0, 5:], done[b].new_tokens)


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------


def test_spec_validation():
    with pytest.raises(ValueError):
        SpecConfig(q_draft=0, gamma=4)
    with pytest.raises(ValueError):
        SpecConfig(q_draft=2, gamma=0)
    assert SpecConfig.parse("2:4") == SpecConfig(q_draft=2, gamma=4)
    with pytest.raises(ValueError):
        SpecConfig.parse("nope")

    # MoE: shared expert capacity couples the verified chunk — rejected
    cfg = reduced(get_config("olmoe-1b-7b"))
    eng = Engine(cfg, init_params(KEY, cfg), max_seq=48)
    prompts = _prompts(cfg, 1, 6)
    with pytest.raises(ValueError):
        eng.generate(prompts, 4, speculate=SpecConfig(2, 2))

    # gamma must fit inside the ring window
    cfg = reduced(get_config("recurrentgemma-9b"))  # window 16
    eng = Engine(cfg, init_params(KEY, cfg), max_seq=48)
    prompts = _prompts(cfg, 1, 6)
    with pytest.raises(ValueError):
        eng.generate(prompts, 4, speculate=SpecConfig(q_draft=1, gamma=15))

    # cache headroom: prompt + n_steps + gamma must fit
    cfg = reduced(get_config("llama3.2-3b"))
    eng = Engine(cfg, init_params(KEY, cfg), max_seq=16)
    with pytest.raises(ValueError):
        eng.generate(_prompts(cfg, 1, 8), 8, speculate=SpecConfig(2, 4))
