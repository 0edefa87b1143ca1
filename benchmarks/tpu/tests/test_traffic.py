import numpy as np
import pytest

from tpubench import spec, traffic

CHAT = spec.load_json(f"{spec.BENCH_DIR}/traffic/phi4-mini.chat.json")


def test_norm_ppf_matches_known_quantiles():
    p = np.array([0.025, 0.5, 0.8413447460685429, 0.999])
    want = np.array([-1.959963984540054, 0.0, 1.0, 3.090232306167813])
    np.testing.assert_allclose(traffic._norm_ppf(p), want, atol=1e-8)


def test_schedule_is_deterministic_in_the_seed():
    a = traffic.schedule(CHAT, 200064, 2**31 + 7, horizon_s=20)
    b = traffic.schedule(CHAT, 200064, 2**31 + 7, horizon_s=20)
    assert [x.at for x in a] == [x.at for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]


def test_every_seed_replays_the_same_schedule_with_its_own_tokens():
    a = traffic.schedule(CHAT, 200064, 1, horizon_s=20)
    b = traffic.schedule(CHAT, 200064, 2, horizon_s=20)
    assert [x.at for x in a] == [x.at for x in b]
    assert [x.prompt.size for x in a] == [x.prompt.size for x in b]
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    sizes = [x.prompt.size for x in a]
    assert sizes != sorted(sizes)  # a shuffled order, not a ramp


def test_lengths_are_clipped_lognormal():
    spec_ = dict(CHAT["output_len"], levels=None)
    x = traffic.lengths(spec_, 1001, traffic.rng(0, 1))
    assert x.min() >= spec_["min"] and x.max() <= spec_["max"]
    assert np.median(x) == spec_["median"]


def test_length_levels_are_quantiles_shared_evenly():
    spec_ = dict(CHAT["prompt_len"], levels=16)
    x = traffic.lengths(spec_, 160, traffic.rng(0, 1))
    values, counts = np.unique(x, return_counts=True)
    assert len(values) == 16 and (counts == 10).all()
    assert x.min() >= spec_["min"] and x.max() <= spec_["max"]


@pytest.mark.parametrize("arr", [{"process": "poisson", "rate_per_s": 2.0},
                                 {"process": "pareto", "rate_per_s": 2.0, "alpha": 2.5}])
def test_gaps_have_the_stated_mean(arr):
    g = traffic.gaps(arr, 20000, traffic.rng(0, 2))
    assert g.mean() == pytest.approx(0.5, rel=0.02)
    assert (g > 0).all()


def test_prompts_are_markov_tokens_inside_the_vocabulary():
    sched = traffic.schedule(CHAT, 1000, 3, horizon_s=5)
    corpus = traffic.MarkovCorpus(1000, 8, traffic.rng(3, 4))
    for a in sched[:5]:
        assert a.prompt.min() >= 0 and a.prompt.max() < 1000
        succ = corpus.successors[a.prompt[:-1]]
        assert (succ == a.prompt[1:, None]).any(axis=1).all()
