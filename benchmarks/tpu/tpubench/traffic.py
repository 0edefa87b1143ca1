"""Open-loop traffic from a traffic file and a seed.

Copies of the repo's generators (``launch/serve.py::poisson_arrivals``,
``pareto_arrivals`` and ``data/synthetic.py::MarkovCorpus``), kept here so no
change to the program can move the yardstick, plus a clipped-lognormal length
sampler.

Every seed gets the same requests at the same times: lengths are the
quantiles ``(i + 1/2) / n`` of the clipped distribution and interarrival
gaps the quantiles of the arrival distribution, in one order drawn from a
fixed stream. The seed draws the prompt tokens (and, elsewhere, the
weights). With some twenty to fifty requests in a window, an order drawn
from the seed moved the median time to first token by half between seeds
(two runs of one seed agreed within a few percent), so the order is held
fixed and a run measures one replayed schedule.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

# stream ids: one independent numpy generator per use of the seed
_GAPS, _PROMPTS, _OUTPUTS, _CORPUS, _TOKENS = range(1, 6)
SCHEDULE_SEED = 0  # the one order of arrivals and lengths every run replays


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


class MarkovCorpus:
    """A fixed sparse first-order Markov chain over the vocabulary with Zipf
    successor weights (copied from ``repro.data.synthetic``), sampled for a
    whole batch of prompts at once."""

    def __init__(self, vocab: int, branching: int, gen: np.random.Generator):
        self.vocab = vocab
        self.successors = gen.integers(0, vocab, size=(vocab, branching))
        w = 1.0 / np.arange(1, branching + 1)
        self.weights = w / w.sum()
        self.branching = branching

    def sample(self, batch: int, seq_len: int, gen: np.random.Generator) -> np.ndarray:
        toks = np.empty((batch, seq_len), np.int32)
        toks[:, 0] = gen.integers(0, self.vocab, size=batch)
        choice = gen.choice(self.branching, size=(seq_len - 1, batch), p=self.weights)
        for t in range(seq_len - 1):
            toks[:, t + 1] = self.successors[toks[:, t], choice[t]]
        return toks


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation, relative
    error under 1.2e-9)."""
    a = [-3.969683028665376e01, 2.209460984245205e02, -2.759285104469687e02,
         1.383577518672690e02, -3.066479806614716e01, 2.506628277459239e00]
    b = [-5.447609879822406e01, 1.615858368580409e02, -1.556989798598866e02,
         6.680131188771972e01, -1.328068155288572e01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e00,
         -2.549732539343734e00, 4.374664141464968e00, 2.938163982698783e00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e00,
         3.754408661907416e00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r + a[5]) * q / \
        (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1)
    return out


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, gen: np.random.Generator) -> np.ndarray:
    """``n`` clipped-lognormal lengths (``median``, ``sigma``, ``min``,
    ``max``) at stratified quantiles, in a seeded order. With ``levels``, the
    lengths take only that many values, the ``levels`` quantiles of the
    distribution, each given to ``n / levels`` requests."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    k = spec.get("levels") or n
    x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(_quantiles(k)))
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return gen.permutation(np.resize(x, n))


def gaps(spec: dict, n: int, gen: np.random.Generator) -> np.ndarray:
    """``n`` interarrival gaps (seconds) of mean ``1/rate_per_s``: the
    exponential (``poisson``) or Lomax (``pareto``, tail index ``alpha``,
    as ``launch/serve.py::pareto_arrivals``) quantiles in a seeded order."""
    rate = spec["rate_per_s"]
    u = _quantiles(n)
    if spec["process"] == "poisson":
        g = -np.log1p(-u) / rate
    elif spec["process"] == "pareto":
        alpha = spec["alpha"]
        if alpha <= 1:
            raise ValueError(f"alpha must be > 1 for a finite mean, got {alpha}")
        g = (alpha - 1.0) / rate * ((1 - u) ** (-1.0 / alpha) - 1)
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return gen.permutation(g)


@dataclasses.dataclass
class Arrival:
    at: float  # scheduled arrival, seconds from the window's start
    prompt: np.ndarray
    max_new_tokens: int


def schedule(traffic: dict, vocab: int, seed: int, horizon_s: float) -> List[Arrival]:
    """Requests arriving from ``-preroll_s`` until ``horizon_s`` past the
    window's start: the same schedule for every seed, with the seed's
    prompt tokens."""
    arr = traffic["arrivals"]
    preroll = float(arr.get("preroll_s", 0.0))
    n = int(math.ceil(arr["rate_per_s"] * (preroll + horizon_s)))
    at = np.cumsum(gaps(arr, n, rng(SCHEDULE_SEED, _GAPS))) - preroll
    plen = lengths(traffic["prompt_len"], n, rng(SCHEDULE_SEED, _PROMPTS))
    olen = lengths(traffic["output_len"], n, rng(SCHEDULE_SEED, _OUTPUTS))
    corp = traffic["corpus"]
    if corp["kind"] != "markov":
        raise ValueError(f"unknown corpus {corp['kind']!r}")
    chain = MarkovCorpus(vocab, corp["branching"], rng(seed, _CORPUS))
    toks = chain.sample(n, int(plen.max()), rng(seed, _TOKENS))
    return [Arrival(float(a), toks[i, : plen[i]].copy(), int(olen[i]))
            for i, a in enumerate(at)]
