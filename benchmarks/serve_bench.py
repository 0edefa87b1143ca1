"""Serving-throughput benchmark: continuous batching vs one-shot generate.

Measures the ISSUE 2 acceptance number: at >=4 concurrent requests the
continuous-batching scheduler must sustain higher tokens/sec than serving the
same workload as sequential one-shot scanned ``Engine.generate`` calls (the
PR 1 fast path). Two arrival regimes:

- ``burst``  — all requests queued at t=0 (pure throughput / makespan);
- ``poisson``— Poisson arrivals at ~2x the sequential service rate, the
  regime the paper's serving workload (§V, OPT token generation) lives in:
  the queue stays non-empty, so the win is batch-feeding, not queueing tricks.

ISSUE 6 adds the request-lifecycle regimes on top (the hardened scheduler's
operating envelope, not just its happy-path throughput):

- ``heavytail`` — Lomax/Pareto arrivals at the same mean rate as the Poisson
  trace but with bursty clumps and long gaps; reports TTFT/TPOT p50/p95/p99
  from the scheduler's own lifecycle records;
- ``cancel sweep`` — a fraction of requests is cancelled right after its
  first streamed token; survivor throughput and reclaimed-slot utilisation
  show cancellation freeing capacity instead of wasting it;
- ``bounded queue`` — a burst twice the queue bound with tight TTFT
  deadlines: overflow rejects loudly at submit, stale queue entries are shed
  before wasting a prefill, and the served remainder keeps its latency.

Both paths are warmed first so XLA compiles (per prompt-length/budget shape)
stay out of the timings. CPU-host numbers are functional sanity, not TPU
claims (benchmarks/common.py).

ISSUE 10 adds the prefix-cache / chunked-prefill regimes (DESIGN.md §12):

- ``shared prefix`` — half the traffic repeats a 24-token system prompt:
  serving it against a warm prefix cache must improve mean TTFT over the
  cold engine while TPOT stays within a bounded regression (both asserted);
- ``long-prompt interleave`` — long prefills dispatched whole-shot vs in
  ``prefill_chunk`` buckets interleaved with decode, reporting the short
  requests' TTFT tail (head-of-line blocking made visible);
- ``prefix overload`` — the warm cache under 2x overload with a bounded
  queue, cancels and deadlines: the refcount ledger must drain to zero and
  ``hits + misses == commits + aborts`` (leak-free accounting, asserted).

PYTHONPATH=src python benchmarks/serve_bench.py [--out BENCH_serve.json]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.data import MarkovCorpus
from repro.infer import Engine, PrefixCache, QueueFullError, Request, Scheduler
from repro.launch.serve import (
    build_requests,
    drive_continuous,
    drive_sequential,
    pareto_arrivals,
    poisson_arrivals,
)
from repro.models import init_params, reduced
from repro.quant import QuantPolicy, quantize_params

N_REQUESTS = 12
PROMPT_LEN = 16
GEN = 24
SLOTS = (4, 8)
CHUNK = 8


def _engine():
    cfg = reduced(get_config("llama3.2-3b"), d_model=256, n_kv_heads=4, d_ff=512)
    params = quantize_params(
        init_params(jax.random.PRNGKey(0), cfg), QuantPolicy(q=4, g=128, iters=4)
    )
    return cfg, params, Engine(cfg, params, max_seq=PROMPT_LEN + GEN + 8)


def _warmup(cfg, engine):
    """Compile every shape both paths will hit: the (PROMPT_LEN, GEN) scan
    generate, the batch-1 prefill, the admit install, and one decode chunk
    per slot width."""
    reqs = build_requests(cfg, 2, PROMPT_LEN, GEN)
    engine.generate(reqs[0].prompt[None], GEN, temperature=1.0, seed=0)
    engine.generate(reqs[0].prompt[None], GEN, temperature=0.0, seed=0)
    for n_slots in SLOTS:
        sched = Scheduler(engine, n_slots=n_slots, chunk=CHUNK)
        for r in reqs:
            sched.submit(r)
        sched.run()


def drive_hardened(
    engine,
    reqs,
    arrivals,
    *,
    n_slots,
    chunk,
    cancel_idx=(),
    max_queue=None,
    prefill_chunk=None,
):
    """Lifecycle-aware serve loop: like ``drive_continuous`` but tolerant of
    requests that never produce a Completion (cancelled / shed / rejected).
    Requests whose index is in ``cancel_idx`` are cancelled right after their
    first streamed token (a client hitting stop). Returns
    (scheduler, completions, makespan_s, n_rejected)."""
    watch = set()
    sched = Scheduler(
        engine,
        n_slots=n_slots,
        chunk=chunk,
        max_queue=max_queue,
        prefill_chunk=prefill_chunk,
        on_tokens=lambda rid, toks: (
            sched.cancel(rid, "client stop after first token")
            if rid in watch
            else None
        ),
    )
    done, rejected, i = [], 0, 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < len(reqs) and arrivals[i] <= now:
            try:
                rid = sched.submit(reqs[i])
                if i in cancel_idx:
                    watch.add(rid)
            except QueueFullError:
                rejected += 1
            i += 1
        if sched.idle:
            if i >= len(reqs):
                break
            time.sleep(max(0.0, arrivals[i] - (time.perf_counter() - t0)))
            continue
        done.extend(sched.step())
    return sched, done, time.perf_counter() - t0, rejected


# -- prefix-cache / chunked-prefill regimes (ISSUE 10, DESIGN.md §12) --------

P_PROMPT = 96    # long prompts so prefill is a compute-visible share of TTFT
P_SHARED = 88    # the repeated system prompt inside them
P_GEN = 8
P_CHUNK = 2      # decode chunk: several chunks per request so TPOT resolves
P_BLOCK = 8      # prefix-block granularity (matches in multiples of 8)


def _prefix_workload(cfg, run_seed, *, n=N_REQUESTS):
    """50% shared-prefix traffic: half the requests repeat a fixed
    P_SHARED-token system prompt with fresh per-run tails, half are fully
    fresh prompts.
    Only the shared prefix can ever hit — tails and unique prompts change
    every run, so the measured hit traffic is honestly 50%."""
    corpus = MarkovCorpus(cfg.vocab, seed=3)
    shared = corpus.sample(1, P_PROMPT, seed=99)[0, :P_SHARED]
    reqs = []
    for i in range(n):
        if i % 2 == 0:
            tail = corpus.sample(
                1, P_PROMPT - P_SHARED, seed=1000 * run_seed + i
            )[0, : P_PROMPT - P_SHARED]
            prompt = np.concatenate([shared, tail])
        else:
            prompt = corpus.sample(
                1, P_PROMPT, seed=1000 * run_seed + 500 + i
            )[0, :P_PROMPT]
        reqs.append(
            Request(prompt=prompt.astype(np.int32), max_new_tokens=P_GEN,
                    seed=10 + i)
        )
    return reqs


def prefix_bench(rows) -> None:
    """Shared-prefix TTFT, long-prompt interleave, and the leak-free overload
    row. Appends rows in place; asserts the §12 acceptance numbers.

    Runs on a wider reduced model than the other regimes: at d_model=256 a
    whole 96-token prefill costs about the same as the warm path's
    install + suffix dispatches, so the cache's prefill savings drown in
    per-call overhead. d_model=512 makes prefill compute-visible, which is
    the regime the cache exists for."""
    cfg = reduced(
        get_config("llama3.2-3b"),
        d_model=512, n_kv_heads=4, d_ff=1536, n_layers=3,
    )
    params = quantize_params(
        init_params(jax.random.PRNGKey(1), cfg), QuantPolicy(q=4, g=128, iters=4)
    )
    max_seq = P_PROMPT + P_GEN + 8
    cold_eng = Engine(cfg, params, max_seq=max_seq)
    warm_eng = Engine(cfg, params, max_seq=max_seq,
                      prefix_cache=PrefixCache(block_tokens=P_BLOCK))
    zeros = np.zeros(N_REQUESTS)

    def serve(eng, run_seed, prefill_chunk=None):
        sched, done, dt = drive_continuous(
            eng, _prefix_workload(cfg, run_seed), zeros,
            n_slots=4, chunk=P_CHUNK, prefill_chunk=prefill_chunk,
        )
        s = sched.summary()
        return s["ttft_s"], s["tpot_s"], dt

    # warmups: compile both engines' shapes; the warm engine's pass also
    # commits the shared prefix, which is exactly the steady state measured.
    # Measured runs interleave cold/warm and take the min-of-N mean so host
    # load drift lands on both sides.
    serve(cold_eng, 0)
    serve(warm_eng, 0)
    cold_runs, warm_runs = [], []
    for rep in (1, 2):
        cold_runs.append(serve(cold_eng, rep))
        warm_runs.append(serve(warm_eng, rep))
    ttft_c, tpot_c, dt_c = min(cold_runs, key=lambda r: r[0]["mean"])
    ttft_w, tpot_w, dt_w = min(warm_runs, key=lambda r: r[0]["mean"])
    st = warm_eng.prefix_cache.stats()
    ttft_gain = 100.0 * (ttft_c["mean"] - ttft_w["mean"]) / ttft_c["mean"]
    for tag, t, p, dt, extra in (
        ("cold", ttft_c, tpot_c, dt_c, ""),
        ("warm", ttft_w, tpot_w, dt_w,
         f";hits={st['hits']};misses={st['misses']}"),
    ):
        rows.append(
            {
                "name": f"serve/prefix_shared50/{tag}",
                "tokens_per_s": round(N_REQUESTS * P_GEN / dt, 2),
                "makespan_s": round(dt, 3),
                "derived": (
                    f"ttft_mean={t['mean']:.3f}s;ttft_p50={t['p50']:.3f}s;"
                    f"ttft_p95={t['p95']:.3f}s;tpot_p50={p['p50'] * 1e3:.1f}ms;"
                    f"prompt={P_PROMPT};shared={P_SHARED};block={P_BLOCK}"
                    f"{extra}"
                ),
            }
        )
    rows.append(
        {
            "name": "serve/prefix_shared50/ttft_gain",
            "tokens_per_s": None,
            "makespan_s": None,
            "derived": f"ttft_mean_gain_pct={ttft_gain:.1f};"
            f"tpot_p50_cold={tpot_c['p50'] * 1e3:.1f}ms;"
            f"tpot_p50_warm={tpot_w['p50'] * 1e3:.1f}ms",
        }
    )
    print(f"prefix shared-50%: ttft mean {ttft_c['mean']:.3f}s -> "
          f"{ttft_w['mean']:.3f}s ({ttft_gain:+.1f}%), "
          f"tpot p50 {tpot_c['p50'] * 1e3:.1f} -> {tpot_w['p50'] * 1e3:.1f}ms, "
          f"{st['hits']} hits")
    assert ttft_w["mean"] < ttft_c["mean"], (
        "acceptance: warm prefix cache must improve mean TTFT at 50% "
        f"shared-prefix traffic (cold {ttft_c['mean']:.3f}s, "
        f"warm {ttft_w['mean']:.3f}s)"
    )
    assert tpot_w["p50"] <= tpot_c["p50"] * 1.5 + 2e-3, (
        "acceptance: TPOT regression must stay bounded "
        f"(cold {tpot_c['p50']:.4f}s, warm {tpot_w['p50']:.4f}s)"
    )

    # -- long-prompt interleave: whole-shot vs bucketed chunked prefill ------
    # All 12 requests are resident at once (n_slots=12), shorts queued ahead
    # of the longs, so every long admission happens while shorts decode.
    # Whole-shot: each long prefill is one 96-token dispatch that blocks the
    # step loop, inflating the shorts' time-to-first-chunk. Chunked: the same
    # prefill lands in P_BLOCK-token slices between decode chunks.
    def interleave_reqs():
        corpus = MarkovCorpus(cfg.vocab, seed=7)
        out = []
        for i in range(8):
            p = corpus.sample(1, 4, seed=400 + i)[0, :4]
            out.append(Request(prompt=p.astype(np.int32), max_new_tokens=P_GEN))
        for i in range(4):
            p = corpus.sample(1, P_PROMPT, seed=300 + i)[0, :P_PROMPT]
            out.append(Request(prompt=p.astype(np.int32), max_new_tokens=4))
        return out

    def interleave(prefill_chunk):
        reqs = interleave_reqs()
        sched, done, dt = drive_continuous(
            cold_eng, reqs, np.zeros(len(reqs)), n_slots=len(reqs),
            chunk=P_CHUNK, prefill_chunk=prefill_chunk,
        )
        short = [sched.outcomes[r.rid].ttft for r in reqs if r.prompt.size <= 8]
        short = np.asarray(sorted(t for t in short if t is not None))
        return float(short[len(short) // 2]), float(short[-1]), dt

    interleave(None), interleave(P_BLOCK)  # compile the interleave shapes
    p50_w, worst_w, dt_w2 = interleave(None)
    p50_ck, worst_ck, dt_ck = interleave(P_BLOCK)
    rows.append(
        {
            "name": "serve/prefill_interleave_long_prompts",
            "tokens_per_s": None,
            "makespan_s": None,
            "derived": (
                f"short_ttft_p50_wholeshot={p50_w:.3f}s;"
                f"short_ttft_p50_chunked={p50_ck:.3f}s;"
                f"short_ttft_max_wholeshot={worst_w:.3f}s;"
                f"short_ttft_max_chunked={worst_ck:.3f}s;"
                f"prefill_chunk={P_BLOCK};long_prompt={P_PROMPT}"
            ),
        }
    )
    print(f"long-prompt interleave: short-request ttft p50 "
          f"{p50_w:.3f}s (whole-shot) vs {p50_ck:.3f}s (chunked), "
          f"worst {worst_w:.3f}s vs {worst_ck:.3f}s")

    # -- overload: leak-free accounting under cancels + deadlines + bounds ---
    over = _prefix_workload(cfg, 2, n=2 * N_REQUESTS)
    for r in over:
        r.ttft_deadline_s = 2.0
    arrivals = poisson_arrivals(len(over), 2.0 * N_REQUESTS / max(dt_w, 0.1),
                                seed=5)
    sched, done, dt, rejected = drive_hardened(
        warm_eng, over, arrivals, n_slots=4, chunk=P_CHUNK,
        max_queue=N_REQUESTS // 2, prefill_chunk=P_BLOCK,
        cancel_idx=set(range(0, 2 * N_REQUESTS, 5)),
    )
    st = warm_eng.prefix_cache.stats()
    rows.append(
        {
            "name": "serve/prefix_overload_leakcheck",
            "tokens_per_s": None,
            "makespan_s": round(dt, 3),
            "derived": (
                f"offered={len(over)};finished={len(done)};rejected={rejected};"
                f"cancelled={sched.counters['cancelled']};"
                f"hits={st['hits']};misses={st['misses']};"
                f"commits={st['commits']};aborts={st['aborts']};"
                f"evictions={st['evictions']};pinned={st['pinned']}"
            ),
        }
    )
    print(f"prefix overload: {len(done)} finished, {rejected} rejected, "
          f"{sched.counters['cancelled']} cancelled; accounting "
          f"{st['hits']}+{st['misses']} == {st['commits']}+{st['aborts']}, "
          f"pinned={st['pinned']}")
    assert st["pinned"] == 0, "refcount leak: pins must drain to zero"
    assert st["hits"] + st["misses"] == st["commits"] + st["aborts"], (
        f"accounting leak: {st}"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--out",
        default=os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json"),
    )
    args = ap.parse_args()

    cfg, params, engine = _engine()
    t0 = time.perf_counter()
    _warmup(cfg, engine)
    print(f"warmup (compiles): {time.perf_counter() - t0:.1f}s")

    reqs = build_requests(cfg, N_REQUESTS, PROMPT_LEN, GEN)
    total_new = sum(r.max_new_tokens for r in reqs)
    rows = []

    def record(name, makespan, extra=""):
        tps = total_new / makespan
        rows.append(
            {
                "name": name,
                "tokens_per_s": round(tps, 2),
                "makespan_s": round(makespan, 3),
                "derived": f"requests={N_REQUESTS};prompt={PROMPT_LEN};gen={GEN};"
                f"q=4;g=128{extra}",
            }
        )
        print(f"{name}: {tps:.1f} tok/s (makespan {makespan:.2f}s)")
        return tps

    # -- burst regime: everything queued at t=0 ------------------------------
    zeros = np.zeros(N_REQUESTS)
    _, seq_dt = drive_sequential(engine, reqs, zeros)
    seq_tps = record("serve/sequential_oneshot/burst", seq_dt)

    cont_tps = {}
    for n_slots in SLOTS:
        sched, done, dt = drive_continuous(
            engine, reqs, zeros, n_slots=n_slots, chunk=CHUNK
        )
        util = sched.steps_active / max(1, sched.decode_steps * sched.n_slots)
        cont_tps[n_slots] = record(
            f"serve/continuous_slots{n_slots}/burst", dt,
            extra=f";chunk={CHUNK};slot_util={util:.2f}",
        )

    # -- poisson regime: arrivals at ~2x the sequential service rate ---------
    rate = 2.0 * N_REQUESTS / seq_dt
    arrivals = poisson_arrivals(N_REQUESTS, rate, seed=1)
    _, seq_p_dt = drive_sequential(engine, reqs, arrivals)
    record(f"serve/sequential_oneshot/poisson_{rate:.1f}rps", seq_p_dt)
    sched, done, dt = drive_continuous(
        engine, reqs, arrivals, n_slots=4, chunk=CHUNK
    )
    util = sched.steps_active / max(1, sched.decode_steps * sched.n_slots)
    record(
        f"serve/continuous_slots4/poisson_{rate:.1f}rps", dt,
        extra=f";chunk={CHUNK};slot_util={util:.2f}",
    )

    speedup = cont_tps[4] / seq_tps
    rows.append(
        {
            "name": "serve/speedup_continuous4_vs_sequential/burst",
            "tokens_per_s": None,
            "makespan_s": None,
            "derived": f"speedup={speedup:.2f}x",
        }
    )
    print(f"continuous(4 slots) vs sequential: {speedup:.2f}x")
    assert speedup > 1.0, (
        "acceptance: continuous batching must beat sequential one-shot "
        f"generate at >=4 slots (got {speedup:.2f}x)"
    )

    def pct_row(name, sched, extra=""):
        """TTFT/TPOT percentiles from the scheduler's lifecycle records."""
        s = sched.summary()
        ttft, tpot = s["ttft_s"], s["tpot_s"]
        by = ";".join(f"{k}={v}" for k, v in sorted(s["by_state"].items()))
        rows.append(
            {
                "name": name,
                "tokens_per_s": None,
                "makespan_s": None,
                "derived": (
                    f"ttft_p50={ttft['p50']:.3f}s;ttft_p95={ttft['p95']:.3f}s;"
                    f"ttft_p99={ttft['p99']:.3f}s;tpot_p50={tpot['p50'] * 1e3:.1f}ms;"
                    f"tpot_p95={tpot['p95'] * 1e3:.1f}ms;{by}{extra}"
                ),
            }
        )
        print(f"{name}: ttft p50/p95/p99 = {ttft['p50']:.3f}/"
              f"{ttft['p95']:.3f}/{ttft['p99']:.3f}s ({by}{extra})")

    # -- heavy-tail (Lomax) arrivals at the same mean rate as the Poisson
    # trace: bursty clumps + long gaps is where tail latency lives ----------
    arrivals_ht = pareto_arrivals(N_REQUESTS, rate, alpha=1.5, seed=2)
    sched, done, dt, _ = drive_hardened(
        engine, build_requests(cfg, N_REQUESTS, PROMPT_LEN, GEN),
        arrivals_ht, n_slots=4, chunk=CHUNK,
    )
    record(f"serve/continuous_slots4/heavytail_{rate:.1f}rps", dt,
           extra=f";chunk={CHUNK};alpha=1.5")
    pct_row(f"serve/latency_slots4/heavytail_{rate:.1f}rps", sched)

    # -- cancellation-rate sweep: cancel right after the first token --------
    for frac in (0.25, 0.5):
        n_cancel = int(N_REQUESTS * frac)
        sched, done, dt, _ = drive_hardened(
            engine, build_requests(cfg, N_REQUESTS, PROMPT_LEN, GEN),
            np.zeros(N_REQUESTS), n_slots=4, chunk=CHUNK,
            cancel_idx=set(range(0, N_REQUESTS, max(1, N_REQUESTS // n_cancel)))
            if n_cancel else set(),
        )
        served = sum(len(c.new_tokens) for c in done)
        tps = served / dt
        rows.append(
            {
                "name": f"serve/cancel_sweep_{int(frac * 100)}pct/burst",
                "tokens_per_s": round(tps, 2),
                "makespan_s": round(dt, 3),
                "derived": (
                    f"cancelled={sched.counters['cancelled']};survivors="
                    f"{len(done)};survivor_tokens={served};chunk={CHUNK}"
                ),
            }
        )
        print(f"cancel {int(frac * 100)}%: {tps:.1f} survivor tok/s, "
              f"{sched.counters['cancelled']} cancelled, makespan {dt:.2f}s")

    # -- bounded admission queue under sustained 2x overload with tight TTFT
    # deadlines: loud rejects when the queue is full, deadline-aware shedding
    # of entries that aged out while waiting, and the served remainder keeps
    # its latency ------------------------------------------------------------
    over = build_requests(cfg, 2 * N_REQUESTS, PROMPT_LEN, GEN)
    ttft_deadline = 0.35
    for r in over:
        r.ttft_deadline_s = ttft_deadline
    # arrivals at ~2x the measured continuous service rate: the queue
    # saturates gradually, so both overflow AND aging are exercised (a t=0
    # burst would only ever reject)
    overload_rps = 2.0 * cont_tps[4] / GEN
    arrivals_ov = poisson_arrivals(len(over), overload_rps, seed=5)
    sched, done, dt, rejected = drive_hardened(
        engine, over, arrivals_ov, n_slots=4, chunk=CHUNK,
        max_queue=N_REQUESTS // 2,
    )
    c = sched.counters
    n_timeout = c["timed_out"]
    rows.append(
        {
            "name": f"serve/bounded_queue_overload_{overload_rps:.1f}rps",
            "tokens_per_s": round(sum(len(x.new_tokens) for x in done) / dt, 2),
            "makespan_s": round(dt, 3),
            "derived": (
                f"offered={len(over)};max_queue={N_REQUESTS // 2};"
                f"rejected={rejected};shed={c['shed']};timed_out={n_timeout};"
                f"finished={len(done)};ttft_deadline={ttft_deadline}s"
            ),
        }
    )
    print(f"bounded queue @{overload_rps:.1f}rps: {rejected} rejected, "
          f"{c['shed']} shed, {n_timeout} timed out, {len(done)} finished "
          f"in {dt:.2f}s")
    pct_row("serve/latency_bounded_queue_overload", sched)
    assert rejected + c["shed"] + n_timeout + len(done) == len(over), (
        "lifecycle leak: every offered request must be rejected, shed, "
        "timed out or finished"
    )

    prefix_bench(rows)

    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
        f.write("\n")
    print("wrote", out)


if __name__ == "__main__":
    main()
