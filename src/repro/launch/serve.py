"""Serving launcher: quantize + continuous-batching generation (paper §V
workload shape: many concurrent decode requests against one weight-resident
quantized model).

PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --q 4 --g 128 \
    --requests 12 --slots 4 --rate 8 --speculate 2:4

PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --q 4 --g 64 \
    --requests 12 --slots 4 --tp 4     # tensor-parallel serving (g must keep
                                       # (k/g) divisible by tp for the
                                       # row-parallel weights; the engine
                                       # errors loudly naming the leaf else)

Requests enter an admission queue and are continuously batched into a
``--slots``-wide decode batch (``repro.infer.Scheduler``): a request joins as
soon as a slot frees up, finishes on its own budget, and its tokens are
identical to a solo ``Engine.generate`` call (tests/test_scheduler.py).
``--rate`` simulates a Poisson arrival process (requests/s; 0 = all queued at
t=0). ``--sequential`` instead serves the same workload as one-shot scanned
``generate`` calls in arrival order — the PR 1 fast path, kept as the
baseline the scheduler is measured against (BENCH_serve.json).

``--format NAME`` picks the registered quantization format (DESIGN.md §2.4);
the choices come straight from the registry (``core/formats.py``), so a newly
registered format serves here with zero launcher changes: the paper's BCQ
(default), FineQuant-style group-wise uniform int-q, the dequantize-then-
matmul baseline the paper benchmarks against, FLUTE-style arbitrary-codebook,
and T-MAC-style ternary — all serve end-to-end through the identical
scheduler/engine stack, so format comparisons isolate the kernel pipeline.

``--speculate q_draft:gamma`` turns decode dispatches into self-speculative
chunks (DESIGN.md §5): a ``q_draft``-bit truncation of the same quantized
weights drafts ``gamma`` tokens per chunk and the full-precision model
verifies them in one batched forward — greedy output stays token-identical,
sampled output follows the exact target distribution, and the draft-acceptance
rate is reported alongside tok/s. Requests opt in per row (every CLI request
opts in). Needs a truncation-capable format (``supports_truncate`` in the
registry — ``bcq`` and ``ternary``); the launcher checks the capability flag,
not a name list.

The CLI serves a float32 reduction of the arch sized for a CPU host;
``chip_smoke.py`` at the repo root drives the same build path
(:func:`build_engine`) with a published config on the chip.

``--tp N`` serves tensor-parallel (DESIGN.md §7): weights are placed
column/row-parallel over an N-way ``model`` mesh under ``shard_map``, KV
caches shard their kv-head dim, and greedy tokens stay identical to the
single-device engine. On a CPU host the launcher forces N placeholder
devices (the flag below must be set before jax initialises, hence the
pre-import peek — same constraint launch/dryrun.py documents).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time

from repro.launch._hostdev import force_host_devices_for_tp

if __name__ == "__main__":
    # CLI only (python -m repro.launch.serve): must run before the first jax
    # import. Library imports of this module (benchmarks pull build_requests)
    # must NOT sniff the host program's argv or mutate its XLA topology.
    force_host_devices_for_tp()

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.formats import format_names, get_format
from repro.data import MarkovCorpus
from repro.infer import Engine, Request, Scheduler, SpecConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, reduced
from repro.quant import QuantPolicy, quantize_params, quantized_bytes


def serving_config(arch: str):
    """The configuration the serving CLIs build: a float32 reduction of the
    arch sized for a CPU host, with >=128-dim linears so quantization still
    bites."""
    base = get_config(arch)
    return reduced(base, d_model=256, n_kv_heads=4,
                   d_ff=512 if base.d_ff else 0,
                   moe_d_ff=256 if base.n_experts else None)


def build_params(cfg, *, q: int, g: int = 128, fmt: str = "bcq",
                 iters: int = 4, seed: int = 0):
    """Random weights from ``seed``, quantized with ``(q, g, fmt)`` (``q=0``
    keeps them dense).

    Init and quantization run as ONE jitted program, so XLA frees each dense
    layer stack as soon as it is packed: the build peak stays near the
    quantized model plus one stack's float32 working copy, instead of the
    whole dense model plus that copy."""
    key = jax.random.PRNGKey(seed)
    if not q:
        return init_params(key, cfg)
    policy = QuantPolicy(q=q, g=g, iters=iters, fmt=fmt)
    return jax.jit(lambda k: quantize_params(init_params(k, cfg), policy))(key)


def build_engine(cfg, *, q: int, g: int = 128, fmt: str = "bcq",
                 iters: int = 4, seed: int = 0, **engine_kw) -> Engine:
    """The serving build path: config → weights → quantization → Engine
    (which fuses QKV/gate-up). ``engine_kw`` goes to :class:`Engine`."""
    return Engine(cfg, build_params(cfg, q=q, g=g, fmt=fmt, iters=iters,
                                    seed=seed), **engine_kw)


def build_requests(cfg, n, prompt_len, gen, *, mixed_temperature=True, seed=3,
                   shared_prefix_len=0):
    """``shared_prefix_len > 0`` gives every request the same leading tokens
    (a shared system prompt) followed by a per-request tail — the workload
    shape the prefix cache (DESIGN.md §12) exists for. The total prompt
    length stays ``prompt_len``."""
    corpus = MarkovCorpus(cfg.vocab, seed=seed)
    if shared_prefix_len >= prompt_len:
        raise ValueError(
            f"shared_prefix_len ({shared_prefix_len}) must leave at least one "
            f"per-request token (prompt_len={prompt_len})"
        )
    shared = corpus.sample(1, prompt_len, seed=99)[0, :shared_prefix_len]
    reqs = []
    for i in range(n):
        prompt = corpus.sample(1, prompt_len, seed=100 + i)[0, :prompt_len]
        if shared_prefix_len:
            prompt = np.concatenate([shared, prompt[shared_prefix_len:]])
        temp = [0.0, 1.0, 0.7][i % 3] if mixed_temperature else 0.0
        reqs.append(
            Request(
                prompt=prompt.astype(np.int32),
                max_new_tokens=gen,
                temperature=temp,
                seed=10 + i,
            )
        )
    return reqs


def poisson_arrivals(n, rate, seed=0):
    """Cumulative arrival offsets (seconds). rate<=0 → everything at t=0."""
    if rate <= 0:
        return np.zeros(n)
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def pareto_arrivals(n, rate, alpha=1.5, seed=0):
    """Heavy-tailed (Lomax / Pareto-II) interarrivals with the same *mean*
    rate as :func:`poisson_arrivals` but bursty clumps and long gaps — the
    tail regime where TTFT percentiles, queue bounds and deadline shedding
    actually matter (Poisson traffic barely exercises them). ``alpha`` is the
    tail index: smaller → heavier tail (alpha must be > 1 for a finite mean;
    the Lomax mean is scale/(alpha-1), so scale = (alpha-1)/rate)."""
    if rate <= 0:
        return np.zeros(n)
    if alpha <= 1:
        raise ValueError(f"alpha must be > 1 for a finite mean, got {alpha}")
    rng = np.random.default_rng(seed)
    scale = (alpha - 1.0) / rate
    return np.cumsum(rng.pareto(alpha, size=n) * scale)


def drive_continuous(engine, reqs, arrivals, *, n_slots, chunk, speculate=None,
                     prefill_chunk=None, tracer=None, metrics=None):
    """Wall-clock serve loop: submit each request at its arrival offset, step
    the scheduler whenever there is work. Returns (scheduler, completions,
    makespan_s) — the scheduler is handed back for utilisation stats.

    ``tracer``/``metrics`` (repro.obs) instrument the run: per-request
    lifecycle spans and the serving metric catalog (DESIGN.md §11).
    ``prefill_chunk`` enables chunked prefill (DESIGN.md §12)."""
    sched = Scheduler(engine, n_slots=n_slots, chunk=chunk, speculate=speculate,
                      prefill_chunk=prefill_chunk, tracer=tracer,
                      metrics=metrics)
    done = []
    t0 = time.perf_counter()
    i = 0
    while len(done) < len(reqs):
        now = time.perf_counter() - t0
        while i < len(reqs) and arrivals[i] <= now:
            sched.submit(reqs[i])
            i += 1
        if sched.idle:
            if i == len(reqs):
                ended = {r: (o.state.name, o.reason) for r, o in sched.outcomes.items()
                         if o.state.name != "FINISHED"}
                raise RuntimeError(f"{len(reqs) - len(done)} requests ended "
                                   f"without completing: {ended}")
            # nothing in flight: sleep until the next arrival
            time.sleep(max(0.0, arrivals[i] - now))
            continue
        done.extend(sched.step())
    return sched, done, time.perf_counter() - t0


def drive_sequential(engine, reqs, arrivals):
    """Baseline: one-shot scanned `generate` per request, in arrival order."""
    t0 = time.perf_counter()
    outs = []
    for req, at in zip(reqs, arrivals):
        wait = at - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        outs.append(
            engine.generate(
                req.prompt[None],
                req.max_new_tokens,
                temperature=req.temperature,
                seed=req.seed,
            )
        )
    return outs, time.perf_counter() - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-3b")
    ap.add_argument("--q", type=int, default=4,
                    help="quantization bits / code planes (0 = dense)")
    ap.add_argument("--g", type=int, default=128)
    ap.add_argument("--format", choices=format_names(), default="bcq",
                    help="registered quantization format (core/formats.py); "
                         "choices track the registry. 'bcq' is the paper's "
                         "LUT-GEMM format; truncation-capable formats "
                         "(supports_truncate) also serve --speculate")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-batch width (concurrent requests)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps per scheduler dispatch (admission "
                         "happens at chunk boundaries)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate in requests/s (0 = all at t=0)")
    ap.add_argument("--sequential", action="store_true",
                    help="serve with one-shot scanned generate calls instead "
                         "of the continuous-batching scheduler (baseline)")
    ap.add_argument("--speculate", type=str, default=None, metavar="QD:GAMMA",
                    help="self-speculative decode chunks from the nested "
                         "QD-bit draft, GAMMA proposals per chunk (e.g. 2:4); "
                         "requires --q > QD to actually speed anything up")
    ap.add_argument("--prefix-cache-mb", type=int, default=0,
                    help="KV prefix-cache budget in MiB (DESIGN.md §12): "
                         "committed prompt prefixes are reused across "
                         "requests under ref-counted LRU eviction (0 = off)")
    ap.add_argument("--prefix-block", type=int, default=16,
                    help="prefix-cache block granularity in tokens: prefixes "
                         "match and commit in whole blocks, so a shared "
                         "system prompt shorter than one block never hits")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill token budget per scheduler step "
                         "(DESIGN.md §12): long prompts prefill in bucketed "
                         "chunks interleaved with decode (0 = whole-shot)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="give every request the same leading tokens (shared "
                         "system prompt) — the prefix-cache workload shape")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard weights/KV over an "
                         "N-way model mesh under shard_map (greedy tokens "
                         "identical to --tp 1; CPU hosts get N forced "
                         "placeholder devices)")
    ap.add_argument("--trace-out", type=str, default=None, metavar="PATH",
                    help="instrument the serve loop with the repro.obs span "
                         "tracer + metrics registry and write a Chrome/"
                         "Perfetto trace-event JSON here (open in "
                         "ui.perfetto.dev); prints the metric snapshot too. "
                         "Host-side spans only — see --profile-dir for "
                         "device timelines")
    ap.add_argument("--profile-dir", type=str, default=None, metavar="DIR",
                    help="opt-in jax.profiler capture: wrap the serve loop "
                         "in jax.profiler.trace(DIR), recording XLA device "
                         "timelines (plus the engine's TraceAnnotation "
                         "scopes) for TensorBoard/Perfetto. Off by default — "
                         "profiling is never free")
    args = ap.parse_args()
    if args.tp < 1:
        ap.error("--tp must be >= 1")
    spec = None
    if args.speculate:
        try:
            spec = SpecConfig.parse(args.speculate)
        except ValueError as e:
            ap.error(f"--speculate: {e}")
    if spec and not args.q:
        ap.error("--speculate requires a quantized model (--q > 0)")
    if spec and not get_format(args.format).supports_truncate:
        capable = [n for n in format_names() if get_format(n).supports_truncate]
        ap.error(f"--speculate needs a truncation-capable format; "
                 f"{args.format!r} has no nested low-bit draft "
                 f"(truncation-capable formats: {', '.join(capable)})")
    if spec and args.sequential:
        ap.error("--speculate drives the continuous-batching scheduler; "
                 "it cannot be combined with --sequential")
    if args.sequential and (args.prefix_cache_mb or args.prefill_chunk):
        ap.error("--prefix-cache-mb/--prefill-chunk drive the scheduler; "
                 "they cannot be combined with --sequential")

    cfg = serving_config(args.arch)
    if cfg.input_kind != "tokens":
        ap.error(f"{args.arch} is a modality-stub arch; see examples/ for the "
                 "embedding-input serving path")
    enable_compile_cache()

    mesh = None
    if args.tp > 1:
        from repro.parallel.tp import make_tp_mesh

        mesh = make_tp_mesh(args.tp)
        print(f"tensor-parallel: {args.tp}-way model mesh over "
              f"{[str(d) for d in mesh.devices.flat]}")

    tracer = registry = None
    if args.trace_out:
        from repro.obs import MetricsRegistry, Tracer

        tracer, registry = Tracer(), MetricsRegistry()

    profile_cm = contextlib.nullcontext()
    if args.profile_dir:
        profile_cm = jax.profiler.trace(args.profile_dir)
        print(f"jax.profiler capture -> {args.profile_dir}")

    headroom = (spec.gamma + 1) if spec else 0
    prefix_cache = None
    if args.prefix_cache_mb > 0:
        from repro.infer import PrefixCache

        prefix_cache = PrefixCache(
            block_tokens=args.prefix_block,
            max_bytes=args.prefix_cache_mb << 20,
        )
    engine = build_engine(cfg, q=args.q, g=args.g, fmt=args.format, mesh=mesh,
                          max_seq=args.prompt_len + args.gen + 8 + headroom,
                          tracer=tracer, prefix_cache=prefix_cache)
    label = f"{args.format} q={args.q} g={args.g}" if args.q else "dense"
    print(f"{cfg.name} {label}: "
          f"{quantized_bytes(engine.params)/2**20:.2f} MiB of weights")
    reqs = build_requests(cfg, args.requests, args.prompt_len, args.gen,
                          shared_prefix_len=args.shared_prefix_len)
    arrivals = poisson_arrivals(args.requests, args.rate, seed=1)
    total_new = sum(r.max_new_tokens for r in reqs)

    if args.sequential:
        with profile_cm:
            outs, dt = drive_sequential(engine, reqs, arrivals)
        print(f"[sequential] {len(outs)} requests, {total_new} tokens in "
              f"{dt:.2f}s ({total_new/dt:.1f} tok/s on this host)")
        print("sample:", outs[0].tokens[0, args.prompt_len:])
    else:
        with profile_cm:
            sched, done, dt = drive_continuous(
                engine, reqs, arrivals, n_slots=args.slots, chunk=args.chunk,
                speculate=spec, prefill_chunk=args.prefill_chunk or None,
                tracer=tracer, metrics=registry,
            )
        util = sched.steps_active / max(1, sched.decode_steps * sched.n_slots)
        tag = "continuous"
        extra = ""
        if spec:
            # steps_active counts emitted tokens in spec mode; occupancy is
            # dispatched row-chunks over capacity
            util = sched.chunk_rows / max(1, sched.decode_steps * sched.n_slots)
            tag = f"speculative q'={spec.q_draft} γ={spec.gamma}"
            extra = f", draft acceptance ~{sched.spec_accept_rate:.0%}"
        print(f"[{tag}] {len(done)} requests, {total_new} tokens in "
              f"{dt:.2f}s ({total_new/dt:.1f} tok/s on this host, "
              f"{args.slots} slots, chunk={args.chunk}, "
              f"slot utilisation {util:.0%}{extra})")
        if prefix_cache is not None:
            st = prefix_cache.stats()
            print(f"prefix cache: {st['hits']} hits / {st['misses']} misses, "
                  f"{st['commits']} commits, {st['evictions']} evictions, "
                  f"{st['cached_bytes']/2**20:.2f} MiB cached "
                  f"({st['nodes']} blocks)")
        print("sample:", done[0].new_tokens)

    if tracer is not None:
        with open(args.trace_out, "w") as f:
            json.dump(tracer.to_chrome(), f)
        st = tracer.stats()
        print(f"trace: {args.trace_out} ({st['buffered']} events, "
              f"{st['evicted']} evicted) — open in ui.perfetto.dev")
        if registry is not None and not args.sequential:
            counters = {
                name: sum(s["value"] for s in fam["series"])
                for name, fam in registry.snapshot().items()
                if fam["type"] == "counter"
            }
            print("metrics:", json.dumps(counters, sort_keys=True))


if __name__ == "__main__":
    main()
