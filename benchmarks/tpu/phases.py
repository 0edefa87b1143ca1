"""Serve one cell with the program's own tracing on, and show where the host
spends each scheduler step.

    python3 benchmarks/tpu/phases.py --workload phi4-mini.chat --seed 7 \\
        --seconds 51 --profile 0

A diagnostic beside ``run.py``, with the same set-up, traffic and serving
loop, but no outputs check and no result line to compare. A ring ``Tracer``
(``repro.obs``) is attached to the scheduler and the engine for the whole
run, so every scheduler step records its ``sched/*`` phases. The reductions
are ``tpubench/steps.py``'s. It prints one JSON line with:

- the window's end-to-end metrics, read as ``run.py --trace 0`` reads them
  but with the ring tracer on;
- the window's longest steps, with the seconds each phase took, and the
  steps of ``[0, trace_s)`` summed up by phase (to set a profiled run
  beside an unprofiled one);
- the engine's work counters over the counted span, with the share of
  prefill rows that went to bucket padding;
- the programs the engine built in that span (its own jitted programs,
  compiled or loaded from the cache) beside the compiles JAX reported
  there (eager ops included);
- the cost of one span, with no tracer and with the ring tracer.

``--profile 1`` also profiles ``[0, trace_s)`` as ``run.py --trace 1`` does
and keeps the ``sched/*`` spans of the capture. It then adds the device idle
time inside scheduler steps per decode chunk, and the idle gaps attributed
to the innermost host span.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))
os.environ["REPRO_AUTOTUNE"] = "0"
os.environ.pop("REPRO_AUTOTUNE_CACHE", None)

SPAN_COST_N = 20000


def span_cost_us(n: int = SPAN_COST_N) -> dict:
    """Host cost of entering and leaving one span, with no profiler session:
    with no tracer (the ``TraceAnnotation`` alone) and with a ring tracer."""
    from repro.infer.spans import span
    from repro.obs import Tracer

    out = {}
    for name, tracer in (("no_tracer", None), ("ring", Tracer(capacity=n))):
        t = time.perf_counter()
        for _ in range(n):
            with span(tracer, "sched/cost", lane="scheduler"):
                pass
        out[name] = (time.perf_counter() - t) / n * 1e6
    return out


def measure(cell, seed: int, seconds: float, profile: bool, *, log=print):
    """Serve ``cell`` once with the ring tracer on; returns the result and
    the serving loop's ``Window``."""
    import jax

    from repro.obs import Tracer
    from tpubench import cell as C
    from tpubench import serve, spec, steps
    from tpubench import trace as tr

    server = cell.traffic["server"]
    # the plain weights stay on the device while serving, as in run.py (a
    # tensor-parallel engine holds the only copy, and set-up returns None)
    plain, engine, arrivals = C.setup(cell, seed, seconds, log=log)
    # serve's clock; the scheduler serve() builds records on its engine's
    ring = Tracer(capacity=1 << 17, clock=time.perf_counter)
    engine.tracer = ring
    trace_dir = os.path.join(spec.ROOT, ".bench_trace") if profile else None
    window = []  # made once the session runs: one made before is not recorded
    seen = {}

    def snap(key):
        seen[key] = (dict(engine.counters), engine.programs_built())

    def on_open():
        snap("open")
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window.append(jax.profiler.TraceAnnotation(tr.WINDOW_SPAN))
            window[0].__enter__()

    def on_close():
        snap("close")
        if trace_dir:
            window[0].__exit__(None, None, None)

    def on_settled():
        if trace_dir:
            jax.profiler.stop_trace()

    w = serve.serve(engine, arrivals, server, seconds, on_open=on_open,
                    on_close=on_close, on_settled=on_settled,
                    count_s=cell.traffic.get("trace_s", C.TRACE_S) if profile else None,
                    drain_max_s=C.DRAIN_MAX_S)
    del plain
    (c0, b0), (c1, b1) = seen["open"], seen["close"]
    counters = {k: c1[k] - c0[k] for k in c0}
    t0 = w.loop_start + max(0.0, -min(a.at for a in arrivals))
    trace_s = min(cell.traffic.get("trace_s", C.TRACE_S), seconds)
    out = {
        "workload": cell.name, "seed": seed, "window_s": seconds, "count_s": w.count_s,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "end_to_end": C.end_to_end(w),
        "slow_steps": steps.slow_steps(ring.events(), t0, t0 + seconds),
        "steps_in_trace_span": steps.step_summary(ring.events(), t0, t0 + trace_s),
        "engine_counters": counters,
        "prefill_pad_pct": steps.prefill_pad_pct(counters),
        "bench_prefill_tokens": sum(n for _, n, _ in w.prefill_chunks),
        "programs_built_in_window": b1 - b0,
        "compiles_in_window": w.compiles_in_window,
        "ring": ring.stats(),
        "span_cost_us": span_cost_us(),
    }
    if trace_dir:
        trc = steps.load(trace_dir)
        out["idle_ms_per_chunk"] = steps.idle_ms_per_chunk(trc)
        out["device_idle_pct"] = 100.0 * (1.0 - tr.busy_s(trc) / trc.window_s)
        out["idle_gaps"] = tr.idle_gaps(trc)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return out, w


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import jax

    from tpubench import spec

    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("phases.py: no TPU", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(spec.ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out, _ = measure(cell, args.seed, args.seconds, bool(args.profile),
                     log=lambda m: print(m, file=sys.stderr, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
