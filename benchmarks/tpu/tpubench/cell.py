"""One run of one cell: set up, serve the window, check the outputs against
the reference, and reduce what was seen to the cell's metrics."""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import time
from types import ModuleType
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from tpubench import arch, reference, serve, spec, traffic, weights
from tpubench import trace as tr
from tpubench.peaks import peaks

DRAIN_MAX_S = 60.0
# A traced run profiles the window's first seconds: the traffic file's
# ``trace_s``, else this.
TRACE_S = 10.0
_FAILED = ("failed", "cancelled", "timed_out", "shed")


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets."""

    arch: ModuleType  # the configuration's architecture module (tpubench.arch)
    mc: dict  # model_config of the configuration file
    qc: dict  # quant of the configuration file
    server: dict
    window: serve.Window
    trace: Optional[tr.Trace]
    peak: dict
    tp: int = 1  # chips each layer is split over; the work is counted per chip

    def window_tokens(self) -> List[tuple]:
        """``(prompt_len, index)`` of each token handed over inside the
        window's step boundaries."""
        a, b = self.window.opened_at, self.window.closed_at
        return [(r.plen, j) for r in self.window.records
                for j, t in enumerate(r.token_times) if a < t <= b]


def warm(engine, server: dict, arrivals, log=print) -> None:
    """Compile every program the window will drive, at the cell's shapes only.

    A scheduler over the engine serves one prompt per prefill bucket up to
    ``prefill_chunk`` (bucket programs, slot install, one decode chunk at the
    cell's slot count). Then each prompt length of the run is prefilled once
    through the engine's own chunked admission, which compiles the small
    slicing and padding ops it runs for each length (a cost of the program,
    which a later change to it can remove)."""
    from repro.infer import Request, Scheduler

    sched = Scheduler(engine, n_slots=server["n_slots"], chunk=server["decode_chunk"],
                      prefill_chunk=server["prefill_chunk"], max_queue=None)
    for b in engine.chunk_buckets:
        if b <= server["prefill_chunk"]:
            sched.submit(Request(prompt=np.ones(b, np.int32), max_new_tokens=1))
    t = time.perf_counter()
    sched.run()
    jax.block_until_ready(sched.slots)
    del sched
    t_p = time.perf_counter()

    lengths = sorted({int(a.prompt.size) for a in arrivals})
    for n in lengths:
        p = engine.begin_admission(np.ones(n, np.int32), max_new_tokens=1, chunked=True)
        while not p.done:
            engine.advance_admission(p, server["prefill_chunk"])
        jax.block_until_ready(p.cache1)
        engine.abort_admission(p)
    log(f"warm-up: programs {t_p - t:.1f}s, {len(lengths)} prompt lengths "
        f"{time.perf_counter() - t_p:.1f}s")


def end_to_end(w: serve.Window) -> Dict[str, float]:
    """The end-to-end metrics of the window ``[0, seconds)``."""
    T = w.seconds
    out_tokens = sum(1 for r in w.records for t in r.token_times if 0.0 <= t < T)
    recs = w.in_window()
    ttft = sorted((r.first - r.arrival) if r.first is not None else math.inf for r in recs)
    span = gaps = 0.0
    for r in w.records:
        ts = [t for t in r.token_times if 0.0 <= t < T]
        if len(ts) >= 2:
            span += ts[-1] - ts[0]
            gaps += len(ts) - 1
    return {
        "output_tok_s": out_tokens / T,
        "ttft_p50_s": _quantile(ttft, 0.5),
        "ttft_p90_s": _quantile(ttft, 0.9),
        "tpot_ms": 1000.0 * span / gaps if gaps else math.inf,
    }


def _quantile(xs: List[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not xs:
        return math.inf
    h = (len(xs) - 1) * p
    lo, hi = int(math.floor(h)), int(math.ceil(h))
    if math.isinf(xs[hi]):
        return math.inf
    return xs[lo] + (h - lo) * (xs[hi] - xs[lo])


def sample_finished(w: serve.Window, check: dict, seed: int) -> List[serve.Record]:
    """The longest finished request and others drawn from the seed, until
    ``sample_tokens`` served tokens or ``max_sequences`` requests."""
    done = [r for r in w.records if r.state == "finished" and r.tokens is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), r.plen))
    rest = [r for r in done if r is not longest]
    order = traffic.rng(seed, 99).permutation(len(rest))
    picked, n_tok = [longest], len(longest.tokens)
    for i in order:
        if n_tok >= check["sample_tokens"] or len(picked) >= check["max_sequences"]:
            break
        picked.append(rest[i])
        n_tok += len(rest[i].tokens)
    return picked


def setup(cell: spec.Cell, seed: int, seconds: float, engine=None, log=print):
    """Weights from the seed, the engine over them (or ``engine`` given the
    new weights), the traffic, and every program the window drives.

    A cell whose configuration states tensor parallelism serves through the
    program's own mesh path, ``Engine(mesh=...)``. The weights are made split
    over the chips. ``shard_model`` re-orders the fused leaves' columns with
    a gather that, on a chip, needs several times that chip's share of a
    stacked leaf, so those leaves reach it from host memory, as from a
    checkpoint. The engine then holds the only copy on the chips, and
    ``plain`` comes back None: the reference makes it again from the
    seed."""
    from repro.infer import Engine

    config, tf = cell.config, cell.traffic
    server = tf["server"]
    mesh = weights.mesh(cell.tp)
    t = time.perf_counter()
    plain = weights.make_weights(config, seed, mesh)
    jax.block_until_ready(plain)
    t_w = time.perf_counter()
    if mesh is not None:
        plain = weights.to_host(plain, arch.relayout(arch.of(config)))
    t_h = time.perf_counter()
    cfg, params = weights.program_params(plain, config)
    if mesh is not None:
        plain = None
    if engine is None:
        engine = Engine(cfg, params, max_seq=server["max_seq"], fuse=False, mesh=mesh)
    elif mesh is None:
        engine.params = params
    else:
        from repro.parallel.tp import shard_model

        engine.params, _ = shard_model(cfg, params, mesh)
    del params
    log(f"weights {t_w - t:.1f}s, to host {t_h - t_w:.1f}s, engine {time.perf_counter() - t_h:.1f}s "
        f"(over {cell.tp} chip(s))")
    arrivals = traffic.schedule(tf, cfg.vocab, seed, horizon_s=seconds + DRAIN_MAX_S)
    t_e = time.perf_counter()
    warm(engine, server, arrivals, log=log)
    log(f"set-up: weights {t_w - t:.1f}s, to host {t_h - t_w:.1f}s, engine and traffic "
        f"{t_e - t_h:.1f}s, warm-up {time.perf_counter() - t_e:.1f}s")
    return plain, engine, arrivals


def memory(devices, key: str) -> int:
    """The largest ``memory_stats()[key]`` over ``devices``."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


def check_shape(tf: dict) -> tuple:
    """Fixed reference input shape of a cell: ``(sequences, max_seq, rows)``
    with room for ``sample_tokens`` plus one longest answer, in 256s."""
    check = tf["check"]
    rows = check["sample_tokens"] + tf["output_len"]["max"]
    return (check["max_sequences"], tf["server"]["max_seq"], -(-rows // 256) * 256)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace_on: bool, *,
             t_start: float, device, engine_hook: Optional[Callable] = None,
             log=print) -> dict:
    config, tf = cell.config, cell.traffic
    server, check = tf["server"], tf["check"]
    # -- set-up ----------------------------------------------------------------
    plain, engine, arrivals = setup(cell, seed, seconds, log=log)
    if engine_hook is not None:
        engine_hook(engine)
    mesh = engine.mesh
    devices = [device] if mesh is None else list(mesh.devices.flat)
    log(f"set-up done: {len(arrivals)} requests scheduled")

    # -- the window --------------------------------------------------------------
    trace_dir = os.path.join(spec.ROOT, ".bench_trace") if trace_on else None
    spans = []
    in_use = []

    def on_open():
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
            # no Python tracer: it records every Python call of the host loop
            # that the idle gaps attribute, and no reader uses its events
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            spans.append(jax.profiler.TraceAnnotation(tr.WINDOW_SPAN))
            spans[0].__enter__()

    def on_close():
        in_use.append(memory(devices, "bytes_in_use"))
        if trace_dir:
            spans[0].__exit__(None, None, None)

    def on_settled():
        if trace_dir:
            jax.profiler.stop_trace()

    w = serve.serve(engine, arrivals, server, seconds, on_open=on_open,
                    on_close=on_close, on_settled=on_settled,
                    count_s=tf.get("trace_s", TRACE_S) if trace_on else None, drain_max_s=DRAIN_MAX_S)
    setup_s = w.loop_start - t_start
    mem_peak = memory(devices, "peak_bytes_in_use")

    # -- free the program's state, then check against the reference ----------------
    del engine
    gc.collect()
    sample = sample_finished(w, check, seed)
    t = time.perf_counter()
    if plain is None and sample:
        plain = weights.make_weights(config, seed, mesh)
    cmp = reference.compare(config, plain, [(_prompt(arrivals, w, r), r.tokens)
                                            for r in sample], check_shape(tf)) \
        if sample else {"max_gap": math.inf, "tokens": 0, "sequences": 0, "top1_agree": 0.0}
    ref_s = time.perf_counter() - t
    recs = w.in_window()
    ttft = sorted(r.first - r.arrival for r in recs if r.first is not None)
    failed = sum(1 for r in recs if r.first is None or r.state in _FAILED)
    short = sum(1 for r in w.records
                if r.state == "finished" and len(r.tokens) != r.max_new)
    limit = check.get("gap_limit")
    checks = {
        "served_token_gap": {"value": cmp["max_gap"], "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "wrong_length_outputs": {"value": short, "limit": 0},
    }
    correct = (limit is not None and cmp["max_gap"] <= limit and failed == 0
               and short == 0 and cmp["tokens"] > 0)

    # -- metrics ----------------------------------------------------------------------
    result_device = {"platform": device.platform, "kind": device.device_kind,
                     "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if not trace_on:
        e2e = end_to_end(w)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m.name] = {"value": e2e[m.name], "unit": m.unit}
    else:
        trc = tr.load(trace_dir)
        run = Run(arch=arch.of(config), mc=config["model_config"], qc=config["quant"],
                  server=server, window=w, trace=trc, peak=peaks(device.device_kind),
                  tp=cell.tp)
        for m in cell.per_layer:
            v = spec.load_reader(m.name)(run)
            if v is None:
                raise RuntimeError(f"per-layer metric {m.name} found nothing to read in "
                                   f"cell {cell.name}, which lists it")
            metrics[m.name] = {"value": v, "unit": m.unit}
        result_device["busy_s"] = tr.busy_s(trc)
        result_device["window_s"] = trc.window_s
        breakdown = {"device_ops": tr.top_ops(trc), "idle_gaps": tr.idle_gaps(trc)}
        shutil.rmtree(trace_dir, ignore_errors=True)

    notes = {
        "seed": seed, "window_s": seconds, "requests_in_window": len(recs),
        "requests_scheduled": len(arrivals),
        "compiles_in_window": w.compiles_in_window,
        "compile_s_in_window": w.compile_s_in_window,
        "submit_late_p50_s": _quantile(sorted(r.submitted - r.arrival for r in recs
                                              if r.submitted is not None), 0.5),
        "submit_late_max_s": max((r.submitted - r.arrival for r in recs
                                  if r.submitted is not None), default=0.0),
        "ttft_quartiles_s": [_quantile(ttft, q) for q in (0.25, 0.5, 0.75)],
        "backlog_open": w.backlog[0], "backlog_close": w.backlog[1],
        "slow_steps": w.slow_steps, "dispatch_retries": w.counters["retries"],
        "drained": w.drained, "reference_s": ref_s,
        "memory_in_use_at_close_bytes": in_use[0] if in_use else None,
        "reference_tokens": cmp["tokens"], "reference_sequences": cmp["sequences"],
        "reference_top1_agree": cmp["top1_agree"],
    }
    out = {"correct": bool(correct), "attempted": len(recs), "failed": failed,
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["notes"] = notes
    out["check"] = checks
    return out


def _prompt(arrivals, w: serve.Window, rec: serve.Record) -> np.ndarray:
    return arrivals[w.records.index(rec)].prompt
