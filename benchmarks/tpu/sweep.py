"""Find a cell's knee: serve its traffic at several fixed arrival rates.

    python3 benchmarks/tpu/sweep.py --workload phi4-mini.chat \\
        --rates 1.5 2 2.5 3 --seconds 20

One process, one set of weights (``--seed``); for each rate the cell's
traffic file with ``rate_per_s`` replaced, served for ``--seconds`` after its
pre-roll. One line of JSON per rate: the end-to-end metrics, slot occupancy
and the backlog (requests queued or mid-prefill) at the window's open and
close. The knee is the highest rate whose backlog does not grow across the
window; a cell's traffic file then fixes its rate at about 0.8 of it.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))
os.environ["REPRO_AUTOTUNE"] = "0"


def sweep(cell, seed, rates, seconds, drain_max_s=10.0, log=print):
    from tpubench import cell as C
    from tpubench import serve, traffic

    engine = None
    for rate in rates:
        tf = copy.deepcopy(cell.traffic)
        tf["arrivals"]["rate_per_s"] = rate
        if engine is None:
            _, engine, arrivals = C.setup(dataclasses.replace(cell, traffic=tf),
                                          seed, seconds)
        else:
            arrivals = traffic.schedule(tf, engine.cfg.vocab, seed,
                                        horizon_s=seconds + C.DRAIN_MAX_S)
            C.warm(engine, tf["server"], arrivals)
        w = serve.serve(engine, arrivals, tf["server"], seconds, drain_max_s=drain_max_s)
        out = C.end_to_end(w)
        c = w.counters
        out.update(rate=rate, requests=len(w.in_window()), backlog_open=w.backlog[0],
                   backlog_close=w.backlog[1], drained=w.drained,
                   occupancy_pct=100.0 * c["steps_active"] / max(1, c["decode_steps"] * c["n_slots"]))
        log(json.dumps(out))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax

    from tpubench import spec

    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep.py: no TPU")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(spec.ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sweep(cell, args.seed, args.rates, args.seconds, log=lambda m: print(m, flush=True))


if __name__ == "__main__":
    main()
