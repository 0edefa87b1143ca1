"""Readings that a cell's correctness limit is set from, for many seeds in
one process.

    python3 benchmarks/tpu/calibrate.py --workload phi4-mini.chat \\
        --seconds 10 --seeds 1 2 3 4 5 6 7 8 9 10 11 12

For each seed: weights and traffic from the seed, a short window at the
cell's own load, the same sample of finished requests a run checks, and two
readings against the float32 reference: the widest gap of the served tokens
(the program, the lower reading) and of the tokens a float8 forward puts
first at the same positions (the control, the upper reading). One line of
JSON per seed; the engine's compiled programs are reused across seeds. The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))
os.environ["REPRO_AUTOTUNE"] = "0"


def calibrate(cell, seeds, seconds, log=print):
    from tpubench import cell as C
    from tpubench import reference, serve, weights

    tf = cell.traffic
    engine = None
    for seed in seeds:
        t = time.perf_counter()
        plain, engine, arrivals = C.setup(cell, seed, seconds, engine)
        w = serve.serve(engine, arrivals, tf["server"], seconds,
                        drain_max_s=C.DRAIN_MAX_S)
        engine.params = None  # free this seed's weights before the next
        sample = C.sample_finished(w, tf["check"], seed)
        seqs = [(C._prompt(arrivals, w, r), r.tokens) for r in sample]
        if plain is None:  # a tensor-parallel engine held the only copy
            plain = weights.make_weights(cell.config, seed, engine.mesh)
        got = reference.compare(cell.config, plain, seqs, C.check_shape(tf), control=True)
        got.update(seed=seed, seconds=time.perf_counter() - t,
                   failed=sum(1 for r in w.in_window() if r.first is None),
                   wrong_length=sum(1 for r in w.records if r.state == "finished"
                                    and len(r.tokens) != r.max_new))
        log(json.dumps(got))
        del plain


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    import jax

    from tpubench import spec

    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate.py: no TPU")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(spec.ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    calibrate(cell, args.seeds, args.seconds, log=lambda m: print(m, flush=True))


if __name__ == "__main__":
    main()
