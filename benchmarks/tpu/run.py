"""Run one cell of the on-chip serving benchmark and print its result.

    python3 benchmarks/tpu/run.py --workload phi4-mini.chat --seed 7 \\
        --seconds 30 --trace 0

The cell (configuration, traffic, metrics) is looked up by name in
``BENCHMARK.json``. Set-up makes the weights on the chip from ``--seed``,
builds the engine and compiles every program the window drives; the window
then serves open-loop traffic for ``--seconds`` through the scheduler; the
served tokens are checked against the float32 reference. ``--trace 0`` prints
the end-to-end metrics, ``--trace 1`` profiles the window and prints the
per-layer ones. The last line of standard output is the result as JSON; the
last lines of standard error are the numbers compared and their limits.

It refuses to run (exit 2, no result) without a TPU or with fewer chips than
the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ["REPRO_AUTOTUNE"] = "0"  # block sizes from the tiling rule, no sweep
os.environ.pop("REPRO_AUTOTUNE_CACHE", None)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"run.py: no program under {ROOT}/src to serve", file=sys.stderr)
        return 2
    from tpubench import spec

    cell = spec.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: cell {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    # small programs too: the per-length slicing ops the admission compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from tpubench.cell import run_cell

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
                   device=devices[0], log=lambda m: print(m, file=sys.stderr, flush=True))
    for name, c in out["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
