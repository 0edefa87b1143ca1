"""Compile a cell's serving programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/tpu/rehearse.py --workload phi4-mini.chat

Lowers the decode-slots program, the 512-token prefill chunk and the slot
install at the cell's real widths, slot count and ``max_seq`` through the
TPU compiler, and prints each program's memory analysis beside the weight and
KV bytes, so a cell that would not fit one chip's 16 GiB is caught before any
chip time. Nothing runs.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["REPRO_AUTOTUNE"] = "0"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from tpubench import spec, weights  # noqa: E402

GiB = 2**30


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", type=int, help="try another slot count")
    ap.add_argument("--max-seq", type=int, help="try another cache length")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    server = dict(cell.traffic["server"])
    if args.slots:
        server["n_slots"] = args.slots
    if args.max_seq:
        server["max_seq"] = args.max_seq

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"  # impl="auto" picks the Pallas kernels

    from repro.infer import Engine

    def placed(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    plain = jax.eval_shape(weights.weights_fn(cell.config), jax.random.PRNGKey(0))
    cfg, params = weights.program_params(placed(plain), cell.config)
    engine = Engine(cfg, params, max_seq=server["max_seq"], fuse=False)
    slots = placed(jax.eval_shape(lambda: engine.init_slots(server["n_slots"])))
    unit = placed(jax.eval_shape(lambda: engine._make_cache(1)))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    b1 = jax.ShapeDtypeStruct((), jnp.bool_, sharding=one)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    chunk = server["prefill_chunk"]
    toks = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one)
    logits1 = jax.ShapeDtypeStruct((1, cfg.vocab), jnp.float32, sharding=one)

    w_bytes = weights.nbytes(plain)
    kv_bytes = weights.nbytes(slots["cache"])
    print(f"{cell.name}: weights {w_bytes / GiB:.3f} GiB, slot KV "
          f"{kv_bytes / GiB:.3f} GiB ({server['n_slots']} slots x "
          f"{server['max_seq']}), batch-1 cache {weights.nbytes(unit) / GiB:.3f} GiB")
    progs = {
        "decode_slots": lambda: engine._scan_decode_slots.lower(
            params, slots, n_steps=server["decode_chunk"]),
        f"prefill_chunk[{chunk}]": lambda: engine._prefill_chunk.lower(
            params, toks, unit, i32, i32),
        "admit": lambda: engine._admit.lower(
            slots, i32, unit, logits1, key, i32, i32, f32, b1),
    }
    worst = 0
    for name, lower in progs.items():
        m = lower().compile().memory_analysis()
        temp = m.temp_size_in_bytes
        worst = max(worst, temp)
        print(f"  {name}: args {m.argument_size_in_bytes / GiB:.3f} GiB, out "
              f"{m.output_size_in_bytes / GiB:.3f} GiB, alias "
              f"{m.alias_size_in_bytes / GiB:.3f} GiB, temp {temp / GiB:.3f} GiB")
    print(f"  weights + slot KV + batch-1 cache + worst temp: "
          f"{(w_bytes + kv_bytes + weights.nbytes(unit) + worst) / GiB:.3f} GiB of 16")


if __name__ == "__main__":
    main()
