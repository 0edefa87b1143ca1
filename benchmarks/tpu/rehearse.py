"""Compile a cell's serving programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/tpu/rehearse.py --workload phi4-mini.chat

Lowers the decode-slots program, the 512-token prefill chunk and the slot
install at the cell's real widths, slot count and ``max_seq`` through the
TPU compiler, and prints each program's memory analysis beside the weight and
KV bytes, so a cell that would not fit one chip's 16 GiB is caught before any
chip time. With ``--reference`` it also compiles the weights' one jitted call
and the float32 reference at the check's shape. A cell whose configuration
states tensor parallelism is compiled over a described mesh of that many
chips, and every number is one chip's. Nothing runs.
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
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from tpubench import arch, spec, weights  # noqa: E402
from tpubench import cell as C  # noqa: E402

GiB = 2**30


def chip_bytes(tree) -> int:
    """Bytes of ``tree`` (placed shapes) on one chip."""
    return sum(int(np.prod(x.sharding.shard_shape(x.shape))) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def _abstract_placement(mesh):
    """The program places its weights and caches with ``device_put`` and
    re-orders fused columns with a gather; a rehearsal has shapes, not
    arrays, so both keep the shapes and take the placement."""
    from repro.core.qtensor import QuantizedTensor
    from repro.parallel import tp

    def put(self, tree, specs):
        def one(x, s):
            if isinstance(x, QuantizedTensor):
                return QuantizedTensor(packed=one(x.packed, s.packed),
                                       scales=one(x.scales, s.scales),
                                       g=x.g, k=x.k, o=x.o, fmt=x.fmt)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, s))
        return jax.tree.map(one, tree, specs, is_leaf=lambda x: isinstance(x, QuantizedTensor))

    tp.relayout_fused_for_tp = lambda cfg, params, n: params
    tp.TPContext._put = put


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--slots", type=int, help="try another slot count")
    ap.add_argument("--max-seq", type=int, help="try another cache length")
    ap.add_argument("--reference", action="store_true",
                    help="also compile the weights' call and the reference")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    server = dict(cell.traffic["server"])
    if args.slots:
        server["n_slots"] = args.slots
    if args.max_seq:
        server["max_seq"] = args.max_seq

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # impl="auto" picks the Pallas kernels

    from repro.infer import Engine

    fn = arch.of(cell.config).weights_fn(cell.config)
    plain = jax.eval_shape(fn, jax.random.PRNGKey(0))
    if cell.tp == 1:
        mesh, one = None, SingleDeviceSharding(topo.devices[0])
        every = jax.tree.map(lambda _: one, plain)
    else:
        mesh = Mesh(np.asarray(topo.devices[:cell.tp]), ("model",))
        one = NamedSharding(mesh, P())
        every = weights.shardings(cell.config, mesh, plain)
        _abstract_placement(mesh)
    plain = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                         plain, every)

    def placed(tree, shardings=None):
        shardings = shardings or jax.tree.map(lambda _: one, tree)
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree, shardings)

    cfg, params = weights.program_params(plain, cell.config)
    engine = Engine(cfg, params, max_seq=server["max_seq"], fuse=False, mesh=mesh)
    params = engine.params
    slots = jax.eval_shape(lambda: engine.init_slots(server["n_slots"]))
    unit = jax.eval_shape(lambda: engine._make_cache(1))
    if mesh is None:
        slots, unit = placed(slots), placed(unit)
    else:
        cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                engine._tp.cache_spec_tree(None))
        unit = placed(unit, cache_sh)
        slots = {k: placed(v, cache_sh) if k == "cache" else placed(v)
                 for k, v in slots.items()}
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    b1 = jax.ShapeDtypeStruct((), jnp.bool_, sharding=one)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    chunk = server["prefill_chunk"]
    toks = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one)
    logits1 = jax.ShapeDtypeStruct((1, cfg.vocab), jnp.float32, sharding=one)

    w_bytes, kv_bytes, unit_bytes = chip_bytes(plain), chip_bytes(slots["cache"]), chip_bytes(unit)
    print(f"{cell.name}: per chip of {cell.tp}: weights {w_bytes / GiB:.3f} GiB, slot KV "
          f"{kv_bytes / GiB:.3f} GiB ({server['n_slots']} slots x "
          f"{server['max_seq']}), batch-1 cache {unit_bytes / GiB:.3f} GiB")
    progs = {
        "decode_slots": lambda: engine._scan_decode_slots.lower(
            params, slots, n_steps=server["decode_chunk"]),
        f"prefill_chunk[{chunk}]": lambda: engine._prefill_chunk.lower(
            params, toks, unit, i32, i32),
        "admit": lambda: engine._admit.lower(
            slots, i32, unit, logits1, key, i32, i32, f32, b1),
    }
    if args.reference:
        mc, g = cell.config["model_config"], cell.config["quant"]["g"]
        n_seq, seq_len, n_rows = C.check_shape(cell.traffic)
        fwd = arch.of(cell.config).forward
        ref_toks = jax.ShapeDtypeStruct((n_seq, seq_len), jnp.int32, sharding=one)
        rows = jax.ShapeDtypeStruct((n_rows, 2), jnp.int32, sharding=one)
        prng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        progs["weights"] = lambda: jax.jit(fn, out_shardings=every).lower(prng)

        def ref(control):
            with jax.default_matmul_precision("highest"):
                return jax.jit(lambda w, t, r: fwd(mc, g, control, 512, w, t, r)).lower(
                    plain, ref_toks, rows)
        progs["reference"] = lambda: ref(False)
        progs["control"] = lambda: ref(True)
    worst = 0
    for name, lower in progs.items():
        m = lower().compile().memory_analysis()
        temp = m.temp_size_in_bytes
        if name in ("decode_slots", f"prefill_chunk[{chunk}]", "admit"):
            worst = max(worst, temp)
        print(f"  {name}: args {m.argument_size_in_bytes / GiB:.3f} GiB, out "
              f"{m.output_size_in_bytes / GiB:.3f} GiB, alias "
              f"{m.alias_size_in_bytes / GiB:.3f} GiB, temp {temp / GiB:.3f} GiB", flush=True)
    print(f"  weights + slot KV + batch-1 cache + worst serving temp: "
          f"{(w_bytes + kv_bytes + unit_bytes + worst) / GiB:.3f} GiB of 16 a chip")


if __name__ == "__main__":
    main()
