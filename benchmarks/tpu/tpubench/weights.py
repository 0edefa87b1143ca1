"""Packed BCQ weights made on the device from the run's seed.

A deployment loads a quantized checkpoint; it does not run the BCQ solver. So
the benchmark fills the packed leaves directly: sign planes uniform at random
and per-group scales of the magnitude the repo's alternating solver gives on
Gaussian weights of std ``1/sqrt(k)`` (measured with ``core.bcq.quantize_bcq``
at q=3, g=128, 4 iterations: plane means 0.973, 0.565, 0.282 and standard
deviations 0.074, 0.057, 0.037, in units of ``1/sqrt(k)``). Sign planes are
packed 8 to a byte along k, least significant bit first.

The weights live in a plain dict that belongs to the benchmark (the layout the
float32 reference reads); which leaves it holds, and how the program's pytree
wraps the same buffers, is the configuration's architecture module's
(``tpubench.arch``). A configuration that states tensor parallelism has them
made already split over the chips, as its architecture's ``SPLIT`` says, so
no chip ever holds a whole stacked matrix; threefry is partitionable, so the
values do not depend on the layout.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from tpubench import arch

SCALE_MEAN = (0.973, 0.565, 0.282)  # per plane, x 1/sqrt(k)
SCALE_STD = (0.074, 0.057, 0.037)
EMBED_STD = 0.02


def jax_key(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole-number seed (also past 32 bits)."""
    state = np.random.SeedSequence([int(seed), stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(state) & 0x7FFFFFFF)


def _bcq_leaf(key, lead: Tuple[int, ...], k: int, o: int, q: int, g: int, sdt):
    def one(kk):
        kb, ks = jax.random.split(kk)
        packed = jax.random.bits(kb, (q, k // 8, o), jnp.uint8)
        z = jax.random.normal(ks, (q, k // g, o), jnp.float32)
        mean = jnp.asarray(SCALE_MEAN[:q], jnp.float32)[:, None, None]
        std = jnp.asarray(SCALE_STD[:q], jnp.float32)[:, None, None]
        scales = jnp.abs(mean + std * z) / np.sqrt(k)
        return packed, scales.astype(sdt)

    if not lead:
        packed, scales = one(key)
    else:
        packed, scales = jax.lax.map(one, jax.random.split(key, lead[0]))
    return {"packed": packed, "scales": scales}


def mesh(tp: int):
    """The program's ``tp``-way tensor-parallel mesh over the first ``tp``
    devices, or None for one chip."""
    if tp == 1:
        return None
    from repro.parallel.tp import make_tp_mesh

    return make_tp_mesh(tp)


def shardings(config: dict, mesh, tree) -> dict:
    """Where each leaf of the weights ``tree`` lives on ``mesh``: a linear's
    packed planes and scales split along the output dim (column-parallel) or
    the reduction dim (row-parallel), every other leaf whole on each chip."""
    mod, axis = arch.of(config), mesh.axis_names[0]

    def visit(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        dims = [None] * leaf.ndim
        if keys[-1] in ("packed", "scales"):
            dims[-1 if arch.split(mod, keys[-2]) == "col" else -2] = axis
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(visit, tree)


def make_weights(config: dict, seed: int, mesh=None) -> dict:
    """The whole model's weights, in one jitted call: on the default device,
    or split over ``mesh``."""
    fn, key = arch.of(config).weights_fn(config), jax_key(seed, 0)
    if mesh is None:
        return jax.jit(fn)(key)
    out = shardings(config, mesh, jax.eval_shape(fn, key))
    return jax.jit(fn, out_shardings=out)(key)


def to_host(tree, names):
    """``tree`` with the leaves under the keys ``names`` copied to host
    memory (JAX's CPU backend), all in one transfer."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    pick = [any(getattr(p, "key", None) in names for p in path) for path, _ in flat]
    moved = iter(jax.device_put(jax.device_get([x for (_, x), m in zip(flat, pick) if m]),
                                jax.devices("cpu")[0]))
    return jax.tree_util.tree_unflatten(treedef, [next(moved) if m else x
                                                  for (_, x), m in zip(flat, pick)])


def program_params(weights: dict, config: dict):
    """``(cfg, params)``: the configuration's own ``program_params``."""
    return arch.of(config).program_params(weights, config)


def nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))
