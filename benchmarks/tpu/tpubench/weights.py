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
(``tpubench.arch``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


def make_weights(config: dict, seed: int) -> dict:
    """The whole model's weights, in one jitted call on the default device."""
    return jax.jit(arch.of(config).weights_fn(config))(jax_key(seed, 0))


def program_params(weights: dict, config: dict):
    """``(cfg, params)``: the configuration's own ``program_params``."""
    return arch.of(config).program_params(weights, config)


def nbytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))
