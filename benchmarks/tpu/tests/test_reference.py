"""The float32 reference against the program's own float32 forward on the
same packed weights, at a small size: both read the packing and the block
the same way, so they agree to float32 rounding."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from tpubench import reference, spec, weights

from conftest import DATA


def test_reference_matches_the_program_forward_in_f32():
    from repro.kernels.ops import impl_mode
    from repro.models import forward

    config = spec.load_json(f"{DATA}/tiny-config.json")
    plain = weights.make_weights(config, 5)
    cfg, params = weights.program_params(plain, config)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, cfg.vocab))
    with impl_mode("ref"), jax.default_matmul_precision("highest"):
        want, _, _ = forward(cfg, params, tokens=jnp.asarray(tokens))
    rows = np.array([(b, s) for b in range(2) for s in range(64)], np.int32)
    got = reference.logits(config, plain, tokens, rows)
    want = np.asarray(want).reshape(-1, cfg.vocab)
    err = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert err < 1e-4


def test_unpack_reads_lsb_first_sign_planes():
    packed = jnp.zeros((2, 1, 1), jnp.uint8).at[0, 0, 0].set(0b00000101)
    scales = jnp.ones((2, 1, 1), jnp.float32).at[1].set(0.5)
    w = np.asarray(reference.unpack(packed, scales, 8))[:, 0]
    # plane 0: rows 0 and 2 are +1, the rest -1; plane 1 all -1 (x 0.5)
    want = np.array([1, -1, 1, -1, -1, -1, -1, -1], np.float32) - 0.5
    np.testing.assert_array_equal(w, want)


def test_gap_is_zero_for_the_reference_argmax():
    lg = jnp.asarray([[0.0, 2.0, 1.0], [3.0, 1.0, 2.5]])
    np.testing.assert_allclose(reference.gaps(lg, np.array([1, 2])), [0.0, 0.5])
