"""Compile the serving path's Pallas kernels for a TPU v5e at Phi-4-mini widths.

Nothing runs: each case lowers ``qmatmul`` through the production block rule
(``kernels/autotune.py``) and compiles it with the TPU compiler for a chip
described by ``jax.experimental.topologies``. That finds what interpret mode
cannot: blocks Mosaic does not tile, casts and gathers it does not lower, and
schedules over the VMEM limit. The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only the
worker given this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import get_format
from repro.kernels.ops import qmatmul

Q, G = 3, 128  # the paper's BCQ setting
DECODE_B, PREFILL_B = 8, 512  # 8 slots; one 512-token prompt's rows

# Phi-4-mini (d_model 3072, 24/8 heads of 128, d_ff 8192, vocab 200064), with
# the engine's fused QKV and gate-up projections: (name, k, out_dims)
PHI4_MINI = [
    ("wqkv", 3072, (3072, 1024, 1024)),
    ("wo", 3072, (3072,)),
    ("w_gate_up", 3072, (8192, 8192)),
    ("w_down", 8192, (3072,)),
    ("lm_head", 3072, (200064,)),
]
# the per-chip shards under --tp 4 that leave the common tiles: wo's local
# k=768 has 6 scale rows (block_k must be all of k), the lm_head shard's
# o=50016 is no multiple of 128 (padded per call)
PHI4_MINI_TP4 = [
    ("wo/tp4", 768, (3072,)),
    ("lm_head/tp4", 3072, (50016,)),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fmt, impl, B, k, out_dims):
    f = get_format(fmt)
    qt = f.struct((), k, sum(out_dims), Q, G, jnp.bfloat16)
    qt = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), qt
    )
    x = jax.ShapeDtypeStruct((B, k), jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(
        lambda x, qt: qmatmul(fmt, x, qt, out_dims, impl=impl, interpret=False)
    )
    compiled = fn.lower(x, qt).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is there
    return compiled


@pytest.mark.parametrize("B", [DECODE_B, PREFILL_B], ids=["decode", "prefill"])
@pytest.mark.parametrize(
    "name,k,out_dims", PHI4_MINI + PHI4_MINI_TP4,
    ids=[c[0] for c in PHI4_MINI + PHI4_MINI_TP4],
)
def test_bcq_mm_compiles_for_v5e(one_chip, name, k, out_dims, B):
    _compile(one_chip, "bcq", "bcq_mm", B, k, out_dims)


@pytest.mark.parametrize(
    "fmt,impl", [("uniform", "uniform_mm"), ("ternary", "ternary_mm"),
                 ("dequant", "dequant_mm")]
)
def test_format_kernel_compiles_for_v5e(one_chip, fmt, impl):
    _compile(one_chip, fmt, impl, DECODE_B, 3072, (8192, 8192))


@pytest.mark.xfail(
    strict=True,
    raises=NotImplementedError,
    reason="Mosaic refuses the 3-D take_along_axis LUT retrieve: "
    "'Only 2D gather is supported'",
)
@pytest.mark.parametrize(
    "fmt,impl", [("bcq", "lutgemm"), ("codebook", "codebook_mm")]
)
def test_lut_kernel_compiles_for_v5e(one_chip, fmt, impl):
    _compile(one_chip, fmt, impl, DECODE_B, 3072, (8192, 8192))
