"""Static contract checker: pass-level units + golden-jaxpr census pins.

The golden tests pin the TP decode step's collective census for
representative configs — the numbers ARE the documented 2L+1 contract
(parallel/tp.py), so a refactor that changes them must change the doc (and
this file) deliberately, never silently.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.staticcheck import census, dtypeflow, lint, transfers, vmem
from repro.analysis.staticcheck.harness import (
    build_cell,
    build_injected_cell,
    expected_collectives,
)
from repro.analysis.staticcheck.jaxpr_walk import walk
from repro.configs import get_config
from repro.kernels import autotune, introspect, ops

needs4 = pytest.mark.needs_multidevice


# ---------------------------------------------------------------------------
# jaxpr walker
# ---------------------------------------------------------------------------


def test_walk_scan_multiplier():
    def f(x):
        def body(c, _):
            return c + jnp.sin(c), None

        c, _ = jax.lax.scan(body, x, None, length=7)
        return c

    sites = list(walk(jax.make_jaxpr(f)(jnp.ones((2,)))))
    sin_sites = [s for s in sites if s.prim == "sin"]
    assert len(sin_sites) == 1
    assert sin_sites[0].repeats == 7
    assert "scan" in sin_sites[0].stack


def test_walk_does_not_descend_pallas():
    from repro.kernels.bcq_mm import bcq_mm
    from repro.core.packing import pack_signs

    rng = np.random.default_rng(0)
    signs = np.where(rng.standard_normal((1, 128, 128)) > 0, 1, -1).astype(np.int8)
    packed = pack_signs(signs)
    scales = jnp.ones((1, 1, 128), jnp.float32)
    x = jnp.ones((8, 128), jnp.float32)
    closed = jax.make_jaxpr(
        lambda x: bcq_mm(x, packed, scales, g=128, block_k=128, block_o=128,
                         interpret=True)
    )(x)
    prims = {s.prim for s in walk(closed)}
    assert "pallas_call" in prims
    # kernel-body prims (the unpack shift/and) must NOT leak into the walk
    inner = {s.prim for s in walk(closed, descend_pallas=True)}
    assert inner - prims  # descending finds strictly more


# ---------------------------------------------------------------------------
# golden census pins (struct-traced, full-size configs)
# ---------------------------------------------------------------------------

# (arch, L) → pinned 2L+1. Changing a number here means the TP communication
# topology changed: update parallel/tp.py's docs in the same commit.
GOLDEN = {
    "llama3.2-3b": 57,  # 28 blocks
    "phi4-mini-3.8b": 65,  # 32 blocks
    "musicgen-medium": 97,  # 48 blocks
}


@needs4
@pytest.mark.parametrize("arch,pinned", sorted(GOLDEN.items()))
@pytest.mark.parametrize("fmt", ["dense", "bcq", "uniform"])
@pytest.mark.parametrize("tp", [1, 2, 4])
def test_golden_census(arch, pinned, fmt, tp):
    cell = build_cell(arch, fmt, tp)
    assert cell.expected_collectives == pinned
    assert expected_collectives(get_config(arch)) == pinned
    assert census.census_cell(cell) == []


@needs4
def test_census_catches_injected_weight_gather():
    cell = build_injected_cell("llama3.2-3b", "bcq", 2)
    violations = census.census_cell(cell)
    big = [v for v in violations if "weight/cache-shaped" in v.message]
    assert big, "injected weight all_gather was not caught"
    # provenance names the offending leaf and the gather's source line
    assert "packed" in big[0].message
    assert "all_gather at" in big[0].message
    # and the count check trips too (one extra collective)
    assert any("collective count" in v.message for v in violations)


@needs4
def test_census_skips_name_unsupported_archs():
    from repro.analysis.staticcheck.harness import build_cells

    cells, skips = build_cells(archs=["olmoe-1b-7b", "xlstm-125m"], fmts=["bcq"], tps=[2])
    assert cells == []
    assert len(skips) == 2
    assert all("tp2" in s for s in skips)


# ---------------------------------------------------------------------------
# dtype-flow
# ---------------------------------------------------------------------------


@needs4
def test_dtypeflow_deploy_clean_ref_dirty():
    from repro.analysis.staticcheck.harness import _build_tp_pieces, _step_fn

    cell = build_cell("llama3.2-3b", "bcq", 2)
    assert dtypeflow.analyze(cell.closed, cell.cell_id, cell.shape_index) == []

    cfg, tpc, structs, cache, tok, pos = _build_tp_pieces("llama3.2-3b", "bcq", 2)
    with ops.impl_mode("ref"):
        closed = jax.make_jaxpr(_step_fn(cfg, tpc))(structs, cache, tok, pos)
    violations = dtypeflow.analyze(closed, "ref", cell.shape_index)
    assert violations, "ref-mode dequantize must be flagged"
    assert "packed" in violations[0].message
    assert "convert_element_type" in violations[0].message


def test_dtypeflow_simple_program():
    # uint8 source flowing to float through plain ops is flagged with source
    def bad(p):
        return p.astype(jnp.float32).sum()

    closed = jax.make_jaxpr(bad)(jax.ShapeDtypeStruct((3, 16, 8), jnp.uint8))
    vs = dtypeflow.analyze(closed, "unit", {(3, 16, 8): "w.packed"})
    assert len(vs) == 1 and "w.packed" in vs[0].message

    # integer-only flow is clean
    def good(p):
        return (p >> 1).sum()

    closed = jax.make_jaxpr(good)(jax.ShapeDtypeStruct((3, 16, 8), jnp.uint8))
    assert dtypeflow.analyze(closed, "unit", {}) == []


def test_dtypeflow_scan_carry_fixpoint():
    # taint entering a scan carry on iteration 2+ still flags the body cast
    def f(p):
        def body(c, _):
            return c + 1, c.astype(jnp.float32)

        _, ys = jax.lax.scan(body, p, None, length=3)
        return ys

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.uint8))
    assert dtypeflow.analyze(closed, "unit", {}) != []


# ---------------------------------------------------------------------------
# transfers
# ---------------------------------------------------------------------------


@needs4
def test_transfer_pass_clean_and_catches_debug_print():
    cell = build_cell("llama3.2-3b", "bcq", 2)
    assert transfers.transfer_violations(cell) == []

    def noisy(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    bad = type(cell)(
        cell_id="unit", arch="-", fmt="-", tp=1,
        closed=jax.make_jaxpr(noisy)(jnp.ones((2,))),
        expected_collectives=0, shape_index={},
    )
    vs = transfers.transfer_violations(bad)
    assert vs and "host-transfer" in vs[0].message


def test_trace_once_harness():
    n, vs = transfers.trace_once_check(fmts=("dense",))
    assert n == 1 and vs == []


# ---------------------------------------------------------------------------
# vmem: estimators + table validation
# ---------------------------------------------------------------------------


def test_vmem_estimators_registered():
    assert set(introspect.known_impls()) >= {
        "bcq_mm", "lutgemm", "uniform_mm", "dequant_mm", "codebook_mm",
        "ternary_mm",
    }
    for impl in introspect.known_impls():
        small = introspect.vmem_bytes(impl, B=8, block_k=128, block_o=128, q=3, g=128)
        big = introspect.vmem_bytes(impl, B=8, block_k=1024, block_o=512, q=3, g=128)
        assert 0 < small < big
    assert introspect.fits_budget("bcq_mm", B=8, block_k=512, block_o=256, q=3, g=128)
    assert not introspect.fits_budget("bcq_mm", B=8, block_k=8192, block_o=2048, q=8, g=128)


def test_autotune_validate_entry_errors():
    ok = autotune.validate_entry("bcq_mm/cpu-interpret/B8/k768/o256/q3/g96", [768, 128])
    assert ok == (768, 128)
    with pytest.raises(ValueError, match="tiling contract"):  # not lane-aligned
        autotune.validate_entry("bcq_mm/cpu-interpret/B8/k768/o256/q3/g96", [768, 64])
    with pytest.raises(ValueError, match="tiling contract"):  # 4 scale rows
        autotune.validate_entry("bcq_mm/tpu/B8/k1024/o256/q3/g128", [512, 256])
    with pytest.raises(ValueError, match="expected impl/backend"):
        autotune.validate_entry("bcq_mm/cpu/B8/k768", [512, 256])
    with pytest.raises(ValueError, match="not g<int>"):
        autotune.validate_entry("bcq_mm/cpu/B8/k768/o256/q3/gX", [512, 256])
    with pytest.raises(ValueError, match="tiling contract"):
        autotune.validate_entry("bcq_mm/cpu/B8/k768/o256/q3/g96", [500, 256])
    with pytest.raises(ValueError, match="pair of positive ints"):
        autotune.validate_entry("bcq_mm/cpu/B8/k768/o256/q3/g96", [768])
    with pytest.raises(ValueError, match="VMEM|budget"):
        autotune.validate_entry("bcq_mm/tpu/B8/k8192/o4096/q8/g8192", [8192, 2048])
    # interpret backends skip the budget check (no VMEM to blow)
    autotune.validate_entry(
        "bcq_mm/cpu-interpret/B8/k8192/o4096/q8/g8192", [8192, 2048]
    )
    # unknown impls skip the budget check but not divisibility
    autotune.validate_entry("future_mm/tpu/B8/k8192/o4096/q8/g8192", [8192, 2048])


def test_autotune_rejects_corrupt_table(tmp_path, monkeypatch):
    bad = tmp_path / "autotune.json"
    bad.write_text("{not json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(bad))
    autotune.clear_cache()
    with pytest.raises(ValueError, match="not valid JSON"):
        autotune._ensure_persisted_loaded()
    bad.write_text(json.dumps({"bcq_mm/cpu/B8/k768/o256/q3/g96": [500, 256]}))
    autotune.clear_cache()
    with pytest.raises(ValueError, match="tiling contract"):
        autotune._ensure_persisted_loaded()
    autotune.clear_cache()


def test_checked_in_table_validates():
    table = autotune._load_table(autotune._TABLE_PATH)
    assert table  # the defaults ship non-empty
    autotune.validate_table(table, path=autotune._TABLE_PATH)


def test_vmem_pass_runs_clean():
    res = vmem.run(archs=["llama3.2-3b"], tps=(1, 2))
    assert res.ok, [str(v) for v in res.violations]
    assert res.checked > 0


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------


def _hits(source, relpath="infer/x.py", rule=None):
    vs = lint.lint_source(source, relpath)
    if rule:
        vs = [v for v in vs if v.passname == f"lint/{rule}"]
    return vs


def test_lint_no_item():
    assert _hits("y = x.item()\n", rule="no-item")
    # no pragma escape for .item()
    assert _hits("y = x.item()  # staticcheck: host-sync(x)\n", rule="no-item")


def test_lint_host_sync_pragma():
    assert _hits("import numpy as np\ny = np.asarray(x)\n", rule="host-sync")
    assert not _hits(
        "import numpy as np\ny = np.asarray(x)  # staticcheck: host-sync(final fetch)\n",
        rule="host-sync",
    )
    assert _hits("v = float(f(x))\n", rule="host-sync")
    assert not _hits("v = float(x)\n", rule="host-sync")  # Name arg: host scalar
    # jnp.asarray is a device put, not a sync
    assert not _hits("import jax.numpy as jnp\ny = jnp.asarray(x)\n", rule="host-sync")
    # out-of-scope dirs are not linted for host syncs
    assert not _hits("import numpy as np\ny = np.asarray(x)\n", relpath="analysis/x.py",
                     rule="host-sync")


def test_lint_raw_shard_map():
    src = "from jax.experimental.shard_map import shard_map\n"
    assert _hits(src, relpath="infer/x.py", rule="raw-shard-map")
    assert not _hits(src, relpath="parallel/compat.py", rule="raw-shard-map")


def test_lint_bare_jit():
    assert _hits("import jax\nf = jax.jit(g)\n", rule="bare-jit")
    assert not _hits("import jax\nf = jax.jit(g, static_argnames=('n',))\n",
                     rule="bare-jit")
    assert not _hits(
        "import jax\nf = jax.jit(g)  # staticcheck: jit-ok(nothing static)\n",
        rule="bare-jit",
    )


def test_lint_repo_is_clean():
    res = lint.run()
    assert res.ok, "\n".join(str(v) for v in res.violations)
    assert res.checked > 50
