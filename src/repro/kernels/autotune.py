"""Measured (block_k, block_o) autotuner for the BCQ Pallas kernels.

The first version of ``ops.quantized_matmul`` hardcoded ``(512, 256, 128, 64)``
preference-ordered block candidates — a single schedule for every shape, batch
and kernel variant. But the best tiling is shape-dependent: decode (B=1) wants
wide output blocks to amortise the activation fetch, GQA K/V projections have
small output dims, and the LUT kernel's VMEM budget (a ``(B, C, 256)`` table
per k-block) caps ``block_k`` differently from the unpack kernel. FLUTE
(Guo et al., 2024) makes the same point for GPU LUT kernels.

Resolution order for a ``(B, k, o, q, g, impl, backend)`` query:

1. **in-process cache** — one dict lookup after the first query;
2. **persisted JSON tables** — the checked-in defaults
   (``autotune_table.json`` next to this module) and, only when
   ``$REPRO_AUTOTUNE_CACHE`` names one, a user table;
3. **measurement** — unless ``REPRO_AUTOTUNE=0``, sweep the valid candidate
   grid with synthetic inputs, pick the fastest, and persist the winner to the
   user table when one is named;
4. **heuristic fallback** — the largest valid blocks whose VMEM estimate fits
   the budget. This is also the no-measurement answer for shapes the tables
   don't know.

Every source answers inside ONE tiling rule (:func:`candidate_blocks`): the
blocks Mosaic accepts. A BlockSpec's last two dims must be multiples of
(8, 128) or span the whole array, so ``block_o`` is a multiple of 128 (or all
of ``o``) and the scales block's ``block_k // g`` rows are a multiple of 8 (or
all ``k / g`` rows). Table entries outside the rule are refused at load, and
interpret-mode runs use the same tiles the chip compiles.

Keys deliberately include the backend (``cpu``/``tpu``/… plus ``-interpret``)
so CPU interpret-mode timings can never masquerade as TPU schedules. The
``impl`` axis spans every registered quantization format's kernels
(``bcq_mm``/``lutgemm``/``uniform_mm``/… — formats register their kernels
for measurement via :func:`register_measure_kernel`, DESIGN.md §2.4), so
per-format winners never collide.

Reproducibility note: ``block_k`` partitions the f32 accumulation, so two
hosts that measure different winners can produce bitwise-different logits
(same math, different reduction split). For cross-host bit-reproducibility
set ``REPRO_AUTOTUNE=0`` — the heuristic/table path is fully deterministic —
or ship a pinned table via ``REPRO_AUTOTUNE_CACHE``. The test suite pins
``REPRO_AUTOTUNE=0`` for exactly this reason (tests/conftest.py).
"""

from __future__ import annotations

import functools
import json
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_CANDIDATE_K = (1024, 512, 256, 128)
_CANDIDATE_O = (512, 256, 128)
_SUBLANE = 8  # second-minor tile of a Mosaic block
_LANE = 128  # minor tile of a Mosaic block

_TABLE_PATH = os.path.join(os.path.dirname(__file__), "autotune_table.json")

# in-process winners: key -> (block_k, block_o)
_cache: Dict[str, Tuple[int, int]] = {}
_persisted_loaded = False


def _user_cache_path() -> Optional[str]:
    """The user table named by ``$REPRO_AUTOTUNE_CACHE``, or None (no user
    table: nothing outside the checked-in defaults is read or written)."""
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or None


def measurement_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def make_key(B: int, k: int, o: int, q: int, g: int, impl: str, backend: str) -> str:
    return f"{impl}/{backend}/B{B}/k{k}/o{o}/q{q}/g{g}"


def backend_tag(interpret: bool) -> str:
    tag = jax.default_backend()
    return f"{tag}-interpret" if interpret else tag


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------


def parse_key(key: str) -> Tuple[str, str, int, int, int, int, int]:
    """``make_key`` output → ``(impl, backend, B, k, o, q, g)``.

    Raises ``ValueError`` naming the key on any malformed segment — table
    entries that cannot be parsed cannot be trusted to describe a schedule.
    """
    parts = key.split("/")
    if len(parts) != 7:
        raise ValueError(
            f"autotune key {key!r}: expected impl/backend/B*/k*/o*/q*/g*"
        )
    impl, backend = parts[0], parts[1]
    nums = []
    for tag, seg in zip("Bkoqg", parts[2:]):
        if not seg.startswith(tag) or not seg[len(tag):].isdigit():
            raise ValueError(
                f"autotune key {key!r}: segment {seg!r} is not {tag}<int>"
            )
        nums.append(int(seg[len(tag):]))
    if not impl or not backend:
        raise ValueError(f"autotune key {key!r}: empty impl/backend segment")
    return (impl, backend, *nums)


def validate_entry(key: str, blocks, *, path: str = "<in-memory>") -> Tuple[int, int]:
    """One table entry → validated ``(block_k, block_o)``; loud on any lie.

    Checks, in order: key parses; blocks is a pair of positive ints; the
    blocks satisfy the tiling rule (``_valid_bk`` + ``_valid_bo``); and —
    for real-hardware backends only (interpret-mode entries have no VMEM) —
    the impl's registered per-grid-step estimate
    fits :func:`repro.kernels.introspect.vmem_budget`. Impls with no
    registered estimator skip the budget check (unpriceable ≠ invalid).
    """
    impl, backend, B, k, o, q, g = parse_key(key)
    if (
        not isinstance(blocks, (list, tuple))
        or len(blocks) != 2
        or not all(isinstance(b, int) and b > 0 for b in blocks)
    ):
        raise ValueError(
            f"autotune table {path}: entry {key!r} blocks {blocks!r} "
            "must be a [block_k, block_o] pair of positive ints"
        )
    bk, bo = blocks
    if not _valid_bk(bk, k, g) or not _valid_bo(bo, o):
        raise ValueError(
            f"autotune table {path}: entry {key!r} blocks ({bk}, {bo}) violate "
            f"the tiling contract (k={k} % block_k == 0, block_k % g == 0 or "
            f"g % block_k == 0 with g={g}, scales rows block_k//g a multiple "
            f"of {_SUBLANE} or all k/g; o={o} % block_o == 0 with block_o a "
            f"multiple of {_LANE} or all of o)"
        )
    if not backend.endswith("-interpret"):
        from repro.kernels import introspect

        try:
            need = introspect.vmem_bytes(impl, B=B, block_k=bk, block_o=bo, q=q, g=g)
        except KeyError:
            return bk, bo
        budget = introspect.vmem_budget()
        if need > budget:
            raise ValueError(
                f"autotune table {path}: entry {key!r} blocks ({bk}, {bo}) "
                f"need ~{need} B of VMEM per grid step, over the "
                f"{budget} B budget ({introspect.VMEM_BYTES} B/core x "
                f"{introspect.VMEM_SLACK} slack) — re-measure with smaller blocks"
            )
    return bk, bo


def validate_table(table: Dict[str, Tuple[int, int]], *, path: str) -> None:
    for key, blocks in table.items():
        validate_entry(key, blocks, path=path)


def _load_table(path: str) -> Dict[str, Tuple[int, int]]:
    """Read one persisted table. Missing file → empty (tables are optional);
    unparseable JSON → loud ``ValueError`` naming the file (a corrupt table
    silently dropped would re-measure — or worse, heuristically guess —
    schedules the operator thinks are pinned)."""
    try:
        with open(path) as f:
            raw = json.load(f)
    except OSError:
        return {}
    except ValueError as e:
        raise ValueError(f"autotune table {path} is not valid JSON: {e}") from e
    if not isinstance(raw, dict):
        raise ValueError(f"autotune table {path}: top level must be an object")
    return {k: tuple(v) if isinstance(v, list) else v for k, v in raw.items()}


def _ensure_persisted_loaded() -> None:
    global _persisted_loaded
    if _persisted_loaded:
        return
    # a named user table wins over checked-in defaults: it was measured there
    merged = _load_table(_TABLE_PATH)
    validate_table(merged, path=_TABLE_PATH)
    user_path = _user_cache_path()
    if user_path is not None:
        user = _load_table(user_path)
        validate_table(user, path=user_path)
        merged.update(user)
    for key, blocks in merged.items():
        _cache.setdefault(key, blocks)
    _persisted_loaded = True


def _persist(key: str, blocks: Tuple[int, int]) -> None:
    path = _user_cache_path()
    if path is None:
        return  # no user table named: the in-process cache holds the winner
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    try:
        table = _load_table(path)
    except ValueError:
        table = {}  # corrupt user cache: start over rather than refuse to tune
    table[key] = blocks
    with open(path, "w") as f:
        json.dump({k: list(v) for k, v in sorted(table.items())}, f, indent=1)


def clear_cache() -> None:
    """Drop in-process state (tests; does not touch persisted files)."""
    global _persisted_loaded
    _cache.clear()
    _persisted_loaded = False


# ---------------------------------------------------------------------------
# candidates + heuristic
# ---------------------------------------------------------------------------


def _valid_bk(c: int, k: int, g: int) -> bool:
    """``block_k = c`` divides k, composes with g, and gives a scales block
    Mosaic accepts: ``c // g`` rows (1 when a group spans blocks) that are a
    multiple of the sublane tile or the whole ``k / g`` axis."""
    if k % c or not (c % g == 0 or g % c == 0):
        return False
    rows = max(c // g, 1)
    return rows % _SUBLANE == 0 or rows == k // g


def _valid_bo(c: int, o: int) -> bool:
    """``block_o = c`` divides o and is lane-aligned or the whole of o."""
    return o % c == 0 and (c % _LANE == 0 or c == o)


def padded_o(o: int) -> int:
    """The output dim the Pallas wrappers dispatch: o itself when a candidate
    block divides it, else o rounded up to the lane width."""
    if any(_valid_bo(c, o) for c in _CANDIDATE_O):
        return o
    return o + (-o % _LANE)


def candidate_blocks(k: int, o: int, g: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The tiling rule: valid (block_k, block_o) axes, largest first, for a
    padded (k, o, g). When no candidate block_k is valid (e.g. k=768 at
    g=128: 6 scale rows), the whole k is, since it spans all k/g rows."""
    bks = tuple(c for c in _CANDIDATE_K if _valid_bk(c, k, g)) or (k,)
    bos = tuple(c for c in _CANDIDATE_O if _valid_bo(c, o))
    return bks, bos


def _fits(impl: Optional[str], B: int, bk: int, bo: int, q: int, g: int) -> bool:
    from repro.kernels import introspect

    try:
        return introspect.fits_budget(impl, B=B, block_k=bk, block_o=bo, q=q, g=g)
    except KeyError:
        return True  # no estimator registered: unpriceable, not refused


def heuristic_blocks(
    k: int, o: int, g: int, *, B: int = 8, q: int = 1, impl: Optional[str] = None
) -> Tuple[int, int]:
    """The largest valid pair (block_k first) whose VMEM estimate fits the
    budget; the smallest valid pair when none does; ``(0, 0)`` when o has no
    valid block."""
    bks, bos = candidate_blocks(k, o, g)
    if not bos:
        return 0, 0
    pairs = [(bk, bo) for bk in bks for bo in bos]
    return next((p for p in pairs if _fits(impl, B, *p, q, g)), pairs[-1])


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------

# impl name -> (kernel loader, synthetic-scales maker). Formats register their
# Pallas kernels here (core/formats.py) so the measurement sweep covers every
# registered format's schedule space; the impl name is also the table-key axis
# that keeps per-format winners from colliding.
_MEASURE_KERNELS: Dict[str, tuple] = {}


def register_measure_kernel(impl: str, loader, make_scales) -> None:
    """Make ``impl`` measurable: ``loader()`` returns the kernel fn (lazy so
    registration never forces a kernel import); ``make_scales(rng, q, k, o, g)``
    returns that format's synthetic scales array."""
    _MEASURE_KERNELS[impl] = (loader, make_scales)


def _load_bcq_mm():
    from repro.kernels.bcq_mm import bcq_mm

    return bcq_mm


def _load_lutgemm():
    from repro.kernels.lutgemm import lutgemm

    return lutgemm


def _bcq_meas_scales(rng, q, k, o, g):
    return rng.standard_normal((q, k // g, o))


register_measure_kernel("bcq_mm", _load_bcq_mm, _bcq_meas_scales)
register_measure_kernel("lutgemm", _load_lutgemm, _bcq_meas_scales)


def _time_once(fn, *args) -> float:
    out = fn(*args)  # warmup: compile/trace
    jax.block_until_ready(out)  # staticcheck: host-sync(wall-clock timing sweep)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)  # staticcheck: host-sync(wall-clock timing sweep)
        best = min(best, time.perf_counter() - t0)
    return best


def _measure(
    B: int, k: int, o: int, q: int, g: int, impl: str, interpret: bool
) -> Optional[Tuple[int, int]]:
    """Sweep the candidate grid on synthetic inputs; return the fastest pair.

    INVARIANT: the swept inputs are freshly-created *concrete* arrays (never
    the caller's, which may be tracers — get_blocks runs inside jit traces of
    the model). Concrete inputs keep the sweep executing eagerly on device at
    trace time: real wall-clock timings, nothing staged into the outer jaxpr
    (verified: outer computation stays at its 3-eqn dispatch regardless of
    sweep size). Do not thread caller arrays into here.
    """
    entry = _MEASURE_KERNELS.get(impl)
    if entry is None:
        return None  # unknown impl: caller falls through to the heuristic
    bks, bos = candidate_blocks(k, o, g)
    # keep the sweep bounded: the 3 largest of each axis cover the useful
    # range; on hardware, only pairs the VMEM budget admits
    pairs = [
        (bk, bo)
        for bk in bks[:3]
        for bo in bos[:3]
        if interpret or _fits(impl, B, bk, bo, q, g)
    ]
    if not pairs:
        return None
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, k)), jnp.float32)
    packed = jnp.asarray(rng.integers(0, 256, (q, k // 8, o)), jnp.uint8)
    scales = jnp.asarray(entry[1](rng, q, k, o, g), jnp.float32)
    fn = entry[0]()

    # every pair obeys the tiling rule, so a candidate that fails to compile
    # is a kernel bug: it raises instead of being skipped in silence
    best, best_t = None, float("inf")
    for bk, bo in pairs:
        t = _time_once(
            functools.partial(fn, g=g, block_k=bk, block_o=bo, interpret=interpret),
            x,
            packed,
            scales,
        )
        if t < best_t:
            best, best_t = (bk, bo), t
    return best


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def get_blocks(
    *,
    B: int,
    k: int,
    o: int,
    q: int,
    g: int,
    impl: str,
    interpret: bool,
    allow_measure: Optional[bool] = None,
) -> Tuple[int, int]:
    """Best known (block_k, block_o) for a padded kernel shape.

    Never raises on unknown shapes: resolution falls through cache → tables →
    measurement (when enabled) → the heuristic. Returns ``(0, 0)`` only when
    no valid tiling exists at all (caller decides how to pad or fail).
    """
    _ensure_persisted_loaded()
    backend = backend_tag(interpret)
    key = make_key(B, k, o, q, g, impl, backend)
    hit = _cache.get(key)
    if hit is not None and _valid_bk(hit[0], k, g) and _valid_bo(hit[1], o):
        return hit

    if allow_measure is None:
        allow_measure = measurement_enabled()
    if allow_measure:
        measured = _measure(B, k, o, q, g, impl, interpret)
        if measured is not None:
            _cache[key] = measured
            _persist(key, measured)
            return measured

    blocks = heuristic_blocks(k, o, g, B=B, q=q, impl=impl)
    if blocks[0] and blocks[1]:
        _cache[key] = blocks  # memoise so the divisibility scan runs once
    return blocks
