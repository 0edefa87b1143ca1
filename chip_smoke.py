"""Chip smoke: serve Phi-4-mini at its published widths on a TPU.

    python3 chip_smoke.py          # one chip: serve, kernel census, logits vs ref
    python3 chip_smoke.py --tp 4   # four chips: tp=4 engine vs tp=1 engine only

The one-chip run drives the serving CLI's own path (``launch/serve.py``:
``build_engine`` → ``Scheduler`` → ``Engine`` → ``qmatmul`` → Pallas
``bcq_mm``) with the published config (bf16, 32 layers, 200k vocab), random
weights from a seed quantized to BCQ q=3, g=128, and 8 requests of 512
prompt tokens and 64 new tokens over 8 slots. It then checks

- that every traced quantized matmul dispatched ``bcq_mm`` and none ``ref``;
- that the Pallas path's prefill and first decode logits for one prompt agree
  with the dequantize+dot oracle (``impl_mode("ref")``) on the same weights.

``--tp 4`` runs only the tensor-parallel engine on four chips against the
tp=1 engine on one of them, in the same process.

Any failure exits non-zero. Block sizes come only from the checked-in
autotune table and the tiling rule: measurement is off and no user table is
read. Times printed are wall times of this smoke run, not benchmarks. The
last stdout line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ["REPRO_AUTOTUNE"] = "0"  # no timing sweep: table + rule only
os.environ.pop("REPRO_AUTOTUNE_CACHE", None)  # no user table

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.infer import Engine  # noqa: E402
from repro.kernels.ops import impl_mode  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    build_engine,
    build_params,
    build_requests,
    drive_continuous,
)
from repro.obs.metrics import default_registry  # noqa: E402
from repro.quant import quantized_bytes  # noqa: E402

ARCH = "phi4-mini-3.8b"
Q, G = 3, 128  # the paper's BCQ setting
N_REQUESTS, N_SLOTS, PROMPT_LEN, NEW_TOKENS, CHUNK = 8, 8, 512, 64, 8
MAX_SEQ = PROMPT_LEN + NEW_TOKENS + 8
SEED = 0

# Relative L2 error ||a - b|| / ||b|| allowed between two logit vectors. The
# Pallas kernel rebuilds each weight in float32 where the ref oracle rounds it
# to bf16 first, and a TP engine sums row-parallel partials in another order.
# Each layer rounds its activations to bf16 (2^-8 relative), so those small
# differences become one-ulp flips that random-walk through the layers: on
# the CPU at d_model=256 this measured 1.4e-2 after 1 layer, 1.7e-2 after 4
# and 2.5e-2 after 16. A tiling, unpack or sharding fault corrupts whole
# blocks of every output and gives an error of order 1.
LOGITS_RTOL = 1e-1


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform {devs[0].platform!r})")
    if len(devs) < n:
        raise SystemExit(f"chip_smoke: needs {n} TPU chips, JAX sees {len(devs)}")
    return devs


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def first_logits(engine: Engine, prompt, *, ref: bool = False, tok=None):
    """(prefill last-token logits, first decode logits, decoded token) for
    one prompt. On a TPU, ``impl="auto"`` runs the Pallas kernels; ``ref``
    traces the dequantize+dot oracle instead. ``tok`` fixes the decoded
    token (default: the prefill argmax) so two paths decode the same input."""
    plen = prompt.shape[1]
    # impl_mode acts at trace time: fresh jit wrappers trace under it
    with impl_mode("ref") if ref else contextlib.nullcontext():
        prefill = jax.jit(lambda p, t, c: engine.prefill_fn(p, t, None, c))
        decode = jax.jit(lambda p, t, c, s: engine.decode_step_fn(p, t, c, s))
        logits, cache = prefill(engine.params, prompt, engine._make_cache(1))
        last = logits[:, -1]
        if tok is None:
            tok = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
        logits2, _ = decode(engine.params, tok, cache, jnp.int32(plen))
    return np.asarray(last), np.asarray(logits2[:, -1]), tok


def check_logits(name: str, got, want) -> None:
    for tag, x in (("got", got), ("want", want)):
        if not np.isfinite(x).all():
            raise AssertionError(f"{name}: non-finite {tag} logits")
    err = rel_l2(got, want)
    same_top1 = bool((got.argmax(-1) == want.argmax(-1)).all())
    log(f"{name}: rel L2 {err:.3e} (limit {LOGITS_RTOL:.0e}), "
        f"max |diff| {float(np.abs(got - want).max()):.3e}, "
        f"max |logit| {float(np.abs(want).max()):.3e}, top-1 agrees: {same_top1}")
    if err > LOGITS_RTOL:
        raise AssertionError(f"{name}: rel L2 {err:.3e} over {LOGITS_RTOL:.0e}")


def check_census() -> None:
    """Every traced quantized matmul call site dispatched ``bcq_mm``."""
    fam = default_registry().snapshot().get("qmatmul_dispatch_total", {"series": []})
    census = {}
    for s in fam["series"]:
        key = s["labels"]["impl"]
        census[key] = census.get(key, 0) + int(s["value"])
    log(f"qmatmul dispatch census (traced call sites by impl): {census}")
    if set(census) != {"bcq_mm"}:
        raise AssertionError(f"census: expected bcq_mm only, got {census}")


def one_chip() -> None:
    dev = jax.devices()[0]
    cfg = get_config(ARCH)
    log(f"config: {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} (no cut) "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"params={cfg.param_dtype} compute={cfg.compute_dtype}; bcq q={Q} g={G}")

    t = time.perf_counter()
    engine = build_engine(cfg, q=Q, g=G, seed=SEED, max_seq=MAX_SEQ)
    jax.block_until_ready(engine.params)
    log(f"build (init + quantize + fuse, compile included): "
        f"{time.perf_counter() - t:.1f}s smoke-run wall time; "
        f"weights {quantized_bytes(engine.params) / 2**30:.3f} GiB; "
        f"peak device memory {peak_bytes(dev) / 2**30:.3f} GiB")

    reqs = build_requests(cfg, N_REQUESTS, PROMPT_LEN, NEW_TOKENS,
                          mixed_temperature=False, seed=SEED)
    sched, done, dt = drive_continuous(engine, reqs, np.zeros(N_REQUESTS),
                                       n_slots=N_SLOTS, chunk=CHUNK)
    n_new = sum(len(c.new_tokens) for c in done)
    log(f"serve: {len(done)} requests x {PROMPT_LEN} prompt tokens, {n_new} new "
        f"tokens over {N_SLOTS} slots in {dt:.1f}s smoke-run wall time "
        f"(compile included); {sched.decode_steps} decode steps")
    bad = {k: v for k, v in sched.counters.items() if v and k != "stopped_early"}
    if len(done) != N_REQUESTS or n_new != N_REQUESTS * NEW_TOKENS or bad:
        raise AssertionError(f"serve: {len(done)} completions, {n_new} tokens, "
                             f"counters {bad}")
    toks = np.concatenate([c.new_tokens for c in done])
    if toks.min() < 0 or toks.max() >= cfg.vocab:
        raise AssertionError(f"serve: token ids outside [0, {cfg.vocab})")
    log(f"sample tokens: {done[0].new_tokens[:16].tolist()}")

    check_census()

    prompt = jnp.asarray(reqs[0].prompt[None], jnp.int32)
    t = time.perf_counter()
    k_pre, k_dec, tok = first_logits(engine, prompt)
    r_pre, r_dec, _ = first_logits(engine, prompt, ref=True, tok=tok)
    log(f"logits check ({time.perf_counter() - t:.1f}s, compile included):")
    check_logits("prefill logits, bcq_mm vs ref", k_pre, r_pre)
    check_logits("first decode logits, bcq_mm vs ref", k_dec, r_dec)
    log(f"peak device memory {peak_bytes(dev) / 2**30:.3f} GiB")


def tensor_parallel(tp: int) -> None:
    from repro.parallel.tp import make_tp_mesh

    devs = jax.devices()
    cfg = get_config(ARCH)
    log(f"config: {cfg.name} layers={cfg.n_layers} (no cut); bcq q={Q} g={G}; "
        f"tp={tp} vs tp=1 on {devs[0]}")
    t = time.perf_counter()
    params = build_params(cfg, q=Q, g=G, seed=SEED)
    one = Engine(cfg, params, max_seq=MAX_SEQ)
    sharded = Engine(cfg, params, mesh=make_tp_mesh(tp), max_seq=MAX_SEQ)
    del params
    jax.block_until_ready((one.params, sharded.params))
    log(f"build: {time.perf_counter() - t:.1f}s smoke-run wall time")

    (req,) = build_requests(cfg, 1, PROMPT_LEN, NEW_TOKENS,
                            mixed_temperature=False, seed=SEED)
    prompt = jnp.asarray(req.prompt[None], jnp.int32)
    t = time.perf_counter()
    o_pre, o_dec, tok = first_logits(one, prompt)
    s_pre, s_dec, _ = first_logits(sharded, prompt, tok=tok)
    log(f"logits check ({time.perf_counter() - t:.1f}s, compile included):")
    check_logits(f"prefill logits, tp={tp} vs tp=1", s_pre, o_pre)
    check_logits(f"first decode logits, tp={tp} vs tp=1", s_dec, o_dec)

    a = one.generate(np.asarray(prompt), NEW_TOKENS).generated(0)
    b = sharded.generate(np.asarray(prompt), NEW_TOKENS).generated(0)
    diverge = int(np.argmax(a != b)) if (a != b).any() else len(a)
    log(f"greedy tokens: {int((a == b).sum())}/{len(a)} agree, first divergence "
        f"at {diverge} (bf16 psum order may flip near-ties)")
    check_census()
    log(f"peak device memory per chip: "
        f"{[round(peak_bytes(d) / 2**30, 3) for d in devs[:tp]]} GiB")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel phase on this many chips")
    args = ap.parse_args()
    devs = require_tpu(args.tp)  # before any work
    log(f"compile cache: {enable_compile_cache()}")
    if args.tp > 1:
        tensor_parallel(args.tp)
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
