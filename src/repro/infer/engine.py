"""Batched generation engine implementing the paper's Fig. 13 strategy.

Weights live in memory **once**, in packed BCQ format. The two stages consume
them differently:

- **summarization / context (prefill)** — compute-bound, large effective batch:
  weights are dequantized and fed to dense matmuls (on TPU: the fused
  dequant-in-VMEM ``bcq_mm`` tile loop; the dequantized matrix never re-enters
  HBM). Rationale (paper §V.B): dequant cost is amortised over many tokens.
- **generation (decode)** — memory-bound single-token steps: LUT-GEMM consumes
  the packed format directly.

The engine also serves *dense* models (pass unquantized params) so the
cuBLAS-analogue baseline uses the identical code path.

Decode fast path (DESIGN.md §2.3): at construction the params are run through
:func:`repro.models.fuse_decode_projections` (``fuse=True`` default) so QKV and
gate/up issue one fused projection kernel each, and ``generate`` runs all N
decode steps as a single jitted ``jax.lax.scan`` (``scan=True`` default) —
sampling happens on device inside the scan body, the KV cache is threaded
through the carry, and the host syncs once for the whole sequence instead of
once per token. Embedding-input (modality-stub) models fall back to the
per-token step loop because ``embed_fn`` runs host-side.

The KV cache stays in place through the decode program: the decode scan
carries it, the model's layer scan carries each stage's stacked K/V cache in
turn, and each step writes one row per batch row and layer into it
(``models/model.py::_apply_stage``, ``models/layers.py::_cache_write``). The
slot-serving programs donate the slot state, and ``generate`` donates its
prefilled cache, so the carried cache and the argument are one buffer.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.infer.prefix_cache import (
    PrefixHandle,
    concat_rows,
    model_identity,
    pad_rows,
)
from repro.infer.spans import span
from repro.infer.speculative import (
    SpecConfig,
    freeze_inactive,
    has_recurrent_state,
    has_ring_buffer,
    select_recurrent_target,
    spec_chunk,
)
from repro.models import forward, fuse_decode_projections, init_cache
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.quant import truncate_params


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray  # (B, prompt+generated)
    prompt_len: int
    steps: int
    spec_stats: Optional[dict] = None  # accept_rate/chunks when speculating
    # per-row index into the generated tokens of the first stop token
    # (-1 = none); set when generate(stop_tokens=...) was given
    stop_positions: Optional[np.ndarray] = None

    def generated(self, b: int = 0) -> np.ndarray:
        """Row ``b``'s generation, truncated at its stop token (inclusive).

        ``tokens`` stays rectangular — decode always runs the full budget and
        truncation is host-side — so this is the accessor that honours
        ``stop_tokens``: everything after the first stop token is cut, and
        the stop token itself is the last element (matching the scheduler's
        early-exit serving contract)."""
        new = self.tokens[b, self.prompt_len :]
        if self.stop_positions is not None and self.stop_positions[b] >= 0:
            return new[: int(self.stop_positions[b]) + 1]
        return new


def stop_positions_for(new_tokens: np.ndarray, stop_tokens) -> np.ndarray:
    """(B, N) generated tokens -> (B,) index of each row's first stop token
    (-1 if the row never emits one)."""
    new_tokens = np.asarray(new_tokens)  # staticcheck: host-sync(host-side stop-token scan on emitted tokens)
    hits = np.isin(new_tokens, np.asarray(list(stop_tokens), np.int32))  # staticcheck: host-sync(stop-token ids are host ints)
    first = np.argmax(hits, axis=1)
    return np.where(hits.any(axis=1), first, -1).astype(np.int32)


@dataclasses.dataclass
class PendingAdmission:
    """Multi-step admission state (chunked prefill, DESIGN.md §12).

    ``Engine.begin_admission`` creates one (consulting the prefix cache and
    installing any matched prefix), ``advance_admission`` runs prefill
    forward by a token budget per call — which is what lets the scheduler
    interleave long-prompt admissions with decode chunks — and
    ``finish_admission`` captures the commit payload and installs the slot.
    ``admit_slot`` is the synchronous composition of the three.
    """

    prompt: jax.Array            # (1, plen) int32, device
    plen: int
    max_new_tokens: int
    temperature: float
    seed: int
    speculate: bool              # per-request opt-in (spec slot batches)
    needs_draft: bool            # the slot batch is speculative
    chunked: bool                # bucket-padded chunk dispatches
    whole: bool                  # single whole-prompt prefill dispatch
    collect: bool                # capture recurrent stacks for prefix commit
    handle: Optional[PrefixHandle] = None
    pos: int = 0                 # target prompt tokens consumed so far
    cache1: object = None        # evolving batch-1 target cache
    logits1: object = None       # (1, V) last-token logits once target done
    # (start_pos, collect_states cache) per collect dispatch — recurrent
    # boundary snapshots for prefix commit are selected out of these
    stack_segments: list = dataclasses.field(default_factory=list)
    dcache1: object = None       # batch-1 draft cache (spec mode)
    prefill_chunks: int = 0      # dispatches so far (lifecycle stamp)

    @property
    def target_done(self) -> bool:
        return self.logits1 is not None

    @property
    def done(self) -> bool:
        return self.target_done and (
            not self.needs_draft or self.dcache1 is not None
        )


def _sample(logits: jax.Array, key: jax.Array, temperature, greedy: bool) -> jax.Array:
    """(B, V) f32 logits → (B,) int32 tokens, on device.

    ``temperature <= 0`` with ``greedy=False`` falls back to argmax instead of
    dividing by zero (``logits / 0`` → ±inf → NaN probabilities in the
    categorical). ``temperature`` may be a traced scalar, so the guard is a
    ``jnp.where`` on the *result*, and the division clamps its denominator —
    bit-identical to the unguarded path for any real temperature > 1e-6.
    """
    if greedy:
        return jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(key, logits / jnp.maximum(temperature, 1e-6))
    return jnp.where(temperature > 0, sampled, jnp.argmax(logits, axis=-1))


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_seq: int = 2048,
        embed_fn=None,
        fuse: bool = True,
        mesh=None,
        tracer=None,
        prefix_cache=None,
    ):
        """``embed_fn(tokens (B,1) int32) → (B,1,D)`` is required for
        embedding-input (modality-stub) models to feed sampled codes back in —
        it stands in for the stubbed frontend (e.g. EnCodec codebook embed).

        ``fuse=False`` keeps the unfused per-projection weight layout
        (debugging / layouts the fuser declines are left unfused anyway).

        ``mesh`` (a 1-D mesh with a ``model`` axis — ``parallel.tp.
        make_tp_mesh``) runs every forward tensor-parallel under ``shard_map``
        (DESIGN.md §7): weights are placed column/row-parallel, KV caches
        shard their kv-head dim, and all decode/serve/speculative paths
        consume the shards; tokens are identical to the single-device engine
        for greedy decoding, logits equal up to psum reassociation.

        ``tracer`` (a :class:`repro.obs.trace.Tracer`) records host-side
        spans around every engine dispatch on the ``engine`` lane. Spans
        wrap the *host* call — dispatch plus any blocking fetch the caller's
        path performs inside them — never code inside a jitted function, so
        instrumentation changes neither the traced programs (§3 trace-once)
        nor the tokens (tests/test_obs.py). Per-op device timing needs a
        ``jax.profiler.trace`` capture (``launch/serve.py --profile-dir``);
        the :func:`jax.profiler.TraceAnnotation` scopes emitted here label
        those captures.

        ``counters`` counts the target-model prefill of the slot-serving
        path as it is dispatched: ``prefill_tokens`` (real prompt rows) and
        ``prefill_rows`` (rows computed, bucket padding included). Decode
        work is counted by the scheduler (``Scheduler.decode_steps``).
        :meth:`programs_built` counts the executables the jitted programs
        hold.

        ``prefix_cache`` (a :class:`repro.infer.prefix_cache.PrefixCache`)
        turns on prompt-prefix KV reuse for the slot-batched admission path
        (DESIGN.md §12): ``admit_slot`` consults it, installs matched prefix
        rows instead of recomputing them, and commits the prompt's prefix
        back on success. Tokens are bit-identical to cold-cache admission.
        Requires a tokens-input, non-VLM, non-MoE model (the same gate as
        slot-batched serving)."""
        self.cfg = cfg
        self.tracer = tracer
        self.counters = {"prefill_tokens": 0, "prefill_rows": 0}
        self.params = fuse_decode_projections(cfg, params) if fuse else params
        self.max_seq = max_seq
        self.embed_fn = embed_fn
        self._unit_cache = None  # lazy batch-1 prefill template (admit_slot)
        self.mesh = mesh
        if mesh is not None:
            from repro.parallel.tp import shard_model

            self.params, self._tp = shard_model(cfg, self.params, mesh)
            fwd = self._tp.forward
        else:
            self._tp = None
            fwd = functools.partial(forward, cfg)

        def _prefill(params, tokens, image_emb, cache):
            kw = (
                {"tokens": tokens}
                if cfg.input_kind == "tokens"
                else {"embeddings": tokens}
            )
            if cfg.family == "vlm":
                kw["image_emb"] = image_emb
            logits, cache, _ = fwd(
                params, **kw, cache=cache, pos=jnp.int32(0), logits_mode="last"
            )
            return logits, cache

        def _decode(params, tok, cache, pos):
            kw = {"tokens": tok} if cfg.input_kind == "tokens" else {"embeddings": tok}
            if cfg.family == "vlm":
                kw["image_emb"] = None
            logits, cache, _ = fwd(
                params, **kw, cache=cache, pos=pos, logits_mode="last"
            )
            return logits, cache

        def _scan_decode(params, logits0, cache, pos0, key, temperature, *, n_steps, greedy):
            """N decode steps as ONE dispatch: sample → step, all on device.

            The carry holds (last logits, cache, position, PRNG key); the
            stacked scan output is the sampled token matrix. The key-split /
            sample order matches the step loop exactly, so scanned and
            step-loop generations are bit-identical (test_engine_scan).
            """

            def body(carry, _):
                logits, cache, pos, key = carry
                key, sub = jax.random.split(key)
                tok = _sample(logits, sub, temperature, greedy)
                logits2, cache = _decode(params, tok[:, None], cache, pos)
                return (logits2[:, -1], cache, pos + 1, key), tok

            (_, cache, _, _), toks = jax.lax.scan(
                body, (logits0, cache, pos0, key), None, length=n_steps
            )
            return toks.T, cache  # (B, n_steps)

        def _admit(slots, slot, cache1, logits1, key, plen, max_new, temperature, greedy):
            """Install a freshly prefilled request into batch row `slot`.

            `cache1` is the batch-1 prefilled cache; every cache leaf is
            (repeat, batch, ...) so the row write is one dynamic-update-slice
            per leaf along axis 1. The slot's whole state row (KV rows,
            recurrent state, position counter, PRNG key, sampling params) is
            overwritten — nothing from the previous tenant survives, which is
            the slot-reset contract (DESIGN.md §4).
            """
            cache = jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                    full, one.astype(full.dtype), slot, axis=1
                ),
                slots["cache"],
                cache1,
            )
            return dict(
                slots,
                cache=cache,
                logits=slots["logits"].at[slot].set(logits1[0]),
                pos=slots["pos"].at[slot].set(plen),
                keys=slots["keys"].at[slot].set(key),
                active=slots["active"].at[slot].set(True),
                remaining=slots["remaining"].at[slot].set(max_new),
                temperature=slots["temperature"].at[slot].set(temperature),
                greedy=slots["greedy"].at[slot].set(greedy),
            )

        def _scan_decode_slots(params, slots, *, n_steps):
            """`n_steps` slot-batched decode steps as ONE dispatch.

            Like `_scan_decode`, but each batch row is an independent request
            with its own position counter, PRNG key and sampling params, plus
            an active mask: inactive rows keep their key/logits/position
            frozen so a row's (key-split, sample) sequence advances exactly
            once per emitted token — the same sequence a solo batch-1
            `generate` of that request produces. Inactive rows still flow
            through the batched forward (they decode garbage into their own
            cache rows at a frozen position, which is harmless: a row's cache
            beyond its position is never attended, and admission rewrites the
            slot's state from scratch).

            Per-row sampling matches batch-1 `_sample` bit-for-bit: the
            categorical is taken over a (1, V) row under vmap, which JAX's
            counter-based PRNG evaluates identically to a standalone call.
            """
            temperature, greedy = slots["temperature"], slots["greedy"]

            def body(carry, _):
                logits, cache, pos, keys, active, remaining = carry
                splits = jax.vmap(jax.random.split)(keys)  # (B, 2, 2)
                new_keys = jnp.where(active[:, None], splits[:, 0], keys)
                sub = splits[:, 1]
                sampled = jax.vmap(
                    lambda lg, kk, t: jax.random.categorical(kk, lg[None] / t)[0]
                )(logits, sub, temperature)
                tok = jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled)
                tok = tok.astype(jnp.int32)
                logits2, cache2 = _decode(params, tok[:, None], cache, pos)
                new_logits = jnp.where(active[:, None], logits2[:, -1], logits)
                new_pos = jnp.where(active, pos + 1, pos)
                new_rem = jnp.where(active, remaining - 1, remaining)
                new_active = active & (new_rem > 0)
                emitted = jnp.where(active, tok, -1)
                return (
                    (new_logits, cache2, new_pos, new_keys, new_active, new_rem),
                    (emitted, active),
                )

            carry = (
                slots["logits"], slots["cache"], slots["pos"],
                slots["keys"], slots["active"], slots["remaining"],
            )
            carry, (toks, actives) = jax.lax.scan(body, carry, None, length=n_steps)
            logits, cache, pos, keys, active, remaining = carry
            out = dict(
                slots,
                logits=logits, cache=cache, pos=pos, keys=keys,
                active=active, remaining=remaining,
            )
            return toks.T, actives.T, out  # (B, n_steps) each

        def _admit_spec(
            slots, slot, cache1, dcache1, logits1, key, dkey, plen, max_new,
            temperature, greedy, spec_on,
        ):
            """Spec-mode admission: the plain install plus the draft-cache row,
            the per-row draft PRNG stream, and the request's FIRST token —
            sampled here exactly as the plain path's first decode step would
            (one key split, same categorical shape), recorded in `t_pend` and
            already counted against the budget."""
            slots = _admit(
                slots, slot, cache1, logits1, key, plen, max_new, temperature, greedy
            )
            dcache = jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                    full, one.astype(full.dtype), slot, axis=1
                ),
                slots["draft_cache"],
                dcache1,
            )
            key2, sub = jax.random.split(key)
            lg = logits1[0]
            tok = jnp.where(
                greedy,
                jnp.argmax(lg),
                jax.random.categorical(sub, lg[None] / temperature)[0],
            ).astype(jnp.int32)
            return dict(
                slots,
                draft_cache=dcache,
                t_pend=slots["t_pend"].at[slot].set(tok),
                spec=slots["spec"].at[slot].set(spec_on),
                keys=slots["keys"].at[slot].set(key2),
                draft_keys=slots["draft_keys"].at[slot].set(dkey),
                remaining=slots["remaining"].at[slot].set(max_new - 1),
                active=slots["active"].at[slot].set(max_new > 1),
            )

        def _scan_spec_slots(params, draft_params, slots, *, n_chunks, gamma):
            """`n_chunks` speculative chunks over the slot batch, ONE dispatch.

            Each chunk commits 1..gamma+1 tokens per row (per-row budgets clip
            the tail); rows with `spec=False` are forced to n_acc=0 inside the
            chunk and so emit exactly one plain-decode token per chunk, with
            a PRNG stream bit-identical to the non-speculative path."""
            temperature, greedy, spec_on = (
                slots["temperature"], slots["greedy"], slots["spec"],
            )

            def body(carry, _):
                state, active, remaining = carry
                commit, n_keep, ns = spec_chunk(
                    cfg, params, draft_params, state, gamma=gamma,
                    greedy=greedy, temperature=temperature, spec_enabled=spec_on,
                    fwd=fwd,
                )
                emit_n = jnp.where(active, jnp.minimum(n_keep, remaining), 0)
                valid = jnp.arange(gamma + 1)[None, :] < emit_n[:, None]
                toks = jnp.where(valid, commit, -1)
                new_remaining = remaining - emit_n
                new_active = active & (new_remaining > 0)
                frozen = freeze_inactive(ns, state, active)
                return (frozen, new_active, new_remaining), (toks, valid)

            state0 = {
                k: slots[k]
                for k in ("t_pend", "pos", "keys", "draft_keys", "cache", "draft_cache")
            }
            (state, active, remaining), (toks, valid) = jax.lax.scan(
                body, (state0, slots["active"], slots["remaining"]), None,
                length=n_chunks,
            )
            b = toks.shape[1]
            toks = toks.transpose(1, 0, 2).reshape(b, -1)  # (B, n_chunks*(γ+1))
            valid = valid.transpose(1, 0, 2).reshape(b, -1)
            out = dict(slots, active=active, remaining=remaining, **state)
            return toks, valid, out

        def _spec_generate(
            params, draft_params, logits0, cache, dcache, pos0, key, dkey,
            temperature, *, n_steps, gamma, greedy,
        ):
            """One-shot speculative generation: chunks under a while_loop until
            every row has emitted `n_steps` tokens (host syncs once)."""
            b = logits0.shape[0]
            cap = n_steps + gamma + 1
            row_keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(b))
            draft_keys = jax.vmap(lambda i: jax.random.fold_in(dkey, i))(jnp.arange(b))
            greedy_vec = jnp.full((b,), greedy)
            temp_vec = jnp.full((b,), temperature)
            spec_on = jnp.ones((b,), bool)

            # first token = the target's own next token from the prefill logits
            splits = jax.vmap(jax.random.split)(row_keys)
            row_keys, sub = splits[:, 0], splits[:, 1]
            sampled = jax.vmap(
                lambda kk, lg: jax.random.categorical(kk, lg[None] / temperature)[0]
            )(sub, logits0)
            t0 = jnp.where(greedy_vec, jnp.argmax(logits0, -1), sampled).astype(
                jnp.int32
            )
            buf0 = jnp.zeros((b, cap), jnp.int32).at[:, 0].set(t0)

            state0 = dict(
                t_pend=t0, pos=pos0, keys=row_keys, draft_keys=draft_keys,
                cache=cache, draft_cache=dcache,
            )
            emitted0 = jnp.ones((b,), jnp.int32)
            stats0 = (jnp.int32(0), jnp.int32(0), jnp.int32(0))

            def cond(carry):
                return jnp.any(carry[1] < n_steps)

            def body(carry):
                state, emitted, buf, (acc, prop, chunks) = carry
                active = emitted < n_steps
                commit, n_keep, ns = spec_chunk(
                    cfg, params, draft_params, state, gamma=gamma,
                    greedy=greedy_vec, temperature=temp_vec, spec_enabled=spec_on,
                    fwd=fwd,
                )

                def wrow(bufrow, vec, start, act):
                    # inactive rows park their junk write beyond n_steps
                    start = jnp.where(act, start, jnp.int32(cap - gamma - 1))
                    return jax.lax.dynamic_update_slice(bufrow, vec, (start,))

                buf = jax.vmap(wrow)(buf, commit, emitted, active)
                frozen = freeze_inactive(ns, state, active)
                # count only acceptances whose commits survive the n_steps
                # slice — the final chunk's clipped tail is not a real win
                counted = jnp.minimum(n_keep - 1, n_steps - emitted)
                emitted = jnp.where(active, emitted + n_keep, emitted)
                stats = (
                    acc + jnp.sum(jnp.where(active, counted, 0)),
                    prop + jnp.sum(active) * gamma,
                    chunks + 1,
                )
                return (frozen, emitted, buf, stats)

            _, _, buf, stats = jax.lax.while_loop(
                cond, body, (state0, emitted0, buf0, stats0)
            )
            return buf[:, :n_steps], stats

        # raw (unjitted) closures: repro.analysis.staticcheck traces these with
        # jax.make_jaxpr to prove the collective/transfer/dtype invariants of
        # the exact programs the jitted attributes below compile
        self.prefill_fn = _prefill
        self.decode_step_fn = _decode
        self.scan_decode_fn = _scan_decode

        # QuantizedTensor statics (g/k/o/fmt) travel in the pytree treedef, so
        # the param tree needs no static_argnums here
        self._prefill = jax.jit(_prefill)  # staticcheck: jit-ok(pytree statics; no donation — unit-cache template is reused)
        self._decode = jax.jit(_decode)  # staticcheck: jit-ok(pytree statics; cache threaded functionally by scan callers)
        # the prefilled cache is dead after the decode scan: donate it so
        # the scan's carried cache updates it in place
        self._scan_decode = jax.jit(
            _scan_decode, static_argnames=("n_steps", "greedy"),
            donate_argnums=(2,),
        )
        # donate the incoming slot state: both return a full replacement and
        # the scheduler drops the old dict, so the n_slots-wide KV cache can
        # be updated in place instead of copied per dispatch (the same hazard
        # launch/dryrun.py documents for the one-shot decode step)
        self._admit = jax.jit(_admit, donate_argnums=(0,))
        self._scan_decode_slots = jax.jit(
            _scan_decode_slots, static_argnames=("n_steps",), donate_argnums=(1,)
        )
        def _release(slots, slot):
            """Deactivate one slot row: the lifecycle layer's slot-reclaim
            primitive (cancel / timeout / quarantine). Only the row's active
            mask and budget change — its cache rows are left as-is, which is
            safe by the write-before-read contract (DESIGN.md §4: garbage
            beyond a row's position is never attended) and admission
            overwrites the entire row anyway."""
            return dict(
                slots,
                active=slots["active"].at[slot].set(False),
                remaining=slots["remaining"].at[slot].set(0),
            )

        self._release = jax.jit(_release, donate_argnums=(0,))
        # row-finiteness of the carried logits: the scheduler's NaN/inf guard
        # reads (B,) bools per chunk instead of hauling (B, vocab) to host
        self._finite_rows = jax.jit(lambda lg: jnp.isfinite(lg).all(axis=-1))  # staticcheck: jit-ok(single-array reduction, nothing to donate or mark static)
        self._admit_spec = jax.jit(_admit_spec, donate_argnums=(0,))
        self._scan_spec_slots = jax.jit(
            _scan_spec_slots, static_argnames=("n_chunks", "gamma"),
            donate_argnums=(2,),
        )
        self._spec_generate = jax.jit(
            _spec_generate, static_argnames=("n_steps", "gamma", "greedy")
        )
        self._draft_params: dict = {}  # q_draft -> truncated param tree
        self._slot_spec: Optional[SpecConfig] = None  # set by init_slots

        # -- prefix-cache KV reuse + chunked prefill (DESIGN.md §12) --------

        def _prefill_chunk(params, tokens, cache, pos, last_idx):
            """One suffix-prefill chunk: `s` fresh tokens mid-sequence against
            a filled cache — the speculative-verify mechanism (`chunked_decode`)
            reused for prefill, so every token attends the installed prefix
            rows plus its intra-chunk predecessors. Returns the (1, V) logits
            of the token at `last_idx`: the last REAL token when the chunk is
            bucket-padded (pad tokens sit at later positions, which causal
            masks make invisible to it)."""
            logits, cache, _ = fwd(
                params, tokens=tokens, cache=cache, pos=pos,
                logits_mode="all", chunked_decode=True,
            )
            last = jax.lax.dynamic_index_in_dim(
                logits, last_idx, axis=1, keepdims=False
            )
            return last, cache

        def _prefill_collect(params, tokens, cache):
            """Whole-prompt prefill that additionally returns recurrent state
            stacked over the time axis (``collect_states``) so prefix commit
            can snapshot the state at block boundaries. Logits and cache rows
            are bit-identical to `_prefill` — collect changes only what the
            recurrent blocks *return*, not what they compute."""
            logits, cache, _ = fwd(
                params, tokens=tokens, cache=cache, pos=jnp.int32(0),
                logits_mode="last", collect_states=True,
            )
            return logits[:, -1], cache

        def _suffix_collect(params, tokens, cache, pos, last_idx):
            """`_prefill_chunk` with recurrent-state collection (warm-hit
            suffix prefill on a recurrent architecture that also commits)."""
            logits, cache, _ = fwd(
                params, tokens=tokens, cache=cache, pos=pos,
                logits_mode="all", chunked_decode=True, collect_states=True,
            )
            last = jax.lax.dynamic_index_in_dim(
                logits, last_idx, axis=1, keepdims=False
            )
            return last, cache

        self.prefill_chunk_fn = _prefill_chunk  # staticcheck traces this raw
        self._prefill_chunk = jax.jit(_prefill_chunk)  # staticcheck: jit-ok(pytree statics; no donation — the evolving cache is also the commit-gather source)
        self._prefill_collect = jax.jit(_prefill_collect)  # staticcheck: jit-ok(pytree statics; same non-donation rationale as _prefill)
        self._suffix_collect = jax.jit(_suffix_collect)  # staticcheck: jit-ok(pytree statics; recurrent-only path, cold at serving scale)
        self._install_rows = jax.jit(L.install_prefix_rows)  # staticcheck: jit-ok(no donation — the batch-1 unit-cache template is reused across admissions)
        self._install_recurrent = jax.jit(L.install_recurrent)  # staticcheck: jit-ok(same template-reuse rationale as _install_rows)
        self._gather_block = jax.jit(
            L.gather_prefix_rows, static_argnums=(2,)
        )
        self._final_recurrent = jax.jit(select_recurrent_target)  # staticcheck: jit-ok(tiny per-leaf select; nothing to donate or mark static)
        self._boundary_snap = jax.jit(  # staticcheck: jit-ok(tiny select+snapshot; nothing to donate or mark static)
            lambda vc, idx: L.snapshot_recurrent(select_recurrent_target(vc, idx))
        )

        self.prefix_cache = prefix_cache
        self._prefix_ok = (
            cfg.input_kind == "tokens" and cfg.family != "vlm"
            and not cfg.n_experts
        )
        self._has_recurrent = has_recurrent_state(cfg)
        self._has_ring = has_ring_buffer(cfg)
        # Bucket-padded chunks need pad-token writes to be DEAD rows (the
        # write-before-read contract): ring buffers wrap pad writes onto live
        # rows and recurrent state folds pad tokens irreversibly, so those
        # architectures fall back to exact-length dispatches (correct, but
        # retraces per length — hence supports_chunked_prefill is False).
        self._chunkable = (
            self._prefix_ok and not self._has_recurrent and not self._has_ring
        )
        if prefix_cache is not None:
            if not self._prefix_ok:
                raise ValueError(
                    "prefix_cache requires a tokens-input, non-VLM, non-MoE "
                    "model (the slot-batched serving gate, DESIGN.md §4)"
                )
            prefix_cache.bind(model_identity(cfg, self.params, mesh))
        # pow-of-2 chunk/prefix buckets: one compile-cache entry per bucket
        # instead of one per prompt length (staticcheck trace-once proof)
        buckets, bkt = [], 8
        while bkt < max_seq:
            buckets.append(bkt)
            bkt *= 2
        buckets.append(max_seq)
        self.chunk_buckets = tuple(dict.fromkeys(buckets))
        self._last_prefix_handle: Optional[PrefixHandle] = None

    def _count_prefill(self, tokens: int, rows: int) -> None:
        self.counters["prefill_tokens"] += tokens
        self.counters["prefill_rows"] += rows

    def programs_built(self) -> int:
        """Executables held by this engine's own jitted programs, summed
        over their compile caches: a program compiled or loaded from the
        persistent compilation cache counts alike. Read on demand, so it
        costs the serving path nothing; it rises when a new shape (a new
        whole-shot prompt length, a new chunk bucket) first runs. It does
        not see the eager ops the engine runs outside those programs (the
        per-length prompt slice and pad of chunked admission), which
        compile too: a ``jax.monitoring`` compile listener sees those."""
        return sum(
            f._cache_size() for f in vars(self).values()
            if hasattr(f, "_cache_size")
        )

    def _make_cache(self, batch: int):
        """A fresh decode cache, TP-sharded (kv-heads over `model`) when the
        engine runs on a mesh so the jitted paths see sharded inputs instead
        of paying a reshard on entry."""
        cache = init_cache(self.cfg, batch, self.max_seq)
        return cache if self._tp is None else self._tp.shard_cache(cache)

    # -- speculative decoding (infer/speculative.py) -------------------------

    def draft_params(self, q_draft: int):
        """The nested ``q_draft``-bit draft view of this engine's params
        (zero extra solve; norms/embeddings/dense leaves shared by reference).
        Cached per ``q_draft`` for the engine's lifetime."""
        if q_draft not in self._draft_params:
            draft = truncate_params(self.params, q_draft)
            if self._tp is not None:
                # plane truncation slices the q axis, never a sharded dim, so
                # the full tree's placement applies verbatim; re-commit so the
                # draft enters jit sharded even if the slice fell off-device
                draft = self._tp.place_params(draft)
            self._draft_params[q_draft] = draft
        return self._draft_params[q_draft]

    def _validate_spec(self, spec: SpecConfig) -> None:
        cfg = self.cfg
        # capability gate (DESIGN.md §2.4): drafts are nested low-bit views of
        # the target's own weights, which only residual-nested formats can
        # provide — refuse before tracing, naming the offending formats AND
        # the registered formats that would work (the capability flag, not a
        # hardcoded name list)
        from repro.core.formats import format_names, get_format
        from repro.core.qtensor import QuantizedTensor

        bad = sorted(
            {
                leaf.fmt
                for leaf in jax.tree.leaves(
                    self.params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
                )
                if isinstance(leaf, QuantizedTensor)
                and not get_format(leaf.fmt).supports_truncate
            }
        )
        if bad:
            capable = [
                n for n in format_names() if get_format(n).supports_truncate
            ]
            raise ValueError(
                f"speculative decoding needs truncation-capable weight formats; "
                f"{bad} do not support nested draft truncation "
                f"(truncation-capable formats: {capable})"
            )
        if cfg.input_kind != "tokens":
            raise ValueError(
                "speculative decoding requires a tokens-input model (host-side "
                "embed_fn cannot run inside the jitted chunk)"
            )
        if cfg.n_experts:
            # verify batches γ+1 tokens through shared expert capacity, which
            # couples them — target logits would differ from step-by-step
            # decode, breaking the exactness contract (same exclusion as
            # slot-batched serving, DESIGN.md §4/§5)
            raise ValueError(
                "speculative decoding does not support MoE models: shared "
                "expert capacity couples the verified chunk's tokens, so the "
                "batched verify is not equivalent to step-by-step decode"
            )
        has_window = any(
            bt == "local_attn" for pattern, _ in cfg.stages for bt in pattern
        )
        if has_window and spec.gamma + 1 >= min(self.max_seq, cfg.window):
            raise ValueError(
                f"gamma={spec.gamma} too large for the ring-buffer window "
                f"{min(self.max_seq, cfg.window)} (need gamma+1 < window)"
            )

    # -- slot-batched serving API (infer/scheduler.py drives these) ---------

    def init_slots(self, n_slots: int, speculate: Optional[SpecConfig] = None) -> dict:
        """Fresh slot-batched decode state: a `n_slots`-wide KV cache plus
        per-slot counters/sampling params. All slots start inactive.

        ``speculate`` switches the slot batch to speculative chunks
        (DESIGN.md §5): the state grows a draft-model cache, per-row pending
        tokens, draft PRNG streams and a per-row opt-in flag; drive it with
        :meth:`spec_decode_slots` instead of :meth:`decode_slots`."""
        if self.cfg.input_kind != "tokens" or self.cfg.family == "vlm":
            raise ValueError(
                "slot-batched serving requires a tokens-input, non-VLM model "
                "(embed_fn/image inputs cannot run inside the slotted scan)"
            )
        if self.cfg.n_experts:
            # MoE expert capacity is shared across the batch: tokens from other
            # slots — including garbage from inactive rows — can evict an
            # active request's tokens from an expert buffer, so slot outputs
            # are neither solo-identical nor slot-history-independent. Reject
            # rather than silently break the scheduler's contract (DESIGN §4).
            raise ValueError(
                "slot-batched serving does not support MoE models: shared "
                "expert capacity couples batch rows, breaking per-request "
                "token-identity (use one-shot Engine.generate instead)"
            )
        slots = {
            "cache": self._make_cache(n_slots),
            "logits": jnp.zeros((n_slots, self.cfg.vocab), jnp.float32),
            "pos": jnp.zeros((n_slots,), jnp.int32),
            "keys": jnp.zeros((n_slots, 2), jnp.uint32),
            "active": jnp.zeros((n_slots,), bool),
            "remaining": jnp.zeros((n_slots,), jnp.int32),
            "temperature": jnp.ones((n_slots,), jnp.float32),
            "greedy": jnp.ones((n_slots,), bool),
        }
        self._slot_spec = speculate
        if speculate is not None:
            self._validate_spec(speculate)
            slots["draft_cache"] = self._make_cache(n_slots)
            slots["t_pend"] = jnp.zeros((n_slots,), jnp.int32)
            slots["spec"] = jnp.zeros((n_slots,), bool)
            slots["draft_keys"] = jnp.zeros((n_slots, 2), jnp.uint32)
        return slots

    # -- admission (whole-shot, prefix-cached, or chunked) -------------------

    @property
    def supports_chunked_prefill(self) -> bool:
        """True when bucket-padded chunked prefill is available: tokens-input,
        non-VLM/MoE, and neither ring-buffer (pad writes wrap onto live rows)
        nor recurrent (state folds pad tokens) architectures."""
        return self._chunkable

    def _bucket_for(self, pos: int, n: int) -> int:
        """Smallest chunk bucket holding ``n`` rows starting at ``pos``.
        Falls back to exact ``n`` near the cache end: a padded write there
        would make ``dynamic_update_slice`` CLAMP its start index and corrupt
        earlier rows (the §12 tail guard)."""
        for b in self.chunk_buckets:
            if b >= n and pos + b <= self.max_seq:
                return b
        return n

    def begin_admission(
        self,
        prompt_tokens,
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        seed: int = 0,
        speculate: bool = True,
        chunked: bool = False,
    ) -> PendingAdmission:
        """Start one slot admission: validate, consult the prefix cache, and
        install any matched prefix into a fresh batch-1 cache. Returns a
        :class:`PendingAdmission` to be driven by :meth:`advance_admission`
        and installed by :meth:`finish_admission` (or released by
        :meth:`abort_admission` on any failure/cancel in between).

        ``chunked=True`` makes :meth:`advance_admission` dispatch bucket-
        padded fixed-budget chunks (requires :attr:`supports_chunked_prefill`)
        so the scheduler can interleave long-prompt prefill with decode.
        """
        prompt = jnp.asarray(prompt_tokens, jnp.int32).reshape(1, -1)
        plen = int(prompt.shape[1])
        spec = self._slot_spec
        headroom = 0 if spec is None else spec.gamma + 1
        if plen + max_new_tokens + headroom > self.max_seq:
            raise ValueError(
                f"prompt_len({plen}) + max_new_tokens({max_new_tokens})"
                f"{f' + speculation headroom({headroom})' if headroom else ''} "
                f"exceeds max_seq={self.max_seq}"
            )
        if chunked and not self._chunkable:
            raise ValueError(
                "chunked prefill is unsupported for this architecture "
                "(ring-buffer/recurrent/MoE/VLM — see "
                "Engine.supports_chunked_prefill)"
            )
        if self._unit_cache is None:
            # one zeroed batch-1 cache per engine: _prefill is purely
            # functional (no donation), so the template is reusable and the
            # admission hot path skips a full max_seq cache alloc+zero
            self._unit_cache = self._make_cache(1)
        handle, pos, cache1, collect = None, 0, None, False
        if self.prefix_cache is not None:
            # at least the last prompt token must prefill (decode needs its
            # logits); ring caps both match and commit at the window — rows
            # past it wrapped during prefill and are not at their positions
            # ring guard: rows sit at their absolute positions only until
            # the buffer wraps (plen > window) — beyond that neither gather
            # nor install sees rows where the trie thinks they are, and a
            # warm suffix dispatch would wrap its own writes onto rows its
            # early tokens attend (the spec-gamma hazard). Prompts past the
            # window bypass the cache entirely (cold whole-shot prefill,
            # which handles the wrap natively).
            wrapped = self._has_ring and plen > min(self.max_seq, self.cfg.window)
            max_match = 0 if wrapped else plen - 1
            max_commit = 0 if wrapped else plen
            handle = self.prefix_cache.begin(
                prompt_tokens, max_match=max_match, max_commit=max_commit
            )
            try:
                if handle.length:
                    rows = concat_rows([nd.rows for nd in handle.matched])
                    total = (
                        self._bucket_for(0, handle.length)
                        if self._chunkable else handle.length
                    )
                    with span(
                        self.tracer, "engine/prefix_install", lane="engine",
                        hit_tokens=handle.length, padded=total,
                    ):
                        cache1 = self._install_rows(
                            self._unit_cache, pad_rows(rows, total)
                        )
                        if self._has_recurrent:
                            snap = handle.matched[-1].snap
                            assert snap is not None, (
                                "recurrent prefix block committed without a "
                                "boundary snapshot"
                            )
                            cache1 = self._install_recurrent(cache1, snap)
                    pos = handle.length
                collect = self._has_recurrent and bool(handle.new_spans)
            except Exception:
                self.prefix_cache.abort(handle)
                raise
        return PendingAdmission(
            prompt=prompt, plen=plen, max_new_tokens=max_new_tokens,
            temperature=temperature, seed=seed, speculate=speculate,
            needs_draft=spec is not None, chunked=chunked,
            whole=(not chunked) and pos == 0, collect=collect,
            handle=handle, pos=pos, cache1=cache1,
        )

    def _advance_once(self, p: PendingAdmission, left: Optional[int]) -> int:
        """One admission dispatch; returns prompt tokens consumed (the draft
        prefill counts its full prompt — it is always whole-shot, see
        :meth:`advance_admission`)."""
        if not p.target_done:
            if p.whole:
                with span(
                    self.tracer, "engine/prefill", lane="engine",
                    prompt_len=p.plen,
                ):
                    if p.collect:
                        p.logits1, vc = self._prefill_collect(
                            self.params, p.prompt, self._unit_cache
                        )
                        p.stack_segments.append((0, vc))
                        p.cache1 = self._final_recurrent(
                            vc, jnp.full((1,), p.plen - 1, jnp.int32)
                        )
                    else:
                        logits, p.cache1 = self._prefill(
                            self.params, p.prompt, None, self._unit_cache
                        )
                        p.logits1 = logits[:, -1]
                p.pos = p.plen
                p.prefill_chunks += 1
                self._count_prefill(p.plen, p.plen)
                return p.plen
            n = p.plen - p.pos
            if left is not None:
                n = min(n, left)
            if p.chunked:
                n = min(n, self.chunk_buckets[-1])
            b = self._bucket_for(p.pos, n) if p.chunked else n
            chunk = p.prompt[:, p.pos : p.pos + n]
            if b > n:
                chunk = jnp.pad(chunk, ((0, 0), (0, b - n)))
            cache = p.cache1 if p.cache1 is not None else self._unit_cache
            with span(
                self.tracer, "engine/prefill_chunk", lane="engine",
                pos=p.pos, n_tokens=n, padded=b,
            ):
                if p.collect:
                    last, vc = self._suffix_collect(
                        self.params, chunk, cache, jnp.int32(p.pos),
                        jnp.int32(n - 1),
                    )
                    p.stack_segments.append((p.pos, vc))
                    p.cache1 = self._final_recurrent(
                        vc, jnp.full((1,), n - 1, jnp.int32)
                    )
                else:
                    last, p.cache1 = self._prefill_chunk(
                        self.params, chunk, cache, jnp.int32(p.pos),
                        jnp.int32(n - 1),
                    )
            p.pos += n
            p.prefill_chunks += 1
            self._count_prefill(n, b)
            if p.pos >= p.plen:
                p.logits1 = last
            return n
        spec = self._slot_spec
        with span(
            self.tracer, "engine/prefill_draft", lane="engine",
            prompt_len=p.plen, q_draft=spec.q_draft,
        ):
            _, p.dcache1 = self._prefill(
                self.draft_params(spec.q_draft), p.prompt, None,
                self._unit_cache,
            )
        return p.plen

    def advance_admission(
        self, pending: PendingAdmission, budget: Optional[int] = None
    ) -> int:
        """Run the pending prefill forward by up to ``budget`` prompt tokens
        (``None`` = to completion); returns tokens consumed. The scheduler
        calls this once per step with its chunk budget, interleaved with
        decode dispatches.

        The speculative draft prefill is always whole-shot (its prompt in one
        dispatch, charged entirely to the step it runs in): the draft cache
        has no prefix blocks to reuse, and splitting it would double the
        chunk machinery for a path whose forward is already the cheap
        ``q_draft``-bit truncation."""
        consumed = 0
        while not pending.done:
            left = None if budget is None else budget - consumed
            if left is not None and left <= 0:
                break
            consumed += self._advance_once(pending, left)
        return consumed

    def finish_admission(
        self, slots: dict, slot: int, pending: PendingAdmission
    ) -> dict:
        """Install a completed admission into ``slot`` and commit the
        prompt's prefix blocks back to the cache (gathered from the final
        batch-1 cache under ref-count; commit happens only after a
        successful install, so a failed install aborts instead)."""
        p = pending
        if not p.done:
            raise ValueError(
                "admission is not finished — drive advance_admission until "
                "pending.done before finish_admission"
            )
        h = p.handle
        if h is not None and h.new_spans and not h.closed and not h.rows:
            bt = self.prefix_cache.block_tokens
            for s, e in h.new_spans:
                h.rows.append(self._gather_block(p.cache1, jnp.int32(s), bt))
                if self._has_recurrent:
                    st, vc = next(
                        seg for seg in reversed(p.stack_segments)
                        if seg[0] < e
                    )
                    h.snaps.append(
                        self._boundary_snap(
                            vc, jnp.full((1,), e - 1 - st, jnp.int32)
                        )
                    )
                else:
                    h.snaps.append(None)
        greedy = p.temperature <= 0
        args = (
            jnp.int32(p.plen),
            jnp.int32(p.max_new_tokens),
            jnp.float32(p.temperature if not greedy else 1.0),
            jnp.bool_(greedy),
        )
        if self._slot_spec is None:
            with span(self.tracer, "engine/admit", lane="engine", slot=slot):
                out = self._admit(
                    slots, jnp.int32(slot), p.cache1, p.logits1,
                    jax.random.PRNGKey(p.seed), *args,
                )
        else:
            with span(
                self.tracer, "engine/admit", lane="engine", slot=slot, spec=True
            ):
                out = self._admit_spec(
                    slots, jnp.int32(slot), p.cache1, p.dcache1, p.logits1,
                    jax.random.PRNGKey(p.seed),
                    jax.random.PRNGKey(p.seed ^ 0x5BEC),
                    *args, jnp.bool_(p.speculate),
                )
        if h is not None:
            self.prefix_cache.complete(h)
        self._last_prefix_handle = h
        return out

    def abort_admission(self, pending: Optional[PendingAdmission]) -> None:
        """Release a pending admission that will never finish (cancel,
        deadline, prefill fault): unpins its prefix handle without
        committing. Safe to call with ``None`` or repeatedly."""
        if pending is not None and pending.handle is not None:
            self.prefix_cache.abort(pending.handle)

    def take_prefix_handle(self) -> Optional[PrefixHandle]:
        """Pop the (already committed/closed) prefix handle of the most
        recent ``admit_slot``/``finish_admission`` — the scheduler reads hit
        stats off it for lifecycle stamps and trace instants."""
        h, self._last_prefix_handle = self._last_prefix_handle, None
        return h

    def admit_slot(
        self,
        slots: dict,
        slot: int,
        prompt_tokens,
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        seed: int = 0,
        speculate: bool = True,
    ) -> dict:
        """Prefill one request (batch-1) and install it into `slot`.

        The prefill compiles per distinct prompt length (same caveat as
        `generate`); the install itself compiles once. The slot then produces
        the exact token stream a solo `generate(prompt, max_new_tokens,
        temperature=..., seed=...)` would.

        With a ``prefix_cache`` attached, the longest committed prefix of the
        prompt is installed from cached rows and only the suffix prefills —
        tokens stay bit-identical to the cold path (DESIGN.md §12); the
        prompt's own prefix blocks are committed back on success. This is
        the synchronous composition of ``begin_admission`` →
        ``advance_admission`` → ``finish_admission``.

        In speculative slot batches (``init_slots(speculate=...)``) the draft
        model is prefilled too and the request's FIRST token is sampled at
        admission (recorded in ``slots["t_pend"][slot]`` and counted against
        the budget — the caller must emit it). ``speculate=False`` opts the
        request out per-row: it decodes one plain target token per chunk with
        its solo-identical PRNG stream.
        """
        pending = self.begin_admission(
            prompt_tokens, max_new_tokens=max_new_tokens,
            temperature=temperature, seed=seed, speculate=speculate,
        )
        try:
            self.advance_admission(pending)
            return self.finish_admission(slots, slot, pending)
        except Exception:
            self.abort_admission(pending)
            raise

    def decode_slots(self, slots: dict, n_steps: int):
        """Run `n_steps` decode steps over the whole slot batch.

        Returns `(tokens (B, n_steps) int32, active (B, n_steps) bool,
        new_slots)`; `tokens[b, t]` is a real emission iff `active[b, t]`.
        """
        with span(
            self.tracer, "engine/scan_decode", lane="engine", n_steps=n_steps
        ):
            return self._scan_decode_slots(self.params, slots, n_steps=n_steps)

    def spec_decode_slots(self, slots: dict, n_chunks: int):
        """Run `n_chunks` speculative chunks over the whole slot batch.

        Returns `(tokens (B, n_chunks*(gamma+1)) int32, valid (B, same) bool,
        new_slots)`; each chunk contributes between 1 and gamma+1 valid tokens
        per active row (1 exactly for rows admitted with speculate=False).
        """
        spec = self._slot_spec
        if spec is None or "draft_cache" not in slots:
            raise ValueError("slots were not initialised with speculate=...")
        with span(
            self.tracer, "engine/spec_chunks", lane="engine",
            n_chunks=n_chunks, gamma=spec.gamma,
        ):
            return self._scan_spec_slots(
                self.params, self.draft_params(spec.q_draft), slots,
                n_chunks=n_chunks, gamma=spec.gamma,
            )

    def release_slot(self, slots: dict, slot: int) -> dict:
        """Reclaim one slot at a chunk boundary (cancel/timeout/quarantine):
        the row goes inactive with zero budget and stops emitting; neighbours
        are untouched (per-row masks) and the next admission overwrites the
        row's whole state. Zero trace on surviving rows — asserted by
        tests/test_lifecycle.py's survivor-invariance suite."""
        return self._release(slots, jnp.int32(slot))

    def finite_logit_rows(self, slots: dict) -> np.ndarray:
        """(B,) host bools: row b's carried next-token logits are all finite.
        The scheduler's NaN/inf guard polls this at chunk boundaries and
        quarantines exactly the poisoned rows."""
        return np.asarray(self._finite_rows(slots["logits"]))  # staticcheck: host-sync(the documented chunk-boundary guard poll — (B,) bools, not (B, vocab))

    def poison_logit_row(self, slots: dict, slot: int) -> dict:
        """Fault-injection hook (infer/faults.py): overwrite one row's
        carried logits with NaN, exactly what an upstream numerical fault
        would leave behind. Host-side, between dispatches — never inside a
        jitted computation."""
        return dict(slots, logits=slots["logits"].at[slot].set(jnp.nan))

    def generate(
        self,
        prompt_tokens: np.ndarray,
        n_steps: int,
        *,
        image_emb: Optional[np.ndarray] = None,
        temperature: float = 0.0,
        seed: int = 0,
        scan: bool = True,
        speculate: Optional[SpecConfig] = None,
        stop_tokens=None,
    ) -> GenerationResult:
        """Greedy (temperature=0) or sampled autoregressive generation.

        ``scan=True`` (default) runs the whole decode as one on-device
        ``lax.scan`` for tokens-input models; ``scan=False`` forces the
        per-token step loop (always used for embedding-input models, whose
        host-side ``embed_fn`` cannot run inside the scan).

        ``n_steps`` is a static scan length: each *distinct* value compiles
        its own scan graph once (then cached for the engine's lifetime).
        Serving highly variable lengths? Bucket them, or use ``scan=False``
        whose single ``_decode`` compilation covers every length.

        ``speculate=SpecConfig(q_draft, gamma)`` decodes self-speculatively
        (DESIGN.md §5): a ``q_draft``-bit truncation of the same params drafts
        ``gamma`` tokens per chunk and the full-precision model verifies them
        in one batched forward — greedy output is token-identical to plain
        greedy decode; ``temperature>0`` output follows the exact target
        distribution via rejection sampling (a *different* stream than the
        plain path's for the same seed — per-row PRNG streams). The result's
        ``spec_stats`` reports the draft acceptance rate.

        ``stop_tokens`` (iterable of token ids) marks per-row early stops:
        decode still runs the full ``n_steps`` budget (the scan length is
        static), but the result records each row's first stop position and
        ``GenerationResult.generated(b)`` returns the truncated completion —
        token-identical, up to the stop, to the untruncated run. The
        *serving* path (``Scheduler``) additionally frees the slot at the
        next chunk boundary, which is where the early exit buys throughput."""
        cfg = self.cfg
        b, s = prompt_tokens.shape[:2]
        if s + n_steps > self.max_seq:
            # the KV cache has exactly max_seq rows per slot; decoding past
            # them would wrap/garble device-side state with no error raised
            raise ValueError(
                f"prompt_len({s}) + n_steps({n_steps}) exceeds the engine's "
                f"cache length max_seq={self.max_seq} — decode past the cache "
                f"produces device-side garbage (build the Engine with a "
                f"larger max_seq or shorten the request)"
            )
        if cfg.input_kind == "tokens":
            pt = np.asarray(prompt_tokens)  # staticcheck: host-sync(prompt validation before any device work)
            if pt.size and (pt.min() < 0 or pt.max() >= cfg.vocab):
                raise ValueError(
                    f"prompt token ids must lie in [0, vocab={cfg.vocab}); got "
                    f"range [{pt.min()}, {pt.max()}] — out-of-range ids index "
                    f"garbage embedding rows device-side"
                )
        cache = self._make_cache(b)
        with span(
            self.tracer, "engine/prefill", lane="engine", prompt_len=s, batch=b
        ):
            logits, cache = self._prefill(
                self.params, jnp.asarray(prompt_tokens), image_emb, cache
            )
        key = jax.random.PRNGKey(seed)
        greedy = temperature <= 0

        def _result(tokens: np.ndarray, **kw) -> GenerationResult:
            sp = None
            if stop_tokens:
                sp = stop_positions_for(tokens[:, s:], stop_tokens)
            return GenerationResult(
                tokens=tokens, prompt_len=s, steps=n_steps,
                stop_positions=sp, **kw,
            )

        if speculate is not None:
            self._validate_spec(speculate)
            if s + n_steps + speculate.gamma > self.max_seq:
                raise ValueError(
                    f"prompt({s}) + n_steps({n_steps}) + gamma({speculate.gamma}) "
                    f"exceeds max_seq={self.max_seq}"
                )
            draft = self.draft_params(speculate.q_draft)
            dcache = self._make_cache(b)
            with span(
                self.tracer, "engine/prefill_draft", lane="engine",
                prompt_len=s, batch=b, q_draft=speculate.q_draft,
            ):
                _, dcache = self._prefill(
                    draft, jnp.asarray(prompt_tokens), image_emb, dcache
                )
            with span(
                self.tracer, "engine/spec_generate", lane="engine",
                n_steps=n_steps, gamma=speculate.gamma,
            ):
                toks, (acc, prop, chunks) = self._spec_generate(
                    self.params, draft, logits[:, -1], cache, dcache,
                    jnp.full((b,), s, jnp.int32), key,
                    jax.random.PRNGKey(seed ^ 0x5BEC),
                    jnp.float32(temperature if not greedy else 1.0),
                    n_steps=n_steps, gamma=speculate.gamma, greedy=greedy,
                )
            tokens = np.concatenate(
                [np.asarray(prompt_tokens), np.asarray(toks)], axis=1  # staticcheck: host-sync(one fetch for the whole speculative generation)
            )
            acc, prop, chunks = int(acc), int(prop), int(chunks)
            return _result(
                tokens,
                spec_stats={
                    "accept_rate": acc / max(prop, 1),
                    "accepted": acc,
                    "proposed": prop,
                    "chunks": chunks,
                    "q_draft": speculate.q_draft,
                    "gamma": speculate.gamma,
                },
            )

        if scan and cfg.input_kind == "tokens":
            with span(
                self.tracer, "engine/scan_decode", lane="engine",
                n_steps=n_steps, batch=b,
            ):
                toks, _ = self._scan_decode(
                    self.params,
                    logits[:, -1],
                    cache,
                    jnp.int32(s),
                    key,
                    jnp.float32(temperature if not greedy else 1.0),
                    n_steps=n_steps,
                    greedy=greedy,
                )
            tokens = np.concatenate([np.asarray(prompt_tokens), np.asarray(toks)], axis=1)  # staticcheck: host-sync(one fetch for the whole scanned decode)
            return _result(tokens)

        out = [np.asarray(prompt_tokens)] if cfg.input_kind == "tokens" else []  # staticcheck: host-sync(prompt is host input)
        for step in range(n_steps):
            if not greedy:
                key, sub = jax.random.split(key)
                tok = _sample(logits[:, -1], sub, temperature, greedy=False)[:, None]
            else:
                tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            out.append(np.asarray(tok))  # staticcheck: host-sync(per-token step loop — the scan path exists to avoid this)
            if cfg.input_kind != "tokens":
                if self.embed_fn is None:
                    raise ValueError(
                        "embedding-input model: pass embed_fn to Engine to map "
                        "sampled codes back to frame embeddings"
                    )
                tok = jnp.asarray(self.embed_fn(np.asarray(tok))).astype(cfg.cdtype)  # staticcheck: host-sync(embed_fn is host-side by contract)
            logits, cache = self._decode(
                self.params, tok, cache, jnp.int32(s + step)
            )
        tokens = np.concatenate(out, axis=1)
        if cfg.input_kind != "tokens":
            if stop_tokens:
                raise ValueError(
                    "stop_tokens is only supported for tokens-input models "
                    "(modality-stub outputs are code streams, not vocab ids)"
                )
            return GenerationResult(tokens=tokens, prompt_len=s, steps=n_steps)
        return _result(tokens)
