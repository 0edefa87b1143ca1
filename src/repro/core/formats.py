"""QuantFormat — the pluggable quantization-format protocol + registry.

The kernel/format boundary is a real, stable API seam (FLUTE generalises LUT
kernels over arbitrary codebooks; FineQuant ships group-wise uniform int-q
behind the same serving stack): a *format* owns how a weight is packed, how
its kernels consume the packed form, how it shards under tensor parallelism,
and which capabilities it supports. Everything else in the framework —
layers, the fuser, the autotuner, TP placement, the engines — talks to the
registry through :func:`repro.kernels.ops.qmatmul` and the methods below, and
never branches on a concrete format again (DESIGN.md §2.4).

Registered formats
------------------
``bcq``      group-wise binary-coding quantization (the paper, §III): q sign
             planes + q per-group scale planes. Kernels: ``bcq_mm`` (unpack →
             MXU, TPU-native) and ``lutgemm`` (paper-faithful LUT). Supports
             nested truncation (self-speculative drafts) and fusion.
``uniform``  FineQuant-style group-wise uniform int-q: q magnitude bit planes
             + a (scale, zero) affine pair per group. Kernel: ``uniform_mm``
             (unpack → affine → MXU, one pass). Supports fusion.
``dequant``  the paper's comparison target — identical packing to ``uniform``
             but served through an explicit dequantize-into-HBM-then-GEMM
             pipeline (``dequant_mm``). Exists so the baseline side of
             Table 3 / Fig. 9 is executable code, not just a citation.
``codebook`` FLUTE-style arbitrary codebook: per-(group, column) table of
             ``2^q`` learned scalar centroids (k-means, or the fixed NF4 grid
             via ``method="nf4"``) with ``q`` index bit planes. Kernel:
             ``codebook_mm`` (LUT retrieve from the VMEM-resident table →
             MXU) — the paper's LUT mechanism generalized beyond sign
             patterns.
``ternary``  T-MAC ``tl2``-style {-1, 0, +1}: two packed bit planes (sign +
             mask) and ONE per-group magnitude ``alpha``. Kernel:
             ``ternary_mm``. Ternary is masked BCQ (``t = 0.5·b1 + 0.5·b2``),
             so it supports ``truncate`` — self-speculation gets a nested
             1-plane BCQ draft at sub-1-bit cost.

Shared physical layout (so sharding/fusion/stacking machinery is generic):
``packed (…, P, k//8, o)`` uint8 code planes, ``scales (…, S, k//g, o)`` group
parameters — P, S and the reconstruction rule are the format's business.

Capability matrix
-----------------
============  ========  =========  =====================================
format        truncate  fuse       kernels (autotune impl keys)
============  ========  =========  =====================================
``bcq``       yes       yes        ``bcq_mm``, ``lutgemm``
``uniform``   no        yes        ``uniform_mm``
``dequant``   no        yes        ``dequant_mm`` (materialise + GEMM)
``codebook``  no        yes        ``codebook_mm`` (LUT retrieve + MXU)
``ternary``   yes       yes        ``ternary_mm`` (drafts run ``bcq_mm``)
============  ========  =========  =====================================
"""

from __future__ import annotations

import abc
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import bcq as bcq_lib
from repro.core import packing
from repro.core.qtensor import QuantizedTensor
from repro.kernels import autotune

_SUBLANE = 8


# ---------------------------------------------------------------------------
# shared Pallas dispatch plumbing (padding + autotuned blocks)
# ---------------------------------------------------------------------------


def _pad_o(packed, scales, o: int):
    """Pad the output dim to the lane block when no candidate divides it."""
    pad = autotune.padded_o(o) - o
    if not pad:
        return packed, scales, o
    packed = jnp.pad(packed, ((0, 0), (0, 0), (0, pad)))
    scales = jnp.pad(scales, ((0, 0), (0, 0), (0, pad)))
    return packed, scales, o + pad


def _pallas_matvec(
    xb, qt: QuantizedTensor, kernel_fn, impl: str, interpret: bool
) -> jax.Array:
    """Padded (B, k) @ qt → (B, o_padded) f32 through a format's Pallas kernel.

    Normalises the batch to the sublane width and the output dim to a valid
    lane block, resolves ``(block_k, block_o)`` through the measured autotuner
    (keys carry ``impl``, so per-format winners never collide), and dispatches.
    """
    packed, scales, o = _pad_o(qt.packed, qt.scales, qt.o)
    B = xb.shape[0]
    pad_b = -B % _SUBLANE
    if pad_b:
        xb = jnp.pad(xb, ((0, pad_b), (0, 0)))
    block_k, block_o = autotune.get_blocks(
        B=xb.shape[0], k=qt.k, o=o, q=qt.q, g=qt.g, impl=impl, interpret=interpret
    )
    if not block_k:
        raise ValueError(f"k={qt.k} has no valid Pallas tiling (g={qt.g})")
    if not block_o:
        raise ValueError(f"o={o} has no valid Pallas tiling")
    y = kernel_fn(
        xb,
        packed,
        scales,
        g=qt.g,
        block_k=block_k,
        block_o=block_o,
        interpret=interpret,
    )
    return y[:B]


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------


class QuantFormat(abc.ABC):
    """One quantization format: packing, kernels, sharding, capabilities.

    Subclasses set ``name`` (the registry key), ``impls`` (Pallas kernel ids
    in preference order — also the autotune-table ``impl`` key axis) and the
    capability flags, and implement ``quantize``/``dequantize``/``matvec``.
    The base class provides the shared-layout defaults for everything else
    (``matmul``, ``nbytes``, ``fuse``, ``tp_specs``, ``relocalize``).
    """

    name: str
    impls: Tuple[str, ...] = ()
    supports_truncate: bool = False  # nested low-bit views (speculative drafts)
    supports_fuse: bool = True  # output-dim fusion (fused QKV / gate-up)

    # -- pack / unpack -------------------------------------------------------

    @abc.abstractmethod
    def quantize(
        self,
        w: jax.Array,
        *,
        q: int,
        g: int,
        scale_dtype=jnp.bfloat16,
        method: str = "alternating",
        iters: int = 8,
    ) -> QuantizedTensor:
        """Quantize + pack a dense 2-D ``(k, o)`` weight. Must be traceable
        (``quant/quantize.py`` maps it over layer-stacked leaves)."""

    @abc.abstractmethod
    def dequantize(self, qt: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
        """Reconstruct the dense ``(…, k, o)`` matrix (supports leading
        layer/expert stacking)."""

    # -- kernel entries ------------------------------------------------------

    @abc.abstractmethod
    def matvec(
        self, xb: jax.Array, qt: QuantizedTensor, *, impl: str, interpret: bool
    ) -> jax.Array:
        """Decode entry: ``(B, k) @ qt → (B, o≥) f32`` consuming the packed
        form directly through the named Pallas kernel (``impl ∈ self.impls``;
        output may carry lane padding — callers slice ``[:, :qt.o]``)."""

    def matmul(self, xb: jax.Array, qt: QuantizedTensor, *, dtype) -> jax.Array:
        """Prefill / oracle entry: dequantize into the compute dtype and run
        one dense dot (XLA-fusable; on TPU deployments the Pallas ``matvec``
        replaces this HLO region — paper Fig. 13's stage split)."""
        w = self.dequantize(qt, dtype=dtype)
        return jnp.dot(xb, w, preferred_element_type=jnp.float32)

    def resolve_impl(
        self, impl: str, interpret: Optional[bool]
    ) -> Tuple[str, bool]:
        """``auto`` → this format's preferred kernel on TPU, ``ref`` elsewhere."""
        if impl == "auto":
            on_tpu = jax.default_backend() == "tpu"
            impl = self.impls[0] if (on_tpu and self.impls) else "ref"
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if impl != "ref" and impl not in self.impls:
            raise ValueError(
                f"format {self.name!r} has no kernel impl {impl!r}; "
                f"available: {('ref',) + tuple(self.impls)}"
            )
        return impl, interpret

    # -- accounting ----------------------------------------------------------

    def nbytes(self, qt: QuantizedTensor) -> int:
        """Packed size in bytes (code planes + scales)."""
        return (
            int(qt.packed.size)
            + int(qt.scales.size) * jnp.dtype(qt.scales.dtype).itemsize
        )

    def scales_shape(self, q: int, groups: int, o: int) -> Tuple[int, ...]:
        """Shape of the per-(group, column) parameter planes."""
        raise NotImplementedError

    def struct(
        self, lead: Tuple[int, ...], k: int, o: int, q: int, g: int, scale_dtype
    ) -> QuantizedTensor:
        """ShapeDtypeStruct-leaved container (dry-run lowering of huge models
        without allocating them — ``quant/quantize.py::quantized_structs``)."""
        return QuantizedTensor(
            packed=jax.ShapeDtypeStruct((*lead, q, k // 8, o), jnp.uint8),
            scales=jax.ShapeDtypeStruct(
                (*lead, *self.scales_shape(q, k // g, o)), jnp.dtype(scale_dtype)
            ),
            g=g,
            k=k,
            o=o,
            fmt=self.name,
        )

    # -- capabilities --------------------------------------------------------

    def truncate(self, qt: QuantizedTensor, q_new: int) -> QuantizedTensor:
        """Nested ``q_new``-bit view. Only formats whose planes are successive
        residual refinements (BCQ) can offer this; everything else refuses."""
        raise ValueError(
            f"format {self.name!r} does not support nested truncation "
            "(self-speculative drafts need a residual-nested format like 'bcq')"
        )

    def fuse(self, qts: Sequence[QuantizedTensor]) -> QuantizedTensor:
        """Concatenate N projections along the output dim (shared-layout
        default — valid for every plane-packed format)."""
        if not self.supports_fuse:
            raise ValueError(
                f"format {self.name!r} does not support output-dim fusion"
            )
        first = qts[0]
        for t in qts[1:]:
            if (t.k, t.q, t.g) != (first.k, first.q, first.g):
                raise ValueError(
                    f"cannot fuse: (k, q, g) mismatch {(t.k, t.q, t.g)} vs "
                    f"{(first.k, first.q, first.g)}"
                )
            if t.scales.dtype != first.scales.dtype:
                raise ValueError("cannot fuse: scale dtype mismatch")
            if t.packed.shape[:-1] != first.packed.shape[:-1]:
                raise ValueError("cannot fuse: leading (layer/expert) dims differ")
        return QuantizedTensor(
            packed=jnp.concatenate([t.packed for t in qts], axis=-1),
            scales=jnp.concatenate([t.scales for t in qts], axis=-1),
            g=first.g,
            k=first.k,
            o=sum(t.o for t in qts),
            fmt=first.fmt,
        )

    # -- tensor parallelism --------------------------------------------------

    def tp_specs(self, dense_spec: P, qt: QuantizedTensor, ax) -> QuantizedTensor:
        """PartitionSpec-leaved container matching the dense weight's
        (possibly layer-stacked) spec ``(…lead, k_ax, o_ax)``.

        Shared-layout rule (subsumes the old BCQ-only ``qt_specs_like`` group
        divisibility logic): the packed k-rows (``k/8``) and the scale groups
        (``k/g``) shard along ``k_ax`` only when the mesh axis divides them —
        group scales must travel WITH the k-rows they scale (the paper's
        group-wise-TP argument, §V.C); an axis that doesn't divide is dropped
        (replicated) and it is the *caller's* job to refuse loudly when
        sharding was mandatory (``parallel/tp.py``)."""
        *lead, k_ax, o_ax = tuple(dense_spec)
        kc = qt.packed.shape[-2]
        kg = qt.scales.shape[-2]

        def keep(axis, dim):
            if axis is None:
                return None
            size = ax.size(axis)
            return axis if (size > 0 and dim % size == 0) else None

        return QuantizedTensor(
            packed=P(*lead, None, keep(k_ax, kc), o_ax),
            scales=P(*lead, None, keep(k_ax, kg), o_ax),
            g=qt.g,
            k=qt.k,
            o=qt.o,
            fmt=qt.fmt,
        )

    def relocalize(self, qt: QuantizedTensor) -> QuantizedTensor:
        """Fix static ``(k, o)`` to per-device shard shapes (shard_map hands
        the body local planes but the statics still say the global shape)."""
        return QuantizedTensor(
            packed=qt.packed,
            scales=qt.scales,
            g=qt.g,
            k=qt.packed.shape[-2] * 8,
            o=qt.packed.shape[-1],
            fmt=qt.fmt,
        )


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, QuantFormat] = {}


def register_format(fmt: QuantFormat) -> QuantFormat:
    """Register a format instance under ``fmt.name`` (last write wins)."""
    _REGISTRY[fmt.name] = fmt
    return fmt


def get_format(name: str) -> QuantFormat:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown quantization format {name!r}; registered formats: "
            f"{sorted(_REGISTRY)}"
        ) from None


def format_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# bcq — the paper's format (group-wise binary-coding quantization)
# ---------------------------------------------------------------------------


class BCQFormat(QuantFormat):
    name = "bcq"
    impls = ("bcq_mm", "lutgemm")
    supports_truncate = True

    def quantize(
        self, w, *, q, g, scale_dtype=jnp.bfloat16, method="alternating", iters=8
    ) -> QuantizedTensor:
        k, o = w.shape
        if method == "alternating":
            scales, binary = bcq_lib.quantize_bcq(w, q=q, g=g, iters=iters)
        elif method == "greedy":
            scales, binary = bcq_lib.quantize_bcq_greedy(w, q=q, g=g)
        else:
            raise ValueError(f"unknown method {method!r}")
        return QuantizedTensor(
            packed=packing.pack_signs(binary),
            scales=scales.astype(scale_dtype),
            g=g,
            k=k,
            o=o,
            fmt=self.name,
        )

    def dequantize(self, qt, dtype=jnp.float32):
        signs = packing.unpack_signs(qt.packed)  # (…, q, k, o) int8
        w = bcq_lib.dequantize(qt.scales.astype(jnp.float32), signs, qt.g)
        return w.astype(dtype)

    def matvec(self, xb, qt, *, impl, interpret):
        from repro.kernels.bcq_mm import bcq_mm
        from repro.kernels.lutgemm import lutgemm

        fn = {"bcq_mm": bcq_mm, "lutgemm": lutgemm}[impl]
        return _pallas_matvec(xb, qt, fn, impl, interpret)

    def scales_shape(self, q, groups, o):
        return (q, groups, o)

    def truncate(self, qt, q_new):
        """The nested ``q_new``-bit approximation: the greedy solver builds
        plane ``i`` as a refinement of the residual left by planes ``< i``
        (paper §III.A), so ``packed[:q_new], scales[:q_new]`` is bit-identical
        to what the solver would emit at ``q=q_new``. The slice is a view at
        trace time; ``g, k, o`` and leading stacking are preserved."""
        if not 1 <= q_new <= qt.q:
            raise ValueError(f"cannot truncate q={qt.q} tensor to q'={q_new}")
        if q_new == qt.q:
            return qt
        return QuantizedTensor(
            packed=qt.packed[..., :q_new, :, :],
            scales=qt.scales[..., :q_new, :, :],
            g=qt.g,
            k=qt.k,
            o=qt.o,
            fmt=qt.fmt,
        )


# ---------------------------------------------------------------------------
# uniform — FineQuant-style group-wise uniform int quantization
# ---------------------------------------------------------------------------


class UniformFormat(QuantFormat):
    name = "uniform"
    impls = ("uniform_mm",)

    def quantize(
        self, w, *, q, g, scale_dtype=jnp.bfloat16, method="alternating", iters=8
    ) -> QuantizedTensor:
        """Closed-form per-group affine: ``code = round((w - min) / s)`` with
        ``s = (max - min) / (2^q - 1)`` — ``method``/``iters`` are ignored
        (kept in the signature so policies drive every format uniformly)."""
        del method, iters
        k, o = w.shape
        bcq_lib._check_args(k, q, g)
        grouped = w.astype(jnp.float32).reshape(k // g, g, o)
        wmin = grouped.min(axis=1)  # (G, o)
        wmax = grouped.max(axis=1)
        scale = jnp.maximum((wmax - wmin) / (2**q - 1), 1e-8)
        codes = jnp.clip(
            jnp.round((grouped - wmin[:, None, :]) / scale[:, None, :]),
            0,
            2**q - 1,
        )
        packed = packing.pack_codes(codes.reshape(k, o).astype(jnp.uint8), q)
        scales = jnp.stack([scale, wmin]).astype(scale_dtype)  # (2, G, o)
        return QuantizedTensor(
            packed=packed, scales=scales, g=g, k=k, o=o, fmt=self.name
        )

    def dequantize(self, qt, dtype=jnp.float32):
        codes = packing.unpack_codes(qt.packed).astype(jnp.float32)  # (…, k, o)
        s = qt.scales[..., 0, :, :].astype(jnp.float32)  # (…, G, o)
        z = qt.scales[..., 1, :, :].astype(jnp.float32)
        *lead, k, o = codes.shape
        grouped = codes.reshape(*lead, k // qt.g, qt.g, o)
        w = grouped * s[..., :, None, :] + z[..., :, None, :]
        return w.reshape(*lead, k, o).astype(dtype)

    def matvec(self, xb, qt, *, impl, interpret):
        from repro.kernels.uniform_mm import uniform_mm

        return _pallas_matvec(xb, qt, uniform_mm, impl, interpret)

    def scales_shape(self, q, groups, o):
        return (2, groups, o)


# ---------------------------------------------------------------------------
# dequant — the paper's baseline: same packing, dequantize-then-GEMM pipeline
# ---------------------------------------------------------------------------


class DequantFormat(UniformFormat):
    """Identical representation to ``uniform`` (so any latency difference is
    *pipeline*, not packing), served the slow way round: materialise the dense
    weight to HBM, then run a stock GEMM — the OPTQ/nuQmm recipe the paper
    benchmarks against (Table 3 / Fig. 9)."""

    name = "dequant"
    impls = ("dequant_mm",)

    def matvec(self, xb, qt, *, impl, interpret):
        from repro.kernels.dequant_mm import dequant_mm

        return _pallas_matvec(xb, qt, dequant_mm, impl, interpret)


# ---------------------------------------------------------------------------
# codebook — FLUTE-style arbitrary-codebook (learned centroids or NF4 grid)
# ---------------------------------------------------------------------------

# The QLoRA NF4 grid: 16 quantiles of N(0, 1) normalised to [-1, 1]; a weight
# group is coded as ``absmax · level`` — the fixed-codebook special case.
_NF4_LEVELS = (
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0,
)


def _kmeans_centroids(grouped: jax.Array, q: int, iters: int) -> jax.Array:
    """Per-(group, column) 1-D Lloyd k-means: ``(G, g, o)`` → ``(G, 2^q, o)``.

    Quantile init (centroid ``i`` at the ``(i+0.5)/2^q`` percentile of the
    group) then ``iters`` assign/update rounds; an empty cluster keeps its old
    centroid. Fully traceable — ``quant/quantize.py`` maps this over
    layer-stacked leaves under ``jax.lax.map``.
    """
    n = 1 << q
    probs = (jnp.arange(n, dtype=jnp.float32) + 0.5) / n
    cent = jnp.moveaxis(jnp.quantile(grouped, probs, axis=1), 0, 1)  # (G, n, o)

    def step(cent, _):
        d = jnp.abs(grouped[:, :, None, :] - cent[:, None, :, :])  # (G, g, n, o)
        onehot = jax.nn.one_hot(jnp.argmin(d, axis=2), n, axis=2)  # (G, g, n, o)
        counts = onehot.sum(axis=1)  # (G, n, o)
        sums = (grouped[:, :, None, :] * onehot).sum(axis=1)
        cent = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), cent)
        return cent, None

    cent, _ = jax.lax.scan(step, cent, None, length=max(int(iters), 1))
    return cent


class CodebookFormat(QuantFormat):
    """Arbitrary scalar codebook per (group, column): ``2^q`` centroids in the
    scales planes, ``q`` index bit planes in the packed planes. The kernel
    retrieves centroids from the VMEM-resident table — the paper's LUT
    mechanism generalized beyond sign patterns (FLUTE)."""

    name = "codebook"
    impls = ("codebook_mm",)

    def quantize(
        self, w, *, q, g, scale_dtype=jnp.bfloat16, method="alternating", iters=8
    ) -> QuantizedTensor:
        """``method``: any of the shared solver names (``alternating`` /
        ``greedy`` / ``kmeans``) runs per-group Lloyd k-means — policies drive
        every format with one vocabulary; ``nf4`` selects the fixed QLoRA grid
        scaled by the group absmax (requires ``q == 4``)."""
        k, o = w.shape
        bcq_lib._check_args(k, q, g)
        grouped = w.astype(jnp.float32).reshape(k // g, g, o)
        if method == "nf4":
            if q != 4:
                raise ValueError(
                    f"method 'nf4' is a fixed 16-entry codebook; needs q=4, got q={q}"
                )
            levels = jnp.asarray(_NF4_LEVELS, jnp.float32)
            absmax = jnp.max(jnp.abs(grouped), axis=1)  # (G, o)
            cent = levels[None, :, None] * absmax[:, None, :]  # (G, 16, o)
        elif method in ("alternating", "greedy", "kmeans"):
            cent = _kmeans_centroids(grouped, q, iters)
        else:
            raise ValueError(f"unknown method {method!r}")
        codes = jnp.argmin(
            jnp.abs(grouped[:, :, None, :] - cent[:, None, :, :]), axis=2
        )  # (G, g, o)
        packed = packing.pack_codes(codes.reshape(k, o).astype(jnp.uint8), q)
        scales = jnp.swapaxes(cent, 0, 1).astype(scale_dtype)  # (2^q, G, o)
        return QuantizedTensor(
            packed=packed, scales=scales, g=g, k=k, o=o, fmt=self.name
        )

    def dequantize(self, qt, dtype=jnp.float32):
        codes = packing.unpack_codes(qt.packed)  # (…, k, o) int32
        *lead, k, o = codes.shape
        cent = jnp.swapaxes(qt.scales.astype(jnp.float32), -3, -2)  # (…, G, 2^q, o)
        idx = codes.reshape(*lead, k // qt.g, qt.g, o)
        w = jnp.take_along_axis(cent, idx, axis=-2)  # (…, G, g, o)
        return w.reshape(*lead, k, o).astype(dtype)

    def matvec(self, xb, qt, *, impl, interpret):
        from repro.kernels.codebook_mm import codebook_mm

        return _pallas_matvec(xb, qt, codebook_mm, impl, interpret)

    def scales_shape(self, q, groups, o):
        return (1 << q, groups, o)


# ---------------------------------------------------------------------------
# ternary — T-MAC tl2-style {-1, 0, +1} (masked BCQ; supports truncation)
# ---------------------------------------------------------------------------


class TernaryFormat(QuantFormat):
    """{-1, 0, +1} codes as two packed bit planes (sign + nonzero mask) and one
    per-group magnitude ``alpha`` — 2 bits + one scale per group, less storage
    than 2-bit BCQ (which carries two scale planes).

    Ternary IS masked BCQ: ``t = 0.5·b1 + 0.5·b2`` with ``b1 = sign | ~mask``
    and ``b2 = sign & mask`` (bit-wise on the packed bytes) — exact in float
    (``0.5·alpha`` and the ±0.5 sums are exact), which is what makes
    ``truncate`` available: the 1-plane slice is a genuine nested BCQ draft at
    0.5 bits of extra storage over nothing (self-speculation, DESIGN.md §4).
    """

    name = "ternary"
    impls = ("ternary_mm",)
    supports_truncate = True

    PLANES = 2  # sign + mask, fixed — the policy's q does not change storage

    def quantize(
        self, w, *, q, g, scale_dtype=jnp.bfloat16, method="alternating", iters=8
    ) -> QuantizedTensor:
        """TWN-style ternarisation per (group, column): threshold init
        ``Δ = 0.75·mean|w|``, then ``iters`` alternating refinements of
        ``alpha = mean(|w| over mask)`` and ``Δ = alpha/2`` (the 1-D Lloyd
        condition for {-α, 0, +α}). ``q``/``method`` are accepted but do not
        change the stored planes — ternary is fixed at 2."""
        del q, method
        k, o = w.shape
        bcq_lib._check_args(k, self.PLANES, g)
        grouped = w.astype(jnp.float32).reshape(k // g, g, o)
        absg = jnp.abs(grouped)
        delta = 0.75 * absg.mean(axis=1)  # (G, o) — the TWN threshold

        def refine(delta, _):
            mask = absg > delta[:, None, :]
            cnt = jnp.maximum(mask.sum(axis=1), 1)
            alpha = (absg * mask).sum(axis=1) / cnt
            return 0.5 * alpha, alpha

        delta, alphas = jax.lax.scan(refine, delta, None, length=max(int(iters), 1))
        alpha = alphas[-1]
        mask = absg > delta[:, None, :]

        sign_pm = jnp.where(grouped >= 0, 1, -1).astype(jnp.int8)
        mask_pm = jnp.where(mask, 1, -1).astype(jnp.int8)
        planes = jnp.stack([sign_pm, mask_pm]).reshape(self.PLANES, k, o)
        return QuantizedTensor(
            packed=packing.pack_signs(planes),
            scales=alpha[None].astype(scale_dtype),  # (1, G, o)
            g=g,
            k=k,
            o=o,
            fmt=self.name,
        )

    def dequantize(self, qt, dtype=jnp.float32):
        planes = packing.unpack_signs(qt.packed).astype(jnp.float32)  # (…, 2, k, o)
        sign = planes[..., 0, :, :]
        nonzero = (planes[..., 1, :, :] + 1.0) * 0.5  # {-1,+1} → {0,1}
        t = sign * nonzero
        *lead, k, o = t.shape
        alpha = qt.scales.astype(jnp.float32)[..., 0, :, :]  # (…, G, o)
        grouped = t.reshape(*lead, k // qt.g, qt.g, o) * alpha[..., :, None, :]
        return grouped.reshape(*lead, k, o).astype(dtype)

    def matvec(self, xb, qt, *, impl, interpret):
        from repro.kernels.ternary_mm import ternary_mm

        return _pallas_matvec(xb, qt, ternary_mm, impl, interpret)

    def scales_shape(self, q, groups, o):
        return (1, groups, o)

    def struct(self, lead, k, o, q, g, scale_dtype):
        """Ternary stores exactly 2 planes whatever the policy's ``q`` says —
        the dry-run struct must agree with ``quantize`` (staticcheck traces
        through these shapes)."""
        del q
        return QuantizedTensor(
            packed=jax.ShapeDtypeStruct((*lead, self.PLANES, k // 8, o), jnp.uint8),
            scales=jax.ShapeDtypeStruct(
                (*lead, 1, k // g, o), jnp.dtype(scale_dtype)
            ),
            g=g,
            k=k,
            o=o,
            fmt=self.name,
        )

    def as_bcq(self, qt: QuantizedTensor) -> QuantizedTensor:
        """The exact 2-plane BCQ view: ``b1 = sign | ~mask``, ``b2 = sign &
        mask`` on the packed bytes, each plane scaled ``alpha/2``. Float-exact
        (``0.5·alpha`` is an exponent decrement; ``±0.5 ± 0.5 ∈ {-1, 0, 1}``
        is exact), so dequantize(as_bcq(qt)) == dequantize(qt) bit-for-bit."""
        sign = qt.packed[..., 0, :, :]
        mask = qt.packed[..., 1, :, :]
        b1 = sign | ~mask
        b2 = sign & mask
        half = (0.5 * qt.scales.astype(jnp.float32)).astype(qt.scales.dtype)
        return QuantizedTensor(
            packed=jnp.stack([b1, b2], axis=-3),
            scales=jnp.concatenate([half, half], axis=-3),  # (…, 2, G, o)
            g=qt.g,
            k=qt.k,
            o=qt.o,
            fmt="bcq",
        )

    def truncate(self, qt, q_new):
        """Nested draft views via the masked-BCQ identity: ``q_new == 2`` is
        the full-precision self (served by ``ternary_mm``); ``q_new == 1``
        re-tags the ``b1 = sign | ~mask`` plane as a 1-plane BCQ tensor
        (drafts then dispatch through ``bcq_mm`` — ``ops.qmatmul`` routes per
        leaf ``fmt``)."""
        if not 1 <= q_new <= self.PLANES:
            raise ValueError(
                f"cannot truncate ternary tensor to q'={q_new} "
                f"(valid: 1..{self.PLANES})"
            )
        if q_new == self.PLANES:
            return qt
        return get_format("bcq").truncate(self.as_bcq(qt), q_new)


# ---------------------------------------------------------------------------
# registration (formats + their kernels' autotune measurement entries)
# ---------------------------------------------------------------------------

register_format(BCQFormat())
register_format(UniformFormat())
register_format(DequantFormat())
register_format(CodebookFormat())
register_format(TernaryFormat())


def _load_uniform_mm():
    from repro.kernels.uniform_mm import uniform_mm

    return uniform_mm


def _load_dequant_mm():
    from repro.kernels.dequant_mm import dequant_mm

    return dequant_mm


def _load_codebook_mm():
    from repro.kernels.codebook_mm import codebook_mm

    return codebook_mm


def _load_ternary_mm():
    from repro.kernels.ternary_mm import ternary_mm

    return ternary_mm


def _affine_meas_scales(rng, q, k, o, g):
    return rng.standard_normal((2, k // g, o))


def _codebook_meas_scales(rng, q, k, o, g):
    return rng.standard_normal((1 << q, k // g, o))


def _ternary_meas_scales(rng, q, k, o, g):
    del q
    return rng.standard_normal((1, k // g, o))


autotune.register_measure_kernel("uniform_mm", _load_uniform_mm, _affine_meas_scales)
autotune.register_measure_kernel("dequant_mm", _load_dequant_mm, _affine_meas_scales)
autotune.register_measure_kernel("codebook_mm", _load_codebook_mm, _codebook_meas_scales)
autotune.register_measure_kernel("ternary_mm", _load_ternary_mm, _ternary_meas_scales)
