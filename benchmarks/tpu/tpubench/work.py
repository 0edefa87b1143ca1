"""Operations and bytes the served work needs, from the model's shapes and
the tokens the traffic asked for: never from a kernel's buffers, so the same
work is counted whatever kernel does it.

``bcq_bytes`` is paper Eq. 3, copied from ``benchmarks/common.py``.
Activations are counted in bf16, as the configurations state.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from tpubench.weights import dims, linears

ACT_BYTES = 2  # bf16


def bcq_bytes(m: int, n: int, q: int, g: int, scale_bytes: int = 2) -> int:
    """Packed BCQ footprint of an (m x n) matrix (paper Eq. 3)."""
    return q * (m * n // 8) + q * (m * n // g) * scale_bytes


def _layer_linears(mc: dict):
    return [(k, o) for _, k, o, per_layer in linears(mc) if per_layer]


def _head(mc: dict) -> Tuple[int, int]:
    return next((k, o) for name, k, o, _ in linears(mc) if name == "lm_head")


def linear_params(mc: dict, head: bool = True) -> int:
    n = dims(mc)["L"] * sum(k * o for k, o in _layer_linears(mc))
    if head:
        k, o = _head(mc)
        n += k * o
    return n


def qmm_work(mc: dict, qc: dict, passes: int, rows: int,
             head_rows: int) -> Tuple[float, float]:
    """(flops, bytes) of ``passes`` forward passes' quantized linears over
    ``rows`` tokens in all, with the output head applied to ``head_rows`` of
    them. Each pass reads every matrix once."""
    q, g = qc["q"], qc["g"]
    L = dims(mc)["L"]
    flops = bytes_ = 0.0
    for k, o in _layer_linears(mc):
        flops += L * 2.0 * rows * k * o
        bytes_ += L * (passes * bcq_bytes(k, o, q, g) + ACT_BYTES * rows * (k + o))
    k, o = _head(mc)
    flops += 2.0 * head_rows * k * o
    bytes_ += passes * bcq_bytes(k, o, q, g) + ACT_BYTES * head_rows * (k + o)
    return flops, bytes_


def roofline_s(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_s"])


def attention_flops(mc: dict, contexts: Iterable[int]) -> float:
    """QK^T and PV of one token per entry, against that many cached keys."""
    d = dims(mc)
    return 4.0 * d["L"] * d["Q"] * float(sum(contexts))
