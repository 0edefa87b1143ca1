"""Operations and bytes the served work needs, from the model's shapes and
the tokens the traffic asked for: never from a kernel's buffers, so the same
work is counted whatever kernel does it.

The shapes come from the configuration's architecture module
(``tpubench.arch``): each quantized linear with the number of layers that
hold it, and the number of layers with attention. ``bcq_bytes`` is paper
Eq. 3, copied from ``benchmarks/common.py``. Activations are counted in
bf16, as the configurations state.
"""

from __future__ import annotations

from types import ModuleType
from typing import Iterable, Tuple

from tpubench.arch import HEAD

ACT_BYTES = 2  # bf16


def bcq_bytes(m: int, n: int, q: int, g: int, scale_bytes: int = 2) -> int:
    """Packed BCQ footprint of an (m x n) matrix (paper Eq. 3)."""
    return q * (m * n // 8) + q * (m * n // g) * scale_bytes


def linear_params(arch: ModuleType, mc: dict, head: bool = True) -> int:
    return sum(count * k * o for name, k, o, count in arch.linears(mc)
               if head or name != HEAD)


def head_flops(arch: ModuleType, mc: dict, rows: int) -> float:
    """The output head applied to ``rows`` tokens."""
    k, o = next((k, o) for name, k, o, _ in arch.linears(mc) if name == HEAD)
    return 2.0 * rows * k * o


def qmm_work(arch: ModuleType, mc: dict, qc: dict, passes: int, rows: int,
             head_rows: int) -> Tuple[float, float]:
    """(flops, bytes) of ``passes`` forward passes' quantized linears over
    ``rows`` tokens in all, with the output head applied to ``head_rows`` of
    them. Each pass reads every matrix once."""
    q, g = qc["q"], qc["g"]
    flops = bytes_ = 0.0
    for name, k, o, count in arch.linears(mc):
        r = head_rows if name == HEAD else rows
        flops += count * 2.0 * r * k * o
        bytes_ += count * (passes * bcq_bytes(k, o, q, g) + ACT_BYTES * r * (k + o))
    return flops, bytes_


def roofline_s(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_s"])


def attention_flops(arch: ModuleType, mc: dict, contexts: Iterable[int]) -> float:
    """QK^T and PV of one token per entry, against that many cached keys, in
    each layer with attention."""
    return (4.0 * arch.attention_layers(mc) * mc["n_heads"] * mc["d_head"]
            * float(sum(contexts)))
