"""Roofline time of the decode steps' quantized linears over the device time
of the quantized-matmul kernels inside the slot-decode program.

Work: every layer's packed matrices and the output head read once per decode
step (paper Eq. 3 bytes), bf16 activations, and 2 k o flops per committed
token, from the model's shapes (``tpubench.work``). Kernel time: trace ops
whose name matches ``KERNELS``, inside executions of ``PROGRAM``."""

from tpubench import trace, work

KERNELS = r"bcq_mm|uniform_mm|ternary_mm|dequant_mm|lutgemm|codebook_mm"
PROGRAM = r"_scan_decode_slots"


def read(run):
    if run.trace is None:
        return None
    t = trace.ops_s(run.trace, KERNELS, PROGRAM)
    steps = len(trace.programs(run.trace, PROGRAM)) * run.server["decode_chunk"]
    tokens = run.window.counters["steps_active"]
    if not t or not steps:
        return None
    flops, bytes_ = work.qmm_work(run.arch, run.mc, run.qc, passes=steps,
                                  rows=tokens, head_rows=tokens)
    return 100.0 * work.roofline_s(flops, bytes_, run.peak) / t
