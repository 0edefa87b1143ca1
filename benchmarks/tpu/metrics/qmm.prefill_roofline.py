"""Roofline time of the prefill chunks' quantized linears over the device
time of the quantized-matmul kernels inside the prefill-chunk programs.

Work: every packed matrix read once per chunk, 2 k o flops per prompt token,
and the output head for the one row per prompt the traffic needs (its last
token), from the model's shapes (``tpubench.work``)."""

from tpubench import trace, work

KERNELS = r"bcq_mm|uniform_mm|ternary_mm|dequant_mm|lutgemm|codebook_mm"
PROGRAM = r"_prefill_chunk"


def read(run):
    chunks = run.window.prefill_chunks
    if run.trace is None or not chunks:
        return None
    t = trace.ops_s(run.trace, KERNELS, PROGRAM)
    if not t:
        return None
    tokens = sum(n for _, n, _ in chunks)
    last = sum(1 for pos, n, plen in chunks if pos + n >= plen)
    flops, bytes_ = work.qmm_work(run.arch, run.mc, run.qc, passes=len(chunks),
                                  rows=tokens, head_rows=last, tp=run.tp)
    return 100.0 * work.roofline_s(flops, bytes_, run.peak) / t
