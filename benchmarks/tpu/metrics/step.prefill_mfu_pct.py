"""Model flops of the prompt tokens prefilled in the window over the prefill-
chunk programs' device time at the chip's bf16 peak: 2 flops per linear
parameter per prompt token, the output head once per prompt, and causal
attention (``tpubench.work``)."""

from tpubench import trace, work

PROGRAM = r"_prefill_chunk"


def read(run):
    chunks = run.window.prefill_chunks
    if run.trace is None or not chunks:
        return None
    t = trace.program_s(run.trace, PROGRAM)
    if not t:
        return None
    tokens = sum(n for _, n, _ in chunks)
    last = sum(1 for pos, n, plen in chunks if pos + n >= plen)
    flops = 2.0 * work.linear_params(run.arch, run.mc, head=False) * tokens
    flops += work.head_flops(run.arch, run.mc, last)
    flops += work.attention_flops(run.arch, run.mc, (p + 1 for pos, n, _ in chunks
                                                     for p in range(pos, pos + n)))
    return 100.0 * flops / (t * run.peak["bf16_flops"])
