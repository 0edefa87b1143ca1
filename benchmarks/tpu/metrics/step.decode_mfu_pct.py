"""Model flops of the decode tokens committed in the window over the slot-
decode program's device time at the chip's bf16 peak: 2 flops per linear
parameter (output head included) per token, plus attention over each token's
live context (``tpubench.work``)."""

from tpubench import trace, work

PROGRAM = r"_scan_decode_slots"


def read(run):
    if run.trace is None:
        return None
    t = trace.program_s(run.trace, PROGRAM)
    toks = run.window_tokens()
    if not t or not toks:
        return None
    flops = 2.0 * work.linear_params(run.arch, run.mc) * len(toks)
    flops += work.attention_flops(run.arch, run.mc, (plen + j + 1 for plen, j in toks))
    return 100.0 * flops / (t * run.peak["bf16_flops"])
