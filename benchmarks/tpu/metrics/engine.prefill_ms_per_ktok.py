"""Device time of the engine's prefill-chunk programs per thousand prompt
tokens prefilled in the window (tokens counted by the benchmark around each
``Engine.advance_admission`` call)."""

from tpubench import trace

PROGRAM = r"_prefill_chunk"


def read(run):
    tokens = sum(n for _, n, _ in run.window.prefill_chunks)
    if run.trace is None or not tokens:
        return None
    t = trace.program_s(run.trace, PROGRAM)
    return 1000.0 * t / tokens * 1000.0 if t else None
