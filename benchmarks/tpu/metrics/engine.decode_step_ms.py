"""Device time of the engine's slot-decode program per scanned decode step,
from the profiler trace."""

from tpubench import trace

PROGRAM = r"_scan_decode_slots"


def read(run):
    if run.trace is None:
        return None
    runs = trace.programs(run.trace, PROGRAM)
    if not runs:
        return None
    steps = len(runs) * run.server["decode_chunk"]
    return 1000.0 * sum(e.dur for e in runs) / 1e9 / steps
