"""Median time from a request's scheduled arrival to the start of its
prefill (the scheduler's first admission transition, from its
``RequestLifecycle`` record), over requests that arrived in the counted span
``[0, count_s)``: the wait for a chunk boundary and for a free slot. The
loop submits only between scheduler steps, so the scheduled arrival stands
for the submit of a front end that accepts requests at any time. A traced
run stops its profiler only once each of those requests has its first
token, so the write-out of the trace delays none of them."""

import statistics


def read(run):
    waits = [r.admitted - r.arrival for r in run.window.counted() if r.admitted is not None]
    return statistics.median(waits) if waits else None
