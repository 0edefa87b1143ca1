"""Share of decode slot-steps that carried a live request in the counted span
``[0, count_s)``: ``steps_active / (decode_steps * n_slots)`` from the
scheduler's counters."""


def read(run):
    c = run.window.counters
    if not c["decode_steps"]:
        return None
    return 100.0 * c["steps_active"] / (c["decode_steps"] * c["n_slots"])
