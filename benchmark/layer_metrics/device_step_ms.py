"""Step programs: device time of the train-step program per execution, from
the trace's program line (the program with the most device time in the
window is the train step)."""

from benchmark import tracing


def read(ctx):
    if ctx["reduced"] is None:
        return None
    found = tracing.step_module(ctx["reduced"])
    if found is None or not found[1]["count"]:
        return None
    return 1e3 * found[1]["total_s"] / found[1]["count"]
