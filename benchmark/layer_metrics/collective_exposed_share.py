"""Parallel backends: time of collective operations on the first device
during which no other operation ran on its core, as a share of the traced
window. Nothing to read on one chip or where the trace holds no collective."""


def read(ctx):
    r = ctx["reduced"]
    if ctx["chips"] < 2 or r is None or r["collective_s"] <= 0:
        return None
    return 100.0 * r["collective_exposed_s"] / r["window_s"]
