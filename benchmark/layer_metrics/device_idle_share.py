"""Device: share of the traced window in which no operation ran on the
device's core, averaged over the chips used."""


def read(ctx):
    r = ctx["reduced"]
    if r is None or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
