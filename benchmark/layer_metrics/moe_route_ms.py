"""Step programs: device time per step of the expert layers' routing: the
scopes `route` (sigmoid scores over all experts, top-k, weights),
`dispatch` (the sort of the pairs by expert, the gather into the grouped
buffer) and `combine` (back to token order, the weighted sum), of
`models/mla_moe.py`. Nothing to read without a trace or in a program that
names no such scope."""

from benchmark import tracing

SCOPES = ("route", "dispatch", "combine")


def read(ctx):
    r = ctx["reduced"]
    if r is None:
        return None
    found = tracing.step_module(r)
    secs = sum(tracing.under(r, scope) for scope in SCOPES)
    if secs <= 0 or found is None or not found[1]["count"]:
        return None
    return 1e3 * secs / found[1]["count"]
