"""Step programs: device time per step under the scope `mtp`
(`models/mla_moe.py`: the multi-token module: its merge, its expert block
with attention, its head and loss). Its attention and experts are ALSO under
`mla_ms` / `moe_experts_ms`, which sum a scope wherever it lies. Nothing
to read without a trace or in a program that names no such scope."""

from benchmark import tracing

SCOPES = ("mtp",)


def read(ctx):
    r = ctx["reduced"]
    if r is None:
        return None
    found = tracing.step_module(r)
    secs = sum(tracing.under(r, scope) for scope in SCOPES)
    if secs <= 0 or found is None or not found[1]["count"]:
        return None
    return 1e3 * secs / found[1]["count"]
