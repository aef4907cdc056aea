"""Step programs: device time per step under the scope `head`
(`models/mla_moe.py`: the final norm, the logits and, inside it under
`loss`, the cross-entropy, chunked over the sequence; the trunk's head and
the multi-token module's). Nothing to read without a trace or in a program
that names no such scope."""

from benchmark import tracing

SCOPES = ("head",)


def read(ctx):
    r = ctx["reduced"]
    if r is None:
        return None
    found = tracing.step_module(r)
    secs = sum(tracing.under(r, scope) for scope in SCOPES)
    if secs <= 0 or found is None or not found[1]["count"]:
        return None
    return 1e3 * secs / found[1]["count"]
