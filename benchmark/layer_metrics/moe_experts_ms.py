"""Step programs: device time per step under the scope `experts` (`moe` of
`models/mla_moe.py`: the grouped matmuls over the experts held, `gmm` /
`tgmm`, with the activation between them), all expert layers, forward,
recomputation and backward. Nothing to read without a trace or in a program
that names no such scope."""

from benchmark import tracing

SCOPES = ("experts",)


def read(ctx):
    r = ctx["reduced"]
    if r is None:
        return None
    found = tracing.step_module(r)
    secs = sum(tracing.under(r, scope) for scope in SCOPES)
    if secs <= 0 or found is None or not found[1]["count"]:
        return None
    return 1e3 * secs / found[1]["count"]
