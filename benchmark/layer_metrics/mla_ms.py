"""Step programs: device time per step under the scope `mla` (`models/mla_moe.py`:
latent attention of every block and of the multi-token module, forward,
recomputation and backward; the flash kernels and the projections, rotary
and relayouts around them), from the trace's `scope_s`. Nothing to read
without a trace or in a program that names no such scope."""

from benchmark import tracing

SCOPES = ("mla",)


def read(ctx):
    r = ctx["reduced"]
    if r is None:
        return None
    found = tracing.step_module(r)
    secs = sum(tracing.under(r, scope) for scope in SCOPES)
    if secs <= 0 or found is None or not found[1]["count"]:
        return None
    return 1e3 * secs / found[1]["count"]
