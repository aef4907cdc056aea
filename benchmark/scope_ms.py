"""A per-layer reader of device time under named scopes: `reader(scopes)`
gives the `read(ctx)` of a `layer_metrics/<metric>.py` that reports the ms a
step the program spends under the `jax.named_scope`s it names, from the
trace's `scope_s` (`tracing.under`), over the step program's executions
(`tracing.step_module`). Sibling scopes are summed; a scope inside another
named one would count twice, so a file names siblings only. Nothing to read
(None, never 0) without a trace or in a program that names none of them.

The readers that came before this file (`mla_ms`, `moe_*_ms`, `mtp_ms`,
`lm_head_ms`) each carry this body themselves; a `benchmark` PR may point
them here."""

from benchmark import tracing


def reader(scopes):
    def read(ctx):
        r = ctx["reduced"]
        if r is None:
            return None
        found = tracing.step_module(r)
        secs = sum(tracing.under(r, scope) for scope in scopes)
        if secs <= 0 or found is None or not found[1]["count"]:
            return None
        return 1e3 * secs / found[1]["count"]
    return read
