"""Per-layer readers of a Pallas kernel's device time: `ms(holds)` and
`roofline(holds, cost)` give the `read(ctx)` of a
`layer_metrics/<metric>.py`. The kernel's instructions are the trace's
`reduced["ops"]` of kind `pallas` whose name holds every string of `holds`
(a custom call's HLO instruction takes the kernel's name, `ssm_scan_fwd.3`),
on the first device, summed over the window and divided by the step
program's executions (`tracing.step_module`). `roofline` divides the least
time of `kernel_costs(...)[cost]` of the configuration's family (the larger
of operations over the bf16 peak and bytes over the HBM peak) by it, in
per cent. Nothing to read (None, never 0) without a trace, without such an
instruction, or where the family counts no such kernel.

The roofline readers that came before this file (`flash_attn_roofline`,
`causal_flash_roofline`, `moe_gmm_roofline`) each carry this body
themselves; a `benchmark` PR may point them here."""

from benchmark import tracing


def _seconds_a_step(ctx, holds):
    r = ctx["reduced"]
    if r is None:
        return None
    found = tracing.step_module(r)
    secs = sum(s for name, s in r["ops"] if name.startswith("pallas:")
               and all(h in name for h in holds))
    if secs <= 0 or found is None or not found[1]["count"]:
        return None
    return secs / found[1]["count"]


def ms(holds):
    def read(ctx):
        secs = _seconds_a_step(ctx, holds)
        return None if secs is None else 1e3 * secs
    return read


def roofline(holds, cost):
    def read(ctx):
        secs = _seconds_a_step(ctx, holds)
        if secs is None or not ctx["peaks"]:
            return None
        least = ctx["family"].kernel_costs(
            ctx["config"], ctx["global_batch"] // ctx["chips"]).get(cost)
        if least is None:
            return None
        return 100.0 * max(
            least["ops"] / ctx["peaks"]["bf16_flops_per_s"],
            least["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]) / secs
    return read
