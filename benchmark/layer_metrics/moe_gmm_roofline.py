"""Kernels: the grouped matmuls' share of their roofline. The least time for
one step's products over the experts held, forward and backward, at the
expected pairs under even routing (`kernel_costs(...)["moe_gmm"]` of the
configuration's family: the larger of operations over the bf16 peak and
bytes over the HBM peak) over the device time per step of the megablox
kernels, whose instructions carry the name of their jitted function inside
the transformations around it (`jvp_jit_gmm___.N`: rows x expert matrices
and the rows' gradient; `transpose_jvp_jit_tgmm___.N`: the matrices'
gradient): every Pallas instruction with `gmm` in its name, on the first
device. Nothing to read where the family counts no such kernel or the trace
holds none."""

from benchmark import tracing


def read(ctx):
    r = ctx["reduced"]
    if r is None or not ctx["peaks"]:
        return None
    found = tracing.step_module(r)
    kernel_s = sum(s for name, s in r["ops"]
                   if name.startswith("pallas:") and "gmm" in name)
    if kernel_s <= 0 or found is None or not found[1]["count"]:
        return None
    cost = ctx["family"].kernel_costs(
        ctx["config"], ctx["global_batch"] // ctx["chips"]).get("moe_gmm")
    if cost is None:
        return None
    least = max(cost["ops"] / ctx["peaks"]["bf16_flops_per_s"],
                cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * found[1]["count"] / kernel_s
