"""Kernels: the CAUSAL flash-attention kernels' share of their roofline. The
least time the chip could take for one step's causal attention, forward and
backward, lower triangle only (`kernel_costs(...)["causal_flash"]` of the
configuration's family: the larger of operations over the bf16 peak and
bytes over the HBM peak; nothing recomputed counted, so the per-block
recomputation and the backward's rebuilt tiles show as a lower share), over
the device time per step of the instructions that hold `flash_` in their
name (`ops/pallas_attention.py`: `flash_fwd`, `flash_dq_dkv`), on the first
device. A file of its own beside `flash_attn_roofline`, whose cost counts
full, unmasked attention. Nothing to read in a configuration whose family
counts no causal kernel, or in a trace without such an instruction."""

from benchmark import tracing


def read(ctx):
    r = ctx["reduced"]
    if r is None or not ctx["peaks"]:
        return None
    found = tracing.step_module(r)
    kernel_s = sum(s for name, s in r["ops"]
                   if name.startswith("pallas:") and "flash_" in name)
    if kernel_s <= 0 or found is None or not found[1]["count"]:
        return None
    cost = ctx["family"].kernel_costs(
        ctx["config"], ctx["global_batch"] // ctx["chips"]).get("causal_flash")
    if cost is None:
        return None
    least = max(cost["ops"] / ctx["peaks"]["bf16_flops_per_s"],
                cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * found[1]["count"] / kernel_s
