"""Kernels: the flash-attention kernels' share of their roofline. The least
time the chip could take for one step's attention forward and backward
passes (`kernel_costs(...)["flash_attn"]` of the configuration's family;
for `gan` benchmark/flops.py: the larger of operations over the bf16 peak
and bytes over the HBM peak; at 4,096 tokens with heads 8 and 32 wide the
operations bound it, 20.9 ms against 2.5 ms a step at batch 256) over the
device time per step of the instructions that hold `flash_` in their name
(`ops/pallas_attention.py` names its kernels `flash_fwd`, `flash_dq_dkv`),
read on the first device, so against one chip's share of the batch. Nothing
to read in a configuration whose family counts no flash kernel, or in a
trace without such an instruction."""

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
        ctx["config"], ctx["global_batch"] // ctx["chips"]).get("flash_attn")
    if cost is None:
        return None
    least = max(cost["ops"] / ctx["peaks"]["bf16_flops_per_s"],
                cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * found[1]["count"] / kernel_s
