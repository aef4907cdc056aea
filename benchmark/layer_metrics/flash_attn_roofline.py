"""Kernels: the flash-attention kernels' share of their roofline. The least
time the chip could take for one step's attention forward and backward
passes (benchmark/flops.py: the larger of operations over the bf16 peak and
bytes over the HBM peak; at 4,096 tokens with heads 8 and 32 wide the
operations bound it, 20.9 ms against 2.5 ms a step at batch 256) over the
summed device time of the step's Pallas custom calls. In sagan128
(`use_pallas`, BN on XLA, no fused stages) every `tpu_custom_call` of the
step is a flash kernel. Nothing to read in a configuration without
attention or a trace without Pallas calls."""

from benchmark import flops, tracing


def read(ctx):
    r, model = ctx["reduced"], ctx["config"]["model"]
    if r is None or not ctx["peaks"] or not model.get("attn_res") \
            or not model.get("use_pallas") or r["kind_s"]["pallas"] <= 0:
        return None
    found = tracing.step_module(r)
    if found is None or not found[1]["count"]:
        return None
    model = dict(model, attn_qk_div=ctx["config"]["attn_qk_div"],
                 attn_v_div=ctx["config"]["attn_v_div"])
    # one chip's share of the batch: kernel time is read on the first device
    cost = flops.flash_step_cost(model, ctx["global_batch"] // ctx["chips"])
    least = max(cost["ops"] / ctx["peaks"]["bf16_flops_per_s"],
                cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    kernel_s = r["kind_s"]["pallas"] / found[1]["count"]
    return 100.0 * least / kernel_s
