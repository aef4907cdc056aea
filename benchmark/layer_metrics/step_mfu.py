"""Step programs: the whole step's share of the chip's bf16 peak. The
operations one step needs (benchmark/flops.py: conv, linear and attention
matmuls, forward and backward as the GAN step requires them, nothing
recomputed counted) times the steps a second of the traced window, over
chips times peak."""

from benchmark import flops


def read(ctx):
    if not ctx["peaks"] or not ctx["steps"]:
        return None
    model = dict(ctx["config"]["model"],
                 attn_qk_div=ctx["config"]["attn_qk_div"],
                 attn_v_div=ctx["config"]["attn_v_div"])
    ops = flops.step_ops(model, ctx["global_batch"])["total"]
    rate = ops * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
