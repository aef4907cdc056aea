"""Step programs: the whole step's share of the chip's bf16 peak. The
operations one step needs, as the configuration's model family counts them
(`step_ops` of `families/<family>.py`; for `gan` benchmark/flops.py: conv,
linear and attention matmuls, forward and backward as the GAN step requires
them, nothing recomputed counted), times the steps a second of the traced
window, over chips times peak."""


def read(ctx):
    if not ctx["peaks"] or not ctx["steps"]:
        return None
    ops = ctx["family"].step_ops(ctx["config"], ctx["global_batch"])["total"]
    rate = ops * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
