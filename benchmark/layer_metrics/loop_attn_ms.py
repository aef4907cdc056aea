"""Step programs: device time per step under the scope `attn_block`
(`models/loop_lm.py`: the attention branch of every layer-pass: the norm
before it, the q, k, v projections, rotary, the causal flash kernels, the
output projection and the norm after it; forward, recomputation and
backward), from the trace's `scope_s`. Nothing to read without a trace or in
a program that names no such scope."""

from benchmark import scope_ms

read = scope_ms.reader(("attn_block",))
