"""Step programs: device time per step under the scope `ffn`
(`models/loop_lm.py`: the SwiGLU branch of every layer-pass with the norms
before and after it; forward, recomputation and backward), from the trace's
`scope_s`. Nothing to read without a trace or in a program that names no
such scope."""

from benchmark import scope_ms

read = scope_ms.reader(("ffn",))
