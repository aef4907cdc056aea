"""Step programs: device time per step under the scope `loop`
(`models/loop_lm.py`: the stack of layers and the final norm of every pass,
forward, recomputation and backward: every operation of it runs on weights
used `total_ut_steps` times a step), from the trace's `scope_s`. Nothing to
read without a trace or in a program that names no such scope."""

from benchmark import scope_ms

read = scope_ms.reader(("loop",))
