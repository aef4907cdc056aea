"""Step programs: device time per step under the scope `mlp`
(`models/sambay.py`: the SwiGLU of every layer with the norm before it;
forward, recomputation and backward), from the trace's `scope_s`: three
quarters of the trunk's matmuls. Nothing to read without a trace or in a
program that names no such scope."""

from benchmark import scope_ms

read = scope_ms.reader(("mlp",))
