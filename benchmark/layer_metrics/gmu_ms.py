"""Step programs: device time per step under the scope `gmu`
(`models/sambay.py`: the gated memory unit: the norm before it, the two
projections and the gate by the memory layer's scan output; forward,
recomputation and backward), from the trace's `scope_s`. Nothing to read
without a trace or in a program that names no such scope."""

from benchmark import scope_ms

read = scope_ms.reader(("gmu",))
