"""Step programs: device time per step under the scope `mamba`
(`models/sambay.py`: the Mamba mixer of every such layer: the norm before
it, `in_proj`, `conv`, `dt_proj`, the `scan` kernels, `out_proj`; forward,
recomputation and backward), from the trace's `scope_s`. Nothing to read
without a trace or in a program that names no such scope."""

from benchmark import scope_ms

read = scope_ms.reader(("mamba",))
