"""Step programs: device time per step under the scopes `head` and `exit`
(`models/loop_lm.py`: after each pass the exit gate, the exit distribution
and its entropy under `exit`, the logits over the whole vocabulary and the
chunked cross-entropy under `head`, which holds `loss`; the two are
siblings, so nothing counts twice), from the trace's `scope_s`. Nothing to
read without a trace or in a program that names neither scope."""

from benchmark import scope_ms

read = scope_ms.reader(("head", "exit"))
