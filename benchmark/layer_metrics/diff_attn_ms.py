"""Step programs: device time per step under the scopes `attn_win`,
`attn_full` and `attn_cross` (`models/sambay.py`: the differential
attention of the window, the full and the cross layer: the norm before it,
`qkv_proj`, the flash kernels under `attn`, the subtraction and its norm
under `diff`, `o_proj`; forward, recomputation and backward), from the
trace's `scope_s`. Nothing to read without a trace or in a program that
names none of them."""

from benchmark import scope_ms

read = scope_ms.reader(("attn_win", "attn_full", "attn_cross"))
