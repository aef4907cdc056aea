"""Kernels: the WINDOWED flash-attention kernels' share of their roofline:
the least time the chip could take for one step's window attention, the
band's operations and bytes alone, forward and backward
(`kernel_costs(...)["window_flash"]` of the configuration's family: the
larger of operations over the bf16 peak and bytes over the HBM peak; tiles
the band's edges cross are computed whole and show as a lower share), over
the device time per step of the instructions that hold both `flash_` and
`_win` in their name (`ops/pallas_attention.py`: `flash_fwd_win`,
`flash_dq_dkv_win`), on the first device. Nothing to read where the family
counts no windowed kernel or the trace holds none."""

from benchmark import kernel_reader

read = kernel_reader.roofline(("flash_", "_win"), "window_flash")
