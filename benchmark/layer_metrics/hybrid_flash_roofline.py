"""Kernels: ALL the flash-attention kernels' share of their roofline in the
decoder-hybrid-decoder family's cell: the least time the chip could take
for one step's attention, four triangles (the two maps of the full and of
the cross layer) and two bands (the window layer's), forward and backward,
nothing recomputed (`kernel_costs(...)["causal_flash"]` of the
configuration's family), over the device time per step of every `flash_`
instruction, windowed or not. The quantity and the reduction are
`causal_flash_roofline`'s, whose entry lists the token cell alone; this
name reports it in the cell this file came with. Nothing to read (None,
never 0) where that reader finds nothing."""

import os

from benchmark import manifest

read = manifest.layer_metric_reader(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "causal_flash_roofline")
