"""Kernels: the causal flash-attention kernels' share of their roofline in
the looped family's cell: the least time the chip could take for one step's
causal attention, every layer-pass, forward and backward, lower triangle
only (`kernel_costs(...)["causal_flash"]` of the configuration's family),
over the device time per step of the `flash_` instructions. The quantity
and the reduction are `causal_flash_roofline`'s, whose entry lists the
token cell alone; this name reports it in the cell this file came with.
Nothing to read (None, never 0) where that reader finds nothing."""

import os

from benchmark import manifest

read = manifest.layer_metric_reader(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "causal_flash_roofline")
