"""Kernels: the selective-scan kernels' share of their roofline: the least
time the chip could take for one step's scans, forward and backward
(`kernel_costs(...)["ssm_scan"]` of the configuration's family: the larger
of bytes over the HBM peak, with u, dt, B, C, y and their gradients crossing
HBM once each in float32, and elementwise operations over the bf16 matmul
peak), over the device time per step of the instructions that hold
`ssm_scan` in their name, on the first device.

NEITHER peak bounds this kernel: the recurrence is elementwise work on the
VPU with one `exp` per channel, state and step on the EUP, no matmul, and a
step depends on the one before it. The peaks.json table has no VPU peak, so
the share reads low by nature (a few per cent) and cannot pass 100; it
moves with the kernel's time and is comparable from PR to PR, not with the
matmul kernels' shares. The states rebuilt in the backward pass are not
counted. Nothing to read where the family counts no such kernel or the
trace holds none."""

from benchmark import kernel_reader

read = kernel_reader.roofline(("ssm_scan",), "ssm_scan")
