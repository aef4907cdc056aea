"""Kernels: device time of the selective-scan kernels per step, in ms:
every Pallas instruction with `ssm_scan` in its name
(`ops/pallas_scan.py`: `ssm_scan_fwd`, `ssm_scan_bwd`; a custom call's HLO
instruction takes the kernel's name, `reduced["ops"]`, kind `pallas`),
summed over the window and divided by the step program's executions
(`tracing.step_module`). Nothing to read without a trace or in a program
that runs no such kernel."""

from benchmark import kernel_reader

read = kernel_reader.ms(("ssm_scan",))
