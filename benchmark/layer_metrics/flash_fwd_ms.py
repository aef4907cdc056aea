"""Kernels: device time of the flash-attention FORWARD kernels per step, in
ms. Reads the kernel's own name: `ops/pallas_attention.py` gives its three
`pl.pallas_call` sites `name="flash_fwd"`, `"flash_dq"`, `"flash_dkv"`, and
a custom call's HLO instruction takes that name (`%flash_fwd.5`), which the
trace reduction keeps (`reduced["ops"]`, kind `pallas`). Summed over every
such instruction of the window and divided by the step program's
executions (`tracing.step_module`). Nothing to read without a trace, in a
configuration that runs no flash kernel, or in a program that does not
name its kernels (there they read `jvp__.N`)."""

from benchmark import tracing


def read(ctx):
    r = ctx["reduced"]
    if r is None:
        return None
    found = tracing.step_module(r)
    secs = [s for name, s in r["ops"]
            if name.startswith("pallas:") and "flash_fwd" in name]
    if not secs or found is None or not found[1]["count"]:
        return None
    return 1e3 * sum(secs) / found[1]["count"]
