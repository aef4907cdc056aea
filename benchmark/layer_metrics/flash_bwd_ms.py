"""Kernels: device time of the flash-attention BACKWARD kernels per step,
in ms: the instructions named `flash_dq` (dQ pass) and `flash_dkv` (dK/dV
pass) by `ops/pallas_attention.py::_bwd_core`, summed over the window and
divided by the step program's executions, as `flash_fwd_ms` does. In a
configuration whose only kernels are flash, `flash_fwd_ms + flash_bwd_ms`
is the Pallas class's time per step."""

from benchmark import tracing


def read(ctx):
    r = ctx["reduced"]
    if r is None:
        return None
    found = tracing.step_module(r)
    secs = [s for name, s in r["ops"] if name.startswith("pallas:")
            and ("flash_dq" in name or "flash_dkv" in name)]
    if not secs or found is None or not found[1]["count"]:
        return None
    return 1e3 * sum(secs) / found[1]["count"]
