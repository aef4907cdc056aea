"""Device: device memory of the fullest chip at the window's end, in MiB
(live arrays plus what the runtime reserves for the loaded programs'
temporaries, read at the same moment; see drivers/train.py::memory_now)."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 2 ** 20 if peak else None
