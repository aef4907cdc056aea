"""Input pipeline: share of the window spent waiting for a batch INSIDE the
program's feed: the summed durations of the `feed/wait` spans that
`data/pipeline.py::DevicePrefetcher.__next__` records around its blocking
queue get (one per delivered batch; `utils/profiling.py::span`), over the
window. The window's batches are the last `steps` records: the harness
calls `next()` once a step and not again after the window. It times the
layer that `loader_wait_share` times from outside; what that one reads
above this one is the call's own overhead. Nothing to read for a resident
feed, with no steps, or in a program that records no spans."""


def read(ctx):
    steps = int(ctx["steps"])
    if ctx["traffic"].get("feed") != "records" or not steps \
            or ctx["window_s"] <= 0:
        return None
    from dcgan_tpu.utils import profiling

    if not hasattr(profiling, "spans"):
        return None
    waits = profiling.spans("feed/wait")[-steps:]
    if len(waits) < steps:
        return None
    return 100.0 * sum(r.duration for r in waits) / ctx["window_s"]
