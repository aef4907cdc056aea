"""Input pipeline: the time the loader needs for one batch, in ms: the mean
`feed/load` span (`next()` on the host loader: records read, decoded,
shuffled, assembled) plus the mean `feed/h2d` span (`to_global`: the start
of the transfer to the device) of the DevicePrefetcher's producer thread
(`data/pipeline.py::DevicePrefetcher._produce`), over the producer records
that started inside the window. The window is placed by the first of its
`feed/wait` records (the harness's first `next()` follows the window's
start by microseconds) and lasts `window_s`. Hold it against
`device_step_ms`: while it is the smaller, the producer keeps the queue
full and the step never waits. Nothing to read for a resident feed, with
no steps, or in a program that records no spans."""


def read(ctx):
    steps = int(ctx["steps"])
    if ctx["traffic"].get("feed") != "records" or not steps:
        return None
    from dcgan_tpu.utils import profiling

    if not hasattr(profiling, "spans"):
        return None
    waits = profiling.spans("feed/wait")[-steps:]
    if len(waits) < steps:
        return None
    lo = waits[0].start
    hi = lo + ctx["window_s"]
    means = []
    for name in ("feed/load", "feed/h2d"):
        secs = [r.duration for r in profiling.spans(name)
                if lo <= r.start <= hi]
        if not secs:
            return None
        means.append(sum(secs) / len(secs))
    return 1e3 * sum(means)
