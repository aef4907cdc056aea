"""A per-layer reader of set-up, read from inside the program: `reader(stage,
misses=False)` gives the `read(ctx)` of a `layer_metrics/<metric>.py` that
sums the `compile/<stage>` records of the program's two set-up programs,
`init` and `train_step`. The records are JAX's compile stages as
`dcgan_tpu/utils/profiling.py` keeps them, one a program and stage, labelled
with the program's name, a trace that ran inside another stage folded into
it. The reading ends with the step program's first `compile/backend`
record: what compiles after it is not set-up (the reference's programs,
after the window, and `benchmark/reference.py`'s own `train_step` among
them). Seconds, or with `misses` the persistent-cache misses those records
count. Nothing to read (None, never 0) with no steps, or in a program that
makes no compile records."""

PROGRAMS = ("init", "train_step")


def reader(stage, misses=False):
    def read(ctx):
        if not ctx["steps"]:
            return None
        from dcgan_tpu.utils import profiling

        if not hasattr(profiling, "compile_records"):
            return None
        records = [r for r in profiling.compile_records()
                   if r.label in PROGRAMS]
        step = [r for r in records if r.name == "compile/backend"
                and r.label == "train_step"]
        if not step:
            return None
        end = step[0].start + step[0].duration
        mine = [r for r in records
                if r.name == "compile/" + stage and r.start <= end]
        if misses:
            counts = [r.count for r in mine if r.count is not None]
            return sum(counts) if counts else None
        return sum(r.duration for r in mine) if mine else None
    return read
