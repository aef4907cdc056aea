"""Driver of training traffic: one run of one training cell.

The window drives, per step, the two calls `trainer._train_run` makes:
`next()` on the feed and `ParallelTrain.step` (with the per-step key folded
from one base key, as the trainer folds it). Set-up builds one object, the
compiled step with its state, drives it through its first three steps from
the seed, reads what the comparison needs, and hands that same object to
the window. The loop keeps `in_flight` steps queued on the device by reading
back the losses of the step that many behind; the window ends when the last
step's losses are on the host. The trainer's own bookkeeping (metrics
cadence, NaN gate, flight recorder, services, checkpoints) is not in it.

After the window: the memory peak is read, the trace reduced, the
program's state freed, and only then the plain reference follows the first
two of those steps (`benchmark/check.py` says what is compared).

This file is the same for every model family. What depends on the family
(the draw of a batch and of each leaf, what is read from the state, the
reference, the numbers worked out from the two, the operation counts) is
looked up by the configuration's `family` key: `manifest.family`,
`families/<family>.py`.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import math
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List

import numpy as np

from benchmark import check, manifest, tracing, traffic, weights

WARMUP_STEPS = 3      # the program's first steps, all through the window's call
CHECK_STEPS = 2       # of which the reference follows two: at 4 s a step for
                      # sagan128 at batch 256 (float32 at "highest", dense
                      # scores), three would outlast the 10 s window
TRACE_SECONDS = 4.0   # a traced run measures at most this long: traces are
                      # large (1.7 MB a sagan128 step) and tracing slows the host
BETA2, ADAM_EPS = 0.999, 1e-8   # the program's Adam constants (no option)


# --- the program under test --------------------------------------------------

def program_config(cell: manifest.Cell):
    """The TrainConfig of the cell: the preset as the CLI resolves it, with
    the configuration file's keys applied (for the shipped files they are the
    preset's own values; a test pins that) and batch, mesh and backend from
    the traffic mix."""
    from dcgan_tpu.config import MeshConfig
    from dcgan_tpu.presets import get_preset

    conf, mix = cell.config, cell.traffic
    cfg = get_preset(conf["preset"])
    model = dataclasses.replace(cfg.model, **conf["model"])
    train = {k: conf["train"][k] for k in
             ("loss", "beta1", "learning_rate", "d_learning_rate",
              "g_learning_rate", "g_ema_decay", "update_mode", "n_critic",
              "precision")
             if k in conf["train"]}
    if (conf["train"]["beta2"], conf["train"]["adam_eps"]) != (BETA2, ADAM_EPS):
        raise ValueError("the program's Adam has beta2=0.999, eps=1e-8")
    return dataclasses.replace(
        cfg, model=model, **train,
        batch_size=int(mix["per_chip_batch"]) * int(mix["chips"]),
        mesh=MeshConfig(**mix["mesh"]), backend=mix["backend"])


@dataclasses.dataclass
class Inputs:
    """What program and reference share, and what outlives the program when
    its state is freed: the family, the mesh, and the draws from the seed."""
    family: Any             # the module of the configuration's model family
    mesh: Any
    batch_shape: tuple      # of one global batch, as the family states it
    batch_sharding: Any
    draw: Callable          # (key, dtype=None) -> the model state, drawn


@dataclasses.dataclass
class Program:
    """The compiled step with what the harness needs around it."""
    cfg: Any
    pt: Any
    inputs: Inputs
    overwrite: Callable     # (state, key) -> state with benchmark weights
    read_first: Dict[str, Callable]   # after step 1: name -> (state, key)
    read_last: Dict[str, Callable]    # after step CHECK_STEPS


def build_program(cell: manifest.Cell, devices, wanted=None) -> Program:
    """`wanted`: the numbers this run compares (a family may read less where
    none of them needs it, as `gan` the first gradient); None reads all."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dcgan_tpu.parallel import batch_sharding, make_mesh, make_parallel_train

    fam = manifest.family(cell.root, cell.config)
    cfg = program_config(cell)
    mesh = make_mesh(cfg.mesh, devices[:cell.chips])
    pt = make_parallel_train(cfg, mesh)
    shapes = fam.drawn(jax.eval_shape(lambda k: pt.init(k), jax.random.key(0)))
    rep = NamedSharding(mesh, P())
    shape = tuple(fam.batch_shape(cell.config, cfg.batch_size))

    def draw(key, dtype=None):
        return weights.draw_tree(shapes, key, fam.draw_leaf, dtype)

    def reading(fn):
        return jax.jit(lambda state, key: fn(state, draw(key)),
                       out_shardings=rep)

    reads = fam.program_readings(cell.config, wanted)
    return Program(
        cfg=cfg, pt=pt,
        inputs=Inputs(family=fam, mesh=mesh, batch_shape=shape,
                      batch_sharding=batch_sharding(mesh, len(shape)),
                      draw=draw),
        overwrite=jax.jit(
            lambda state, key: fam.initial_state(state, draw(key)),
            out_shardings=pt.shardings, donate_argnums=(0,)),
        read_first={n: reading(f) for n, f in reads["first"].items()},
        read_last={n: reading(f) for n, f in reads["last"].items()})


def initial_state(prog: Program, seed: int):
    """The program's own init (optimizer state, counters), then every leaf
    the family has the benchmark draw (for `gan`: weights, BN statistics,
    power-iteration vectors)."""
    state = prog.pt.init(weights.seed_key(seed, weights.PROGRAM_INIT))
    return prog.overwrite(state, weights.seed_key(seed, weights.WEIGHTS))


def resident_batches(cell: manifest.Cell, inp: Inputs, seed: int) -> List:
    return traffic.resident_batches(
        weights.seed_key(seed, weights.BATCHES),
        int(cell.traffic["resident_batches"]), inp.batch_shape,
        inp.batch_sharding, inp.family.draw_batch)


def make_feed(cell: manifest.Cell, prog: Program, seed: int, cache_root: str):
    """(iterator of device batches, close())."""
    mix, inp = cell.traffic, prog.inputs
    if mix["feed"] == "resident":
        batches = resident_batches(cell, inp, seed)

        def cycle():
            i = 0
            while True:
                yield batches[i % len(batches)]
                i += 1
        return cycle(), (lambda: None)
    import jax

    from dcgan_tpu.data import DataConfig, make_dataset

    _, size, _, channels = inp.batch_shape      # records hold images
    data_dir = traffic.ensure_records(cache_root, mix["records"], size,
                                      channels)
    dcfg = DataConfig(
        data_dir=data_dir, image_size=size, channels=channels,
        batch_size=prog.cfg.batch_size // jax.process_count(),
        record_dtype=mix["records"]["dtype"],
        min_after_dequeue=int(mix.get("shuffle_buffer",
                                      prog.cfg.shuffle_buffer)),
        n_threads=int(mix.get("loader_threads",
                              prog.cfg.num_loader_threads)),
        seed=int(seed) % (2 ** 31), normalize=prog.cfg.normalize_inputs,
        prefetch_device_batches=prog.cfg.prefetch_device_batches)
    feed = make_dataset(dcfg, inp.batch_sharding)
    return feed, getattr(feed, "close", lambda: None)


def first_steps(prog: Program, state, feed, seed: int, keep_batches: bool):
    """The first WARMUP_STEPS steps through the window's own call and feed,
    with what the family reads of the first CHECK_STEPS of them.
    Returns (state, base key, readings, the batches if asked for)."""
    import jax

    base = weights.seed_key(seed, weights.STEP_KEYS)
    key0 = weights.seed_key(seed, weights.WEIGHTS)
    losses, kept, read = [], [], {}
    for i in range(WARMUP_STEPS):
        batch = next(feed)
        if keep_batches and i < CHECK_STEPS:
            kept.append(batch)
        state, m = prog.pt.step(state, batch, jax.random.fold_in(base, i))
        if i < CHECK_STEPS:
            losses.append(m)
        if i == 0:
            read.update({n: f(state, key0) for n, f in prog.read_first.items()})
        if i == CHECK_STEPS - 1:
            read.update({n: f(state, key0) for n, f in prog.read_last.items()})
    # reading the last warm-up step's losses back too drains the device, so
    # that the window starts with nothing in flight. What is read as whole
    # leaves (the first gradient, the size of the parameters) waits on the
    # host for the reference's: the window's device memory is the program's
    got = jax.device_get({"losses": losses, **read, "last": m})
    del read, got["last"]
    return state, base, _scalars_to_float(got), kept


def _scalars_to_float(tree):
    import jax

    return jax.tree.map(lambda v: float(v) if np.ndim(v) == 0 else v, tree)


def window(prog: Program, state, feed, base, seconds: float, in_flight: int):
    """The measured window. Returns (state, facts)."""
    import jax
    from jax.profiler import TraceAnnotation

    spans = {"next": [], "step": [], "readback": []}
    pending: collections.deque = collections.deque()
    failed = 0
    clock = time.perf_counter

    def read_back(m):
        nonlocal failed
        t = clock()
        with TraceAnnotation("bench_readback"):
            vals = jax.device_get(m)
        spans["readback"].append(clock() - t)
        if not all(math.isfinite(float(v)) for v in vals.values()):
            failed += 1

    steps = 0
    with TraceAnnotation(tracing.WINDOW):
        t0 = clock()
        while True:
            t = clock()
            with TraceAnnotation("bench_next"):
                batch = next(feed)
            spans["next"].append(clock() - t)
            key = jax.random.fold_in(base, WARMUP_STEPS + steps)
            t = clock()
            with TraceAnnotation("bench_step"):
                state, m = prog.pt.step(state, batch, key)
            spans["step"].append(clock() - t)
            pending.append(m)
            steps += 1
            if len(pending) > in_flight:
                read_back(pending.popleft())
            if clock() - t0 >= seconds:
                break
        while pending:
            read_back(pending.popleft())
        elapsed = clock() - t0
    return state, {"steps": steps, "failed": failed, "window_s": elapsed,
                   "spans": spans}


def memory_now(devices) -> Dict[str, int]:
    """Device memory of the fullest chip, read now (the run reads it at the
    window's end). On this runtime `bytes_in_use` counts live arrays only; a
    loaded program's temporaries are booked under `bytes_reserved`
    (`largest_free_block_bytes` is the limit less both; on the chip the
    step's own `memory_analysis()` gives 7,461 MB of temporaries for
    sagan128 at batch 256 where 7,434 MB are reserved, and 2,032 MB for
    dcgan128 at batch 512 where 2,018 MB are: PERF.md section 3), so what
    the chip holds is their sum, both read at the same moment. The two peak
    counters, which need not coincide in time, are given beside it."""
    best = {"memory_peak_bytes": 0}
    for d in devices:
        s = d.memory_stats() or {}
        arrays = int(s.get("bytes_in_use", 0))
        reserved = int(s.get("bytes_reserved", 0))
        if arrays + reserved >= best["memory_peak_bytes"]:
            best = {"memory_peak_bytes": arrays + reserved,
                    "arrays_bytes": arrays, "reserved_bytes": reserved,
                    "arrays_peak_bytes": int(s.get("peak_bytes_in_use", 0)),
                    "reserved_peak_bytes": int(s.get("peak_bytes_reserved", 0)),
                    "memory_limit_bytes": int(s.get("bytes_limit", 0))}
    return best


def replica_gap(params, n_devices: int) -> float:
    """Widest relative gap between the chips' copies of the parameters, by a
    fingerprint (sum and sum of squares of every leaf) worked out on each
    chip from its own copy."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves(params)
    if any(len(x.addressable_shards) != n_devices for x in leaves):
        return math.inf

    @jax.jit
    def fingerprint(xs):
        return jnp.stack([jnp.stack([jnp.sum(x), jnp.sum(x * x)])
                          for x in xs])

    prints = [np.asarray(fingerprint([x.addressable_shards[d].data
                                      for x in leaves]), np.float64)
              for d in range(n_devices)]
    scale = np.maximum(np.abs(prints[0]), 1e-30)
    return float(max(np.max(np.abs(p - prints[0]) / scale)
                     for p in prints[1:]))


# --- the reference's side ----------------------------------------------------

def reference_readings(cell: manifest.Cell, inp: Inputs, seed: int,
                       batches: List, **variant) -> dict:
    """The family's plain reference through the same CHECK_STEPS steps: the
    same weights (drawn in float32), batches and keys. `variant`: the
    keyword arguments of one of the family's `variants` (the control in
    lower precision, a planted fault)."""
    return inp.family.reference_readings(
        cell.config, inp.mesh, lambda key: inp.draw(key, "float32"),
        weights.seed_key(seed, weights.WEIGHTS),
        weights.seed_key(seed, weights.STEP_KEYS), batches, CHECK_STEPS,
        **variant)


def check_batches(cell: manifest.Cell, inp: Inputs, seed: int,
                  delivered: List[np.ndarray]):
    """The reference's batches, made by the benchmark alone, and the fed
    cell's `feed_gap`. Resident: drawn again from the seed. Records: each
    delivered row names its record; the batch is rebuilt from the records
    the benchmark wrote and the delivered rows are held against it."""
    import jax

    if cell.traffic["feed"] == "resident":
        return resident_batches(cell, inp, seed)[:CHECK_STEPS], {}
    mix = cell.traffic
    _, size, _, channels = inp.batch_shape
    records = traffic.record_images(mix["records"], size, channels)
    gap, out = 0.0, []
    for rows in delivered:
        ids = traffic.record_ids(rows)
        if ids.min() < 0 or ids.max() >= len(records):
            gap = math.inf
            ids = np.clip(ids, 0, len(records) - 1)
        want = traffic.normalize(records[ids])
        gap = max(gap, float(np.max(np.abs(rows - want))))
        out.append(jax.device_put(want, inp.batch_sharding))
    return out, {"feed_gap": gap}


# --- one run -------------------------------------------------------------------

def run(cell: manifest.Cell, *, seed: int, seconds: float,
        trace: bool, t_start: float, devices, cache_root: str,
        device_metrics: bool = True) -> dict:
    """One run of a training cell; returns the result line as a dict.
    `device_metrics=False` is the CPU rehearsal of the tests: the line then
    names the CPU and carries no metric (a number from a CPU run is never
    written under the name of a device metric)."""
    import jax

    traffic.check_mix(cell.traffic)
    devices = list(devices)[:cell.chips]
    kind = devices[0].device_kind
    peaks = manifest.peaks(cell.root, kind) if device_metrics else None
    compiles: List[float] = []
    in_window = False

    def on_event(name, secs, **_):
        if in_window and "backend_compile" in name:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)

    prog = build_program(cell, devices, wanted=set(cell.limits))
    state = initial_state(prog, seed)
    feed, close_feed = make_feed(cell, prog, seed, cache_root)
    fed = cell.traffic["feed"] == "records"
    trace_dir = None
    try:
        state, base, prog_read, kept = first_steps(prog, state, feed, seed,
                                                   keep_batches=fed)
        # a full collection now, so that none of the interpreter's (50-120 ms
        # in this process) falls into the window by the luck of the count
        gc.collect()
        if trace:
            seconds = min(seconds, TRACE_SECONDS)
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(trace_dir)
        setup_s = time.time() - t_start
        in_window = True
        state, facts = window(prog, state, feed, base, seconds,
                              int(cell.traffic["in_flight"]))
        in_window = False
        if trace:
            jax.profiler.stop_trace()
    finally:
        close_feed()
        jax.monitoring.unregister_event_duration_listener(on_event)
    mem = memory_now(devices)
    numbers: Dict[str, float] = {}
    if cell.chips > 1:
        numbers["replica_gap"] = replica_gap(state["params"], cell.chips)
    delivered = [np.asarray(b) for b in kept]
    reduced = None
    if trace:
        reduced = tracing.reduce(tracing.load_xplane(
            tracing.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # free the program's state before the reference takes the chip
    inp, batch = prog.inputs, prog.cfg.batch_size
    del state, kept, feed, prog
    gc.collect()
    t_ref = time.time()
    batches, feed_numbers = check_batches(cell, inp, seed, delivered)
    ref_read = reference_readings(cell, inp, seed, batches)
    numbers.update(inp.family.numbers(prog_read, ref_read, inp.mesh))
    del ref_read
    numbers.update(feed_numbers)
    verdict = check.judge(numbers, cell.limits)
    reference_s = time.time() - t_ref

    ctx = {"reduced": reduced, "spans": facts["spans"],
           "window_s": facts["window_s"], "steps": facts["steps"],
           "global_batch": batch, "chips": cell.chips, "config": cell.config,
           "family": inp.family, "traffic": cell.traffic, "peaks": peaks,
           "memory_peak_bytes": mem["memory_peak_bytes"]}
    metrics: Dict[str, dict] = {}
    if device_metrics and not trace:
        values = {"train_images_per_s": batch * facts["steps"] / facts["window_s"],
                  "setup_s": setup_s}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise manifest.ManifestError(
                    f"the training driver does not measure {m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    elif device_metrics:
        if reduced is None:
            raise RuntimeError("the traced window holds no device operation")
        for m in cell.per_layer:
            value = manifest.layer_metric_reader(cell.root, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), **mem}
    result = {"correct": verdict["correct"] and facts["failed"] == 0,
              "attempted": facts["steps"], "failed": facts["failed"],
              "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = tracing.breakdown(reduced)
    if not device_metrics:
        result["not_measured"] = [m["name"] for m in
                                  cell.end_to_end + cell.per_layer]
    result["run"] = {"workload": cell.name, "seed": seed,
                     "window_s": facts["window_s"], "steps": facts["steps"],
                     "setup_s": setup_s, "reference_s": reference_s,
                     "compiles_in_window": len(compiles)}
    result["check"] = verdict["compared"]
    check.print_compared(verdict)
    return result
