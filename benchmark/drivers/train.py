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
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import math
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import check, manifest, reference, tracing, traffic, weights

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


def reference_configs(conf: dict):
    mcfg = dict(conf["model"], attn_qk_div=conf["attn_qk_div"],
                attn_v_div=conf["attn_v_div"])
    return mcfg, dict(conf["train"])


def _moment_leaves(opt_state, moment: str) -> Dict[str, Any]:
    """{"gen/deconv1/w": leaf} out of the optimizer state: the leaves under
    Adam's `mu` or `nu`, named by the dict keys that follow it."""
    import jax

    out = {}
    for net in ("gen", "disc"):
        flat, _ = jax.tree_util.tree_flatten_with_path(opt_state[net])
        for path, leaf in flat:
            keys = [getattr(k, "name", getattr(k, "key", None)) for k in path]
            if moment in keys:
                tail = [str(k) for k in keys[keys.index(moment) + 1:]]
                out["/".join([net] + tail)] = leaf
    return out


@dataclasses.dataclass
class Program:
    """The compiled step with what the harness needs around it."""
    cfg: Any
    pt: Any
    mesh: Any
    img_sharding: Any
    shapes: Any
    overwrite: Callable     # (state, key) -> state with benchmark weights
    grad_norms: Callable    # (opt state) -> {leaf: ||first gradient||}
    grad_leaves: Callable   # (opt state) -> {leaf: first gradient}
    stat_leaves: Callable   # (bn state, key) -> {leaf: its change since init}
    delta_norms: Callable   # (params, key) -> {leaf: ||change||}


def build_program(cell: manifest.Cell, devices) -> Program:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dcgan_tpu.parallel import batch_sharding, make_mesh, make_parallel_train

    cfg = program_config(cell)
    mesh = make_mesh(cfg.mesh, devices[:cell.chips])
    pt = make_parallel_train(cfg, mesh)
    shapes = jax.eval_shape(lambda k: pt.init(k), jax.random.key(0))
    rep = NamedSharding(mesh, P())

    def overwrite(state, key):
        ms = weights.make_model_state(shapes, key)
        return {**state, "params": ms["params"], "bn": ms["bn"],
                "ema_gen": jax.tree.map(jnp.copy, ms["params"]["gen"])}

    def grad_norms(opt_state):
        return {n: jnp.sqrt(jnp.sum(v.astype(jnp.float32)) / (1.0 - BETA2))
                for n, v in _moment_leaves(opt_state, "nu").items()}

    def grad_leaves(opt_state):
        # after one step from zero moments mu is (1 - beta1) x the gradient
        return {n: m.astype(jnp.float32) / (1.0 - cfg.beta1)
                for n, m in _moment_leaves(opt_state, "mu").items()}

    def stat_leaves(bn, key):
        return reference.stat_changes(
            bn, weights.make_model_state(shapes, key)["bn"])

    def delta_norms(params, key):
        return reference.delta_norms(
            params, weights.make_model_state(shapes, key)["params"])

    return Program(
        cfg=cfg, pt=pt, mesh=mesh, img_sharding=batch_sharding(mesh, 4),
        shapes=shapes,
        overwrite=jax.jit(overwrite, out_shardings=pt.shardings,
                          donate_argnums=(0,)),
        grad_norms=jax.jit(grad_norms, out_shardings=rep),
        grad_leaves=jax.jit(grad_leaves, out_shardings=rep),
        stat_leaves=jax.jit(stat_leaves, out_shardings=rep),
        delta_norms=jax.jit(delta_norms, out_shardings=rep))


def initial_state(prog: Program, seed: int):
    """The program's own init (optimizer state, counters), then every
    weight, BN statistic and power-iteration vector drawn by the benchmark."""
    state = prog.pt.init(weights.seed_key(seed, 9))
    return prog.overwrite(state, weights.seed_key(seed, 0))


def make_feed(cell: manifest.Cell, prog: Program, seed: int, cache_root: str):
    """(iterator of device batches, close())."""
    mix, m = cell.traffic, prog.cfg.model
    shape = (prog.cfg.batch_size, m.output_size, m.output_size, m.c_dim)
    if mix["feed"] == "resident":
        batches = traffic.resident_batches(
            weights.seed_key(seed, 1), int(mix["resident_batches"]),
            shape, prog.img_sharding)

        def cycle():
            i = 0
            while True:
                yield batches[i % len(batches)]
                i += 1
        return cycle(), (lambda: None)
    import jax

    from dcgan_tpu.data import DataConfig, make_dataset

    data_dir = traffic.ensure_records(cache_root, mix["records"],
                                      m.output_size, m.c_dim)
    dcfg = DataConfig(
        data_dir=data_dir, image_size=m.output_size, channels=m.c_dim,
        batch_size=prog.cfg.batch_size // jax.process_count(),
        record_dtype=mix["records"]["dtype"],
        min_after_dequeue=int(mix.get("shuffle_buffer",
                                      prog.cfg.shuffle_buffer)),
        n_threads=int(mix.get("loader_threads",
                              prog.cfg.num_loader_threads)),
        seed=int(seed) % (2 ** 31), normalize=prog.cfg.normalize_inputs,
        prefetch_device_batches=prog.cfg.prefetch_device_batches)
    feed = make_dataset(dcfg, prog.img_sharding)
    return feed, getattr(feed, "close", lambda: None)


def first_steps(prog: Program, state, feed, seed: int, keep_batches: bool,
                keep_gradient: bool = True):
    """The first WARMUP_STEPS steps through the window's own call and feed,
    with the readings of the first CHECK_STEPS of them (the first gradient
    itself only where a number of the cell needs it).
    Returns (state, base key, readings, the batches if asked for)."""
    import jax

    base = weights.seed_key(seed, 2)
    losses, kept, grad, gvec, stats, delta = [], [], None, None, None, None
    key0 = weights.seed_key(seed, 0)
    for i in range(WARMUP_STEPS):
        images = next(feed)
        if keep_batches and i < CHECK_STEPS:
            kept.append(images)
        state, m = prog.pt.step(state, images, jax.random.fold_in(base, i))
        if i < CHECK_STEPS:
            losses.append(m)
        if i == 0:
            grad = prog.grad_norms(state["opt"])
            if keep_gradient:
                gvec = prog.grad_leaves(state["opt"])
            stats = prog.stat_leaves(state["bn"], key0)
        if i == CHECK_STEPS - 1:
            delta = prog.delta_norms(state["params"], key0)
    # reading the last warm-up step's losses back too drains the device, so
    # that the window starts with nothing in flight. Where the first
    # gradient itself is kept (the size of the parameters) it waits on the
    # host for the reference's: the window's device memory is the program's
    got = jax.device_get({"losses": losses, "grad": grad, "delta": delta,
                          "gvec": gvec, "stats": stats, "last": m})
    del gvec
    readings = {
        "losses": [{k: float(v) for k, v in m.items()} for m in got["losses"]],
        "grad": {k: float(v) for k, v in got["grad"].items()},
        "delta": {k: float(v) for k, v in got["delta"].items()},
        "gvec": got["gvec"], "stats": got["stats"]}
    return state, base, readings, kept


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
                images = next(feed)
            spans["next"].append(clock() - t)
            key = jax.random.fold_in(base, WARMUP_STEPS + steps)
            t = clock()
            with TraceAnnotation("bench_step"):
                state, m = prog.pt.step(state, images, key)
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

def reference_readings(cell: manifest.Cell, mesh, shapes, seed: int,
                       batches: List, *, operand: str = "float32",
                       rows: Optional[slice] = None) -> dict:
    """The plain reference through the same CHECK_STEPS steps: same weights,
    batches and keys. `operand` and `rows` are the control's and the
    faults' knobs (lower precision; a part of the batch only)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mcfg, tcfg = reference_configs(cell.config)
    rep = NamedSharding(mesh, P())
    key0 = weights.seed_key(seed, 0)
    f32 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, "float32"), shapes)
    make = jax.jit(lambda k: reference.init_state(
        weights.make_model_state(f32, k)), out_shardings=rep)
    params0 = jax.jit(lambda k: weights.make_model_state(f32, k)["params"],
                      out_shardings=rep)
    n_shards = 1 if rows is not None else mesh.shape["data"]
    step = reference.make_step(mcfg, tcfg, operand, n_shards)
    state = make(key0)
    base = weights.seed_key(seed, 2)
    first_gradient = jax.jit(
        lambda opt: reference.first_gradient(opt, tcfg), out_shardings=rep)
    stat_changes = jax.jit(
        lambda bn, k: reference.stat_changes(
            bn, weights.make_model_state(f32, k)["bn"]), out_shardings=rep)
    losses, grad, gvec, stats = [], None, None, None
    for i in range(CHECK_STEPS):
        images = batches[i] if rows is None else \
            jax.device_put(batches[i][rows], rep)
        state, loss, norms = step(state, images, jax.random.fold_in(base, i))
        losses.append(loss)
        if i == 0:
            grad, gvec = norms, first_gradient(state["opt"])
            stats = stat_changes(state["bn"], key0)
    delta = jax.jit(reference.delta_norms, out_shardings=rep)(
        state["params"], params0(key0))
    got = jax.device_get({"losses": losses, "grad": grad, "delta": delta,
                          "stats": stats})
    del state
    return {"losses": [{k: float(v) for k, v in m.items()}
                       for m in got["losses"]],
            "grad": {k: float(v) for k, v in got["grad"].items()},
            "delta": {k: float(v) for k, v in got["delta"].items()},
            "gvec": gvec, "stats": got["stats"]}


def compare(read: dict, ref: dict, mesh) -> Dict[str, float]:
    """The training numbers of `read` (the program's readings, or those of
    the reference put in its place) against the reference's `ref`. The
    first gradients meet on the device here, leaf by leaf."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    norm = lambda x: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    read = {**read, "stat_diff": {k: norm(read["stats"][k] - v)
                                  for k, v in ref["stats"].items()}}
    if read.get("gvec") is not None:
        diff = jax.device_get(jax.jit(
            reference.diff_norms, out_shardings=NamedSharding(mesh, P()))(
                read["gvec"], ref["gvec"]))
        read["grad_diff"] = {k: float(v) for k, v in diff.items()}
    return check.training_numbers(
        read, {**ref, "stat": {k: norm(v) for k, v in ref["stats"].items()}})


def check_batches(cell: manifest.Cell, prog_cfg, img_sharding, seed: int,
                  delivered: List[np.ndarray]):
    """The reference's batches, made by the benchmark alone, and the fed
    cell's `feed_gap`. Resident: drawn again from the seed. Records: each
    delivered row names its record; the batch is rebuilt from the records
    the benchmark wrote and the delivered rows are held against it."""
    import jax

    mix, m = cell.traffic, prog_cfg.model
    shape = (prog_cfg.batch_size, m.output_size, m.output_size, m.c_dim)
    if mix["feed"] == "resident":
        return traffic.resident_batches(
            weights.seed_key(seed, 1), int(mix["resident_batches"]),
            shape, img_sharding)[:CHECK_STEPS], {}
    records = traffic.record_images(mix["records"], m.output_size, m.c_dim)
    gap, out = 0.0, []
    for rows in delivered:
        ids = traffic.record_ids(rows)
        if ids.min() < 0 or ids.max() >= len(records):
            gap = math.inf
            ids = np.clip(ids, 0, len(records) - 1)
        want = traffic.normalize(records[ids])
        gap = max(gap, float(np.max(np.abs(rows - want))))
        out.append(jax.device_put(want, img_sharding))
    return out, {"feed_gap": gap}


# --- one run -------------------------------------------------------------------

def run(cell: manifest.Cell, *, root: str, seed: int, seconds: float,
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
    peaks = manifest.peaks(root, kind) if device_metrics else None
    compiles: List[float] = []
    in_window = False

    def on_event(name, secs, **_):
        if in_window and "backend_compile" in name:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)

    prog = build_program(cell, devices)
    state = initial_state(prog, seed)
    feed, close_feed = make_feed(cell, prog, seed, cache_root)
    fed = cell.traffic["feed"] == "records"
    trace_dir = None
    try:
        state, base, prog_read, kept = first_steps(
            prog, state, feed, seed, keep_batches=fed,
            keep_gradient=bool(check.GRADIENT_NUMBERS & set(cell.limits)))
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
    mesh, shapes, cfg, img_sh = prog.mesh, prog.shapes, prog.cfg, prog.img_sharding
    del state, kept, feed, prog
    gc.collect()
    t_ref = time.time()
    batches, feed_numbers = check_batches(cell, cfg, img_sh, seed, delivered)
    ref_read = reference_readings(cell, mesh, shapes, seed, batches)
    numbers.update(compare(prog_read, ref_read, mesh))
    del ref_read
    numbers.update(feed_numbers)
    verdict = check.judge(numbers, cell.limits)
    reference_s = time.time() - t_ref

    batch = cfg.batch_size
    ctx = {"reduced": reduced, "spans": facts["spans"],
           "window_s": facts["window_s"], "steps": facts["steps"],
           "global_batch": batch, "chips": cell.chips, "config": cell.config,
           "traffic": cell.traffic, "peaks": peaks,
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
            value = manifest.layer_metric_reader(root, m["name"])(ctx)
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
