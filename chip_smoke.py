"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU chip, the normal entry points, the flagship model at
its full width (the `celeba64` preset as shipped: DCGAN 64x64, gf=df=64,
z=100, batch 64, bf16 compute; weights random from a seed):

  data     a PNG corpus (made from the seed) through `dcgan_tpu.data.prepare`
           into TFRecords, read back through the NATIVE loader (built with
           g++ on the spot — a missing toolchain is an error here, not a
           fallback) and held against the Python loader, the repo's
           reference for it
  train    `dcgan_tpu.train.cli.main` — synthetic stream for a few tens of
           steps, then a few more from the records — with the persistent
           compile cache on and a checkpoint written
  serve    the sampler service as `python -m dcgan_tpu.serve` constructs
           it, from that checkpoint: bucket ladder warmed, a few dozen
           requests answered, one of them held against
           `dcgan_tpu.generate`, clean drain
  kernels  one `sagan64` train step on the flash attention kernels, proven
           COMPILED from the program text (`tpu_custom_call`) and held at
           the loss level against the same step with dense attention on
           XLA; the bf16 XLA `celeba64` step against the float32 one

`--chips 4` runs instead ONLY the data-parallel path and what it is compared
with: `celeba64` at global batch 256 on a (data=4, model=1) mesh, on both
backends, against the same batch, seed and steps on a one-device mesh.

The numbers on the phase lines (wall, compile, step time, peak memory) are
smoke readings, labelled with the device; they are not benchmark rows.

The last line of stdout is `{"ok": true, "device": {...}}` with the device
as jax reports it. Without a TPU, or when any phase raises or any check
fails, the run exits non-zero and that line says `"ok": false`.

    python chip_smoke.py [--out DIR] [--chips 4]
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback
import warnings

REPO = os.path.dirname(os.path.abspath(__file__))

SEED = 0
TRAIN_STEPS = 40          # synthetic stream
RECORD_STEPS = 10         # continued from TFRecords through the loader
CORPUS_IMAGES = 512
SERVE_REQUESTS = 48
DP_STEPS = 3
DP_GLOBAL_BATCH = 256

#: loss-level agreement asked of two bf16 programs that compute the same
#: step through different kernels (bf16 carries ~3 significant digits)
BF16_LOSS_RTOL = 2e-2
#: first-step loss agreement of one program under two partitionings.
#: tests/test_parallel.py holds the float32 sharded step to 1e-5; in bf16
#: the float32 reduction-order noise between partitionings (~1e-7) now and
#: then lands on a rounding boundary and flips an activation by one bf16 ulp
#: (7e-5 on the loss at 16 px / 8 channels on four CPU devices; 4.4e-5 on
#: g_loss at full width on four v5e chips, PR 21), hence ten times that.
#: Later steps inherit Adam's sign noise on near-zero gradients (that test
#: bounds it on the params by 2*lr) and are held to the bf16 bound instead.
DP_FIRST_STEP_RTOL = 1e-4


class SmokeFailure(Exception):
    """A check on what a phase produced did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _device() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


class Probe:
    """Compile time and persistent-cache counters, from the `compile/backend`
    records of the program's span store (utils/profiling.py)."""

    def __init__(self) -> None:
        from dcgan_tpu.train.warmup import CompileCacheMonitor

        self.cache = CompileCacheMonitor()
        self.since = time.perf_counter()

    def snapshot(self) -> dict:
        from dcgan_tpu.utils import profiling

        compile_s = sum(r.duration for r in profiling.spans("compile/backend")
                        if r.start >= self.since)
        return {**self.cache.counters(), "compile_s": compile_s}


@contextlib.contextmanager
def phase(name: str, probe: Probe):
    """Time a phase and print its line; what the phase learned goes into
    the yielded dict. A phase that raises prints nothing: the run is over."""
    import jax

    before, t0 = probe.snapshot(), time.perf_counter()
    info: dict = {}
    yield info
    after = probe.snapshot()
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "phase": name, "device": jax.devices()[0].device_kind,
        "wall_s": round(time.perf_counter() - t0, 2),
        "compile_s": round(after["compile_s"] - before["compile_s"], 2),
        "cache": {k: int(after[k] - before[k])
                  for k in ("requests", "hits", "misses")},
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        **info}), flush=True)


def _check_images(imgs, size: int, what: str) -> None:
    import numpy as np

    check(imgs.shape[1:] == (size, size, 3), f"{what}: shape {imgs.shape}")
    check(bool(np.isfinite(imgs).all()) and float(np.abs(imgs).max()) <= 1.0,
          f"{what}: not finite in [-1, 1]")


def _check_trained_to(ckpt_dir: str, first: int, last: int) -> dict:
    """The newest checkpoint is step `last`, and every loss logged on the
    way from `first` is finite."""
    from dcgan_tpu.serve.sources import latest_finalized_step

    check(latest_finalized_step(ckpt_dir) == last,
          f"newest checkpoint is step {latest_finalized_step(ckpt_dir)}, "
          f"asked for {last}")
    return _check_losses(ckpt_dir, first, last)


def _scalar_rows(ckpt_dir: str) -> list:
    rows = []
    with open(os.path.join(ckpt_dir, "events.jsonl")) as f:
        for line in f:
            e = json.loads(line)
            if e["kind"] == "scalars" and "d_loss" in e["values"]:
                rows.append((e["step"], e["values"]))
    return rows


def _check_losses(ckpt_dir: str, first: int, last: int) -> dict:
    import math

    rows = [(s, v) for s, v in _scalar_rows(ckpt_dir) if first <= s <= last]
    check(rows and rows[-1][0] == last,
          f"no loss row for step {last} in {ckpt_dir}/events.jsonl")
    for step, vals in rows:
        check(math.isfinite(vals["d_loss"]) and math.isfinite(vals["g_loss"]),
              f"non-finite loss at step {step}: {vals}")
    return {"loss_rows": len(rows),
            "d_loss_last": rows[-1][1]["d_loss"],
            "g_loss_last": rows[-1][1]["g_loss"]}


# -- data ---------------------------------------------------------------------

def make_corpus(out_dir: str, n: int, side: int) -> None:
    """A procedural PNG corpus from SEED (gradients, a disc, noise): the
    point is the disk -> converter -> loader path, not the pictures."""
    import numpy as np
    from PIL import Image

    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(SEED)
    yy, xx = np.mgrid[0:side, 0:side].astype(np.float32) / side
    for i in range(n):
        a, b, c = rng.uniform(-3, 3, 3)
        img = np.stack([np.sin(a * xx + b * yy + c + ch) for ch in range(3)],
                       -1)
        cx, cy = rng.uniform(0.2, 0.8, 2)
        disc = ((xx - cx) ** 2 + (yy - cy) ** 2
                < rng.uniform(.05, .3) ** 2)[..., None]
        img = np.where(disc, rng.uniform(-1, 1, 3).astype(np.float32), img)
        img = img + rng.normal(0, 0.05, img.shape).astype(np.float32)
        arr = np.clip((img * 0.5 + 0.5) * 255, 0, 255).astype(np.uint8)
        Image.fromarray(arr).save(os.path.join(out_dir, f"{i:05d}.png"))


def _one_epoch(records: str, size: int, batch: int, *, native: bool):
    """Every image of one pass over `records`, rows in a canonical order
    (the loaders shuffle, each in its own way)."""
    import numpy as np

    from dcgan_tpu.data import DataConfig, make_dataset

    cfg = DataConfig(data_dir=records, image_size=size, batch_size=batch,
                     record_dtype="uint8", loop=False, use_native=native,
                     n_threads=2, min_after_dequeue=batch, seed=SEED)
    rows = np.concatenate(list(make_dataset(cfg)))
    flat = rows.reshape(len(rows), -1)
    return rows[np.lexsort(flat[:, :8].T[::-1])]


def phase_data(out: str, info: dict, *, size: int, batch: int) -> str:
    import numpy as np

    from dcgan_tpu.data import native, prepare

    t0 = time.perf_counter()
    lib = native._build_library()   # raises without a toolchain: no fallback
    info["loader"] = "native"
    info["loader_lib"] = os.path.basename(lib)
    info["loader_build_s"] = round(time.perf_counter() - t0, 2)

    corpus, records = os.path.join(out, "corpus"), os.path.join(out, "records")
    make_corpus(corpus, CORPUS_IMAGES, size)
    prepare.main(["--input_dir", corpus, "--output_dir", records,
                  "--image_size", str(size), "--crop_size", "0",
                  "--num_shards", "4", "--overwrite"])

    got = _one_epoch(records, size, batch, native=True)
    want = _one_epoch(records, size, batch, native=False)
    check(len(got) == len(want) == CORPUS_IMAGES,
          f"loader epochs of {len(got)} / {len(want)} images")
    _check_images(got, size, "native loader")
    # the two normalise uint8 -> [-1, 1] in float32 by formulas that round
    # differently: one ulp at 1.0 is 1.2e-7
    info["native_vs_python_max_abs"] = float(np.abs(got - want).max())
    check(info["native_vs_python_max_abs"] <= 1e-6,
          "native and Python loaders disagree on the same records")
    info["images"] = int(len(got))
    return records


# -- train --------------------------------------------------------------------

def phase_train(out: str, info: dict, *, model_flags: list) -> str:
    from dcgan_tpu.train import cli

    ckpt = os.path.join(out, "train", "ckpt")
    cli.main(model_flags + [
        "--synthetic", "--max_steps", str(TRAIN_STEPS),
        "--checkpoint_dir", ckpt,
        "--sample_dir", os.path.join(out, "train", "samples"),
        "--sample_every_steps", str(TRAIN_STEPS // 2),
        "--save_summaries_secs", "0", "--log_every_steps", "10",
        "--nan_check_steps", "10", "--seed", str(SEED)])
    info.update(_check_trained_to(ckpt, 1, TRAIN_STEPS))
    return ckpt


def phase_train_records(ckpt: str, records: str, info: dict) -> None:
    """The same run continued from disk: `--checkpoint_dir` alone resumes
    (config.json adopted), and the feed is the record loader."""
    from dcgan_tpu.train import cli

    total = TRAIN_STEPS + RECORD_STEPS
    cli.main(["--checkpoint_dir", ckpt, "--data_dir", records,
              "--max_steps", str(total)])
    info.update(_check_trained_to(ckpt, TRAIN_STEPS + 1, total))


# -- serve --------------------------------------------------------------------

def phase_serve(out: str, ckpt: str, info: dict, *, size: int,
                steps: int) -> None:
    import jax
    import numpy as np

    from dcgan_tpu import generate as generate_cli
    from dcgan_tpu.serve import __main__ as serve_cli

    server, _ = serve_cli.build_server(serve_cli.build_parser().parse_args(
        ["--checkpoint_dir", ckpt, "--seed", str(SEED)]))
    meta = server.start(timeout=600)
    check(meta["step"] == steps,
          f"served state is at step {meta['step']}, trained {steps}")
    info["buckets"] = list(server.ladder.buckets)
    info["cold_start_ms"] = round(server.cold_ms["cold_start_ms"], 1)

    # the latent rows generate.py draws for SEED, alone in the queue so they
    # ride the batch-8 bucket generate.py's --batch_size 8 compiles too
    z = np.asarray(jax.random.uniform(
        jax.random.fold_in(jax.random.key(SEED), 0), (8, 100),
        minval=-1.0, maxval=1.0))
    served = server.submit(z=z).result(timeout=120)

    rng = np.random.default_rng(SEED)
    responses = [server.submit(int(n), seed=i) for i, n in enumerate(
        rng.integers(1, 17, size=SERVE_REQUESTS))]
    t0 = time.perf_counter()
    n_images = 0
    for r in responses:
        imgs = r.result(timeout=120)
        n_images += len(imgs)
        _check_images(imgs, size, "served images")
    info["answer_s"] = round(time.perf_counter() - t0, 3)
    server.stop(drain=True)
    report = server.report()
    check(int(report["serve/completed"]) == SERVE_REQUESTS + 1
          and int(report["serve/dropped"]) == 0,
          f"drain left work behind: {report}")
    check(report.get("serve/compile_requests_after_warmup", 0) == 0,
          "a request compiled after the bucket warmup")
    info.update(requests=SERVE_REQUESTS + 1, images=n_images + 8)

    npz = os.path.join(out, "serve", "generate.npz")
    generate_cli.generate(generate_cli.build_parser().parse_args(
        ["--checkpoint_dir", ckpt, "--out_dir", os.path.join(out, "serve"),
         "--num_images", "8", "--batch_size", "8", "--grid", "0",
         "--npz", npz, "--seed", str(SEED)]))
    want = np.load(npz)["images"]
    info["serve_vs_generate_max_abs"] = float(np.abs(served - want).max())
    check(bool(np.array_equal(served, want)),
          "the service and generate.py disagree on the same latents")


# -- opt-in kernels -----------------------------------------------------------

def _images(batch: int, size: int):
    import numpy as np

    return np.random.default_rng(SEED).uniform(
        -1, 1, size=(batch, size, size, 3)).astype(np.float32)


def _one_step(cfg, mesh, *, steps: int = 1, timed: int = 0):
    """Compile `cfg`'s train step for `mesh` and run `steps` of it from the
    SEED init on the SEED batch: `.rows` (per-step metrics), `.text` (the
    compiled program), `.step_ms` (over a block_until_ready-ended window of
    `timed` further steps), `.state` and `.images` as left on the mesh."""
    import types

    import jax

    from dcgan_tpu.parallel import batch_sharding, make_parallel_train

    pt = make_parallel_train(cfg, mesh)
    state = pt.init(jax.random.key(SEED))
    images = jax.device_put(
        _images(cfg.batch_size, cfg.model.output_size),
        batch_sharding(mesh, 4))
    key = jax.random.key(SEED + 1)
    compiled = pt.programs["train_step"].lower(state, images, key).compile()
    text = compiled.as_text()
    rows = []
    for i in range(steps):
        state, m = compiled(state, images, jax.random.fold_in(key, i))
        rows.append({k: float(v) for k, v in m.items()})
    step_ms = None
    if timed:
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for i in range(timed):
            state, m = compiled(state, images,
                                jax.random.fold_in(key, steps + i))
        jax.block_until_ready(state)
        step_ms = (time.perf_counter() - t0) / timed * 1e3
    return types.SimpleNamespace(rows=rows, text=text, step_ms=step_ms,
                                 state=state, images=images)


def _close(a: dict, b: dict, rtol: float,
           keys=("d_loss", "g_loss")) -> dict:
    """Relative loss differences; raises past `rtol`."""
    import math

    out = {}
    for k in keys:
        check(math.isfinite(a[k]) and math.isfinite(b[k]),
              f"non-finite {k}: {a[k]} vs {b[k]}")
        out[k] = abs(a[k] - b[k]) / max(abs(b[k]), 1e-12)
        check(out[k] <= rtol, f"{k} {a[k]} vs {b[k]}: rel {out[k]:.3g} > "
                              f"{rtol:g}")
    return out


def _with_model(cfg, **kw):
    return dataclasses.replace(cfg,
                               model=dataclasses.replace(cfg.model, **kw))


def phase_kernels(info: dict, *, dcgan, sagan, expect_kernels: bool) -> None:
    """`dcgan`/`sagan`: the celeba64 / sagan64 TrainConfigs. `expect_kernels`
    is False only in the CPU rehearsal, where the kernels are interpreted
    and no `tpu_custom_call` can appear."""
    from dcgan_tpu.parallel import make_mesh

    mesh = make_mesh(dcgan.mesh)

    def run(cfg, *, kernels: bool, timed: int = 0):
        ran = _one_step(cfg, mesh, timed=timed)
        n_calls = ran.text.count("tpu_custom_call")
        if expect_kernels:
            check((n_calls > 0) == kernels,
                  f"{n_calls} tpu_custom_call(s) in a step that should "
                  f"{'run' if kernels else 'not run'} Pallas kernels")
        return ran.rows[0], n_calls, ran.step_ms

    xla, _, step_ms = run(dcgan, kernels=False, timed=20)
    info["celeba64_xla"] = {**xla, "step_ms": round(step_ms, 3)}
    f32, _, _ = run(dataclasses.replace(dcgan, precision="f32"),
                    kernels=False)
    info["celeba64_bf16_vs_f32"] = _close(xla, f32, BF16_LOSS_RTOL)
    dense, _, _ = run(_with_model(sagan, use_pallas=False), kernels=False)
    flash, n_calls, _ = run(sagan, kernels=True)
    info["sagan64_flash"] = {"tpu_custom_calls": n_calls,
                             "vs_dense": _close(flash, dense,
                                                BF16_LOSS_RTOL)}


# -- four chips ---------------------------------------------------------------

def phase_data_parallel(info: dict, *, base, backend: str) -> None:
    """`base` at global batch DP_GLOBAL_BATCH: a (data=n, model=1) mesh over
    every device against a one-device mesh, same batch, seed and steps.

    gspmd draws one global z batch whatever the mesh, so both losses must
    agree at every step. shard_map folds the shard index into the key — the
    fake batch differs by construction — so what must agree is the FIRST
    step's `d_loss_real`, which depends only on the initial params, the BN
    moments and the real batch (the comparison tests/test_shard_map.py
    makes); from the second step on D has trained on different fakes and
    nothing is comparable. Either way every shard of a parameter must hold
    the same bits after the updates (the sync-DP guarantee)."""
    import math

    import jax
    import numpy as np

    from dcgan_tpu.config import MeshConfig
    from dcgan_tpu.parallel import make_mesh

    n = len(jax.devices())
    cfg = dataclasses.replace(base, batch_size=DP_GLOBAL_BATCH,
                              backend=backend,
                              mesh=MeshConfig(data=n, model=1))
    one = make_mesh(MeshConfig(data=1, model=1), jax.devices()[:1])
    ref_rows = _one_step(
        dataclasses.replace(cfg, mesh=MeshConfig(data=1, model=1)), one,
        steps=DP_STEPS).rows
    ran = _one_step(cfg, make_mesh(cfg.mesh), steps=DP_STEPS, timed=10)
    rows, images, text = ran.rows, ran.images, ran.text

    def devices_of(x):
        return {s.device for s in x.addressable_shards}

    # code that has only seen virtual CPU devices may have put everything
    # on devices()[0]: the batch must be cut n ways, every parameter must
    # have a replica on each chip
    check(len(devices_of(images)) == n
          and {s.data.shape[0] for s in images.addressable_shards}
          == {DP_GLOBAL_BATCH // n},
          f"batch is not split over {n} devices")
    for leaf in jax.tree_util.tree_leaves(ran.state["params"]):
        check(len(devices_of(leaf)) == n,
              f"a parameter lives on {len(devices_of(leaf))} of {n} devices")
        first, *others = (np.asarray(s.data) for s in leaf.addressable_shards)
        check(all(np.array_equal(first, o) for o in others),
              "the replicas of a parameter differ after the updates")
    n_allreduce = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    check(n_allreduce > 0, "no all-reduce in the compiled step: the "
                           "gradients are not being summed across chips")
    info.update(devices=n, all_reduces=n_allreduce,
                step_ms=round(ran.step_ms, 3), losses=rows,
                losses_one_device=ref_rows)
    if backend == "gspmd":
        info["rel_diff"] = [
            _close(a, b, DP_FIRST_STEP_RTOL if i == 0 else BF16_LOSS_RTOL)
            for i, (a, b) in enumerate(zip(rows, ref_rows))]
    else:
        info["rel_diff"] = [_close(rows[0], ref_rows[0], DP_FIRST_STEP_RTOL,
                                   ("d_loss_real",))]
        check(all(math.isfinite(v) for r in rows for v in r.values()),
              f"non-finite loss on the {n}-device mesh: {rows}")


# -- driver -------------------------------------------------------------------

def run_one_chip(out: str, probe: Probe) -> None:
    from dcgan_tpu.presets import get_preset

    dcgan, sagan = get_preset("celeba64"), get_preset("sagan64")
    size, batch = dcgan.model.output_size, dcgan.batch_size
    with phase("data", probe) as info:
        records = phase_data(out, info, size=size, batch=batch)
    with phase("train", probe) as info:
        ckpt = phase_train(out, info, model_flags=["--preset", "celeba64"])
    with phase("train-records", probe) as info:
        phase_train_records(ckpt, records, info)
    with phase("serve", probe) as info:
        phase_serve(out, ckpt, info, size=size,
                    steps=TRAIN_STEPS + RECORD_STEPS)
    with phase("kernels", probe) as info:
        phase_kernels(info, dcgan=dcgan, sagan=sagan, expect_kernels=True)


def run_four_chips(probe: Probe) -> None:
    from dcgan_tpu.presets import get_preset

    for backend in ("gspmd", "shard_map"):
        with phase(f"data-parallel-{backend}", probe) as info:
            phase_data_parallel(info, base=get_preset("celeba64"),
                                backend=backend)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default=os.path.join(REPO, ".chip_smoke_out"),
                   help="directory for run artefacts (corpus, records, "
                        "checkpoints, samples, event files)")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the data-parallel path on four chips "
                        "and the one-device run it is compared with")
    args = p.parse_args(argv)

    import jax

    device = _device()
    if device["platform"] != "tpu" or device["count"] != args.chips:
        # no fallback: nothing below may run, and so be reported, on
        # another backend or on another number of chips than was asked for
        print(json.dumps({"ok": False, "device": device,
                          "error": f"need {args.chips} TPU chip(s)"}))
        return 1

    # on this path a loader that cannot be built is an error, wherever the
    # pipeline would otherwise warn and carry on in Python
    warnings.filterwarnings("error", message="native loader unavailable")

    from dcgan_tpu.train import warmup

    cache_dir = warmup.configure_compile_cache(
        warmup.resolve_cache_dir(entry_point=True))
    probe = Probe()
    print(json.dumps({"device": device, "jax": jax.__version__,
                      "compile_cache_dir": cache_dir, "out": args.out}),
          flush=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(probe)
        else:
            run_one_chip(args.out, probe)
    except BaseException as e:  # report, then fail: never ends with 0
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device,
                          "error": f"{type(e).__name__}: {e}"[:500]}))
        return 1
    total = probe.snapshot()
    print(json.dumps({
        "wall_s": round(time.perf_counter() - t0, 1),
        "compile_s": round(total["compile_s"], 1),
        "cache": {k: int(total[k]) for k in ("requests", "hits", "misses")}}))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
