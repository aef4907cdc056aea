"""Benchmark: DCGAN-64 training throughput (images/sec/chip).

Flagship config = the reference's headline workload: DCGAN 64x64, batch 64,
z=100, Adam(2e-4, 0.5) — its hot loop ran two host<->device round-trips, a
numpy-fed z, and a gRPC weight sync per step (image_train.py:147-194,
SURVEY.md §3.1). Here the whole D+G step is one compiled XLA program with
donated state and on-device PRNG, so steady-state throughput is pure device
time.

Baseline: the reference publishes no numbers (README "Benchmarks"). The
driver-defined north star is >=4x a single-V100 TF DCGAN-64 baseline; public
single-V100 TF DCGAN-64 trainers at batch 64 sustain roughly 2000 images/sec,
which we adopt (documented assumption) as baseline=2000 for vs_baseline.

Device: every row names the device it ran on (`platform`, `device_kind`,
`device_count`, as jax reports them), and a CPU backend prints no
throughput row unless BENCH_PLATFORM=cpu asked for one — a measurement that
finds no chip fails, it does not fall back. One process: main() runs in
the process that was started, so nothing else dials the chip first.

Output contract (the driver parses the LAST stdout line): the headline
row {"metric", "value", "unit", "vs_baseline"} is always the FINAL JSON
line on stdout. Every A/B knob — PIPELINE_GD=1 (_bench_pipeline_ab),
ZERO_STAGE={2,3} (_bench_zero_ab), PROGRESSIVE=1, PRECISION
(_bench_precision_ab), COMM_OVERLAP=1
(_bench_comm_overlap_ab) — prints its extra row(s) BEFORE the headline
row, and all non-row context goes to stderr, so adding a knob can never
break the last-line parse. tests/test_comm_overlap.py pins the row
order.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# process-start anchor for the startup_ms field: time-to-first-step is
# measured from interpreter entry (import cost included — restarts pay it)
_T_PROC_START = time.perf_counter()

V100_TF_BASELINE_IMG_PER_SEC = 2000.0

# The reference's headline workload knobs (image_train.py:42-48).
# BENCH_* env overrides exist for local smoke runs (e.g. BENCH_PLATFORM=cpu
# BENCH_BATCH=8 BENCH_STEPS=3); the driver's TPU run uses the defaults.
BATCH = int(os.environ.get("BENCH_BATCH", 64))
STEPS_MEASURE = int(os.environ.get("BENCH_STEPS", 400))
STEPS_WARMUP = 5
# Steps per dispatched program (ParallelTrain.multi_step, a lax.scan), which
# sheds the per-dispatch host cost (not measured on the current machine).
# 1 = the plain per-step path (also the default for CPU smoke runs, where
# compiling the scanned program costs minutes). Clamped to BENCH_STEPS so a
# smoke run never exceeds the requested steps.
_SCAN_DEFAULT = 1 if os.environ.get("BENCH_PLATFORM") == "cpu" else 50
SCAN = max(1, min(int(os.environ.get("BENCH_SCAN", _SCAN_DEFAULT)),
                  STEPS_MEASURE))


def _bench_sample(cfg, pt, state, n_chips: int) -> None:
    """BENCH_MODE=sample: generation (inference) throughput through
    ParallelTrain.sample — the serve analogue of the reference's only
    generation path, the in-graph sampler (image_train.py:179-192).

    One dispatch per call (there is no scanned multi-sample), so the z
    batch is deliberately large (default 1024/chip) to amortize the
    per-dispatch host cost; z lives on device and is reused across calls —
    throughput needs device work, not fresh latents.
    """
    import jax
    import jax.numpy as jnp

    batch = int(os.environ.get("BENCH_SAMPLE_BATCH", 1024)) * n_chips
    z = jax.random.uniform(jax.random.key(2), (batch, cfg.model.z_dim),
                           minval=-1.0, maxval=1.0, dtype=jnp.float32)
    labels = (jnp.asarray(
        np.arange(batch) % cfg.model.num_classes),) \
        if cfg.model.num_classes else ()
    imgs = pt.sample(state, z, *labels)      # compile + warmup
    float(imgs[0, 0, 0, 0])                  # sync: the value is the window's end

    windows = int(os.environ.get("BENCH_WINDOWS", 3))
    # own knob: sample dispatch count must not silently track the
    # train-step BENCH_STEPS knob (the two measure different programs)
    n_calls = int(os.environ.get("BENCH_SAMPLE_CALLS", 20))
    dt = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(n_calls):
            imgs = pt.sample(state, z, *labels)
        float(imgs[0, 0, 0, 0])
        dt = min(dt, time.perf_counter() - t0)

    img_per_sec_chip = batch * n_calls / dt / n_chips
    arch = os.environ.get("BENCH_PRESET", "") or (
        f"SAGAN-{cfg.model.output_size}" if cfg.model.attn_res
        else f"DCGAN-{cfg.model.output_size}")
    row = {
        "metric": f"{arch} sampler (inference) throughput "
                  f"(batch {batch // n_chips}/chip, bf16)",
        "value": round(img_per_sec_chip, 1),
        "unit": "images/sec/chip",
        # vs the same adopted train baseline is meaningless for inference;
        # report the ratio to our own measured train rate out-of-band (docs)
        "vs_baseline": None,
    }
    if cfg.model.attn_res:
        # same generation stamp as the train rows (VERDICT r4 #1), with the
        # same flash/dense split (ADVICE r5 #1): stamp the generation of the
        # attention code this config actually EXECUTES, so a flash-only
        # ATTN_GEN bump can never retire dense sampler capture history
        if cfg.model.use_pallas:
            from dcgan_tpu.ops.pallas_attention import ATTN_GEN
            row["gen"] = ATTN_GEN
        else:
            from dcgan_tpu.ops.attention import DENSE_ATTN_GEN
            row["gen"] = DENSE_ATTN_GEN
    print(json.dumps({**row, **_device_fields()}))
    print(f"chips={n_chips} batch={batch} calls={n_calls} wall={dt:.2f}s "
          f"ms_per_step={dt / n_calls * 1e3:.2f}", file=sys.stderr)


def _device_fields() -> dict:
    """What the row ran on, as jax reports it — appended to the headline
    row so a CPU smoke row can never be read as a chip row."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": len(jax.devices())}


def _state_mib_per_chip(state) -> float:
    """Per-chip resident train-state MiB — the number the ZeRO ladder
    moves (one shared derivation: parallel/sharding.state_bytes_per_chip,
    also what the zero-stage tests pin)."""
    from dcgan_tpu.parallel.sharding import state_bytes_per_chip

    return round(state_bytes_per_chip(state) / 2**20, 2)


def _time_arm(run, st, step_idx: int, windows: int):
    """One A/B arm's timing harness, shared by the pipelined and ZeRO
    rows so the two A/B methodologies cannot drift: a compile+warmup
    call, then best-of-`windows` wall clock, each window ended by reading
    its last metric back. `run(state, step_idx) ->
    (state, metrics, step_idx)`. Returns (state, metrics, step_idx,
    best_window_seconds)."""
    st, metrics, step_idx = run(st, step_idx)        # compile + warmup
    float(metrics["d_loss"])                         # sync
    dt = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        st, metrics, step_idx = run(st, step_idx)
        float(metrics["d_loss"])
        dt = min(dt, time.perf_counter() - t0)
    return st, metrics, step_idx, dt


def _bench_zero_ab(cfg, mesh, n_chips: int, images, base) -> None:
    """ZERO_STAGE={2,3}: the state-sharding A/B row (ISSUE 13).

    Measures the SAME config per-step at zero_stage 1 and each stage up
    to ZERO_STAGE, and prints one extra BENCH-style row with every arm's
    ms_per_step + peak_state_mib (per-chip resident state bytes from the
    live shardings). The contract the acceptance rides on: peak_state_mib
    strictly DECREASING from stage 1 -> 3 while throughput stays within
    noise — the ZeRO win as a number, not a claim. Printed BEFORE the
    headline row so the driver's last-line parse is unchanged.
    """
    import dataclasses

    import jax

    from dcgan_tpu.parallel import make_parallel_train

    top = int(os.environ["ZERO_STAGE"])
    steps = max(1, int(os.environ.get("BENCH_ZERO_STEPS",
                                      min(STEPS_MEASURE, 60))))
    windows = int(os.environ.get("BENCH_WINDOWS", 3))
    arms = {}
    for stage in [s for s in (1, 2, 3) if s <= top]:
        cfg_s = dataclasses.replace(
            cfg, mesh=dataclasses.replace(cfg.mesh, zero_stage=stage))
        pt_s = make_parallel_train(cfg_s, mesh)
        st = pt_s.init(jax.random.key(0))
        peak_state = _state_mib_per_chip(st)

        def run(st, step_idx, _pt=pt_s):
            for _ in range(steps):
                st, metrics = _pt.step(st, images,
                                       jax.random.fold_in(base, step_idx))
                step_idx += 1
            return st, metrics, step_idx

        st, _metrics, _idx, dt = _time_arm(run, st, 0, windows)
        arms[f"zero{stage}"] = {
            "ms_per_step": round(dt / steps * 1e3, 3),
            "images_per_sec_chip": round(
                cfg.batch_size * steps / dt / n_chips, 1),
            "peak_state_mib": peak_state,
        }
        del st  # free the arm's state before the next arm compiles
    arch = os.environ.get("BENCH_PRESET", "") or (
        f"DCGAN-{cfg.model.output_size}")
    z1, ztop = arms["zero1"], arms[f"zero{top}"]
    print(json.dumps({
        "metric": f"{arch} ZeRO state-sharding A/B (batch {BATCH}/chip, "
                  "per-step dispatch, bf16)",
        "value": ztop["images_per_sec_chip"],
        "unit": "images/sec/chip",
        "vs_baseline": round(ztop["images_per_sec_chip"]
                             / V100_TF_BASELINE_IMG_PER_SEC, 3),
        **arms,
        # the headline memory claim as one unitless number
        "state_mib_zero1_over_top": round(
            z1["peak_state_mib"] / ztop["peak_state_mib"], 3)
        if ztop["peak_state_mib"] else None,
    }))


def _bench_comm_overlap_ab(cfg, mesh, n_chips: int, images, base) -> None:
    """COMM_OVERLAP=1: the collective overlap A/B row (ISSUE 20).

    Measures the SAME workload per-step with `--comm_overlap off` vs
    `bucket` (vs `prefetch` too when the ZeRO stage is 3) on the
    shard_map backend — the backend whose hand-placed collectives the
    knob restructures (gspmd's half is scheduler flags; its program is
    unchanged) — at zero_stage = ZERO_STAGE when set, else 2. Each arm
    reports ms_per_step AND its collective-census op counts from the
    traced step program, so the row carries the acceptance contract
    directly: the bucket arm's op count strictly below the per-leaf
    baseline's, wall-clock alongside. Printed BEFORE the headline row
    so the driver's last-line parse is unchanged.
    """
    import dataclasses

    import jax

    from dcgan_tpu.analysis.semantic import CENSUS_PRIMS, _walk_jaxpr
    from dcgan_tpu.parallel import make_parallel_train

    stage = max(2, int(os.environ.get("ZERO_STAGE") or 2))
    if cfg.backend != "shard_map" and (cfg.mesh.model != 1
                                       or cfg.mesh.spatial
                                       or cfg.mesh.shard_opt
                                       or cfg.grad_clip > 0):
        print("COMM_OVERLAP=1 skipped: the A/B runs the shard_map "
              "backend and this config does not compose with it",
              file=sys.stderr)
        return
    steps = max(1, int(os.environ.get("BENCH_OVERLAP_STEPS",
                                      min(STEPS_MEASURE, 60))))
    windows = int(os.environ.get("BENCH_WINDOWS", 3))
    arms = {}
    for mode in ["off", "bucket"] + (["prefetch"] if stage == 3 else []):
        cfg_o = dataclasses.replace(
            cfg, backend="shard_map", comm_overlap=mode,
            mesh=dataclasses.replace(cfg.mesh, zero_stage=stage))
        pt_o = make_parallel_train(cfg_o, mesh)
        st = pt_o.init(jax.random.key(0))
        census = {}

        def visit(eqn, _c=census):
            kind = CENSUS_PRIMS.get(eqn.primitive.name)
            if kind is not None:
                _c[kind] = _c.get(kind, 0) + 1
        _walk_jaxpr(jax.jit(pt_o.step).trace(
            st, images, jax.random.fold_in(base, 0)).jaxpr.jaxpr, visit)

        def run(st, step_idx, _pt=pt_o):
            for _ in range(steps):
                st, metrics = _pt.step(st, images,
                                       jax.random.fold_in(base, step_idx))
                step_idx += 1
            return st, metrics, step_idx

        st, _metrics, _idx, dt = _time_arm(run, st, 0, windows)
        arms[mode] = {
            "ms_per_step": round(dt / steps * 1e3, 3),
            "images_per_sec_chip": round(
                cfg.batch_size * steps / dt / n_chips, 1),
            "collective_ops": dict(sorted(census.items())),
            "collective_ops_total": sum(census.values()),
        }
        del st  # free the arm's state before the next arm compiles
    arch = os.environ.get("BENCH_PRESET", "") or (
        f"DCGAN-{cfg.model.output_size}")
    best = arms.get("prefetch") or arms["bucket"]
    print(json.dumps({
        "metric": f"{arch} collective overlap A/B (shard_map, "
                  f"zero_stage={stage}, batch {BATCH}/chip)",
        "value": best["images_per_sec_chip"],
        "unit": "images/sec/chip",
        "vs_baseline": round(best["images_per_sec_chip"]
                             / V100_TF_BASELINE_IMG_PER_SEC, 3),
        **arms,
    }))


def _bench_precision_ab(cfg, mesh, n_chips: int, images, base) -> None:
    """PRECISION=bf16: the reduced-precision A/B row (ISSUE 17).

    Measures the SAME workload per-step against an explicit f32 control
    arm (precision="f32" forces f32 params+compute even when the headline
    config computes in bf16) and the @<precision> arm. Every arm reports
    ms_per_step + images_per_sec_chip + peak_state_mib (bf16 params halve
    the resident param/nu bytes; mu stays f32 master). `ms_f32_over_best`
    is the control's ms_per_step over the policy arm's. Printed BEFORE the
    headline row so the driver's last-line parse is unchanged.
    """
    import dataclasses

    import jax

    from dcgan_tpu.parallel import make_parallel_train

    precision = os.environ["PRECISION"]
    steps = max(1, int(os.environ.get("BENCH_PRECISION_STEPS",
                                      min(STEPS_MEASURE, 60))))
    windows = int(os.environ.get("BENCH_WINDOWS", 3))

    arms = {}
    for tag in ("f32", precision):
        cfg_a = dataclasses.replace(cfg, precision=tag)
        pt_a = make_parallel_train(cfg_a, mesh)
        st = pt_a.init(jax.random.key(0))
        peak_state = _state_mib_per_chip(st)

        def run(st, step_idx, _pt=pt_a):
            for _ in range(steps):
                st, metrics = _pt.step(st, images,
                                       jax.random.fold_in(base, step_idx))
                step_idx += 1
            return st, metrics, step_idx

        st, _metrics, _idx, dt = _time_arm(run, st, 0, windows)
        arms[tag] = {
            "ms_per_step": round(dt / steps * 1e3, 3),
            "images_per_sec_chip": round(
                cfg.batch_size * steps / dt / n_chips, 1),
            "peak_state_mib": peak_state,
        }
        del st  # free the arm's state before the next arm compiles
    arch = os.environ.get("BENCH_PRESET", "") or (
        f"DCGAN-{cfg.model.output_size}")
    f32, best = arms["f32"], arms[precision]
    print(json.dumps({
        "metric": f"{arch} precision A/B (batch {BATCH}/chip, "
                  "per-step dispatch)",
        "value": best["images_per_sec_chip"],
        "unit": "images/sec/chip",
        "vs_baseline": round(best["images_per_sec_chip"]
                             / V100_TF_BASELINE_IMG_PER_SEC, 3),
        **arms,
        "best_arm": precision,
        # the headline speed claim as one unitless number: control
        # ms_per_step over the policy arm's (>1 = the policy won)
        "ms_f32_over_best": round(
            f32["ms_per_step"] / best["ms_per_step"], 4)
        if best["ms_per_step"] else None,
    }))


def _bench_progressive_ab(cfg, mesh, n_chips: int, base) -> None:
    """PROGRESSIVE=1: the progressive-resolution A/B rows (ISSUE 15).

    Two extra BENCH-style rows, both printed BEFORE the headline row so
    the driver's last-line parse is unchanged:

    1. the schedule A/B — the SAME model trained 64-only vs as a
       64 -> 128 schedule driven through the shipped PhaseRuntime
       (surface build, state carry, the lot), with per-phase ms_per_step
       and the measured switch_ms. The contract: phase-0 throughput ==
       the fixed-resolution arm within noise (the schedule machinery is
       free until a switch), and switch_ms is a one-off cost, not a
       per-step tax.
    2. a standalone 256px single-phase row — the perf story finally
       covers more than one shape (ROADMAP item 5). BENCH_256_BATCH
       overrides its per-chip batch (default: the headline batch).
    """
    import dataclasses

    import jax

    from dcgan_tpu.progressive import PhaseRuntime, parse_schedule

    steps = max(1, int(os.environ.get("BENCH_PROGRESSIVE_STEPS",
                                      min(STEPS_MEASURE, 40))))
    windows = int(os.environ.get("BENCH_WINDOWS", 3))
    base_res = cfg.model.output_size
    top_res = base_res * 2
    spec = f"{base_res}:{steps},{top_res}:*"
    cfg_p = dataclasses.replace(
        cfg, progressive=spec,
        model=dataclasses.replace(cfg.model, output_size=top_res))
    rt = PhaseRuntime(
        cfg_p, mesh,
        parse_schedule(spec, model=cfg_p.model,
                       batch_size=cfg_p.batch_size,
                       max_steps=cfg_p.max_steps,
                       grad_accum=cfg_p.grad_accum),
        cfg_p.max_steps)

    rng = np.random.default_rng(7)

    def _imgs(res, batch):
        import jax.numpy as jnp

        return jnp.asarray(rng.uniform(
            -1, 1, size=(batch, res, res, cfg.model.c_dim))
            .astype(np.float32))

    def _arm(pt_i, st, images, tag):
        def run(st, step_idx, _pt=pt_i, _img=images):
            for _ in range(steps):
                st, metrics = _pt.step(st, _img,
                                       jax.random.fold_in(base, step_idx))
                step_idx += 1
            return st, metrics, step_idx
        st, _m, _idx, dt = _time_arm(run, st, 0, windows)
        return st, {
            "ms_per_step": round(dt / steps * 1e3, 3),
            "images_per_sec_chip": round(
                cfg.batch_size * steps / dt / n_chips, 1),
        }

    arms = {}
    # fixed-resolution control: its own init, the phase-0 config alone
    _cfg0, pt0 = rt.surface(0)
    st = pt0.init(jax.random.key(0))
    st, arms[f"fixed{base_res}"] = _arm(pt0, st, _imgs(base_res,
                                                      cfg.batch_size),
                                        "fixed")
    del st
    # the scheduled run: phase 0, the live switch, phase 1
    st = pt0.init(jax.random.key(0))
    st, arms[f"phase_r{base_res}"] = _arm(pt0, st,
                                          _imgs(base_res, cfg.batch_size),
                                          "p0")
    t_sw = time.perf_counter()
    st = rt.advance(st)
    jax.block_until_ready(jax.tree_util.tree_leaves(st)[0])
    switch_ms = (time.perf_counter() - t_sw) * 1e3
    _cfg1, pt1 = rt.surface(1)
    st, arms[f"phase_r{top_res}"] = _arm(pt1, st,
                                         _imgs(top_res, cfg.batch_size),
                                         "p1")
    del st
    arch = os.environ.get("BENCH_PRESET", "") or f"DCGAN-{base_res}"
    f0 = arms[f"fixed{base_res}"]
    p1 = arms[f"phase_r{top_res}"]
    print(json.dumps({
        "metric": f"{arch} progressive {base_res}->{top_res} A/B "
                  f"(batch {BATCH}/chip, per-step dispatch, bf16)",
        "value": p1["images_per_sec_chip"],
        "unit": "images/sec/chip",
        "vs_baseline": None,  # cross-resolution rates have no 64px baseline
        **arms,
        "switch_ms": round(switch_ms, 1),
        "carried_leaves": rt.last_carried,
    }))

    # standalone 256px single-phase row (the new shape in the perf story)
    res = 256
    b256 = int(os.environ.get("BENCH_256_BATCH", BATCH)) * n_chips
    steps256 = max(1, int(os.environ.get("BENCH_256_STEPS",
                                         min(STEPS_MEASURE, 20))))
    from dcgan_tpu.parallel import make_parallel_train

    cfg256 = dataclasses.replace(
        cfg, batch_size=b256, progressive="",
        model=dataclasses.replace(cfg.model, output_size=res))
    pt256 = make_parallel_train(cfg256, mesh)
    st = pt256.init(jax.random.key(0))
    img256 = _imgs(res, b256)

    def run256(st, step_idx):
        for _ in range(steps256):
            st, metrics = pt256.step(st, img256,
                                     jax.random.fold_in(base, step_idx))
            step_idx += 1
        return st, metrics, step_idx

    st, _m, _idx, dt = _time_arm(run256, st, 0, windows)
    print(json.dumps({
        "metric": f"DCGAN-{res} train throughput "
                  f"(batch {b256 // n_chips}/chip, bf16)",
        "value": round(b256 * steps256 / dt / n_chips, 1),
        "unit": "images/sec/chip",
        "vs_baseline": None,  # the adopted V100 baseline is a 64px number
        "ms_per_step": round(dt / steps256 * 1e3, 3),
        "peak_state_mib": _state_mib_per_chip(st),
    }))
    del st


def _bench_pipeline_ab(cfg, pt, n_chips: int, images, base) -> None:
    """PIPELINE_GD=1: the pipelined G/D dispatch A/B row (ISSUE 7).

    Measures the SAME config twice at per-step dispatch — the fused
    train_step program vs the gen_fakes/d_update/g_update stage loop the
    trainer runs under --pipeline_gd (driven through the trainer's own
    GDPipeline buffer manager, so the benched dataflow is the shipped
    one) — and prints one extra BENCH-style row with both arms'
    ms_per_step + devstep_ms. Per-step FLOPs are conservation-equal
    across the arms, so
    this row is the regression guard that the stage split's extra
    dispatches stay in the noise, not a speedup claim. Printed BEFORE
    the headline row so the driver's last-line parse is unchanged.
    """
    import jax

    from dcgan_tpu.train.gd_pipeline import GDPipeline

    steps = max(1, int(os.environ.get("BENCH_PIPELINE_STEPS",
                                      min(STEPS_MEASURE, 60))))
    windows = int(os.environ.get("BENCH_WINDOWS", 3))

    def _fused(state, step_idx):
        for _ in range(steps):
            state, metrics = pt.step(state, images,
                                     jax.random.fold_in(base, step_idx))
            step_idx += 1
        return state, metrics, step_idx

    pipe = GDPipeline()

    def _pipelined(state, step_idx):
        for _ in range(steps):
            state, metrics = pipe.step(pt, state, images,
                                       jax.random.fold_in(base, step_idx))
            step_idx += 1
        return state, metrics, step_idx

    arms = {}
    for arm, run in (("fused", _fused), ("pipelined", _pipelined)):
        # fresh state per arm (donation consumed the other arm's): arms
        # must not share optimizer history either
        st = pt.init(jax.random.key(0))
        st, metrics, step_idx, dt = _time_arm(run, st, 0, windows)
        devstep = None
        if os.environ.get("BENCH_DEVSTEP", "1") != "0":
            try:
                import tempfile

                from dcgan_tpu.utils.trace import digest, find_trace, \
                    stage_step_ms
                with tempfile.TemporaryDirectory() as td:
                    jax.profiler.start_trace(td)
                    try:
                        st, metrics, step_idx = run(st, step_idx)
                        float(metrics["d_loss"])
                    finally:
                        jax.profiler.stop_trace()
                    d = digest(find_trace(td))
                    if d["source"] != "none" and d["program_ms_median"] > 0:
                        # stage-summed per-step time when the track names
                        # the stage programs (TPU module tracks); busiest-
                        # program median otherwise — same convention as the
                        # trainer's perf/device/step_ms
                        devstep = (stage_step_ms(d)
                                   if arm == "pipelined" else 0.0) \
                            or d["program_ms_median"]
            except OSError as e:  # trace dir / file IO: the field is optional
                print(f"{arm} devstep capture failed: {e!r}", file=sys.stderr)
        arms[arm] = {
            "ms_per_step": round(dt / steps * 1e3, 3),
            "images_per_sec_chip": round(
                cfg.batch_size * steps / dt / n_chips, 1),
            "devstep_ms": round(devstep, 4) if devstep else None,
        }
        pipe.drain("bench-arm-end")
    f, p = arms["fused"], arms["pipelined"]
    speedup = f["ms_per_step"] / p["ms_per_step"] \
        if p["ms_per_step"] > 0 else None
    arch = os.environ.get("BENCH_PRESET", "") or (
        f"DCGAN-{cfg.model.output_size}")
    print(json.dumps({
        "metric": f"{arch} pipelined G/D A/B (batch {BATCH}/chip, "
                  "per-step dispatch, bf16)",
        "value": p["images_per_sec_chip"],
        "unit": "images/sec/chip",
        "vs_baseline": round(p["images_per_sec_chip"]
                             / V100_TF_BASELINE_IMG_PER_SEC, 3),
        "fused": f, "pipelined": p,
        # unitless ratio: fused ms_per_step / pipelined ms_per_step
        "fused_over_pipelined": round(speedup, 4) if speedup else None,
    }))


def main() -> None:
    import jax

    if os.environ.get("BENCH_PLATFORM"):
        # an explicit platform for CPU smoke runs (jax is imported, so the
        # config — not JAX_PLATFORMS — is what can still be set)
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    # the compile cache, placed like every other entry point's: with a
    # primed cache the startup_ms field below records the deserialize-not-
    # compile path
    from dcgan_tpu.train import warmup

    warmup.configure_compile_cache(
        warmup.resolve_cache_dir(entry_point=True))
    import jax.numpy as jnp

    from dcgan_tpu.config import MeshConfig, TrainConfig
    from dcgan_tpu.parallel import make_mesh, make_parallel_train

    n_chips = len(jax.devices())
    if jax.devices()[0].platform == "cpu" \
            and os.environ.get("BENCH_PLATFORM") != "cpu":
        # no fallback: a throughput row from the CPU backend is printed
        # only when a CPU smoke run was asked for by name
        print(json.dumps({"metric": "bench_error", "value": None,
                          "unit": "images/sec/chip", "vs_baseline": None,
                          "error": "no accelerator: jax selected the CPU "
                                   "backend (set BENCH_PLATFORM=cpu for a "
                                   "CPU smoke row)", **_device_fields()}))
        sys.exit(1)
    preset_name = os.environ.get("BENCH_PRESET", "")
    if preset_name:
        # Bench any named config (VERDICT r1 #4): the preset supplies
        # architecture + loss + optimizer recipe; batch/mesh are re-derived
        # for the chips actually present (BENCH_BATCH stays per-chip).
        import dataclasses

        from dcgan_tpu.presets import get_preset

        base = get_preset(preset_name)
        cfg = dataclasses.replace(
            base,
            batch_size=BATCH * n_chips,
            mesh=MeshConfig(),
            grad_accum=int(os.environ.get("BENCH_ACCUM", 1)),
            # only an EXPLICIT BENCH_BACKEND overrides the preset's own
            # backend — clobbering it would measure a config that isn't
            # the preset (and stamp the preset's rev onto it)
            backend=os.environ.get("BENCH_BACKEND", base.backend))
    else:
        # the BENCH_* model knobs: dcgan_tpu/utils/bench_env.py
        # documents each
        from dcgan_tpu.utils.bench_env import bench_model_config

        mcfg, _ = bench_model_config()
        cfg = TrainConfig(
            model=mcfg,                 # flagship default: 64x64, gf=df=64
            batch_size=BATCH * n_chips,
            mesh=MeshConfig(),
            # BENCH_ACCUM=K: gradient-accumulation cost — same global batch,
            # K scanned microbatches per optimizer update. Composes with the
            # other BENCH_* model knobs rather than forking its own config.
            grad_accum=int(os.environ.get("BENCH_ACCUM", 1)),
            backend=os.environ.get("BENCH_BACKEND", "gspmd"))
    # BENCH_ATTN_RES=R: self-attention at an arbitrary feature-map
    # resolution (sequence length R*R) on top of WHATEVER config was built
    # above — preset or default. This is the long-context bench knob: at
    # R=128 (S=16384) the dense [S, S] form cannot allocate at train batch
    # sizes and only the flash path runs (DESIGN.md §8).
    from dcgan_tpu.utils.bench_env import apply_attn_res_override

    cfg = apply_attn_res_override(cfg)
    mesh = make_mesh(cfg.mesh)
    pt = make_parallel_train(cfg, mesh)

    size = cfg.model.output_size
    state = pt.init(jax.random.key(0))
    if os.environ.get("BENCH_MODE") == "sample":
        _bench_sample(cfg, pt, state, n_chips)
        return
    images = jnp.asarray(np.random.default_rng(0).uniform(
        -1, 1, size=(cfg.batch_size, size, size, cfg.model.c_dim))
        .astype(np.float32))
    labels = (jnp.asarray(np.arange(cfg.batch_size) % cfg.model.num_classes),
              ) if cfg.model.num_classes else ()
    base = jax.random.key(1)

    # Warmup compiles exactly the program the measurement uses. Each
    # window ends by reading its last metric back — the value is the
    # natural end of the window, and waiting for it is the sync.
    if SCAN > 1:
        imgs_k = jnp.broadcast_to(images, (SCAN,) + images.shape)
        labels_k = tuple(jnp.broadcast_to(l, (SCAN,) + l.shape)
                         for l in labels)
        state, metrics = pt.multi_step(
            state, imgs_k, jax.random.split(jax.random.fold_in(base, 999),
                                            SCAN), *labels_k)
    else:
        for i in range(STEPS_WARMUP):
            state, metrics = pt.step(state, images,
                                     jax.random.fold_in(base, i), *labels)
    float(metrics["d_loss"])
    # time-to-first-step: interpreter entry -> the first compiled step's
    # value readback (compile + warmup included); a run against a primed
    # compile cache should show this dropping to the deserialize floor.
    startup_ms = (time.perf_counter() - _T_PROC_START) * 1e3

    # Best of WINDOWS measurement windows (the run-to-run spread on the
    # current machine is not measured; medians and quartiles are the
    # benchmark PR's, ROADMAP Queue 1 item 0).
    windows = int(os.environ.get("BENCH_WINDOWS", 3))
    n_calls = max(1, STEPS_MEASURE // SCAN)
    steps_window = n_calls * SCAN if SCAN > 1 else STEPS_MEASURE
    if steps_window != STEPS_MEASURE:
        print(f"note: BENCH_STEPS={STEPS_MEASURE} rounded to {steps_window} "
              f"(multiple of BENCH_SCAN={SCAN})", file=sys.stderr)
    dt = float("inf")
    final_d_loss = 0.0
    step_idx = STEPS_WARMUP
    for _ in range(windows):
        t0 = time.perf_counter()
        if SCAN > 1:
            for _ in range(n_calls):
                keys = jax.random.split(jax.random.fold_in(base, step_idx),
                                        SCAN)
                state, metrics = pt.multi_step(state, imgs_k, keys, *labels_k)
                step_idx += 1
        else:
            for _ in range(STEPS_MEASURE):
                state, metrics = pt.step(state, images,
                                         jax.random.fold_in(base, step_idx),
                                         *labels)
                step_idx += 1
        final_d_loss = float(metrics["d_loss"])  # hard sync ends the window
        dt = min(dt, time.perf_counter() - t0)

    # devstep_ms (ISSUE 6): the device's OWN step time from a short trace
    # digest — host wall-clock rows carry host noise the device timeline
    # does not, so BENCH rows pin both. Only trace-file IO may leave the
    # field null; any other failure of the capture is the run's failure.
    devstep_ms = None
    if os.environ.get("BENCH_DEVSTEP", "1") != "0":
        try:
            import tempfile

            from dcgan_tpu.utils.trace import devstep_ms as devstep_of
            with tempfile.TemporaryDirectory() as td:
                jax.profiler.start_trace(td)
                try:
                    # stop_trace in the finally: a raise inside the traced
                    # region must not leave the profiler active for the
                    # rest of the process (any later start_trace would
                    # fail, and it would trace into a deleted tempdir)
                    if SCAN > 1:
                        keys = jax.random.split(
                            jax.random.fold_in(base, step_idx), SCAN)
                        state, metrics = pt.multi_step(state, imgs_k, keys,
                                                       *labels_k)
                    else:
                        for _ in range(min(5, STEPS_MEASURE)):
                            state, metrics = pt.step(
                                state, images,
                                jax.random.fold_in(base, step_idx), *labels)
                            step_idx += 1
                    # device work lands inside the trace
                    float(metrics["d_loss"])
                finally:
                    jax.profiler.stop_trace()
                devstep_ms = devstep_of(td, per_exec=max(1, SCAN))
        except OSError as e:  # trace dir / file IO: the field is optional
            print(f"devstep capture failed: {e!r}", file=sys.stderr)

    img_per_sec = cfg.batch_size * steps_window / dt
    img_per_sec_chip = img_per_sec / n_chips
    if preset_name:
        arch = preset_name
    else:
        arch = (f"SAGAN-{cfg.model.output_size}" if cfg.model.attn_res
                else f"DCGAN-{cfg.model.output_size}")
        if cfg.grad_accum > 1:
            arch += f" grad_accum={cfg.grad_accum}"
    row = {
        "metric": f"{arch} train throughput (batch {BATCH}/chip, bf16)",
        "value": round(img_per_sec_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_per_sec_chip / V100_TF_BASELINE_IMG_PER_SEC, 3),
        "startup_ms": round(startup_ms, 1),
        # the device timeline's median per-step program time (null when
        # the capture failed); host ms_per_step minus this is host overhead
        "devstep_ms": round(devstep_ms, 4) if devstep_ms else None,
        # per-chip resident state footprint (ISSUE 13): the number the
        # --zero_stage ladder moves; derived from the live shardings
        "peak_state_mib": _state_mib_per_chip(state),
    }
    if os.environ.get("PRECISION"):
        # the precision-ladder A/B row (ISSUE 17) — printed
        # before the headline row so the driver's last-line parse holds
        _bench_precision_ab(cfg, mesh, n_chips, images, base)
    if os.environ.get("COMM_OVERLAP") == "1":
        # the collective overlap A/B row (ISSUE 20) — printed before the
        # headline row so the driver's last-line parse is unchanged
        if mesh.shape["data"] < 2:
            print("COMM_OVERLAP skipped: the overlap arms shard over the "
                  "data axis, which needs size > 1", file=sys.stderr)
        else:
            _bench_comm_overlap_ab(cfg, mesh, n_chips, images, base)
    if os.environ.get("ZERO_STAGE") in ("2", "3"):
        # the ZeRO state-sharding A/B row (ISSUE 13) — printed before the
        # headline row so the driver's last-line parse is unchanged
        if mesh.shape["data"] < 2:
            print("ZERO_STAGE skipped: stages >= 2 need a data axis of "
                  "size > 1", file=sys.stderr)
        else:
            _bench_zero_ab(cfg, mesh, n_chips, images, base)
    if os.environ.get("PROGRESSIVE") == "1":
        # the progressive-resolution A/B + 256px rows (ISSUE 15) — printed
        # before the headline row so the driver's last-line parse holds
        if cfg.model.attn_res:
            print("PROGRESSIVE=1 skipped: --progressive does not compose "
                  "with attention-bearing configs (resolution-anchored "
                  "site)", file=sys.stderr)
        else:
            _bench_progressive_ab(cfg, mesh, n_chips, base)
    if os.environ.get("PIPELINE_GD") == "1":
        # the pipelined G/D A/B row (ISSUE 7) — printed before the headline
        # row so the driver's last-line parse contract is unchanged
        if cfg.model.num_classes or cfg.update_mode != "sequential":
            print("PIPELINE_GD=1 skipped: pipelined stages are "
                  "unconditional sequential-update only", file=sys.stderr)
        else:
            _bench_pipeline_ab(cfg, pt, n_chips, images, base)
    if cfg.model.attn_res:
        # Attention-bearing configs stamp the generation of the attention
        # code they actually EXECUTE — flash kernels or the dense path —
        # so harvest renders never mix measurements of superseded attention
        # code into one spread column (VERDICT r4 #1), and a flash-only
        # generation bump never retires dense-config history.
        if cfg.model.use_pallas:
            from dcgan_tpu.ops.pallas_attention import ATTN_GEN
            row["gen"] = ATTN_GEN
        else:
            from dcgan_tpu.ops.attention import DENSE_ATTN_GEN
            row["gen"] = DENSE_ATTN_GEN
    if preset_name:
        # preset rows additionally stamp the preset revision (presets.py:
        # PRESET_REVS) — same never-mix-configs contract for preset changes
        from dcgan_tpu.presets import PRESET_REVS
        row["rev"] = PRESET_REVS.get(preset_name, 1)
    print(json.dumps({**row, **_device_fields()}))
    # context to stderr so the stdout contract stays one JSON line
    print(f"chips={n_chips} global_batch={cfg.batch_size} "
          f"steps={steps_window} scan={SCAN} wall={dt:.2f}s "
          f"ms_per_step={dt / steps_window * 1e3:.2f} "
          f"d_loss={final_d_loss:.3f}", file=sys.stderr)


if __name__ == "__main__":
    main()
