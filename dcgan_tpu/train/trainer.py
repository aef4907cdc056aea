"""The training driver: mesh bring-up, data feed, hot loop, observability.

This is the capability-parity replacement for the reference's `train()`
(image_train.py:51-194) with the cluster machinery swapped for SPMD:

reference                                   | here
--------------------------------------------|----------------------------------
ClusterSpec/Server/ps-role (55-63)          | initialize_multihost + Mesh
replica_device_setter (65-67)               | sharding rules (parallel/)
distorted_inputs + feed_dict loop (69,153)  | make_dataset -> sharded arrays
numpy batch_z feeds (151-152)               | on-device PRNG inside the step
combined D+G sess.run (156-158)             | one jitted sharded train step
Supervisor summaries @10s (155-178)         | MetricWriter (JSONL), chief-only
fixed-z 8x8 grid every 100 steps (179-192)  | sample() + save_sample_grid
Supervisor 600s checkpoints (123-129)       | Checkpointer.maybe_save
load() restore-latest (142-146)             | Checkpointer.restore_latest
per-step stdout log (160-169)               | per-step stdout log (chief)

The loop is step-bounded (max_steps, reference :150) and restartable: state
(params, BN stats, both Adam moments, step) round-trips through Orbax.

Host-services layer (docs/DESIGN.md "Host services"): the dispatch thread's
per-step work is pulling an already-transferred device batch from the
background feed queue (data/pipeline.DevicePrefetcher) and dispatching the
next compiled program; metric materialization runs lag-by-one (step N's
scalars while step N+1 computes) and every expensive writer path — param/
activation histograms, sample-grid PNGs, JSONL/TB IO — runs on the
train/services.py background worker. `--async_services=false` restores the
fully-inline loop.

Multi-host fail-operational layer (docs/DESIGN.md §6c.1,
train/coordination.py): every recovery decision that changes which
collectives run next is itself a collective — NaN-gate verdicts are
allgathered (anomaly consensus, so rollback works under multi-host with a
sharded device-resident snapshot), a signal on any host becomes a
whole-job coordinated stop through the collective final save
(`--coord_stop`), and `--collective_timeout_secs` arms a watchdog that
turns a hung collective into per-process stack dumps + a nonzero exit.

Observability plane (docs/DESIGN.md §6e): `--profile_trigger` starts an
on-demand device trace mid-run (digested in-process into `perf/device/*`
compute/collective/idle-gap attribution), the crash flight recorder
(`--flight_recorder_steps`) dumps the last K steps of telemetry on every
dying exit path, `--fleet_health_steps` allgathers a per-host health
vector into `fleet/*` straggler metrics, and one counter registry
(utils/metrics.CounterRegistry) feeds all three.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Iterator, Optional

import jax
import numpy as np

from dcgan_tpu.analysis import tripwire
from dcgan_tpu.config import (TrainConfig, is_token_arch, load_config,
                              save_config)
from dcgan_tpu.data import (
    DataConfig,
    make_dataset,
    quarantine,
    synthetic_batches,
    to_global,
)
from dcgan_tpu.parallel import (
    batch_sharding,
    initialize_multihost,
    is_chief,
    make_mesh,
    make_parallel_train,
)
from dcgan_tpu.testing import chaos
from dcgan_tpu.train import coordination, warmup
from dcgan_tpu.train.flight_recorder import FlightRecorder, recorder_path
from dcgan_tpu.train.gd_pipeline import GDPipeline
from dcgan_tpu.train.rollback import RollbackManager
from dcgan_tpu.train.services import make_services
from dcgan_tpu.utils.checkpoint import Checkpointer
from dcgan_tpu.utils.images import save_sample_grid
from dcgan_tpu.utils.metrics import (
    CounterRegistry,
    MetricWriter,
    param_histograms,
)
from dcgan_tpu.utils.profiling import (
    StartupProfile,
    StepTimer,
    TraceCapture,
    span,
)

Pytree = Any


def _data_iterator(cfg: TrainConfig, mesh, *, synthetic: bool,
                   data_dir: Optional[str] = None,
                   seed_offset: int = 0,
                   n_threads: Optional[int] = None,
                   min_after_dequeue: Optional[int] = None,
                   skip_batches: int = 0) -> Iterator:
    """Yields sharded image batches — (images, labels) pairs for conditional
    models (cfg.model.num_classes > 0).

    `skip_batches` fast-forwards a REBUILT iterator past batches an earlier
    incarnation already consumed (the live-elasticity switch, ISSUE 18:
    the yielded arrays are committed to the mesh, so a mesh change forces
    a rebuild — but the stream position must carry, or the synthetic
    generator restarts at batch 0 and the post-switch run diverges from
    its same-topology control). Synthetic streams skip at the host
    generator (cheap — no slicing, no upload); real-data loaders discard
    yielded batches (best-effort: a threaded shuffle stream has no exact
    position to restore anyway)."""
    if is_token_arch(cfg.model.arch):
        return _token_data_iterator(cfg, mesh, synthetic=synthetic,
                                    seed_offset=seed_offset,
                                    skip_batches=skip_batches)
    sharding = batch_sharding(mesh, 4, spatial=cfg.mesh.spatial)
    conditional = cfg.model.num_classes > 0
    label_sharding = batch_sharding(mesh, 1) if conditional else None
    if synthetic:
        # to_global needs this process's ADDRESSABLE BLOCK of the
        # global batch (pipeline.process_local_box). The naive
        # per-process slice (batch/process_count x full height) is that
        # block only while each process's devices cover whole mesh
        # rows; under a spatial mesh whose "model" axis spans
        # processes, the block is a batch-slice x height-slice instead
        # — and processes sharing a batch row MUST contribute
        # height-slices of the SAME images. Seeding the stream by the
        # block's BATCH OFFSET (not the process index) guarantees
        # that: co-row processes draw identical full-height images and
        # cut different height slices, while batch-disjoint processes
        # draw distinct streams at 1/P of the global host cost.
        # Single-process keeps the exact previous stream (offset 0,
        # full box).
        from dcgan_tpu.data.pipeline import (
            DevicePrefetcher,
            process_local_box,
        )

        size = cfg.model.output_size
        box = process_local_box(
            sharding, (cfg.batch_size, size, size, cfg.model.c_dim))
        n_local = box[0].stop - box[0].start
        if cfg.synthetic_global_stream:
            # layout-invariant stream (ISSUE 12): every process draws the
            # FULL global batch from the offset-0 seed and cuts its own
            # block, so the global batch sequence is bit-identical for
            # every process layout over the same mesh — the property the
            # elastic shrink/grow drills replay losses across. Costs P x
            # the host generation; single-process (full box) it IS the
            # default stream, byte for byte.
            src = synthetic_batches(
                cfg.batch_size, size, cfg.model.c_dim,
                seed=cfg.seed + seed_offset,
                num_classes=cfg.model.num_classes)

            def cut(batch):
                if isinstance(batch, tuple):
                    return batch[0][tuple(box)], batch[1][box[0]]
                return batch[tuple(box)]
        else:
            src = synthetic_batches(
                n_local, size, cfg.model.c_dim,
                seed=cfg.seed + seed_offset + box[0].start,
                num_classes=cfg.model.num_classes)
            hwc = (box[1], box[2], box[3])

            def cut(batch):
                if isinstance(batch, tuple):
                    return batch[0][(slice(None),) + hwc], batch[1]
                return batch[(slice(None),) + hwc]

        for _ in range(skip_batches):
            next(src)
        if cfg.synthetic_device_cache > 0:
            def it():
                # pre-staged device pool, cycled forever: the loop consumes
                # already-resident sharded arrays, so measurements see the
                # trainer machinery, not the host->device transport
                pool = [to_global(cut(next(src)), sharding, label_sharding)
                        for _ in range(cfg.synthetic_device_cache)]
                while True:
                    yield from pool
            return it()
        host_batches = (cut(b) for b in src)
        if cfg.prefetch_device_batches > 0:
            # same background feed thread as the real-data path: synthetic
            # batch generation + H2D transfer overlap device compute
            # (labels, when present, are generated in-range — no gate)
            return DevicePrefetcher(host_batches, sharding, label_sharding,
                                    depth=cfg.prefetch_device_batches)

        def it():
            for batch in host_batches:
                yield to_global(batch, sharding, label_sharding)
        return it()
    if jax.process_count() > 1:
        # The file-shard ownership model (process i owns shards i, i+P, ...)
        # assumes batch-disjoint processes. A spatial mesh whose "model"
        # (height) axis spans processes makes two processes co-own one batch
        # row — they would need to assemble height-slices of the SAME
        # images, which a threaded shuffle loader cannot reproduce
        # deterministically across processes. The synthetic path supports
        # such layouts (common-seed global batch, sliced per process);
        # real data requires the model axis to fit within each process's
        # devices (height sharding then happens on-device, not at load).
        from dcgan_tpu.data.pipeline import process_local_box

        size = cfg.model.output_size
        box = process_local_box(
            sharding, (cfg.batch_size, size, size, cfg.model.c_dim))
        full = (size, size, cfg.model.c_dim)
        if any(b.stop - b.start != g for b, g in zip(box[1:], full)):
            raise ValueError(
                "real-data loading requires each process's devices to cover "
                "full images (the spatial 'model' axis must not span "
                f"processes; this process's block is {box}). Lay the mesh "
                "out with model <= local_device_count, or use synthetic "
                "data for cross-process height-sharding experiments.")
    the_dir = data_dir if data_dir is not None else cfg.data_dir
    # The dataset.json manifest's wire format is authoritative — the same
    # policy evals/__main__.py applies (no flag there at all). The
    # cfg.record_dtype knob covers manifest-less corpora (e.g. shards in
    # the reference's own layout, which has no manifest). Without this,
    # prepare's uint8 default + the trainer's float64 parity default would
    # fail the manifest check on the README quickstart.
    from dcgan_tpu.data.pipeline import read_manifest

    wire_dtype = read_manifest(the_dir).get("record_dtype",
                                            cfg.record_dtype)
    if wire_dtype != cfg.record_dtype and is_chief():
        print(f"[dcgan_tpu] adopting record_dtype={wire_dtype!r} from "
              f"{the_dir}/dataset.json (config said {cfg.record_dtype!r})")
    dcfg = DataConfig(
        data_dir=the_dir,
        image_size=cfg.model.output_size,
        channels=cfg.model.c_dim,
        batch_size=cfg.batch_size // jax.process_count(),
        record_dtype=wire_dtype,
        min_after_dequeue=min_after_dequeue if min_after_dequeue is not None
        else cfg.shuffle_buffer,
        n_threads=n_threads if n_threads is not None
        else cfg.num_loader_threads,
        seed=cfg.seed + seed_offset,
        normalize=cfg.normalize_inputs,
        label_feature=cfg.label_feature if conditional else "",
        num_classes=cfg.model.num_classes if conditional else 0,
        prefetch_device_batches=cfg.prefetch_device_batches,
        max_corrupt_records=cfg.max_corrupt_records)
    ds = make_dataset(dcfg, sharding, label_sharding)
    for _ in range(skip_batches):
        next(ds)
    return ds


def _token_data_iterator(cfg: TrainConfig, mesh, *, synthetic: bool,
                         seed_offset: int = 0,
                         skip_batches: int = 0) -> Iterator:
    """Sharded int32 id batches [batch, seq_len] for the token family,
    through the same `DevicePrefetcher` (or inline `to_global`) as image
    batches. Synthetic ids only: token records have no loader yet."""
    if not synthetic:
        raise ValueError(
            f"arch={cfg.model.arch!r} trains on synthetic ids only "
            "(--synthetic): "
            "token records have no reader in data/ yet")
    from dcgan_tpu.data.pipeline import DevicePrefetcher, process_local_box
    from dcgan_tpu.data.synthetic import synthetic_id_batches

    sharding = batch_sharding(mesh, 2)
    m = cfg.model
    box = process_local_box(sharding, (cfg.batch_size, m.seq_len))
    src = synthetic_id_batches(box[0].stop - box[0].start, m.seq_len,
                               m.vocab_size,
                               seed=cfg.seed + seed_offset + box[0].start)
    for _ in range(skip_batches):
        next(src)
    if cfg.prefetch_device_batches > 0:
        return DevicePrefetcher(src, sharding, None,
                                depth=cfg.prefetch_device_batches)
    return (to_global(batch, sharding) for batch in src)


def _sample_data_iterator(cfg: TrainConfig, mesh, *, synthetic: bool,
                          skip_batches: int = 0) -> Optional[Iterator]:
    """The reference's SECOND input pipeline over sample_image_dir
    (image_train.py:84), feeding the every-100-steps sample-loss probe
    (:179-192). Optional here: present in synthetic mode (held-out stream,
    different seed) or when sample_image_dir exists on disk; absent
    otherwise — the probe is skipped, not an error (the reference crashed
    without the directory)."""
    if synthetic:
        return _data_iterator(cfg, mesh, synthetic=True, seed_offset=100,
                              skip_batches=skip_batches)
    exists = os.path.isdir(cfg.sample_image_dir)
    if jax.process_count() > 1:
        # The probe runs mesh-wide collectives; every process must make the
        # same enabled/disabled decision or the job deadlocks at the first
        # probe step. Enabled only if ALL hosts see the directory.
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(np.asarray([exists]))
        all_exist = bool(np.all(gathered))
        # warn on ANY partial visibility — including the chief itself missing
        # the mount — since the probe silently disables mesh-wide
        if bool(np.any(gathered)) and not all_exist and is_chief():
            print("[dcgan_tpu] sample_image_dir "
                  f"{cfg.sample_image_dir!r} is not visible on every host "
                  f"(visibility per process: {gathered.ravel().tolist()}); "
                  "sample-loss probe disabled")
        exists = all_exist
    if exists:
        # a light pipeline: the probe consumes one batch per 100 steps, so a
        # small shuffle pool and few threads are plenty
        return _data_iterator(
            cfg, mesh, synthetic=False, data_dir=cfg.sample_image_dir,
            seed_offset=100, n_threads=2,
            min_after_dequeue=4 * cfg.batch_size,
            skip_batches=skip_batches)
    return None


def _install_stop_handlers(cfg: TrainConfig) -> coordination.CoordinatedStop:
    """Graceful shutdown: SIGTERM/SIGINT set a process-local flag the hot
    loop polls, and the loop breaks at the next step boundary to force a
    final checkpoint — a TPU-VM preemption notice becomes a resumable
    stop. One-shot: the handler restores default semantics on first
    delivery so a second signal can still kill a hung final save.

    Multi-host (ISSUE 4): handlers are installed only under
    `cfg.coord_stop`, because the flag alone is not enough — save() is a
    collective, and one process breaking out alone would deadlock the
    others. CoordinatedStop.poll() allgathers the flags at each step
    boundary so the whole job agrees to break together; with
    coord_stop=False multi-host keeps PR 3's default signal semantics (the
    job restarts from the last periodic save — the reference Supervisor's
    recovery contract, image_train.py:123-141).

    The caller restores the original handlers in a finally block so an
    exception mid-run cannot leave the flag-only handler installed on a
    process whose loop is gone."""
    stop = coordination.CoordinatedStop()
    if jax.process_count() == 1 or cfg.coord_stop:
        stop.install()
    return stop


def train(cfg: TrainConfig, *, synthetic_data: bool = False,
          max_steps: Optional[int] = None) -> Pytree:
    """Run the training loop; returns the final state pytree."""
    # form the multi-host job BEFORE deciding on signal handlers: on an
    # env-driven bring-up (JAX_COORDINATOR_ADDRESS) process_count() is
    # still 1 until this runs, and installing the flag-only handler on a
    # coord_stop=False multi-host process would swallow the first SIGTERM
    # without anyone ever polling the flag (idempotent — _train's own call
    # is then a no-op)
    initialize_multihost()
    # thread-discipline tripwire (ISSUE 8, DCGAN_THREAD_CHECKS=1): wrap
    # the collective entry points and mark THIS thread as the dispatch
    # thread for the run — any collective issued from a background thread
    # raises instead of deadlocking the mesh minutes later. Free when the
    # env knob is off (nothing wrapped, the scope is a bare yield).
    tripwire.maybe_install()
    stop = _install_stop_handlers(cfg)
    try:
        with tripwire.dispatch_scope():
            return _train(cfg, synthetic_data=synthetic_data,
                          max_steps=max_steps, stop=stop)
    finally:
        stop.restore()


def _flight_context(cfg: TrainConfig, startup: StartupProfile,
                    flight: FlightRecorder) -> dict:
    """Dump-time header context for the crash flight recorder."""
    out = {"process": jax.process_index()}
    if cfg.precision:
        # name the active precision policy in every crash dump (ISSUE 17):
        # a NaN abort under bf16 must be attributable to the ladder at
        # a glance. Crash-path-only IO — absent under the default policy,
        # so this never touches the parity-pinned event stream.
        out["precision"] = cfg.precision
    if not startup.done:
        # ISSUE 6 satellite: a run that died before its first step ships
        # the startup phases it DID complete (init/restore/warmup so far)
        # instead of losing the breakdown with the crash
        out["startup_partial"] = {k: round(v, 1) for k, v in
                                  startup.summary().items()}
    if flight.note:
        out["fleet_note"] = flight.note
    return out


def _train(cfg: TrainConfig, *, synthetic_data: bool,
           max_steps: Optional[int],
           stop: coordination.CoordinatedStop) -> Pytree:
    initialize_multihost()
    # Warm start (DESIGN.md §6d): the persistent compile cache must be
    # configured before the FIRST compile of this run (pt.init in the run
    # body), and after the multi-host bring-up (per-process keying reads
    # the real process index). Startup phases are profiled from here —
    # restarts are this trainer's normal response to faults (PRs 3-4), so
    # time-to-first-step is tracked like throughput.
    startup = StartupProfile()
    # Crash flight recorder (ISSUE 6, DESIGN.md §6e): created before ANY
    # fallible setup so a death in config validation, restore, or warmup
    # still dumps (with the partial startup breakdown); the ring fills
    # once the loop records steps. Crash-path-only IO — nothing is
    # written unless the run dies.
    flight = FlightRecorder(
        recorder_path(cfg.checkpoint_dir),
        capacity=cfg.flight_recorder_steps,
        context=lambda: _flight_context(cfg, startup, flight))
    cache_dir = warmup.configure_compile_cache(
        warmup.resolve_cache_dir(cfg.compile_cache_dir),
        per_process=cfg.compile_cache_per_process)
    cache_mon = warmup.CompileCacheMonitor() if cache_dir is not None \
        else None
    try:
        return _train_run(cfg, synthetic_data=synthetic_data,
                          max_steps=max_steps, stop=stop, startup=startup,
                          cache_dir=cache_dir, cache_mon=cache_mon,
                          flight=flight)
    except BaseException as e:
        # every non-returning exit ships the telemetry ring: the NaN
        # abort keeps its step attribution (the gate stamps e.step), any
        # other exception records where the loop had gotten to. The dump
        # is best-effort by contract — it can never mask the error.
        flight.dump("nan-abort" if isinstance(e, FloatingPointError)
                    else "exception",
                    step=getattr(e, "step", None),
                    extra={"error": repr(e)[:500]})
        raise
    finally:
        if cache_mon is not None:
            # freeze the run's counters on EVERY exit — config validation
            # errors and failed warmups included; a process that calls
            # train() again (tests, drills) must not see this run's
            # counters move with the next run's compiles
            cache_mon.close()


def _train_run(cfg: TrainConfig, *, synthetic_data: bool,
               max_steps: Optional[int],
               stop: coordination.CoordinatedStop, startup: StartupProfile,
               cache_dir: Optional[str],
               cache_mon, flight: FlightRecorder) -> Pytree:
    if cfg.fid_every_steps and jax.process_count() > 1 \
            and cfg.fid_num_samples % jax.process_count():
        raise ValueError(
            f"fid_num_samples ({cfg.fid_num_samples}) must divide evenly "
            f"over {jax.process_count()} processes — the in-training probe "
            "splits the sample budget per process (VERDICT r2 #5)")
    total_steps = max_steps if max_steps is not None else cfg.max_steps
    with startup.phase("init"):
        mesh = make_mesh(cfg.mesh)
        # Progressive-resolution schedule (ISSUE 15, DESIGN.md §6j):
        # resolution becomes a scheduled training dimension — the run is a
        # sequence of phases, each with its own compiled ParallelTrain
        # surface over the ONE shared mesh. The runtime owns the phase
        # table, the per-phase surfaces, and the cross-phase state carry;
        # pt below always points at the CURRENT phase's surface. None for
        # fixed-resolution runs — every progressive branch is strictly
        # opt-in (the parity contract).
        prog = None
        if cfg.progressive:
            from dcgan_tpu.progressive import PhaseRuntime, parse_schedule

            prog = PhaseRuntime(
                cfg, mesh,
                parse_schedule(cfg.progressive, model=cfg.model,
                               batch_size=cfg.batch_size,
                               max_steps=cfg.max_steps,
                               steps_per_call=cfg.steps_per_call,
                               grad_accum=cfg.grad_accum,
                               fade_steps=cfg.progressive_fade_steps),
                total_steps, make_pt=make_parallel_train)
            pt = None  # chosen after the latest checkpoint step is known
        else:
            pt = make_parallel_train(cfg, mesh)
    chief = is_chief()
    # Pipelined G/D dispatch (ISSUE 7, DESIGN.md §6f): the step runs as
    # three stage programs with the D step consuming the fake stack
    # produced during the PREVIOUS step (staleness 1). The stack lives in
    # this trainer-held buffer, OUTSIDE the checkpoint pytree — both modes
    # save/restore the identical state tree. None under the default fused
    # mode: every pipeline branch below is strictly opt-in, so the
    # default-flags dispatch stream and event values are untouched (the
    # parity contract).
    pipeline = GDPipeline() if cfg.pipeline_gd else None
    # Live in-run elasticity (ISSUE 18, DESIGN.md §6l): a preemption/
    # capacity notice switches the run onto `--elastic_target_devices`
    # (or back) WITHOUT a restart. Two halves, both strictly opt-in (every
    # live_* branch below is gated on live_rt, so the default dispatch
    # stream and event bytes are untouched — the parity contract):
    # NoticePlane folds the local notice sources (touch file, SIGUSR1,
    # chaos fault) into a boundary-poll consensus with the stop plane's
    # shape, and LiveTopologyRuntime holds one warmed ParallelTrain per
    # topology so the switch dispatches only cached executables. The
    # runtime adopts the launch surface built above; the target surface
    # builds lazily (warmup builds it eagerly so both get primed).
    live_rt = None
    notice = None
    if cfg.elastic_target_devices:
        from dcgan_tpu.elastic import live as live_elastic

        live_rt = live_elastic.LiveTopologyRuntime(
            cfg, mesh, make_pt=make_parallel_train, launch_pt=pt)
        notice = live_elastic.NoticePlane(cfg.elastic_notice_file)
        notice.install()
    # the quarantine tally is process-global (it spans both loader
    # implementations and the train+sample pipelines); this run reports its
    # own delta — captured BEFORE any loader thread starts — so counts from
    # an earlier run in the same process don't bleed into the event stream
    corrupt_base = quarantine.count()

    ckpt = Checkpointer(cfg.checkpoint_dir,
                        save_interval_secs=cfg.save_model_secs,
                        save_interval_steps=cfg.save_model_steps,
                        max_to_keep=cfg.max_checkpoints)

    # Checkpoints carry their config (VERDICT r1 #3): a resume with a
    # different architecture must fail HERE with a readable message, not
    # deep inside Orbax as a tree/shape mismatch; generate/evals read the
    # same file so sampling needs zero architecture flags. The check is
    # gated on an actual checkpoint existing — a stale config.json from a
    # run that died before its first save must not claim the directory.
    saved_cfg = load_config(cfg.checkpoint_dir)
    if saved_cfg is not None and ckpt.latest_step() is not None \
            and saved_cfg.model != cfg.model:
        changed = {
            f.name: (getattr(saved_cfg.model, f.name),
                     getattr(cfg.model, f.name))
            for f in dataclasses.fields(cfg.model)
            if getattr(saved_cfg.model, f.name) != getattr(cfg.model, f.name)}
        raise ValueError(
            f"checkpoint_dir {cfg.checkpoint_dir!r} holds a run with a "
            f"different architecture (saved != requested): {changed}. "
            "Resume without architecture flags (the config.json is "
            "adopted), or point --checkpoint_dir at a fresh directory.")
    if chief:
        save_config(cfg, cfg.checkpoint_dir)
    writer = MetricWriter(cfg.checkpoint_dir,
                          every_secs=cfg.save_summaries_secs,
                          enabled=chief,
                          tensorboard=cfg.tensorboard)

    # Progressive resume (ISSUE 15): the restore template must be the
    # phase tree that PRODUCED the latest checkpoint — a boundary-step
    # save carries the pre-switch tree (the switch below runs before the
    # first new-phase dispatch), so the schedule-derived phase is
    # deterministic; the sidecar's phase tag cross-checks it, catching a
    # --progressive spec edited between runs before Orbax turns it into
    # an opaque tree mismatch. The tag itself is stamped on every save.
    if prog is not None:
        latest = ckpt.latest_step()
        prog.start(latest)
        if latest is not None:
            from dcgan_tpu.elastic import sidecar as _sidecar

            payload = _sidecar.read(cfg.checkpoint_dir, latest) or {}
            prog.check_resume_tag(payload.get("progressive"), latest)
        pcfg = prog.cfg
        pt = prog.pt
        ckpt.progressive_tag = prog.tag()
        if chief:
            print(f"[dcgan_tpu] progressive schedule "
                  f"{cfg.progressive!r}: starting in phase {prog.index} "
                  f"(r{prog.resolution}, batch {pcfg.batch_size}, "
                  f"{prog.n_phases} phase(s) this run)", flush=True)
    else:
        pcfg = cfg

    with startup.phase("init"):
        state = pt.init(jax.random.key(cfg.seed))
    with startup.phase("restore"):
        restored = ckpt.restore_latest(state)
    if restored is not None:
        state = restored
        if chief:
            print(f"[dcgan_tpu] restored checkpoint at step "
                  f"{int(jax.device_get(state['step']))}")

    # NaN rollback-and-skip (train/rollback.py): under nan_policy="rollback"
    # a last-good snapshot is refreshed every K steps and a gate trip
    # restores it instead of aborting; None under the default policy — the
    # snapshot cost is strictly opt-in. Single-process keeps the host copy
    # (zero extra HBM); multi-host keeps a sharded DEVICE-RESIDENT copy —
    # each process holds only its addressable shards, and the jitted
    # snapshot/restore copies run on every process at the same
    # consensus-agreed point (ISSUE 4: the decision to take this branch is
    # itself allgathered in _nan_gate, so the dispatches stay
    # mesh-consistent).
    rollback = None
    if cfg.nan_policy == "rollback":
        rollback = RollbackManager(every=cfg.rollback_snapshot_steps,
                                   max_rollbacks=cfg.max_rollbacks,
                                   lr_backoff=cfg.rollback_lr_backoff,
                                   chief=chief,
                                   device_resident=jax.process_count() > 1)

    # fixed z for comparable sample grids across the run — drawn once, like
    # the reference's graph-build-time sample_z (image_train.py:77)
    rows, cols = cfg.sample_grid
    n_samples = max(cfg.sample_size, rows * cols)
    data_axis = mesh.shape["data"]
    n_samples = -(-n_samples // data_axis) * data_axis  # data-axis multiple
    # a one-network token family has no sampler: no z, and the config
    # already refused every service that would ask for one
    sample_z = None if is_token_arch(cfg.model.arch) else jax.random.uniform(
        jax.random.key(cfg.seed + 1), (n_samples, cfg.model.z_dim),
        minval=-1.0, maxval=1.0)
    sample_labels = None
    if cfg.model.num_classes:
        sample_labels = jax.numpy.arange(sample_z.shape[0]) \
            % cfg.model.num_classes

    rebucketer = None
    with startup.phase("data"):
        if prog is not None:
            # mid-run re-bucketing (ISSUE 15, progressive/rebucket.py):
            # the loaders bake decode resolution and batch into their
            # threads at construction, so each phase switch closes and
            # re-opens them through this one factory — same iterators the
            # fixed-resolution path builds, pointed at the phase config
            # (with {res} data-dir placeholders resolved per phase)
            from dcgan_tpu.progressive import Rebucketer

            def _open_phase(phase_cfg):
                d = _data_iterator(phase_cfg, mesh,
                                   synthetic=synthetic_data)
                s = _sample_data_iterator(phase_cfg, mesh,
                                          synthetic=synthetic_data) \
                    if cfg.sample_every_steps else None
                return d, s
            rebucketer = Rebucketer(_open_phase)
            data, sample_data = rebucketer.open(pcfg)
        else:
            data = _data_iterator(cfg, mesh, synthetic=synthetic_data)
            # The global-mesh held-out stream feeds the sample-loss probe
            # and, in single-process runs, the FID probe's real side; the
            # multihost FID probe streams its own local-mesh iterator
            # instead, so don't spin a producerless loader for it.
            sample_data = _sample_data_iterator(
                cfg, mesh, synthetic=synthetic_data) \
                if cfg.sample_every_steps or (cfg.fid_every_steps
                                              and jax.process_count() == 1) \
                else None
    # fixed z for the loss probe, tiled to the probe batch size (the
    # reference feeds the same sample_z every time, image_train.py:77,181)
    eval_z = jax.numpy.resize(sample_z, (pcfg.batch_size, cfg.model.z_dim)) \
        if sample_data is not None else None
    base_key = jax.random.key(cfg.seed + 2)
    conditional = cfg.model.num_classes > 0

    # In-training surrogate FID/KID probe (evals/ rig; fid_every_steps > 0).
    # Single-process: streams the shared held-out iterator and samples via
    # pt.sample. Multi-host (VERDICT r2 #5): the probe splits the budget per
    # process through the evals rig's distributed scoring path — each
    # process streams its own real share over a LOCAL mesh (the global-mesh
    # sample_data yields arrays this host cannot fully address) and
    # generates with a process-distinct z stream on a local sampler (the
    # global-mesh pt.sample is a collective over one shared z — the wrong
    # program for split scoring, same reasoning as evals/__main__), then
    # the moment statistics and reservoirs all-gather into one global score
    # identical on every process.
    fid_feature = None
    fid_probe_data = None  # multihost: per-process local-mesh stream
    n_proc = jax.process_count()
    if cfg.fid_every_steps:
        if n_proc > 1:
            from dcgan_tpu.config import MeshConfig

            if not synthetic_data and os.path.isdir(cfg.sample_image_dir):
                # Same guard as evals --multihost: with fewer shards than
                # processes, shard_for_process falls back to "everyone
                # reads everything" and the merged real moments sample
                # with replacement — a silently biased score driving
                # best-checkpoint retention.
                from dcgan_tpu.data.pipeline import list_shards

                n_shards = len(list_shards(cfg.sample_image_dir))
                if n_shards < n_proc:
                    raise ValueError(
                        f"the multihost FID probe needs at least one "
                        f"TFRecord shard per process for a disjoint real "
                        f"split: {n_shards} shard(s) < {n_proc} processes "
                        f"in {cfg.sample_image_dir!r} (re-shard with "
                        f"`python -m dcgan_tpu.data.prepare "
                        f"--num_shards {n_proc}`)")
            probe_mesh = make_mesh(MeshConfig(), jax.local_devices())
            fid_probe_data = _sample_data_iterator(cfg, probe_mesh,
                                                   synthetic=synthetic_data)
        else:
            fid_probe_data = sample_data
        if fid_probe_data is None:
            raise ValueError(
                "fid_every_steps needs a held-out stream: provide "
                "sample_image_dir (or run synthetic), the same source the "
                "sample-loss probe uses")
        from dcgan_tpu.evals.features import make_random_feature_fn

        fid_feature = make_random_feature_fn(cfg.model.output_size,
                                             cfg.model.c_dim)
    fid_real_side = None  # (StreamingStats, FeaturePool) after first probe
    fid_best = float("inf")
    fid_local_sampler = None  # lazy jit, multihost probe only
    best_ckpt = None      # lazy Checkpointer for checkpoint_dir/best
    if cfg.fid_every_steps:
        # resume re-seeds the best score from the persisted record —
        # otherwise the first post-restart probe (fid < inf) would
        # OVERWRITE a genuinely better pre-preemption best checkpoint
        # (max_to_keep=1 deletes it)
        import json

        try:
            with open(os.path.join(cfg.checkpoint_dir, "best",
                                   "score.json")) as f:
                fid_best = float(json.load(f)["fid"])
        except (OSError, ValueError, KeyError, TypeError):
            pass
        if n_proc > 1:
            # score.json lives on the chief's filesystem; every process
            # must carry the SAME best score or the collective best-save
            # deadlocks when branches diverge
            from jax.experimental import multihost_utils

            fid_best = float(multihost_utils.broadcast_one_to_all(
                np.asarray(fid_best, np.float64)))

    # AOT warmup (DESIGN.md §6d): compile every program and every known
    # future call shape up front — the k=1 n_critic tail, the
    # steps_per_call scan, the sampler/probe/summarize shapes, and (when
    # the cache can make it stick) the rollback LR-backoff rebuild variant
    # as a fully-built pre-warmed ParallelTrain. With the persistent cache
    # active the loop's first dispatches deserialize the warmed entries, so
    # `warm_proof` below can seed the watchdog's mesh-warm gate and
    # `compiled_ks` exemption set from warmup proof instead of waiting for
    # first live steps.
    # "fleet-warm": every process's live dispatches will HIT the primed
    # cache — true single-process and in the shared-dir multi-host mode,
    # false for per-process dirs under multi-host (JAX writes entries
    # chief-only, so non-chief proc<i>/ stores never fill and their live
    # dispatches still compile). Everything that assumes warm hits —
    # watchdog warm proof, the compiled_ks seed, the pre-warmed backoff
    # swap that deliberately skips the recompile exemption — rides on this
    # one predicate, never on the cache dir merely being set.
    cache_fleet_wide = cache_dir is not None and \
        warmup.cache_serves_all_processes(cfg.compile_cache_per_process)
    pt_backoff = None   # pre-warmed LR-backoff surface for the 1st rollback
    warm_ms: dict = {}
    warm_base = None    # cache counters at end of warmup: the progressive
                        # switch prints its compile-request delta from here
    if cfg.aot_warmup:
        if chief and cache_dir is None:
            print("[dcgan_tpu] --aot_warmup without a compile cache: "
                  "warmed programs are recompiled at first live dispatch "
                  "(compile timings still recorded); set "
                  "--compile_cache_dir or JAX_COMPILATION_CACHE_DIR so "
                  "dispatches deserialize the warmed entries", flush=True)
        if chief and cache_dir is not None and not cache_fleet_wide:
            print("[dcgan_tpu] --compile_cache_per_process under "
                  "multi-host: JAX writes cache entries from the "
                  "chief only, so non-chief proc<i>/ stores stay empty — "
                  "warm restarts still recompile there, and warmup is NOT "
                  "used as watchdog warm proof (use one shared "
                  "--compile_cache_dir to warm the whole fleet)",
                  flush=True)
        with startup.phase("warmup"):
            if prog is not None:
                # progressive warmup (ISSUE 15): EVERY phase's programs
                # enter the plan up front (@r<res> rows for the other
                # phases), then each is PRIMED with one throwaway
                # dispatch — the PR 9 serve-plane mechanism that makes
                # zero-compile-requests-after-warmup literal, so a
                # mid-run resolution switch dispatches only
                # already-executed programs
                plan = prog.build_warmup_plan(
                    state,
                    sample_z=sample_z if cfg.sample_every_steps else None,
                    sample_labels=sample_labels)
                warm_ms = warmup.aot_compile(plan)
                prime_ms = prog.prime(
                    sample_z=sample_z if cfg.sample_every_steps else None,
                    sample_labels=sample_labels)
                if chief:
                    print("[dcgan_tpu] progressive warmup primed "
                          + ", ".join(f"{k} {v:.0f}ms"
                                      for k, v in prime_ms.items()),
                          flush=True)
            elif live_rt is not None:
                # live-elastic warmup (ISSUE 18): BOTH topologies' programs
                # enter the plan (@t<data>x<model> rows for the target
                # submesh), then each topology is primed with one
                # throwaway dispatch per program — the same PR 14
                # mechanism, transposed from resolution phases to mesh
                # change, so a notice-driven switch dispatches only
                # already-executed programs (compile-request delta 0)
                plan = live_rt.build_warmup_plan(
                    state,
                    sample_z=sample_z if cfg.sample_every_steps else None,
                    sample_labels=sample_labels)
                warm_ms = warmup.aot_compile(plan)
                prime_ms = live_rt.prime(
                    sample_z=sample_z if cfg.sample_every_steps else None,
                    sample_labels=sample_labels)
                if chief:
                    print("[dcgan_tpu] live-elastic warmup primed "
                          + ", ".join(f"{k} {v:.0f}ms"
                                      for k, v in prime_ms.items()),
                          flush=True)
            else:
                plan, pt_backoff = warmup.build_warmup_plan(
                    cfg, pt, state,
                    sample_z=sample_z if cfg.sample_every_steps else None,
                    sample_labels=sample_labels, eval_z=eval_z,
                    make_backoff_pt=(lambda c: make_parallel_train(c, mesh))
                    if cache_fleet_wide else None)
                warm_ms = warmup.aot_compile(plan)
            # every peer past its compiles before anyone proceeds: the warm
            # proof the watchdog gate needs, and the point where startup
            # skew is paid once instead of surfacing inside guarded windows
            coordination.warmup_barrier()
        if cache_mon is not None:
            warm_base = cache_mon.counters()
        if chief:
            print("[dcgan_tpu] aot warmup compiled "
                  + f"{len(warm_ms)} program(s): "
                  + ", ".join(f"{k} {v:.0f}ms"
                              for k, v in warm_ms.items()), flush=True)
    # priming (progressive) warms every process's in-process dispatch
    # caches directly, so it is warm proof even without a fleet-wide
    # persistent cache; the plain AOT path still needs cache hits to stick
    warm_proof = cfg.aot_warmup and (
        cache_fleet_wide or (prog is not None and prog.primed)
        or (live_rt is not None and live_rt.primed))

    start_step = int(jax.device_get(state["step"]))
    t_start = time.time()
    metrics = {}
    timer = StepTimer(window=cfg.timing_window,
                      images_per_step=pcfg.batch_size)

    # Async host services (train/services.py): every non-step host action —
    # metric materialization, param/activation histograms, sample-grid PNG
    # encode, JSONL/TB IO — runs on a single background worker so the
    # dispatch thread's only per-step jobs are pulling a prefetched device
    # batch and dispatching the next program. Mesh-wide collectives (the
    # FID probe's all-gathers, Orbax collective saves, the pt.summarize/
    # pt.sample dispatches themselves) stay HERE on the dispatch thread:
    # collectives issued from per-process background threads have no
    # cross-process ordering guarantee against this thread's and would
    # deadlock the mesh. cfg.async_services=False degrades every submit()
    # to an inline call at the same site — the pre-async loop structure,
    # same metric values and event ordering.
    svc = make_services(cfg.async_services)
    deferred = cfg.async_services

    # Counter registry (ISSUE 6, utils/metrics.py): ONE typed read surface
    # over the counters that previously lived in four unrelated places —
    # the scalar rows' recovery extras, the flight-recorder records, and
    # the fleet health vector all read the same snapshot, so they can
    # never drift apart on what "the run's counters" means.
    registry = CounterRegistry()
    registry.provide("services_queue", svc.pending)
    registry.provide("services_dropped",
                     lambda: int(getattr(svc, "dropped", 0)))
    registry.provide("corrupt_records",
                     lambda: quarantine.count() - corrupt_base)
    if rollback is not None:
        registry.provide("rollbacks", lambda: rollback.rollbacks)
    if prog is not None:
        # flight-recorder records and the fleet health vector both name
        # the active phase through the one counter surface (ISSUE 15)
        registry.provide("progressive_phase", lambda: prog.index)
    if live_rt is not None:
        # the ACTIVE topology's device count (ISSUE 18): flight-recorder
        # dumps after a switch name the mesh the run was actually on
        registry.provide("live_topology", lambda: live_rt.device_count)
    if cache_mon is not None:
        registry.provide_group(
            ("compile_cache_requests", "compile_cache_hits",
             "compile_cache_misses"),
            lambda: {"compile_cache_" + k: v
                     for k, v in cache_mon.counters().items()})
    master_f32 = 0
    if cfg.precision:
        # f32 master-moment census (ISSUE 17): counted ONCE at startup —
        # the optimizer tree's dtype layout is static for the run — and
        # exposed through the one counter surface so flight-recorder dumps
        # and the fleet health vector can both see a bf16 run that lost
        # its master copy (count 0 where the param census says sub-f32)
        from dcgan_tpu.elastic.rules import count_master_f32_leaves

        master_f32 = count_master_f32_leaves(state)
        registry.provide("master_f32_leaves", lambda: master_f32)

    # Trace capture (ISSUE 6): the scheduled window arms only when
    # --profile_dir was explicitly set (its PR-1 contract); the trigger
    # file adds ON-DEMAND capture — touch it mid-run, the next boundary
    # starts a profile_num_steps capture, and the digest below turns the
    # closed capture into perf/device/* attribution without any offline
    # tool pass. Trigger-only runs park traces under checkpoint_dir/trace.
    trace_dir = cfg.profile_dir or (
        os.path.join(cfg.checkpoint_dir, "trace")
        if cfg.profile_trigger else "")

    # call sizes (k) dispatched while a capture window was open: the digest
    # normalizes the busiest program's median by the LARGEST k actually in
    # the window, not cfg.steps_per_call — a window caught entirely inside
    # a k=1 realign/tail stretch would otherwise report a step time
    # steps_per_call x too small
    capture_ks: list = []

    def _on_trace_capture(stop_step: int) -> None:
        """A capture just closed: resolve THE file it wrote here on the
        dispatch thread (one glob — back-to-back captures or shared-dir
        peers would misattribute a worker-time "newest" lookup), then
        digest it on the services worker — host-local file IO + parsing
        only, so the collective-thread rule is untouched. Chief-only:
        peers capture traces (per-process timelines are themselves useful
        artifacts) but only the chief materializes events."""
        ks = capture_ks[:]
        del capture_ks[:]
        if not chief:
            return
        spc = max(ks) if ks else max(1, cfg.steps_per_call)
        import socket

        from dcgan_tpu.utils.trace import digest, find_trace, stage_step_ms
        try:
            trace_path = find_trace(trace_dir, host=socket.gethostname())
        except OSError as e:
            print(f"[dcgan_tpu] trace capture ending at step {stop_step} "
                  f"left no trace file: {e!r}", flush=True)
            return

        def _digest_task(s=stop_step, path=trace_path):
            d = digest(path)
            if d["source"] == "none":
                print(f"[dcgan_tpu] trace capture ending at step {s} has "
                      "no device events; nothing to digest", flush=True)
                return
            step_ms = d["program_ms_median"] / spc
            if cfg.pipeline_gd:
                # pipelined dispatch (ISSUE 7): one trainer step is the
                # d_update AND g_update executions — the busiest-program
                # median alone would report roughly half a step. Sum the
                # stage medians when the track names the stage programs
                # (TPU module tracks do; the CPU op-level fallback keeps
                # the busiest-program estimate).
                step_ms = stage_step_ms(d) or step_ms
            row = {
                "perf/device/compute_ms": d["compute_ms"],
                "perf/device/collective_ms": d["collective_ms"],
                "perf/device/idle_gap_ms": d["idle_gap_ms"],
                "perf/device/span_ms": d["span_ms"],
                # the device's own per-step time: the busiest program's
                # median execution, normalized for scanned multi-step
                # dispatch (stage-summed under --pipeline_gd)
                "perf/device/step_ms": step_ms,
                # collective time hidden behind compute (ISSUE 20): the
                # --comm_overlap A/B's trace-level attribution
                "perf/device/overlap_frac": d["overlap_frac"],
            }
            print(f"[dcgan_tpu] trace digest (ending step {s}, "
                  f"{d['source']} track, top program {d['program']!r} "
                  f"x{d['program_n']}): "
                  + " ".join(f"{k.rsplit('/', 1)[1]}={v:.3f}"
                             for k, v in row.items()), flush=True)
            writer.write_scalars(s, row)
        svc.submit(_digest_task, tag="trace-digest")

    trace = TraceCapture(trace_dir,
                         start_step=start_step + cfg.profile_start_step,
                         num_steps=cfg.profile_num_steps,
                         schedule=bool(cfg.profile_dir),
                         trigger_path=cfg.profile_trigger,
                         # chief-only removal: peers key off the mtime, so
                         # a shared-filesystem fleet all captures one touch
                         # and the digesting process can never lose the
                         # remove race
                         consume=chief,
                         on_capture=_on_trace_capture)

    # Hung-collective watchdog (train/coordination.py; off at the default
    # collective_timeout_secs=0): a deadline around each dispatch/consume
    # window, consensus allgather, and collective save. Expiry dumps every
    # thread's stack with step+phase context and exits nonzero so the
    # launcher restarts the job from the last checkpoint instead of letting
    # one lost peer hang the whole pod forever. The first loop iteration's
    # dispatch is exempt (it compiles); the FID probe and sample/summarize
    # telemetry tails are deliberately unguarded (legitimately long or
    # droppable — not the collectives that wedge a mesh). A trip now also
    # dumps the flight-recorder ring (ISSUE 6) so the stacks arrive with
    # the telemetry that led up to them.
    watchdog = coordination.make_watchdog(
        cfg.collective_timeout_secs,
        pre_dump=lambda phase, step: flight.dump(
            "watchdog", step=step, extra={"phase": phase}))

    # The watchdog must not arm until the mesh is PROVEN warm: compile
    # time is per-process, so right after THIS process's first dispatch a
    # guarded collective can legitimately block for however long the
    # SLOWEST peer's compile takes (startup skew), and a deadline there
    # would kill a healthy job. "Warm" = proof that every peer is past its
    # first compile: the first metric readback completing (_host_vals), a
    # boundary-N>0 stop poll returning (each device stream runs that
    # allgather only after its step program), or — ISSUE 5 — warmup proof:
    # every peer returned from the AOT warmup barrier with the persistent
    # cache primed, so live dispatches deserialize (bounded IO) instead of
    # compiling. Single-process has no peer skew to wait out.
    mesh_warm = n_proc == 1 or warm_proof

    def _guard(phase: str, step: int):
        """A watchdog guard that is a free no-op until the mesh is warm."""
        return watchdog.guard(phase, step) if mesh_warm \
            else coordination.NULL_GUARD

    if rollback is not None and pipeline is not None:
        # Drain-before-restore (ISSUE 7): the in-flight fake stack was
        # generated by the diverged weights the rollback is fleeing — it
        # must never train the restored state, and its device memory must
        # be free before the restore copies allocate. Parked on the
        # manager's restore hook (structurally tied to restore(), so no
        # call site can forget it); the nested guard names the phase if a
        # drain-window hang trips the watchdog, then hands the deadline
        # back to the enclosing rollback-restore arm.
        def _drain_for_restore():
            with _guard("pipeline-drain", step_num):
                if pipeline.drain("rollback") and chief:
                    print("[dcgan_tpu] rollback drained the in-flight "
                          "pipelined fake stack (stale generator output; "
                          "refilled from the restored state at the next "
                          "dispatch)", flush=True)
        rollback.on_restore = _drain_for_restore

    def _stage(tree) -> None:
        """Start D2H copies of a dispatched program's outputs now, so the
        background worker's device_get finds them (mostly) materialized."""
        for leaf in jax.tree_util.tree_leaves(tree):
            leaf.copy_to_host_async()

    def _snapshot_params(params):
        """A capture of `params` that survives the next step's buffer
        donation, for the background histogram writer.

        Single-process: a device-side copy (rollback.device_copy — the
        same jitted identity the snapshot manager uses) producing fresh
        buffers (pt.step's donate_argnums only invalidates the ORIGINAL
        leaves), which the worker device_gets while the next steps run.
        Multi-process: a synchronous device_get on the dispatch thread —
        the copy program would be a mesh-wide dispatch, and the histogram
        tick is chief-only + wall-clock-gated, so dispatching it from one
        process would wedge the other processes' collective queues (same
        reason the FID probe stays on this thread); only the histogram
        reduction + file IO move to the worker there."""
        if deferred and n_proc == 1:
            from dcgan_tpu.train.rollback import device_copy

            snap = device_copy(params)
            _stage(snap)
            return snap
        return jax.device_get(params)

    def _host_vals(p: dict) -> dict:
        """Materialized {name: float} metric scalars for one step's record,
        cached on the record — ONE transfer shared by every consumer
        (NaN gate, step log, summary writer); per-scalar float() would
        issue a device round-trip each."""
        nonlocal mesh_warm
        if p.get("host") is None:
            p["host"] = {k: float(v) for k, v in
                         jax.device_get(p["metrics"]).items()}
            # a completed cross-process readback is the warm proof the
            # watchdog gating waits for (see mesh_warm above)
            mesh_warm = True
            if not startup.done:
                # first proven device-progress point = time-to-first-step
                startup.first_step()
                _report_startup(p["step"])
        return p["host"]

    def _report_startup(step: int) -> None:
        """One startup-breakdown report per run, at the first completed
        step: phase ms + compile-cache counters + per-program warmup
        compile ms + restore stats. Always printed (stdout is free);
        written as JSONL perf/ keys ONLY when a warm-start knob is active —
        default-flags event streams stay byte-identical (parity contract).
        """
        row = startup.summary()
        if cache_mon is not None:
            c = cache_mon.counters()
            row.update({
                "perf/compile_cache_requests": c["requests"],
                "perf/compile_cache_hits": c["hits"],
                "perf/compile_cache_misses": c["misses"],
            })
        for name, ms in warm_ms.items():
            row[f"perf/compile_ms/{name}"] = ms
        rs = ckpt.last_restore_stats
        if rs is not None:
            row.update({
                "perf/restore/verify_files": rs["files"],
                "perf/restore/verify_bytes": rs["bytes_read"],
                "perf/restore/verify_cached_bytes": rs["bytes_cached"],
                "perf/restore/verify_ms": rs["verify_ms"],
            })
        rr = ckpt.last_reshard
        if rr is not None:
            # cross-topology restore (ISSUE 12): reshard cost joins the
            # startup breakdown so tools/bench_startup.py's cross arm can
            # report it alongside TTFS
            row.update({
                "perf/restore/reshard_ms": rr["reshard_ms"],
                "perf/restore/reshard_leaves": rr["leaves"],
            })
        if chief:
            import json as _json

            print("[dcgan_tpu] startup "
                  + _json.dumps({k: round(v, 1) for k, v in row.items()}),
                  flush=True)
            if cache_dir is not None or cfg.aot_warmup:
                svc.submit(lambda s=step, r=dict(row):
                           writer.write_scalars(s, r), tag="startup")
            if rr is not None:
                # gated by the reshard EVENT itself (never by warm-start
                # knobs): same-topology streams stay byte-identical —
                # sidecar present, keys absent (the parity contract's
                # absent-until-event clause, like anomaly/rollbacks)
                erow = {
                    "elastic/resharded": 1.0,
                    "elastic/saved_processes": rr["saved_processes"],
                    "elastic/saved_devices": rr["saved_devices"],
                    "elastic/host_stage": rr["host_stage"],
                    "perf/restore/reshard_ms": rr["reshard_ms"],
                    "perf/restore/reshard_leaves": rr["leaves"],
                }
                svc.submit(lambda s=step, r=erow:
                           writer.write_scalars(s, r), tag="elastic")
            if cfg.precision:
                # reduced-precision ladder (ISSUE 17): one row naming the
                # active policy (numeric code — write_scalars coerces to
                # float) + the f32 master-moment census. Gated on the knob:
                # precision="" streams carry neither key (parity contract;
                # the policy STRING rides the flight-recorder header)
                prow = {
                    "perf/precision/policy": float(
                        {"f32": 0, "bf16": 1}[cfg.precision]),
                    "perf/precision/master_f32_leaves": float(master_f32),
                }
                svc.submit(lambda s=step, r=prow:
                           writer.write_scalars(s, r), tag="precision")

    def _health_extras() -> dict:
        """Recovery counters riding the scalar rows — absent until nonzero,
        so default-config event streams are byte-identical to pre-recovery
        builds (the parity contract). Reads the counter registry (ISSUE 6)
        — the same snapshot the flight recorder and health vector see."""
        c = registry.snapshot()
        out = {}
        if c.rollbacks:
            out["anomaly/rollbacks"] = c.rollbacks
        if c.corrupt_records:
            out["data/corrupt_records"] = c.corrupt_records
        return out

    def _flight_record(p: dict, gate: str) -> None:
        """One flight-recorder ring record per consumed step: in-memory
        deque append + counter reads on the dispatch thread; the losses
        ride along only when this step's metrics already materialized
        (the recorder must never force a device readback)."""
        if not flight.enabled:
            return
        host = p.get("host")
        rec = {
            "step": p["step"], "time": time.time(), "gate": gate,
            "step_ms": timer.last_step_ms, "host_ms": timer.last_host_ms,
            "metrics": dict(host) if host else None,
            "counters": registry.snapshot().as_dict(),
        }
        if "pipeline" in p:
            # --pipeline_gd only (ISSUE 7): which pipeline phase this step
            # dispatched under ("fill"/"steady") — a crash dump from a
            # mid-fill or mid-drain hang must say so; absent in fused mode
            # so default dumps are unchanged
            rec["pipeline"] = p["pipeline"]
        flight.record(rec)

    def _nan_gate(p: dict, *, force: bool = False) -> bool:
        """Numerical-health gate (SURVEY.md §5) with anomaly CONSENSUS
        (ISSUE 4): each process computes a local verdict over its view of
        the replicated metrics, then the verdicts are allgathered so every
        host takes the identical abort/rollback branch — a non-finite
        value visible on one host only (host-side readback fault, or a
        per-process chaos plan) must never leave the others dispatching
        collectives into a dead mesh. The gate cadence is step-keyed, so
        every process enters the consensus collective at the same
        invocation; `force` (the rollback manager certifying a snapshot
        candidate off-cadence) is step-keyed too. testing/chaos.py can
        poison THIS process's view of the metrics (once) to drill the
        consensus path without real divergence. Returns whether the gate
        EVALUATED (False = off-cadence skip) — the flight recorder's
        gate-verdict column reads this instead of re-deriving the cadence,
        so the two can never disagree."""
        s = p["step"]
        if not force and not (cfg.nan_check_steps
                              and s % cfg.nan_check_steps == 0):
            return False
        vals = dict(_host_vals(p))
        if chaos.should_inject_nan(s):
            vals["d_loss"] = float("nan")
        local_bad = not all(np.isfinite(v) for v in vals.values())
        with _guard("nan-consensus", s):
            bad, trippers = coordination.anomaly_consensus(local_bad)
        if bad:
            where = f" (tripped on process(es) {trippers})" \
                if n_proc > 1 else ""
            err = FloatingPointError(
                f"non-finite training metrics at step {s}{where}: "
                f"{vals} — inspect the last checkpoint in "
                f"{cfg.checkpoint_dir}")
            err.step = s
            raise err
        return True

    def _consume_metrics(p: dict) -> None:
        """Host-side consumers of one step's replicated metric scalars:
        numerical-health gate (abort or hand the trainer's rollback
        handler a FloatingPointError, per nan_policy), stdout step log,
        and the time-throttled scalar events. With async services this
        runs lag-by-one: step N's scalars materialize while step N+1 runs
        on device, so the blocking device_get overlaps compute instead of
        serializing the pipeline; a NaN still trips with the right step
        number, one step later. All cadence math uses the record's own
        step, so attribution is identical in both modes."""
        s = p["step"]
        try:
            gated = _nan_gate(p)
        except FloatingPointError:
            # the failing step must be the ring's LAST record — the
            # acceptance contract a dump reader leans on
            _flight_record(p, "trip")
            raise
        if chief and cfg.log_every_steps and s % cfg.log_every_steps == 0:
            m = _host_vals(p)
            epoch = s * pcfg.batch_size // epoch_size
            # the two players' losses, or a token arch's: its objective
            # and each head's or exit's loss, and the looped arch's mean
            # exit step
            names = ["d_loss", "g_loss",
                     *sorted(k for k in m if k.startswith("loss")),
                     "exit_mean_step"]
            losses = " ".join(f"{k} {m[k]:.4f}" for k in names if k in m)
            print(f"[dcgan_tpu] epoch {epoch} step {s} "
                  f"time {time.time() - t_start:.1f}s {losses}")
        # record AFTER the step log so the ring rides the materialization
        # the log already paid for (default chief logs every step); still
        # never forces a readback of its own
        _flight_record(p, "ok" if gated else "")
        if p["write_scalars"]:
            row = {**_host_vals(p), **timer.summary(), **_health_extras(),
                   **(prog.scalar_extras(s) if prog is not None else {})}
            svc.submit(lambda: writer.write_scalars(s, row), tag="scalars")

    # one step's metrics record awaiting its lag-by-one consumption
    pending: Optional[dict] = None

    def _do_rollback(e: FloatingPointError) -> None:
        """Recovery executor for a tripped gate under nan_policy="rollback":
        restore the snapshot (raises RollbackExhausted past the budget),
        drop checkpoints saved inside the poisoned window (the NaN entered
        somewhere after the last verified snapshot — a save from that span
        may embed it), surface anomaly/rollbacks, apply LR backoff (a
        rebuild of the compiled step — rare-event cost), and re-key the
        step stream so the replayed window draws fresh z instead of
        bitwise re-running into the same divergence. The data iterator is
        NOT rewound: the offending batch window is skipped by construction.
        """
        nonlocal state, step_num, pending, pt, base_key, pt_backoff
        fail_step = getattr(e, "step", step_num)
        # recovery's COLLECTIVE half stays under the watchdog: the
        # device-resident restore dispatches and delete_steps_after's
        # named barrier are exactly the blocking points where a wedged
        # peer would otherwise hang every host with no process dying for
        # the coordination service to notice. (The jitted copy was already
        # compiled at snapshot time, so no compile runs in this window.)
        # Only the optional pt rebuild below — a real recompile — is
        # exempted.
        if mesh_warm:
            watchdog.arm("rollback-restore", fail_step)
        state, step_num = rollback.restore(e)
        pending = None
        # checkpoint_dir/best is deliberately NOT dropped: its retention is
        # score-gated (a best-save only happens when the FID probe improved,
        # and a diverging state scores badly), so a best snapshot from the
        # poisoned window is both unlikely and self-evidencing — deleting a
        # possibly-genuinely-best checkpoint would destroy data on a guess
        dropped = ckpt.delete_steps_after(step_num)
        if chief:
            if dropped:
                print(f"[dcgan_tpu] dropped checkpoint step(s) {dropped} "
                      f"saved inside the poisoned window", flush=True)
            svc.submit(lambda s=fail_step, n=rollback.rollbacks:
                       writer.write_scalars(s, {"anomaly/rollbacks": n}),
                       tag="anomaly")
        watchdog.disarm()  # collectives done; the rebuild below compiles
        if rollback.lr_backoff < 1.0:
            scale = rollback.lr_scale()
            if pt_backoff is not None and rollback.rollbacks == 1:
                # the AOT warmup phase pre-built and cache-primed exactly
                # this variant (warmup.backoff_config — one shared
                # construction, so the HLO and cache key are bit-identical):
                # the swapped-in surface deserializes at its next dispatch
                # instead of recompiling mid-recovery, and compiled_ks
                # stays intact — no recompile event, no exemption needed
                pt = pt_backoff
                pt_backoff = None  # scale^2 at a 2nd rollback: rebuild then
                if chief:
                    print(f"[dcgan_tpu] rollback LR backoff: base rates "
                          f"scaled by {scale:.3g} (pre-warmed surface "
                          f"swapped in — no recompile)", flush=True)
            else:
                pt = make_parallel_train(
                    warmup.backoff_config(cfg, scale), mesh)
                # the rebuilt step programs compile on their next dispatch
                # — exempt those windows from the watchdog like the first
                # ones
                compiled_ks.clear()
                if chief:
                    print(f"[dcgan_tpu] rollback LR backoff: base rates "
                          f"scaled by {scale:.3g}", flush=True)
        base_key = jax.random.fold_in(jax.random.key(cfg.seed + 2),
                                      rollback.rollbacks)

    def _consume_or_rollback(p: dict) -> bool:
        """Consume one metrics record; True = consumed clean, False = the
        gate tripped and the run was rolled back (the caller restarts its
        iteration from the restored state). With nan_policy="abort"
        (default) the FloatingPointError propagates exactly as before."""
        try:
            _consume_metrics(p)
            return True
        except FloatingPointError as e:
            if rollback is None:
                raise
            _do_rollback(e)
            return False

    # step_num is tracked on the host (it equals state["step"], which the
    # trainer fully determines) — touching the device array every iteration
    # would force a per-step host sync and serialize the pipeline.
    # hoisted: reads the manifest once per phase; progressive runs resolve
    # the {res} data-dir placeholder so the epoch counter reads the REAL
    # phase manifest, and the switch below re-reads it for the next phase
    def _phase_epoch_size() -> int:
        if prog is None:
            return max(1, _epoch_size(cfg))
        from dcgan_tpu.progressive import phase_data_cfg

        return max(1, _epoch_size(phase_data_cfg(pcfg)))

    epoch_size = _phase_epoch_size()
    step_num = start_step
    # call shapes (steps_per_call k values) already dispatched against the
    # CURRENT `pt` — the watchdog only arms dispatch windows for these;
    # cleared when a rollback LR backoff rebuilds the compiled step.
    # Warmup proof seeds the set (both the k=1 tail and the scan shape were
    # AOT-compiled into the persistent cache), so guarded dispatch starts
    # at the FIRST boundary instead of after one live pass per shape.
    compiled_ks: set = set()
    if warm_proof:
        compiled_ks.add(1)
        if cfg.steps_per_call > 1:
            compiled_ks.add(cfg.steps_per_call)
    if rollback is not None:
        # arm the initial restore point: a fresh init or a checkpoint
        # restore — both trusted (the checkpoint passed integrity
        # verification; a NaN could not have been saved past the gate)
        rollback.snapshot(step_num, state)
    # PROTOCOL ANCHOR (ISSUE 14): the boundary-poll branch structure of
    # this loop — self-signal fault, stop poll, hang fault, dispatch,
    # lag-by-one consume, fleet-health cadence, snapshot-certify, and the
    # post-loop final flush + final save — is mirrored step-for-step by
    # the protocol simulator (analysis/simulate.py::_virtual_trainer),
    # which drives the REAL coordination/rollback/checkpoint decision
    # code through it and lockstep-audits every collective schedule.
    # Reordering collectives here WILL drift analysis/protocol.lock.jsonl
    # (a DCG012 finding); update the mirror with the change and
    # regenerate the lock deliberately.
    try:
        while step_num < total_steps:
            svc.raise_if_failed()  # a dead telemetry worker fails loudly
            chaos.maybe_self_signal(step_num)  # drill: preemption notice
            # Coordinated stop (ISSUE 4): single-process reads the local
            # flag; multi-host under coord_stop allgathers the flags at
            # EVERY boundary — the decision to enter a collective must be
            # symmetric, so it cannot be gated on the local flag alone.
            stop_sig, stop_origins = None, []
            if n_proc == 1:
                stop_sig, stop_origins = stop.poll()
            elif cfg.coord_stop:
                with _guard("stop-consensus", step_num):
                    stop_sig, stop_origins = stop.poll()
                if not mesh_warm and step_num > start_step:
                    # warm proof for NON-chief processes (which may not
                    # materialize metrics for many steps): a boundary-N>0
                    # poll returning means every peer dispatched its first
                    # step — each device stream runs the allgather only
                    # after that step's program, so everyone is past
                    # compile
                    mesh_warm = True
            if stop_sig is not None:
                if chief:
                    where = f" on process(es) {stop_origins}" \
                        if n_proc > 1 else ""
                    print(f"[dcgan_tpu] received signal {stop_sig}{where} "
                          f"— checkpointing at step {step_num} and exiting")
                # preemption post-mortem context (ISSUE 6): the telemetry
                # that led into the stop, stamped with the step being
                # saved — crash-path-only IO, so parity holds
                flight.dump("coordinated-stop", step=step_num,
                            extra={"signal": int(stop_sig)})
                if pipeline is not None:
                    # release the in-flight fake stack before the final
                    # collective save allocates (ISSUE 7) — the stop
                    # decision is consensus-agreed, so every process
                    # drains at the same boundary
                    with _guard("pipeline-drain", step_num):
                        pipeline.drain("coordinated-stop")
                # drain the services queue BEFORE the final save below: the
                # emergency checkpoint must not outrun queued JSONL/TB
                # events, or a post-stop inspection sees a stream truncated
                # mid-write relative to the state that was saved
                svc.drain()
                break
            # Live-elasticity notice poll (ISSUE 18, DESIGN.md §6l): the
            # same boundary-poll consensus shape as the stop poll above —
            # the local sources (touch file, SIGUSR1, chaos fault) fold
            # into one verdict through notice_consensus, so every process
            # takes the identical switch branch at the identical boundary.
            # Single-process (the live-switch scope) reads the local
            # verdict with no collective; the guarded multi-host arm is
            # the consensus half the protocol tier proves symmetric.
            notice_sig = 0
            if live_rt is not None:
                if n_proc == 1:
                    notice_sig, notice_origins = notice.poll(step_num)
                else:
                    with _guard("notice-consensus", step_num):
                        notice_sig, notice_origins = notice.poll(step_num)
            if notice_sig:
                live_target = live_rt.target_index(notice_sig)
                verdict_name = live_elastic.VERDICT_NAMES.get(
                    notice_sig, "?")
                if live_target is None:
                    # already on the asked-for topology (a grow notice on
                    # the full mesh, a repeated shrink): consume the
                    # notice — an unacked file would re-raise every
                    # boundary — and carry on without a switch
                    notice.ack(step=step_num, verdict=notice_sig,
                               target=live_rt.tag(), switch_ms=0.0)
                    if chief:
                        print(f"[dcgan_tpu] {verdict_name} notice at step "
                              f"{step_num}: already on {live_rt.tag()} — "
                              f"consumed, no switch", flush=True)
                else:
                    # Live topology switch: the PR 14 phase-boundary
                    # sequence pointed at a mesh change. Flush the
                    # lag-by-one record (pre-switch metrics; a gate trip
                    # rolls back BEHIND the boundary — the consumed
                    # notice is NOT re-raised, the scheduler re-notifies
                    # if it still wants the capacity) -> services drain
                    # (queued telemetry referencing old-mesh arrays lands
                    # before their buffers die) -> G/D pipeline drain
                    # (the fake stack is mesh-committed) -> state
                    # re-scatter onto the target surface -> loader
                    # rebuild (batches are mesh-committed too),
                    # fast-forwarded past the consumed prefix -> fresh
                    # rollback snapshot -> StepTimer/compiled_ks re-armed.
                    # With --aot_warmup both topologies were primed at
                    # startup, so the switch issues zero compile requests
                    # (the printed delta, drill-pinned).
                    if pending is not None:
                        prev, pending = pending, None
                        if not _consume_or_rollback(prev):
                            continue
                    t_sw = time.perf_counter()
                    svc.drain()
                    if pipeline is not None:
                        with _guard("pipeline-drain", step_num):
                            pipeline.drain("elastic-switch")
                    old_tag = live_rt.tag()
                    state = live_rt.switch(state, notice_sig)
                    pt = live_rt.pt
                    mesh = live_rt.mesh
                    for closing in (data, sample_data):
                        if closing is not None and hasattr(closing,
                                                           "close"):
                            try:
                                closing.close()
                            except Exception:
                                pass
                    data = _data_iterator(
                        cfg, mesh, synthetic=synthetic_data,
                        skip_batches=step_num - start_step)
                    if sample_data is not None:
                        se = cfg.sample_every_steps
                        probes = (step_num // se - start_step // se) \
                            if se else 0
                        if cfg.fid_every_steps and fid_real_side is not None:
                            # the one-shot real side consumed its batches
                            # from this stream too
                            probes += -(-cfg.fid_num_samples
                                        // cfg.batch_size)
                        sample_data = _sample_data_iterator(
                            cfg, mesh, synthetic=synthetic_data,
                            skip_batches=probes)
                        if n_proc == 1 and cfg.fid_every_steps:
                            # single-process probe aliases the held-out
                            # stream — re-point it at the rebuilt one
                            fid_probe_data = sample_data
                    timer = StepTimer(window=cfg.timing_window,
                                      images_per_step=pcfg.batch_size)
                    compiled_ks.clear()
                    if live_rt.primed:
                        compiled_ks.add(1)
                        if cfg.steps_per_call > 1:
                            compiled_ks.add(cfg.steps_per_call)
                    if rollback is not None:
                        # a NaN right after the switch must restore the
                        # NEW topology's tree, never re-scatter the old
                        rollback.snapshot(step_num, state)
                    switch_ms = (time.perf_counter() - t_sw) * 1e3
                    note = ""
                    if cache_mon is not None and warm_base is not None:
                        d = warmup.CompileCacheMonitor.delta(
                            cache_mon.counters(), warm_base)
                        note = f" compile_requests_delta=" \
                               f"{int(d['requests'])}"
                    if chief:
                        print(f"[dcgan_tpu] live elastic switch at step "
                              f"{step_num}: {old_tag} -> {live_rt.tag()} "
                              f"({verdict_name} notice, "
                              f"{live_rt.last_switch_ms:.1f}ms state "
                              f"move) switch_ms={switch_ms:.1f}{note}",
                              flush=True)
                        srow = {
                            "elastic/live_notice_step": float(step_num),
                            "elastic/live_switch_ms": switch_ms,
                            "elastic/live_target_mesh":
                                float(live_rt.device_count),
                            "elastic/live_resumed_step": float(step_num)}
                        svc.submit(lambda s=step_num, r=srow:
                                   writer.write_scalars(s, r),
                                   tag="elastic")
                    notice.ack(step=step_num, verdict=notice_sig,
                               target=live_rt.tag(), switch_ms=switch_ms)
            # Phase boundary (ISSUE 15, DESIGN.md §6j): the switch decision
            # is a pure function of step_num and the schedule, so every
            # process takes it at the same boundary with zero extra
            # collectives (the protocol tier's progressive config pins the
            # symmetry). Sequence: flush the lag-by-one record (old-phase
            # metrics; a trip here rolls back BEHIND the boundary and the
            # switch re-evaluates) -> services drain barrier (queued
            # telemetry referencing old-phase arrays lands before their
            # buffers die) -> G/D pipeline drain -> state carry onto the
            # next phase's surface -> loader re-bucket -> fresh rollback
            # snapshot (a NaN right after the switch must restore the NEW
            # tree) -> watchdog compiled_ks re-armed for the new surface.
            # With --aot_warmup every dispatched program was primed at
            # startup, so the whole switch issues zero compile requests
            # (the printed delta, CompileCacheMonitor-pinned).
            if prog is not None and prog.switch_due(step_num):
                if pending is not None:
                    prev, pending = pending, None
                    if not _consume_or_rollback(prev):
                        continue
                t_sw = time.perf_counter()
                svc.drain()
                if pipeline is not None:
                    with _guard("pipeline-drain", step_num):
                        pipeline.drain("phase-switch")
                old_res = prog.resolution
                state = prog.advance(state)
                pt = prog.pt
                pcfg = prog.cfg
                ckpt.progressive_tag = prog.tag()
                data, sample_data = rebucketer.reopen(pcfg)
                eval_z = jax.numpy.resize(
                    sample_z, (pcfg.batch_size, cfg.model.z_dim)) \
                    if sample_data is not None else None
                timer = StepTimer(window=cfg.timing_window,
                                  images_per_step=pcfg.batch_size)
                epoch_size = _phase_epoch_size()
                compiled_ks.clear()
                if prog.primed:
                    compiled_ks.add(1)
                    if cfg.steps_per_call > 1:
                        compiled_ks.add(cfg.steps_per_call)
                if rollback is not None:
                    rollback.snapshot(step_num, state)
                switch_ms = (time.perf_counter() - t_sw) * 1e3
                note = ""
                if cache_mon is not None and warm_base is not None:
                    d = warmup.CompileCacheMonitor.delta(
                        cache_mon.counters(), warm_base)
                    note = f" compile_requests_delta={int(d['requests'])}"
                if chief:
                    print(f"[dcgan_tpu] progressive phase {prog.index} at "
                          f"step {step_num}: r{old_res} -> "
                          f"r{prog.resolution} (batch {pcfg.batch_size}, "
                          f"{prog.last_carried} leaves carried) "
                          f"switch_ms={switch_ms:.1f}{note}", flush=True)
                    srow = {**prog.scalar_extras(step_num + 1),
                            "progressive/switch_ms": switch_ms}
                    svc.submit(lambda s=step_num, r=srow:
                               writer.write_scalars(s, r),
                               tag="progressive")
            # steps_per_call > 1: dispatch K steps as one scanned program
            # when aligned to a K boundary with K steps remaining (a
            # checkpoint restore can land mid-boundary; single steps
            # realign, and the tail below max_steps runs single too). Keys
            # are per-step fold-ins, identical to the single-step path, so
            # a run produces the same step keys whatever the call size.
            k = cfg.steps_per_call
            if not (k > 1 and step_num % k == 0
                    and step_num + k <= total_steps):
                k = 1
            # dispatch/consume window under the watchdog deadline — except
            # iterations that COMPILE: the first dispatch of each call
            # shape (the k=1 tail after scanned k=K calls included), the
            # first dispatch after a rollback LR-backoff rebuilt `pt`, and
            # everything before the mesh is warm (a peer may still be in
            # ITS first compile) — compile time is legitimate and
            # unbounded by this knob
            if mesh_warm and k in compiled_ks:
                # stage-resolved phase labels under --pipeline_gd (ISSUE 7):
                # a trip inside the refill after a rollback reads
                # "pipeline-fill", a steady-state trip "pipeline-dispatch"
                # — the fused path keeps its historical label
                if pipeline is None:
                    phase = "step-dispatch"
                else:
                    phase = "pipeline-dispatch" if pipeline.primed \
                        else "pipeline-fill"
                watchdog.arm(phase, step_num)
            chaos.maybe_hang(step_num)  # drill: a peer that goes silent
            trace.maybe_start(step_num)
            if trace.active:
                capture_ks.append(k)  # this boundary is inside the window
            labels = None
            if k == 1:
                key = jax.random.fold_in(base_key, step_num)
                with span("train/next"):
                    batch = next(data)
                images, labels = batch if conditional else (batch, None)
                with span("train/dispatch"):
                    if prog is not None:
                        # image-space fade-in (ISSUE 15): inside a fade
                        # window the real batch blends toward its
                        # previous-resolution content through the phase's
                        # jitted blend (alpha a traced scalar); a no-op
                        # dispatch-free identity at alpha == 1
                        images = prog.fade_images(images, step_num)
                    if conditional:
                        state, metrics = pt.step(state, images, key, labels)
                    elif pipeline is not None:
                        # pipelined dispatch (ISSUE 7): d_update consumes
                        # the stack g_update produced during the previous
                        # step; an unprimed buffer (run start, post-
                        # rollback, post-drain) dispatches the gen_fakes
                        # fill first — the watchdog phase armed above names
                        # which case a hang died in
                        state, metrics = pipeline.step(pt, state, images,
                                                       key)
                    else:
                        state, metrics = pt.step(state, images, key)
            else:
                # one vmapped dispatch for all K per-step keys (a python
                # loop of fold_ins would pay K of the per-dispatch
                # overheads this path exists to shed); same per-step keys
                # as the single-step path
                keys = jax.vmap(jax.random.fold_in, (None, 0))(
                    base_key, jax.numpy.arange(step_num, step_num + k))
                key = keys[-1]  # for the cadence consumers below
                with span("train/next"):
                    batches = [next(data) for _ in range(k)]
                with span("train/dispatch"):
                    if conditional:
                        imgs_k = jax.numpy.stack([p[0] for p in batches])
                        lbls_k = jax.numpy.stack([p[1] for p in batches])
                        state, metrics = pt.multi_step(state, imgs_k, keys,
                                                       lbls_k)
                        images, labels = batches[-1]
                    else:
                        imgs_k = jax.numpy.stack(batches)
                        state, metrics = pt.multi_step(state, imgs_k, keys)
                        images = batches[-1]
            compiled_ks.add(k)  # dispatch returned: this shape is compiled
            new_step = step_num + k
            cur = {"step": new_step, "metrics": metrics,
                   "write_scalars": False}
            if pipeline is not None:
                # the step's pipeline phase rides the record so the flight
                # recorder can stamp it (fill vs steady), lag-by-one safe —
                # the tag is captured at dispatch, consumed whenever
                cur["pipeline"] = pipeline.last_phase

            # the dispatch thread's host work per iteration is what the two
            # spans below time: their durations are perf/host_ms_mean and
            # perf/dispatch_occupancy (StepTimer.note_host)
            with span("train/consume") as consume:
                if deferred:
                    # lag-by-one metric window: consume the PREVIOUS step's
                    # scalars now — its D2H copies have had a full step to
                    # land, so the materialization below reads cached values
                    # instead of blocking dispatch on the device — and start
                    # this step's copies for the next iteration.
                    if pending is not None:
                        prev, pending = pending, None
                        if not _consume_or_rollback(prev):
                            continue  # rolled back: restart from restored
                    _stage(metrics)
                else:
                    # inline escape hatch: NaN gate + step log at the
                    # original call site, synced to THIS step (true step
                    # latency)
                    if not _consume_or_rollback(cur):
                        continue
            timer.note_host(consume.duration)
            # With per-step logging (the default, matching the reference's
            # every-step stdout log) each tick follows one metric
            # materialization — true step latency, lagged by one step in
            # async mode; with log_every_steps=0 it measures dispatch
            # cadence only.
            timer.tick(steps=k)

            with span("train/services") as services:
                if chief and writer.ready():
                    if deferred:
                        # written at the next flush
                        cur["write_scalars"] = True
                    else:
                        row = {**_host_vals(cur), **timer.summary(),
                               **_health_extras(),
                               **(prog.scalar_extras(new_step)
                                  if prog is not None else {})}
                        svc.submit(lambda s=new_step, r=row:
                                   writer.write_scalars(s, r), tag="scalars")
                    snap = _snapshot_params(state["params"])
                    svc.submit(lambda s=new_step, t=snap:
                               writer.write_histograms(s, param_histograms(t)),
                               tag="histograms")
                if deferred:
                    pending = cur
                watchdog.disarm()  # dispatch/consume window completed

                # Fleet health plane (ISSUE 6): one compact float32 allgather
                # per cadence, issued HERE on the dispatch thread
                # (collective-thread rule — a background-thread collective
                # would interleave nondeterministically against step
                # dispatches and wedge the mesh). Every process
                # contributes its HEALTH_FIELDS vector;
                # the chief materializes fleet/* (straggler skew, slowest
                # host, queue/drop/recovery totals) and the slowest-host line
                # is parked on the watchdog + flight recorder so a later trip
                # names the likely wedged peer.
                if cfg.fleet_health_steps and \
                        new_step % cfg.fleet_health_steps == 0:
                    tsum = timer.summary()
                    c = registry.snapshot()
                    vec = np.asarray(
                        [new_step, tsum.get("perf/step_ms_mean", 0.0),
                         tsum.get("perf/host_ms_mean", 0.0), c.services_queue,
                         c.services_dropped, c.rollbacks, c.corrupt_records,
                         c.progressive_phase],
                        np.float32)
                    with _guard("fleet-health", new_step):
                        table = coordination.fleet_health_gather(vec)
                    frow, fleet_note = coordination.fleet_metrics(table)
                    watchdog.set_note(fleet_note)
                    flight.note = fleet_note
                    if chief:
                        svc.submit(lambda s=new_step, r=frow:
                                   writer.write_scalars(s, r),
                                   tag="fleet-health")

                # per-layer activation histograms + sparsity (the reference's
                # _activation_summary channel, distriubted_model.py:75-80). The
                # summarize DISPATCH runs on every process — it is a compiled
                # mesh program — only the chief's device_get + write moves to
                # the worker (the outputs are fresh replicated arrays; nothing
                # donates them).
                if cfg.activation_summary_steps and \
                        new_step % cfg.activation_summary_steps == 0:
                    acts = pt.summarize(state, images,
                                        jax.random.fold_in(key, 1),
                                        labels) if conditional else \
                        pt.summarize(state, images, jax.random.fold_in(key, 1))
                    if chief:
                        _stage(acts)
                        svc.submit(lambda s=new_step, a=acts:
                                   writer.write_activations(s,
                                                            jax.device_get(a)),
                                   tag="activations")

                if cfg.sample_every_steps and \
                        new_step % cfg.sample_every_steps == 0:
                    imgs_dev = pt.sample(state, sample_z, sample_labels) \
                        if sample_labels is not None \
                        else pt.sample(state, sample_z)
                    if chief:
                        _stage(imgs_dev)
                        path = os.path.join(cfg.sample_dir,
                                            f"train_{new_step:08d}.png")

                        def _grid_task(s=new_step, a=imgs_dev, p=path):
                            imgs = jax.device_get(a)
                            save_sample_grid(p, imgs[:rows * cols],
                                             (rows, cols))
                            writer.write_image_event(s, "samples", p)
                        svc.submit(_grid_task, tag="sample-grid")
                    # held-out loss probe on the sample pipeline's batch with
                    # the fixed z — the reference's sess.run([sampler,
                    # d_loss, g_loss]) + print every 100 steps
                    # (image_train.py:179-192)
                    if sample_data is not None:
                        if conditional:
                            s_imgs, s_labels = next(sample_data)
                            ev = pt.eval_losses(state, s_imgs, eval_z,
                                                s_labels)
                        else:
                            s_imgs = next(sample_data)
                            ev = pt.eval_losses(state, s_imgs, eval_z)
                        if chief:
                            _stage(ev)

                            def _probe_task(s=new_step, e=ev):
                                vals = {k: float(v) for k, v in
                                        jax.device_get(e).items()}
                                print(f"[dcgan_tpu] [sample] step {s} "
                                      f"d_loss {vals['d_loss']:.8f} "
                                      f"g_loss {vals['g_loss']:.8f}")
                                writer.write_scalars(
                                    s, {f"sample/{k}": v
                                        for k, v in vals.items()})
                            svc.submit(_probe_task, tag="sample-probe")
            timer.note_host(services.duration)

            # The in-training FID/KID probe stays ENTIRELY on the dispatch
            # thread: its real-side streaming, feature all-gathers, and
            # the best-checkpoint Orbax save are mesh-wide collectives,
            # and collectives issued from a background thread have no
            # cross-process ordering against this thread's step dispatches
            # — two processes interleaving them differently deadlocks the
            # mesh. Only the two result scalars go through the writer
            # queue (the writer itself is single-threaded).
            if cfg.fid_every_steps and new_step % cfg.fid_every_steps == 0:
                from dcgan_tpu.evals.job import (
                    FeaturePool,
                    compute_fid,
                    stats_from_batches,
                )

                dist = n_proc > 1
                if dist:
                    # Local sampler over the gathered generator tree:
                    # compiled once (weights are arguments, not closed-over
                    # constants), fed fresh weights each probe. Mirrors
                    # steps.py sample's EMA selection.
                    from jax.experimental import multihost_utils as mh

                    g_src = state["ema_gen"] if cfg.g_ema_decay > 0.0 \
                        else state["params"]["gen"]
                    host_gen = jax.tree_util.tree_map(
                        lambda x: mh.process_allgather(x, tiled=True),
                        (g_src, state["bn"]["gen"]))
                    if fid_local_sampler is None:
                        from dcgan_tpu.models import sampler_apply

                        fid_local_sampler = jax.jit(
                            lambda p, b, z, lbls=None: sampler_apply(
                                p, b, z, cfg=cfg.model, labels=lbls))

                    def _sample_fn(z, lbls=None, _g=host_gen):
                        return fid_local_sampler(_g[0], _g[1], z, lbls) \
                            if lbls is not None \
                            else fid_local_sampler(_g[0], _g[1], z)
                else:
                    def _sample_fn(z, lbls=None, _s=state):
                        return pt.sample(_s, z, lbls) if lbls is not None \
                            else pt.sample(_s, z)

                n = cfg.fid_num_samples
                t_fid = time.time()
                if fid_real_side is None:
                    # real-side statistics are computed ONCE, at the first
                    # probe: the held-out set is fixed, so re-streaming it
                    # each probe would double probe cost and add real-side
                    # sampling noise to the eval/fid trend. Multihost: each
                    # process streams its share, then the sides merge into
                    # one global real side (treated as already-global by
                    # compute_fid).
                    reals = (b[0] for b in fid_probe_data) if conditional \
                        else fid_probe_data
                    r_pool = FeaturePool(fid_feature[1], n, seed=cfg.seed)
                    r_stats = stats_from_batches(fid_feature[0], reals,
                                                 n // n_proc,
                                                 fid_feature[1], pool=r_pool)
                    if dist:
                        from dcgan_tpu.evals.job import (
                            allgather_merge_pool,
                            allgather_merge_stats,
                        )

                        r_stats = allgather_merge_stats(r_stats)
                        r_pool = allgather_merge_pool(r_pool)
                    fid_real_side = (r_stats, r_pool)
                fid_result = compute_fid(
                    _sample_fn, None, image_size=cfg.model.output_size,
                    c_dim=cfg.model.c_dim, z_dim=cfg.model.z_dim,
                    num_samples=n, batch_size=cfg.batch_size,
                    num_classes=cfg.model.num_classes, seed=cfg.seed,
                    feature_fn=fid_feature[0], feature_dim=fid_feature[1],
                    kid=True, kid_subset_size=max(2, min(1000, n // 4)),
                    kid_subsets=20, kid_pool_size=n,
                    distributed=dist, real_side=fid_real_side)
                if chief:
                    print(f"[dcgan_tpu] [fid] step {new_step} "
                          f"fid {fid_result['fid']:.6f} "
                          f"kid {fid_result['kid']:.3e} "
                          f"({n} samples, {time.time() - t_fid:.1f}s)")
                    svc.submit(lambda s=new_step, r=dict(
                        fid_result): writer.write_scalars(s, {
                            "eval/fid": r["fid"],
                            "eval/kid": r["kid"],
                        }), tag="fid-scalars")
                # best-checkpoint retention: when the probe improves on the
                # best FID seen this run, snapshot into checkpoint_dir/best
                # (its own manager, max_to_keep=1) — training ends with
                # both the latest state AND the best-scoring one on disk.
                # The periodic/latest cadence is untouched. Multihost: the
                # gathered score is identical on every process, so every
                # process takes this branch together and the Orbax save
                # stays a valid collective; only the chief touches
                # score.json/config.json.
                if fid_result["fid"] < fid_best:
                    import json

                    fid_best = fid_result["fid"]
                    best_dir = os.path.join(cfg.checkpoint_dir, "best")
                    if best_ckpt is None:
                        # sync save: each best-save is final before
                        # training continues, so async machinery would
                        # only be joined
                        best_ckpt = Checkpointer(best_dir, max_to_keep=1,
                                                 async_save=False)
                        # its own config.json so `generate
                        # --checkpoint_dir ckpt/best` works zero-flag like
                        # any checkpoint dir
                        if chief:
                            save_config(cfg, best_dir)
                    best_ckpt.save(new_step, state, force=True)
                    if chief:
                        # persisted score: resume re-seeds fid_best from
                        # this
                        tmp = os.path.join(best_dir, "score.json.tmp")
                        with open(tmp, "w") as f:
                            json.dump({"fid": fid_best,
                                       "step": int(new_step)}, f)
                        os.replace(tmp,
                                   os.path.join(best_dir, "score.json"))
                        print(f"[dcgan_tpu] [fid] new best "
                              f"({fid_best:.6f}) — saved "
                              f"{cfg.checkpoint_dir}/best/{new_step}")

            trace.maybe_stop(new_step, sync=metrics)
            if rollback is not None and rollback.due(new_step):
                # refresh the restore point — but only with VERIFIED state:
                # force the gate on this step's metrics (off-cadence too),
                # and flush the lag-by-one record first so a trip here
                # attributes to the right step. Forcing materialization
                # costs one host sync per K steps — the snapshot's price.
                # Guarded: the forced readback and the mesh-wide snapshot
                # copy both block on peers (the copy compiled at the
                # pre-loop snapshot, so no compile runs here).
                try:
                    with _guard("snapshot-certify", new_step):
                        _nan_gate(cur, force=True)
                        if pending is not None:
                            _consume_metrics(pending)
                            pending = None
                        rollback.snapshot(new_step, state)
                except FloatingPointError as e:
                    _do_rollback(e)
                    continue
            with _guard("collective-save", new_step):
                if ckpt.maybe_save(new_step, state):
                    # drain-on-checkpoint barrier: every telemetry event
                    # submitted before this checkpoint is durable before
                    # training proceeds past it — a preemption right after
                    # a save cannot lose events older than the checkpoint
                    svc.drain()
            step_num = new_step

        # final lag-by-one flush: the last step's NaN gate / log / scalars
        # (fires before the final forced save below, so a NaN in the last
        # step still aborts the run rather than being checkpointed quietly)
        if pending is not None:
            _consume_metrics(pending)
            pending = None
        if chief:
            svc.submit(writer.flush, tag="tb-flush", droppable=False)
        svc.close()  # drain-on-exit barrier; re-raises worker failures
        if chief and getattr(svc, "dropped", 0):
            print(f"[dcgan_tpu] host-services backpressure dropped "
                  f"{svc.dropped} telemetry event(s) (training was never "
                  f"stalled for them; raise the queue bound or slow the "
                  f"summary cadence to keep them all)")
    except BaseException:
        # exception exit: the tail below (final save, watchdog.close())
        # never runs, so close the enforcement thread here — a driver
        # that catches aborts and calls train() in a loop must not
        # accumulate one daemon thread per failed run. An explicit except
        # (not sys.exc_info() in the finally) because train() may itself
        # be running inside a caller's except block, where exc_info() is
        # non-None even on a clean exit.
        watchdog.close()
        raise
    finally:
        # clean shutdown on EVERY exit path (normal, signal break, NaN
        # abort, loader error): stop the device-feed threads and the
        # services worker without masking an in-flight exception. The
        # watchdog is DISARMED (not closed — the final collective save
        # below still wants its deadline) so a fast abort path cannot race
        # a stale deadline into a spurious process exit during cleanup.
        watchdog.disarm()
        if notice is not None:
            # hand SIGUSR1 back on every exit path — a process that calls
            # train() again (tests, drills) must not deliver a late
            # notice into a dead plane
            notice.restore()
        if pipeline is not None:
            # release the buffer on every exit path (normal completion,
            # abort, loader error) — nothing past the loop consumes it
            pipeline.drain("shutdown")
        for closing in (svc, data, sample_data, fid_probe_data):
            if closing is None or not hasattr(closing, "close"):
                continue
            try:
                closing.close()
            except Exception:
                pass
    # final forced save at the step actually reached (== total_steps unless
    # a shutdown signal broke the loop early); skip if the periodic save
    # already wrote this exact step. Guarded: this is THE collective a
    # coordinated stop must complete on every process, and the one PR 3
    # feared enough to skip multi-host signal handling entirely.
    try:
        trace.close()
        writer.close()
        if ckpt.latest_step() != step_num:
            if mesh_warm:
                watchdog.arm("final-save", step_num)
            ckpt.save(step_num, state, force=True)
        ckpt.wait()
    finally:
        # close() disarms both enforcement layers even when a closer or
        # the save raises — a caller handling that exception must not be
        # os._exit'd by a stale deadline mid-cleanup, nor leak the
        # enforcement thread
        watchdog.close()
    return state


def _epoch_size(cfg: TrainConfig) -> int:
    """Examples per epoch for the log's epoch counter.

    The dataset.json manifest's num_examples when the data_dir carries one
    (prepare.py writes it), else the reference's hard-coded
    image_num = 107766*3 (image_train.py:44) — which was wrong for every
    non-CelebA dataset; strict-parity runs without a manifest keep it.
    """
    import json

    from dcgan_tpu.data.pipeline import MANIFEST_NAME

    try:
        with open(os.path.join(cfg.data_dir, MANIFEST_NAME)) as f:
            manifest = json.load(f)
        n = manifest.get("num_examples") if isinstance(manifest, dict) \
            else None
        if n:
            return int(n)
    except (OSError, ValueError):
        pass
    return 323_298
