"""Live in-run elasticity (ISSUE 18): preemption-notice-driven mesh
shrink/grow without a restart.

PR 12's elastic layer made topology a restart-time degree of freedom: a
checkpoint carries its sharding sidecar, and the next launch reshards onto
whatever mesh it finds. This module removes the restart from the loop for
the advance-notice case — a scheduler that says "you lose half the slice
in 30s" (or "your capacity is back") mid-run:

- `NoticePlane` is the signal half: a notice lands on ONE host as a touch
  file (`--elastic_notice_file`), a SIGUSR1, or a `testing/chaos.py`
  `preempt_notice_at_step`/`grow_notice_at_step` fault. `poll(step)` turns
  the process-local observation into a mesh-uniform verdict through
  `coordination.notice_consensus` — the same boundary-poll consensus shape
  as `CoordinatedStop.poll`, so every process takes the identical switch
  branch at the identical step boundary. File reads and the post-switch
  ack write ride `utils/retry.retry_io` ("notice-poll" / "notice-ack"):
  a transient stat/read blip is retried instead of being misread as
  "no notice" on one host and "notice" on another.

- `LiveTopologyRuntime` is the compiled-surface half, the
  progressive-plane mechanism (progressive/phases.py::PhaseRuntime)
  transposed from model-surface growth to mesh change: one
  `ParallelTrain` per topology (the launch mesh and the
  `--elastic_target_devices` submesh), both AOT-warmed up front under
  `@t<data>x<model>` plan suffixes and primed with one throwaway dispatch
  per program, so the switch itself dispatches only cached executables —
  compile-request delta 0 across a shrink or grow-back. `switch(state)`
  moves the LIVE state between meshes through the elastic host path
  (`jax.device_get` -> `reshard.put_host_tree` onto the target surface's
  sharded templates), which re-scatters ZeRO-2/3 resident shards and
  replicated leaves alike.

The trainer (train/trainer.py) sequences the two around the PR 14
phase-boundary machinery: lag-by-one metric flush -> services drain ->
GD-pipeline drain -> fresh rollback snapshot -> `switch` -> re-armed
StepTimer/compiled_ks/fleet cadence on the new mesh. Scope: the switch is
single-controller (process_count == 1) — a *process* cannot leave a live
jax job; multi-host runs keep the consensus plane (the notice still
coordinates a clean stop) but reject `--elastic_target_devices` at
validation, and the restart-based sidecar path (DESIGN §6h) remains the
cross-process-count story. The protocol tier's `live-elastic-switch`
lattice config proves switch symmetry for the consensus half on virtual
multi-host meshes.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from dcgan_tpu.testing import chaos
from dcgan_tpu.utils.retry import retry_io

Pytree = Any

#: re-exported verdict encoding (testing/chaos.py is the one definition:
#: the chaos hook's return value IS a consensus vote)
NOTICE_NONE = chaos.NOTICE_NONE
NOTICE_GROW = chaos.NOTICE_GROW
NOTICE_SHRINK = chaos.NOTICE_SHRINK

VERDICT_NAMES = {NOTICE_NONE: "none", NOTICE_GROW: "grow",
                 NOTICE_SHRINK: "shrink"}


def _parse_notice_text(text: str) -> int:
    """Notice-file content -> verdict. An empty file is a shrink notice
    (`touch $file` is the operational fast path); "grow"/"restore" ask for
    the grow-back direction; anything else reads as shrink."""
    word = text.strip().split("\n", 1)[0].strip().lower()
    return NOTICE_GROW if word in ("grow", "restore", "grow-back") \
        else NOTICE_SHRINK


class NoticePlane:
    """Process-local notice sources + the mesh-uniform consensus poll.

    Mirrors `coordination.CoordinatedStop`: `install()` registers a
    one-shot SIGUSR1 handler that only sets a flag (main thread only —
    signal module constraint; restored by `restore()` in the trainer's
    finally block); `poll(step)` folds the local sources (signal flag,
    notice file, chaos plan) into one int verdict and runs it through
    `notice_consensus`, so the returned verdict is identical on every
    process. `ack(...)` renames a consumed notice file out of the poll
    path and writes `<file>.ack` with the switch record — the contract a
    notifying scheduler can wait on.
    """

    def __init__(self, notice_file: str = "") -> None:
        self.notice_file = notice_file
        self._sig_verdict = NOTICE_NONE
        self._restore: dict = {}

    def install(self) -> None:
        if threading.current_thread() is not threading.main_thread():
            return

        def _on_signal(signum, frame):
            self._sig_verdict = NOTICE_SHRINK

        self._restore[signal.SIGUSR1] = signal.signal(
            signal.SIGUSR1, _on_signal)

    def restore(self) -> None:
        for s, h in self._restore.items():
            signal.signal(s, h)
        self._restore.clear()

    # -- local sources -------------------------------------------------------

    def _read_notice_file(self) -> int:
        """One retry_io-guarded stat+read of the notice file. The read is
        inside the retried closure so EVERY failure mode (stat, open,
        read) gets the same bounded-retry treatment — the hazard this
        guards is asymmetry: one host's transient EIO reading "no notice"
        while its peers read "notice" would still converge via consensus,
        but a *flaky* yes/no on the same host across boundaries is noise
        the retries squeeze out at the source."""
        def read():
            if not os.path.exists(self.notice_file):
                return NOTICE_NONE
            with open(self.notice_file, "r", encoding="utf-8") as f:
                return _parse_notice_text(f.read())

        try:
            return retry_io(read, tag="notice-poll")
        except OSError as e:
            # still failing after the retry budget: treat as no-notice
            # (the file is still there — the next boundary re-polls) but
            # say so; silent misreads are the failure mode this plane
            # exists to kill
            print(f"[dcgan_tpu] notice-file poll failed after retries "
                  f"({e}) — treating as no notice this boundary",
                  flush=True)
            return NOTICE_NONE

    def local_verdict(self, step: int) -> int:
        """Fold this process's sources; consuming reads (the signal flag
        clears, the chaos hook is one-shot) are safe because the verdict
        feeds straight into the consensus collective below — once
        observed locally it WILL be agreed fleet-wide this boundary."""
        v = chaos.poll_notice(step)
        if self._sig_verdict:
            v = max(v, self._sig_verdict)
            self._sig_verdict = NOTICE_NONE
        if self.notice_file:
            v = max(v, self._read_notice_file())
        return v

    # -- consensus -----------------------------------------------------------

    def poll(self, step: int) -> Tuple[int, List[int]]:
        """(mesh-uniform verdict, processes that raised it) — the
        boundary-poll collective. Same shape as CoordinatedStop.poll: in
        multi-host runs this is one tiny allgather per boundary; single
        process it is the local verdict with no collective."""
        from dcgan_tpu.train import coordination

        return coordination.notice_consensus(self.local_verdict(step))

    def ack(self, *, step: int, verdict: int, target: str,
            switch_ms: float) -> None:
        """Consume the notice file (rename — a second notice can land at
        the same path later) and write the ack record a notifying
        scheduler polls for. Best-effort beyond the retry budget: the
        switch already happened; bookkeeping must not unwind it."""
        if not self.notice_file:
            return
        record = json.dumps({
            "step": int(step), "verdict": VERDICT_NAMES.get(verdict, "?"),
            "target_mesh": target, "switch_ms": round(switch_ms, 3)})

        def write():
            if os.path.exists(self.notice_file):
                os.replace(self.notice_file,
                           self.notice_file + ".consumed")
            with open(self.notice_file + ".ack", "w",
                      encoding="utf-8") as f:
                f.write(record + "\n")

        try:
            retry_io(write, tag="notice-ack")
        except OSError as e:
            print(f"[dcgan_tpu] notice ack write failed after retries: {e}",
                  flush=True)


def submesh_config(cfg, n_devices: int):
    """The target topology's TrainConfig: identical run semantics (global
    batch, model, schedule — the math is layout-invariant), only the mesh
    data axis resized to fit `n_devices`."""
    model = cfg.mesh.model
    if n_devices % model:
        raise ValueError(
            f"elastic_target_devices={n_devices} is not divisible by the "
            f"model axis ({model}) — the live switch keeps the model axis "
            "and resizes data")
    return dataclasses.replace(
        cfg, mesh=dataclasses.replace(cfg.mesh, data=n_devices // model))


class LiveTopologyRuntime:
    """The trainer's live-elasticity companion: two compiled topology
    surfaces (launch mesh + target submesh), warmup/priming for both, and
    the state move between them. Deliberately shaped like
    progressive/phases.py::PhaseRuntime so the trainer's switch block is
    the same sequence with a different `advance`."""

    def __init__(self, cfg, mesh, *, make_pt: Optional[Callable] = None,
                 launch_pt: Any = None):
        import jax

        if jax.process_count() != 1:
            raise ValueError(
                "--elastic_target_devices requires a single-controller run "
                f"(process_count == 1, got {jax.process_count()}): a "
                "process cannot leave a live jax job — multi-host "
                "elasticity is the restart-based sidecar path (DESIGN §6h)")
        self.base_cfg = cfg
        n_full = int(mesh.devices.size)
        n_target = int(cfg.elastic_target_devices)
        if n_target == n_full:
            raise ValueError(
                f"elastic_target_devices={n_target} equals the launch "
                "topology — nothing to switch to")
        if not 0 < n_target <= len(jax.devices()):
            raise ValueError(
                f"elastic_target_devices={n_target} must be in "
                f"[1, {len(jax.devices())}] (available devices)")
        if make_pt is None:
            from dcgan_tpu.parallel import make_parallel_train

            make_pt = make_parallel_train
        self._make_pt = make_pt
        # index 0 = launch topology (trainer's existing cfg/mesh/pt slot
        # in); index 1 = the configured target. Direction maps onto
        # device count: SHRINK -> fewer devices, GROW -> more.
        self._counts = (n_full, n_target)
        self._surfaces: Dict[int, Tuple[Any, Any, Any]] = {}
        self.index = 0
        self.primed = False
        self.last_switch_ms: float = 0.0
        self.switches = 0
        self._launch = (cfg, mesh)
        if launch_pt is not None:
            # adopt the trainer's already-built launch surface instead of
            # constructing a duplicate compiled-program table for it
            self._surfaces[0] = (cfg, mesh, launch_pt)

    # -- surfaces ------------------------------------------------------------

    def surface(self, i: int) -> Tuple[Any, Any, Any]:
        """(cfg_i, mesh_i, pt_i) for topology i, built lazily and kept —
        the switch must land on an already-built, already-warmed
        surface."""
        if i not in self._surfaces:
            import jax

            from dcgan_tpu.parallel import make_mesh

            if i == 0:
                cfg_i, mesh_i = self._launch
            else:
                cfg_i = submesh_config(self.base_cfg, self._counts[i])
                mesh_i = make_mesh(
                    cfg_i.mesh,
                    list(jax.devices())[:self._counts[i]])
            self._surfaces[i] = (cfg_i, mesh_i, self._make_pt(cfg_i,
                                                              mesh_i))
        return self._surfaces[i]

    @property
    def cfg(self):
        return self.surface(self.index)[0]

    @property
    def mesh(self):
        return self.surface(self.index)[1]

    @property
    def pt(self):
        return self.surface(self.index)[2]

    @property
    def device_count(self) -> int:
        """Devices on the ACTIVE topology — the CounterSnapshot
        `live_topology` value the flight recorder stamps on records."""
        return self._counts[self.index]

    def tag(self, i: Optional[int] = None) -> str:
        """`t<data>x<model>` — the warmup-plan suffix and the
        `elastic/live_target_mesh` event value for topology i."""
        i = self.index if i is None else i
        cfg_i = self.surface(i)[0]
        n = self._counts[i]
        model = cfg_i.mesh.model
        return f"t{n // model}x{model}"

    # -- switching -----------------------------------------------------------

    def target_index(self, verdict: int) -> Optional[int]:
        """Which topology a verdict asks for, or None when already there
        (a grow notice on the full mesh, a second shrink on the submesh —
        consume without switching)."""
        if verdict == NOTICE_SHRINK:
            want = min(range(2), key=lambda i: self._counts[i])
        elif verdict == NOTICE_GROW:
            want = max(range(2), key=lambda i: self._counts[i])
        else:
            return None
        return None if want == self.index else want

    def switch(self, state: Pytree, verdict: int) -> Pytree:
        """Move the LIVE state onto the verdict's topology: host-stage the
        full arrays (`jax.device_get` — single-controller, every shard is
        addressable; ZeRO-2/3 resident shards gather here) and re-scatter
        them per the target surface's shardings via the elastic host path.
        The caller has already drained the GD pipeline and services and
        flushed lag-by-one metrics; it re-snapshots rollback and re-arms
        the timers after. Times itself into `last_switch_ms` (the trainer
        adds drain/re-arm time on top for the event row)."""
        import jax

        from dcgan_tpu.elastic.reshard import put_host_tree
        from dcgan_tpu.train import warmup

        target = self.target_index(verdict)
        if target is None:
            return state
        t0 = time.perf_counter()
        _cfg_t, _mesh_t, pt_t = self.surface(target)
        # the target-sharded template: eval_shape only — nothing allocates
        template = warmup.state_example(pt_t)
        moved = put_host_tree(jax.device_get(state), template)
        self.index = target
        self.switches += 1
        self.last_switch_ms = (time.perf_counter() - t0) * 1e3
        return moved

    # -- warmup + priming ----------------------------------------------------

    def build_warmup_plan(self, state: Pytree, *, sample_z=None,
                          sample_labels=None
                          ) -> List[Tuple[str, Callable, tuple]]:
        """Every program BOTH topologies can dispatch, as warmup-plan rows;
        the launch topology's rows keep their plain names (existing
        per-program perf/compile_ms keys and coverage pins read
        unchanged), the target's are suffixed `@t<data>x<model>`. The
        non-current topology lowers against eval_shape templates and
        target-sharded ShapeDtypeStructs — nothing allocates there."""
        import jax
        import jax.numpy as jnp

        from dcgan_tpu.parallel import batch_sharding
        from dcgan_tpu.train import warmup

        plan: List[Tuple[str, Callable, tuple]] = []
        for i in range(2):
            cfg_i, mesh_i, pt_i = self.surface(i)
            if i == self.index:
                st = state
                z = sample_z
                lbl = sample_labels
                eval_z = jnp.resize(
                    jnp.zeros((1, cfg_i.model.z_dim), jnp.float32),
                    (cfg_i.batch_size, cfg_i.model.z_dim)) \
                    if cfg_i.sample_every_steps else None
            else:
                st = warmup.state_example(pt_i)
                z = None if sample_z is None else jax.ShapeDtypeStruct(
                    tuple(sample_z.shape), jnp.float32,
                    sharding=batch_sharding(mesh_i, 2))
                lbl = None if sample_labels is None \
                    else jax.ShapeDtypeStruct(
                        tuple(sample_labels.shape), sample_labels.dtype,
                        sharding=batch_sharding(mesh_i, 1))
                eval_z = jax.ShapeDtypeStruct(
                    (cfg_i.batch_size, cfg_i.model.z_dim), jnp.float32,
                    sharding=batch_sharding(mesh_i, 2)) \
                    if cfg_i.sample_every_steps else None
            rows, _bk = warmup.build_warmup_plan(
                cfg_i, pt_i, st,
                sample_z=z if cfg_i.sample_every_steps else None,
                sample_labels=lbl, eval_z=eval_z,
                make_backoff_pt=None)
            rows = [("init", pt_i.programs["init"],
                     (jax.random.key(0),))] + list(rows)
            suffix = "" if i == self.index else f"@{self.tag(i)}"
            plan += [(n + suffix, f, a) for n, f, a in rows]
        return plan

    def prime(self, *, sample_z=None, sample_labels=None
              ) -> Dict[str, float]:
        """One throwaway dispatch per program per topology — the PR 9/14
        mechanism that makes zero-compile-requests-after-warmup LITERAL:
        an AOT-compiled program's first __call__ still re-traces and,
        with host-fed args, builds an input transfer program; priming
        absorbs both for the submesh too, so the live switch re-traces
        nothing. Returns {topology tag: prime_ms}. Dispatch-thread only
        (mesh programs)."""
        import jax

        from dcgan_tpu.train.rollback import device_copy

        timings: Dict[str, float] = {}
        for i in range(2):
            t0 = time.perf_counter()
            cfg_i, mesh_i, pt_i = self.surface(i)
            key = jax.random.key(0)
            st = pt_i.init(jax.random.fold_in(key, 7))
            imgs = _zero_images(cfg_i, mesh_i)
            lbls = ()
            if cfg_i.model.num_classes:
                lbls = (_zero_labels(cfg_i, mesh_i),)
            if cfg_i.pipeline_gd:
                fakes = pt_i.gen_fakes(st, key)
                st, m = pt_i.d_update(st, imgs, fakes, key)
                st, _fakes, m = pt_i.g_update(st, key)
            else:
                st, m = pt_i.step(st, imgs, key, *lbls)
            k = cfg_i.steps_per_call
            if k > 1:
                import jax.numpy as jnp

                keys = jax.vmap(jax.random.fold_in, (None, 0))(
                    key, jnp.arange(k))
                imgs_k = jnp.broadcast_to(imgs, (k,) + imgs.shape)
                lbls_k = tuple(jnp.broadcast_to(x, (k,) + x.shape)
                               for x in lbls)
                st, m = pt_i.multi_step(st, imgs_k, keys, *lbls_k)
            if cfg_i.sample_every_steps and sample_z is not None:
                z_i = _zero_z(tuple(sample_z.shape), mesh_i)
                s_lbls = ()
                if sample_labels is not None:
                    s_lbls = (_zero_labels_like(sample_labels, mesh_i),)
                pt_i.sample(st, z_i, *s_lbls)
                import jax.numpy as jnp

                eval_z = jnp.resize(jnp.zeros_like(z_i[:1]),
                                    (cfg_i.batch_size, cfg_i.model.z_dim))
                pt_i.eval_losses(st, imgs, eval_z, *lbls)
            if cfg_i.activation_summary_steps:
                pt_i.summarize(st, imgs, key, *lbls)
            # identity-copy signatures the run dispatches later on this
            # topology: the post-switch rollback snapshot (full state) and
            # the histogram snapshot (params subtree)
            st = device_copy(st)
            device_copy(st["params"])
            jax.block_until_ready(jax.tree_util.tree_leaves(m))
            del st
            timings[self.tag(i)] = (time.perf_counter() - t0) * 1e3
        self.primed = True
        return timings


def _image_sds(cfg, mesh):
    import jax
    import jax.numpy as jnp

    from dcgan_tpu.parallel import batch_sharding

    size = cfg.model.output_size
    return jax.ShapeDtypeStruct(
        (cfg.batch_size, size, size, cfg.model.c_dim), jnp.float32,
        sharding=batch_sharding(mesh, 4, spatial=cfg.mesh.spatial))


def _zero_images(cfg, mesh):
    """All-zero image batch with the topology's live sharding, assembled
    per-shard (each device uploads only its slice)."""
    import jax
    import numpy as np

    sds = _image_sds(cfg, mesh)
    return jax.make_array_from_callback(
        sds.shape, sds.sharding,
        lambda idx: np.zeros([len(range(*s.indices(sds.shape[d])))
                              for d, s in enumerate(idx)], np.float32))


def _zero_z(shape, mesh):
    import jax
    import numpy as np

    from dcgan_tpu.parallel import batch_sharding

    sh = batch_sharding(mesh, len(shape))
    return jax.make_array_from_callback(
        tuple(shape), sh,
        lambda idx: np.zeros([len(range(*s.indices(shape[d])))
                              for d, s in enumerate(idx)], np.float32))


def _zero_labels(cfg, mesh):
    import jax
    import numpy as np

    from dcgan_tpu.parallel import batch_sharding

    sh = batch_sharding(mesh, 1)
    return jax.make_array_from_callback(
        (cfg.batch_size,), sh,
        lambda idx: np.zeros(
            len(range(*idx[0].indices(cfg.batch_size))), np.int32))


def _zero_labels_like(labels, mesh):
    import jax
    import numpy as np

    from dcgan_tpu.parallel import batch_sharding

    n = int(labels.shape[0])
    sh = batch_sharding(mesh, 1)
    return jax.make_array_from_callback(
        (n,), sh,
        lambda idx: np.zeros(len(range(*idx[0].indices(n))),
                             np.asarray(labels).dtype))
