"""Per-phase training surfaces + cross-phase state carry (ISSUE 15).

`PhaseRuntime` owns the progressive run's compiled-surface table: one
`ParallelTrain` per schedule phase (built against the ONE shared mesh),
the per-phase AOT warmup plans whose rows join the trainer's plan under
`@r<resolution>` suffixes, the priming dispatches that make a mid-run
resolution switch dispatch only already-executed programs (zero compile
requests after warmup — the PR 9 serve-plane mechanism: an AOT-compiled
program's first __call__ still re-traces and, with host-fed args, builds
an input transfer program, so warmup runs ONE throwaway dispatch per
program per phase to absorb both), and the state carry that moves a live
train state across a model-surface change.

State carry rules (DESIGN.md §6j):

- Leaves are matched by PATH after a per-family rename, then guarded by
  SHAPE+DTYPE equality: a matched leaf with equal shape transfers, every
  other leaf keeps its fresh per-phase init.
- dcgan indexes generator stages from the TOP (deconv1 is the widest),
  so growing the stack by d stages renames old `deconv{i}` ->
  `deconv{i+d}` and `bn{i}` -> `bn{i+d}` (i >= 1) inside every
  gen-rooted subtree (params/bn/SN state, ema_gen, and the Adam moments
  that mirror them) — the whole old generator minus its z-side top
  (proj/bn0, which are new-at-this-phase) carries. The discriminator
  indexes from the INPUT, so its early convs carry under the identity
  map and only the new top conv + head init fresh.
- resnet/stylegan carry by plain name+shape matching (their per-stage
  trees don't index-shift the same way; whatever matches transfers).
- Carried leaves keep their device buffers when the old and new
  shardings are equivalent (the common case — one mesh, one rule table,
  same path+shape => same spec, so ZeRO-2/3 resident shards carry
  without movement); a spec change reshards through the elastic host
  path (`elastic/reshard.put_host_tree` per leaf).
"""

from __future__ import annotations

import re
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from dcgan_tpu.progressive.schedule import ProgressiveSchedule

Pytree = Any

#: gen-rooted path prefixes whose stage names index-shift when the dcgan
#: stack grows (the Adam moments mirror params/gen under opt/gen/...)
_GEN_ROOTS = ("params/gen/", "bn/gen/", "ema_gen/", "opt/gen/")

_GEN_STAGE_RE = re.compile(r"^(deconv|bn|sn_deconv)(\d+)$")


def _rename_gen_segment(seg: str, shift: int) -> Optional[str]:
    """dcgan generator stage rename old->new for a stack grown by `shift`
    stages; None = the old leaf has no home in the new tree (proj/bn0 —
    the z-side top is new at each phase)."""
    m = _GEN_STAGE_RE.match(seg)
    if m is None:
        return seg
    kind, idx = m.group(1), int(m.group(2))
    if kind == "bn" and idx == 0:
        return None  # the top BN is new-at-this-phase (top_ch changed)
    return f"{kind}{idx + shift}"


def carry_path(path: str, *, arch: str, shift: int) -> Optional[str]:
    """Where an OLD-phase leaf lands in the NEW tree (path string, "/"
    separated — elastic/rules.path_str form), or None when it has no
    home. Identity for non-dcgan families and for shift == 0."""
    if arch != "dcgan" or shift == 0 \
            or not path.startswith(_GEN_ROOTS):
        return path
    segs = path.split("/")
    out = []
    for seg in segs:
        if seg == "proj" and path.startswith(_GEN_ROOTS):
            return None  # z-side projection: shape follows top_ch, new
        new = _rename_gen_segment(seg, shift)
        if new is None:
            return None
        out.append(new)
    return "/".join(out)


def carry_state(old_state: Pytree, new_state: Pytree, *, arch: str,
                shift: int) -> Tuple[Pytree, int]:
    """Merge an old phase's live state into a fresh new-phase init.

    Returns (merged tree, carried-leaf count)."""
    import jax

    from dcgan_tpu.elastic.rules import path_str

    old_by_path: Dict[str, Any] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(old_state)[0]:
        new_home = carry_path(path_str(path), arch=arch, shift=shift)
        if new_home is not None:
            old_by_path[new_home] = leaf

    carried = 0

    def merge(path, fresh):
        nonlocal carried
        old = old_by_path.get(path_str(path))
        if old is None:
            return fresh
        if tuple(getattr(old, "shape", ())) \
                != tuple(getattr(fresh, "shape", ())) \
                or getattr(old, "dtype", None) != getattr(fresh, "dtype",
                                                          None):
            return fresh  # shape guard: a renamed leaf that no longer fits
        carried += 1
        old_sh = getattr(old, "sharding", None)
        new_sh = getattr(fresh, "sharding", None)
        if old_sh is None or new_sh is None \
                or old_sh.is_equivalent_to(new_sh, len(old.shape)):
            return old  # same placement: the live buffers carry verbatim
        # spec changed across phases (rare — one mesh, one rule table):
        # reshard through the elastic host path, per-shard upload
        from dcgan_tpu.elastic.reshard import put_host_tree

        return put_host_tree(jax.device_get(old), fresh)

    merged = jax.tree_util.tree_map_with_path(merge, new_state)
    return merged, carried


class PhaseRuntime:
    """The trainer's progressive-run companion: current phase index, the
    per-phase compiled surfaces, warmup/priming, and the switch's state
    carry. Built once after the mesh; `start()` picks the resume phase
    from the latest checkpoint step."""

    def __init__(self, cfg, mesh, schedule: ProgressiveSchedule,
                 total_steps: int,
                 make_pt: Optional[Callable] = None):
        self.base_cfg = cfg
        self.mesh = mesh
        self.schedule = schedule
        self.total_steps = int(total_steps)
        if make_pt is None:
            from dcgan_tpu.parallel import make_parallel_train

            make_pt = make_parallel_train
        self._make_pt = make_pt
        schedule.validate_mesh(dict(mesh.shape), spatial=cfg.mesh.spatial,
                               grad_accum=cfg.grad_accum)
        self.starts = schedule.starts(self.total_steps)
        # phases that actually run under this run length
        self.n_phases = sum(1 for s in self.starts
                            if s < self.total_steps) or 1
        self.index: int = 0
        self._surfaces: Dict[int, Tuple[Any, Any]] = {}  # i -> (cfg_i, pt_i)
        self._fade: Dict[int, Any] = {}
        self.primed = False
        self.last_switch_ms: float = 0.0
        self.last_carried: int = 0

    # -- per-phase surfaces --------------------------------------------------

    def phase_cfg(self, i: int):
        return self.surface(i)[0]

    def surface(self, i: int) -> Tuple[Any, Any]:
        """(phase TrainConfig, ParallelTrain) for phase i, built lazily
        and kept — the switch must swap to an already-built surface."""
        if i not in self._surfaces:
            cfg_i = self.schedule.config_for(self.base_cfg, i)
            self._surfaces[i] = (cfg_i, self._make_pt(cfg_i, self.mesh))
        return self._surfaces[i]

    @property
    def cfg(self):
        return self.surface(self.index)[0]

    @property
    def pt(self):
        return self.surface(self.index)[1]

    @property
    def resolution(self) -> int:
        return self.schedule.phases[self.index].resolution

    def tag(self) -> Dict[str, int]:
        """The sidecar phase tag (elastic/sidecar.py payload extension):
        which phase's tree a checkpoint carries."""
        return {"phase": int(self.index), "resolution": int(self.resolution)}

    # -- lifecycle -----------------------------------------------------------

    def start(self, latest_step: Optional[int]) -> int:
        """Pick the starting phase: 0 for a fresh run, else the phase that
        PRODUCED the latest checkpoint (its tree is the restore
        template; a boundary-step checkpoint resumes pre-switch and the
        loop switches immediately after restore)."""
        self.index = 0 if latest_step is None else min(
            self.schedule.index_for_state(int(latest_step),
                                          self.total_steps),
            self.n_phases - 1)
        self.surface(self.index)
        return self.index

    def check_resume_tag(self, payload_tag: Optional[dict],
                         latest_step: int) -> None:
        """Cross-check the checkpoint sidecar's phase tag against the
        schedule-derived resume phase — a schedule edited between runs
        must fail loudly here, not as an Orbax tree mismatch."""
        if not payload_tag:
            return
        saved = int(payload_tag.get("phase", -1))
        saved_res = int(payload_tag.get("resolution", -1))
        if saved != self.index or saved_res != self.resolution:
            raise ValueError(
                f"checkpoint at step {latest_step} was saved in progressive "
                f"phase {saved} (r{saved_res}) but the current schedule "
                f"resolves that step to phase {self.index} "
                f"(r{self.resolution}) — the --progressive spec changed "
                "between runs; restore with the saving schedule or point at "
                "a fresh checkpoint_dir")

    def switch_due(self, step: int) -> bool:
        nxt = self.index + 1
        return nxt < self.n_phases and step >= self.starts[nxt]

    def advance(self, state: Pytree) -> Pytree:
        """The switch's state half: build/enter the next phase's surface
        and carry the live state across the model-surface growth. New
        leaves init fresh from the phase seed; carried leaves transfer
        (elastic reshard path when their spec moved). Times itself into
        `last_switch_ms` (the data/loader half is the trainer's —
        rebucket.py — and adds its own time on top)."""
        import jax

        t0 = time.perf_counter()
        old_cfg = self.cfg
        self.index += 1
        cfg_i, pt_i = self.surface(self.index)
        shift = cfg_i.model.num_up_layers - old_cfg.model.num_up_layers
        fresh = pt_i.init(jax.random.key(
            self.base_cfg.seed + 1000 + self.index))
        merged, carried = carry_state(
            state, fresh, arch=cfg_i.model.arch, shift=shift)
        self.last_carried = carried
        self.last_switch_ms = (time.perf_counter() - t0) * 1e3
        return merged

    # -- fade ----------------------------------------------------------------

    def alpha(self, step: int) -> float:
        return self.schedule.alpha_at(step, self.total_steps)

    def fade_program(self, i: Optional[int] = None):
        """The phase's jitted image-space fade blend
        `(images, alpha) -> images`: alpha * x + (1 - alpha) *
        up(down(x)) — D's real distribution ramps from
        previous-resolution content to full detail over the fade window
        (alpha is a traced f32 scalar, one compile per phase). Only built
        when the schedule fades."""
        i = self.index if i is None else i
        if i not in self._fade:
            self._fade[i] = _make_fade(self.surface(i)[0], self.mesh)
        return self._fade[i]

    def fade_images(self, images, step: int):
        """Apply the fade blend when inside a fade window; identity (no
        dispatch) otherwise."""
        a = self.alpha(step)
        if a >= 1.0:
            return images
        import numpy as np

        return self.fade_program()(images, np.float32(a))

    # -- scalar-row extras (event keys gated "progressive schedule") ---------

    def scalar_extras(self, step: int) -> Dict[str, float]:
        if len(self.schedule.phases) == 1:
            # a single-phase schedule IS the existing trainer (the parity
            # A/B pins its JSONL byte-identical) — no progressive keys
            return {}
        out = {
            "progressive/phase": float(self.index),
            "progressive/resolution": float(self.resolution),
        }
        if self.schedule.fade_steps:
            a = self.alpha(max(step - 1, 0))
            if a < 1.0:
                out["progressive/alpha"] = float(a)
        return out

    # -- warmup + priming ----------------------------------------------------

    def build_warmup_plan(self, state: Pytree, *, sample_z=None,
                          sample_labels=None
                          ) -> List[Tuple[str, Callable, tuple]]:
        """Every program every phase can dispatch, as warmup-plan rows
        suffixed `@r<resolution>` (the current phase's rows keep their
        plain names so the existing per-program perf/compile_ms keys and
        coverage pins read unchanged). `state` is the CURRENT phase's
        live/template state; other phases lower against eval_shape
        templates (warmup.state_example — nothing allocates)."""
        import jax
        import jax.numpy as jnp

        from dcgan_tpu.train import warmup

        plan: List[Tuple[str, Callable, tuple]] = []
        for i in range(self.n_phases):
            cfg_i, pt_i = self.surface(i)
            st = state if i == self.index else warmup.state_example(pt_i)
            eval_z = jnp.resize(
                jnp.zeros((1, cfg_i.model.z_dim), jnp.float32),
                (cfg_i.batch_size, cfg_i.model.z_dim)) \
                if cfg_i.sample_every_steps else None
            rows, _bk = warmup.build_warmup_plan(
                cfg_i, pt_i, st,
                sample_z=sample_z if cfg_i.sample_every_steps else None,
                sample_labels=sample_labels, eval_z=eval_z,
                make_backoff_pt=None)
            rows = [("init", pt_i.programs["init"],
                     (jax.random.key(0),))] + list(rows)
            if self.schedule.fade_steps and i > 0:
                img_sds = _image_sds(cfg_i, self.mesh)
                rows.append(("fade", self.fade_program(i),
                             (img_sds, jnp.float32(0.5))))
            suffix = "" if i == self.index \
                else f"@r{self.schedule.phases[i].resolution}"
            plan += [(n + suffix, f, a) for n, f, a in rows]
        return plan

    def prime(self, *, sample_z=None, sample_labels=None) -> Dict[str, float]:
        """One throwaway dispatch per program per phase, making
        zero-compile-requests-after-warmup LITERAL (the PR 9 serve-plane
        mechanism): the jit dispatch caches populate here — with the
        persistent cache active each priming compile deserializes the
        entry `aot_compile` just wrote — so a later phase switch (and the
        current phase's first live steps) re-trace nothing. Returns
        {phase label: prime_ms}. Dispatch-thread only (mesh programs)."""
        import jax
        import numpy as np

        from dcgan_tpu.train.rollback import device_copy

        timings: Dict[str, float] = {}
        for i in range(self.n_phases):
            t0 = time.perf_counter()
            cfg_i, pt_i = self.surface(i)
            key = jax.random.key(0)
            st = pt_i.init(jax.random.fold_in(key, 7))
            imgs = _zero_images(cfg_i, self.mesh)
            lbls = ()
            if cfg_i.model.num_classes:
                lbls = (_zero_labels(cfg_i, self.mesh),)
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
                s_lbls = (sample_labels,) if sample_labels is not None else ()
                pt_i.sample(st, sample_z, *s_lbls)
                import jax.numpy as jnp

                eval_z = jnp.resize(sample_z,
                                    (cfg_i.batch_size, cfg_i.model.z_dim))
                pt_i.eval_losses(st, imgs, eval_z, *lbls)
            if cfg_i.activation_summary_steps:
                pt_i.summarize(st, imgs, key, *lbls)
            # the identity-copy signatures the run dispatches later: the
            # rollback snapshot (full state) and the single-process
            # histogram snapshot (params subtree)
            st = device_copy(st)
            device_copy(st["params"])
            if self.schedule.fade_steps and i > 0:
                self.fade_program(i)(imgs, np.float32(0.5))
            # sync on whatever the last dispatch returned (the pipelined
            # branch's final metrics carry g_loss only)
            jax.block_until_ready(jax.tree_util.tree_leaves(m))
            del st
            timings[f"phase{i}@r{self.schedule.phases[i].resolution}"] = \
                (time.perf_counter() - t0) * 1e3
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
    """A concrete all-zero image batch with the phase's live sharding,
    assembled per-process (multi-host safe: each device uploads only its
    shard)."""
    import jax
    import numpy as np

    sds = _image_sds(cfg, mesh)
    return jax.make_array_from_callback(
        sds.shape, sds.sharding,
        lambda idx: np.zeros([len(range(*s.indices(sds.shape[d])))
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


def _make_fade(cfg, mesh):
    """The phase's fade-blend program: images -> alpha * images +
    (1 - alpha) * upsample(downsample(images)). Down is a 2x2 mean pool,
    up a nearest repeat — previous-resolution content at the phase's
    size. alpha is a traced f32 scalar argument (one compile covers the
    whole ramp). No donation (not in DONATED_PROGRAMS by design)."""
    import jax
    import jax.numpy as jnp

    from dcgan_tpu.parallel import batch_sharding
    from dcgan_tpu.parallel.sharding import replicated

    img_sh = batch_sharding(mesh, 4, spatial=cfg.mesh.spatial)

    def fade(images, alpha):
        b, h, w, c = images.shape
        low = images.reshape(b, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))
        up = jnp.repeat(jnp.repeat(low, 2, axis=1), 2, axis=2)
        return alpha * images + (1.0 - alpha) * up

    return jax.jit(fade, in_shardings=(img_sh, replicated(mesh)),
                   out_shardings=img_sh)
