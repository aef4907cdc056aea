"""Sharded train-step compilation: one jitted SPMD program over the mesh.

What the reference does per step — every worker pulls all weights from the PS
over gRPC, computes an independent update, and pushes it back (image_train.py:
55-67,156-158) — becomes a single compiled program: batch sharded over "data",
params laid out per the sharding rules, gradient all-reduce and synced-BN
moments lowered by GSPMD to ICI collectives, and the whole train state donated
so parameters update in place in HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
from jax.sharding import Mesh

from dcgan_tpu.config import TrainConfig, is_token_arch
from dcgan_tpu.parallel.mesh import make_mesh
from dcgan_tpu.parallel.sharding import (
    batch_sharding,
    replicated,
    state_shardings,
)
from dcgan_tpu.train.steps import make_train_step
# imported for its compile listeners: they must be in place before the
# first trace of `init` below (the `compile/*` records)
from dcgan_tpu.utils import profiling  # noqa: F401

Pytree = Any

#: `programs`-dict names whose state argument (argnum 0) is donated — in
#: BOTH backends, by construction. The semantic analyzer (DCG007) holds
#: this in both directions against the compiled executables: every donated
#: input of these programs must be realized as an `input_output_aliases`
#: pair (donated-but-unaliased is a silent copy), and no program OUTSIDE
#: this set may donate (an undeclared donor invalidates buffers a caller
#: still holds). Adding a donating program
#: means adding it here and regenerating analysis/programs.lock.jsonl.
DONATED_PROGRAMS = ("train_step", "multi_step", "d_update", "g_update")


@dataclasses.dataclass(frozen=True)
class ParallelTrain:
    """Compiled, mesh-sharded training surface.

    init(key) -> sharded state
    step(state, images, key)          (unconditional models)
    step(state, images, key, labels)  (conditional models)
    sample(state, z[, labels]) -> images (replicated output for host saving)

    A one-network likelihood family (a token arch, loss "lm") has `init`
    and `step(state, ids [B, S] int32, key) -> (state, scalar metrics)`
    and nothing else: `programs` holds "init" and "train_step" alone, and
    every other surface (`sample`, `summarize`, `eval_losses`, `multi_step`,
    `gen_fakes`, `d_update`, `g_update`) raises by its name when called.
    """
    mesh: Mesh
    cfg: TrainConfig
    shardings: Pytree
    init: Callable
    step: Callable
    sample: Optional[Callable] = None
    summarize: Optional[Callable] = None
                         # (state, images, key[, labels]) -> activation stats
    eval_losses: Optional[Callable] = None
                           # (state, images, z[, labels]) -> loss metrics
                           # on a held-out batch, no state update
    multi_step: Optional[Callable] = None
                           # (state, images [K,B,...], keys [K][, labels
                           # [K,B]]) -> (state, last step's metrics): K train
                           # steps as ONE compiled lax.scan program — one
                           # host dispatch instead of K (the host round-trip
                           # the reference paid per step, SURVEY.md §2.4 #10,
                           # amortized K-fold)
    # pipelined stage programs (ISSUE 7, --pipeline_gd; unconditional
    # models only — traced lazily, so merely building them for a
    # conditional config is harmless):
    gen_fakes: Optional[Callable] = None
                           # (state, key) -> [n_critic, B, H, W, C] fake
                           # stack — the fill/refill program
    d_update: Optional[Callable] = None
                           # (state, images, fakes, key) -> (state,
                           # metrics): critic update(s) consuming the
                           # provided stack (dead after this dispatch —
                           # the trainer's buffer manager drops it)
    g_update: Optional[Callable] = None
                           # (state, key) -> (state, fakes, metrics):
                           # generator update returning the next step's
                           # d_update input (staleness 1)
    programs: Dict[str, Callable] = dataclasses.field(default_factory=dict)
                           # the same jitted surfaces under stable names
                           # ("init", "train_step", "multi_step", "sampler",
                           # "summarize", "eval_losses", "gen_fakes",
                           # "d_update", "g_update") — the enumeration
                           # the AOT warmup phase (train/warmup.py) lowers
                           # and the per-program perf/compile_ms keys are
                           # reported under; derived from the fields in
                           # __post_init__ so the two backends cannot
                           # drift apart

    def __post_init__(self):
        # thread-discipline tripwire (ISSUE 8): under DCGAN_THREAD_CHECKS=1
        # every program dispatch asserts it runs on the dispatch thread —
        # wrapped BEFORE the programs dict is derived so both surfaces
        # agree; a no-op (nothing wrapped) when the tripwire is off. Both
        # backends construct ParallelTrain, so this one hook covers them.
        from dcgan_tpu.analysis import tripwire

        tripwire.wrap_parallel_train(self)
        if not self.programs:
            named = {
                "init": self.init, "train_step": self.step,
                "multi_step": self.multi_step, "sampler": self.sample,
                "summarize": self.summarize,
                "eval_losses": self.eval_losses,
                "gen_fakes": self.gen_fakes, "d_update": self.d_update,
                "g_update": self.g_update}
            object.__setattr__(self, "programs", {
                n: f for n, f in named.items() if f is not None})
        for field in ("sample", "summarize", "eval_losses", "multi_step",
                      "gen_fakes", "d_update", "g_update"):
            if getattr(self, field) is None:
                object.__setattr__(self, field, _refusal(field, self.cfg))


def _refusal(name: str, cfg: TrainConfig) -> Callable:
    def refuse(*args, **kwargs):
        raise NotImplementedError(
            f"ParallelTrain.{name}: a one-network likelihood family "
            f"(arch={cfg.model.arch!r}) has the programs 'init' and "
            f"'train_step' only")
    return refuse


def make_lm_parallel_train(cfg: TrainConfig, mesh: Mesh) -> ParallelTrain:
    """The likelihood step of a token arch over a data-parallel mesh:
    state replicated (the rule table; the mesh has no expert axis), id
    batches [B, S] sharded over "data", the state donated."""
    from dcgan_tpu.train.steps import make_lm_train_step

    if mesh.shape["model"] != 1:
        raise ValueError(
            f"arch={cfg.model.arch!r} runs over a data-parallel mesh, got "
            f"{dict(mesh.shape)}")
    if cfg.batch_size % mesh.shape["data"]:
        raise ValueError(
            f"batch_size {cfg.batch_size} must divide over the "
            f"{mesh.shape['data']}-way data axis")
    fns = make_lm_train_step(cfg, mesh=mesh)
    shardings = state_shardings(
        jax.eval_shape(fns.init, jax.random.key(0)), mesh)
    rep = replicated(mesh)
    return ParallelTrain(
        mesh=mesh, cfg=cfg, shardings=shardings,
        init=jax.jit(fns.init, out_shardings=shardings),
        step=jax.jit(fns.train_step,
                     in_shardings=(shardings, batch_sharding(mesh, 2), rep),
                     out_shardings=(shardings, rep), donate_argnums=(0,)))


def make_multi_step_body(step_fn: Callable) -> Callable:
    """K train steps as one lax.scan over `step_fn`, returning the final
    state and the LAST step's metrics. Shared by both backends so the scan
    carry/metrics semantics cannot diverge.

    Exception: the lazily-computed "r1" metric (TrainConfig.r1_interval > 1)
    reports the window MAX — the last step of a scan window is almost never
    an R1 on-step, so last-step reporting would chart the penalty as zeros.
    """
    def multi_body(state, images, keys, labels=None):
        def body(s, xs):
            if labels is None:
                img, key = xs
                return step_fn(s, img, key)
            img, key, lbl = xs
            return step_fn(s, img, key, lbl)
        xs = (images, keys) if labels is None else (images, keys, labels)
        state, ms = jax.lax.scan(body, state, xs)
        return state, {k: (v.max() if k == "r1" else v[-1])
                       for k, v in ms.items()}
    return multi_body


def make_parallel_train(cfg: TrainConfig,
                        mesh: Optional[Mesh] = None) -> ParallelTrain:
    if cfg.backend == "shard_map":
        from dcgan_tpu.parallel.shard_map_backend import make_shard_map_train

        return make_shard_map_train(cfg, mesh)
    mesh = mesh or make_mesh(cfg.mesh)
    if is_token_arch(cfg.model.arch):
        return make_lm_parallel_train(cfg, mesh)
    pallas_mesh = None
    if cfg.model.use_pallas and cfg.model.attn_res and mesh.size > 1 \
            and not cfg.mesh.spatial:
        # pallas_call is opaque to GSPMD: left alone, the partitioner would
        # gather the batch around every flash kernel. On a data-parallel
        # mesh the kernels run per batch shard in a shard_map nested in this
        # jit (ops/attention.py::attn_apply's pallas_mesh route). Under a
        # spatial mesh attention already runs in its own shard_map and
        # composes as ring x flash (attn_mesh below). Everything but
        # attention is XLA's and partitions as it always did.
        if mesh.shape["model"] > 1:
            raise ValueError(
                "use_pallas with attention under the gspmd backend needs a "
                "data-parallel or spatial mesh, got "
                f"mesh={dict(mesh.shape)} without spatial: the flash "
                "kernels' per-shard route splits the batch over 'data' "
                "alone and would run replicated over 'model'")
        pallas_mesh = mesh
    spatial = cfg.mesh.spatial
    img_sh = batch_sharding(mesh, 4, spatial=spatial)
    constrain_fake = None
    if spatial:
        # Pin generator outputs to the real-image sharding. Without this the
        # SPMD partitioner can leave the fake branch replicated over "model"
        # while the real branch is height-sharded, and its shared-conv-kernel
        # gradient comes out double-counted (~2x) — see make_train_step.
        constrain_fake = lambda x: jax.lax.with_sharding_constraint(x, img_sh)
    # Under a spatial mesh, attention blocks run as sequence-parallel ring
    # attention over the "model" axis (shard_map nested in the jitted step)
    # instead of letting the partitioner all-gather k/v (ops/attention.py).
    attn_mesh = mesh if (spatial and cfg.model.attn_res) else None
    rep = replicated(mesh)
    z_sh = batch_sharding(mesh, 2)
    lbl_sh = batch_sharding(mesh, 1)

    # scanned-batch shardings: step axis in front, batch sharded on axis 1
    def _scan_sh(base):
        return jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, *base.spec))

    constrain_micro = None
    if cfg.grad_accum > 1:
        # same hard requirement the shard_map backend enforces: a microbatch
        # that doesn't divide over the data axis would make GSPMD pad every
        # microbatch to uneven shards — a silent throughput loss, not an
        # error — so reject it here too
        n_data = mesh.shape["data"]
        if (cfg.batch_size // cfg.grad_accum) % n_data:
            raise ValueError(
                f"microbatch {cfg.batch_size // cfg.grad_accum} "
                f"(batch_size/grad_accum) must divide over the {n_data}-way "
                "data axis")
        # Pin the step's (grad_accum, micro, ...) input reshapes to
        # scan-axis-in-front shardings: left alone the partitioner may keep
        # the "data" sharding on the leading (scan) axis after the reshape,
        # which serializes the accumulation loop across the mesh. Rank
        # disambiguates the three step inputs (images 5d / z 3d / labels 2d).
        _micro_sh = {5: _scan_sh(img_sh), 3: _scan_sh(z_sh),
                     2: _scan_sh(lbl_sh)}

        def constrain_micro(x):
            sh = _micro_sh.get(x.ndim)
            return x if sh is None else \
                jax.lax.with_sharding_constraint(x, sh)

    # --- ZeRO-2/3 hooks (ISSUE 13, arXiv:2004.13336) ----------------------
    # Under zero_stage >= 2 the step's gradient/update/forward sites get
    # sharding constraints from the rule engine: grads constrained to the
    # data-sharded ZeRO specs (the partitioner lowers the cross-replica sum
    # as a reduce-scatter), the shard-local Adam updates constrained back
    # to the resident param layout (stage 2: ONE fused all-gather rebuilds
    # replicated params per update; stage 3: identity — params stay
    # resident sharded and forwards gather just in time via gather_params).
    # Under `--comm_overlap` (ISSUE 20, DESIGN §6n) these constraint hooks
    # are already the right shape: the partitioner owns collective
    # placement and combining here, so gspmd's half of the overlap plane
    # is the async-collective XLA scheduler flags the CLI arms before
    # backend init (parallel/comm.py::maybe_apply_xla_overlap_flags) —
    # the explicit bucket/prefetch restructuring lives in the shard_map
    # backend, whose hand-placed collectives the scheduler cannot move.
    zero = cfg.mesh.zero_stage
    zero_hooks = None
    shardings = None
    if zero >= 2:
        from dcgan_tpu.elastic import rules as _rules
        from dcgan_tpu.train.steps import ZeroHooks, init_train_state

        # one init trace + one residency derivation, shared with the jit
        # wiring below (fns.init is the same function, so the shape tree
        # is identical)
        state_shapes = jax.eval_shape(
            lambda k: init_train_state(k, cfg), jax.random.key(0))
        _rules.validate_zero_state(state_shapes, dict(mesh.shape),
                                   zero_stage=zero)
        wsc = jax.lax.with_sharding_constraint
        grad_sh = {net: _rules.grad_shardings(state_shapes["params"][net],
                                              mesh)
                   for net in ("gen", "disc")}
        shardings = state_shardings(state_shapes, mesh, spatial=spatial,
                                    shard_opt=cfg.mesh.shard_opt,
                                    zero_stage=zero)
        resident_sh = shardings["params"]

        def _pin(tree, sh_tree):
            return jax.tree_util.tree_map(lambda x, s: wsc(x, s),
                                          tree, sh_tree)

        if zero >= 3:
            # the stage-1 param layout: what a forward's just-in-time
            # gather rebuilds (stage 2 skips the gather — params are
            # already resident in this layout)
            base_sh = state_shardings(state_shapes, mesh, spatial=spatial,
                                      shard_opt=cfg.mesh.shard_opt
                                      )["params"]
            gather_params = lambda p, net: _pin(p, base_sh[net])
        else:
            gather_params = lambda p, net: p
        zero_hooks = ZeroHooks(
            reduce_grads=lambda g, net: _pin(g, grad_sh[net]),
            gather_updates=lambda u, net: _pin(u, resident_sh[net]),
            gather_params=gather_params)

    fns = make_train_step(cfg, constrain_fake=constrain_fake,
                          constrain_micro=constrain_micro,
                          attn_mesh=attn_mesh, pallas_mesh=pallas_mesh,
                          zero_hooks=zero_hooks)

    if shardings is None:
        state_shapes = jax.eval_shape(fns.init, jax.random.key(0))
        shardings = state_shardings(state_shapes, mesh, spatial=spatial,
                                    shard_opt=cfg.mesh.shard_opt)
    conditional = cfg.model.num_classes > 0

    init = jax.jit(fns.init, out_shardings=shardings)

    multi_body = make_multi_step_body(fns.train_step)

    if conditional:
        step = jax.jit(
            fns.train_step,
            in_shardings=(shardings, img_sh, rep, lbl_sh),
            out_shardings=(shardings, rep),
            donate_argnums=(0,))
        sample = jax.jit(
            fns.sample,
            in_shardings=(shardings, z_sh, lbl_sh),
            out_shardings=rep)
        summarize = jax.jit(
            fns.summarize,
            in_shardings=(shardings, img_sh, rep, lbl_sh),
            out_shardings=rep)
        eval_losses = jax.jit(
            fns.eval_losses,
            in_shardings=(shardings, img_sh, z_sh, lbl_sh),
            out_shardings=rep)
        multi_step = jax.jit(
            multi_body,
            in_shardings=(shardings, _scan_sh(img_sh), rep, _scan_sh(lbl_sh)),
            out_shardings=(shardings, rep),
            donate_argnums=(0,))
    else:
        step = jax.jit(
            fns.train_step,
            in_shardings=(shardings, img_sh, rep),
            out_shardings=(shardings, rep),
            donate_argnums=(0,))
        sample = jax.jit(
            fns.sample,
            in_shardings=(shardings, z_sh),
            out_shardings=rep)
        summarize = jax.jit(
            fns.summarize,
            in_shardings=(shardings, img_sh, rep),
            out_shardings=rep)
        eval_losses = jax.jit(
            fns.eval_losses,
            in_shardings=(shardings, img_sh, z_sh),
            out_shardings=rep)
        multi_step = jax.jit(
            multi_body,
            in_shardings=(shardings, _scan_sh(img_sh), rep),
            out_shardings=(shardings, rep),
            donate_argnums=(0,))

    # Pipelined stage programs (ISSUE 7): the fake stack is image-shaped
    # with the n_critic slot axis in front — the same scan-axis-in-front
    # sharding the multi_step inputs use (batch sharded on axis 1, slot
    # axis unsharded). Only the state is donated: the consumed fake stack
    # is dead after the dispatch too, but d_update has no fake-shaped
    # output to alias it onto, so donating it would be a no-op plus a
    # donation warning per compile — the trainer's buffer manager frees
    # it by dropping its reference instead (gd_pipeline.py).
    fake_sh = _scan_sh(img_sh)
    gen_fakes = jax.jit(fns.gen_fakes,
                        in_shardings=(shardings, rep),
                        out_shardings=fake_sh)
    d_update = jax.jit(fns.d_update,
                       in_shardings=(shardings, img_sh, fake_sh, rep),
                       out_shardings=(shardings, rep),
                       donate_argnums=(0,))
    g_update = jax.jit(fns.g_update,
                       in_shardings=(shardings, rep),
                       out_shardings=(shardings, fake_sh, rep),
                       donate_argnums=(0,))

    return ParallelTrain(mesh=mesh, cfg=cfg, shardings=shardings,
                         init=init, step=step, sample=sample,
                         summarize=summarize, eval_losses=eval_losses,
                         multi_step=multi_step, gen_fakes=gen_fakes,
                         d_update=d_update, g_update=g_update)
