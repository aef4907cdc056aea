"""Explicit-collective training backend: shard_map + psum/pmean by hand.

The default backend (parallel/api.py) states shardings and lets GSPMD insert
the collectives. This one is the other idiom: shard_map — via
`utils/backend.shard_map`, the one call site (DCG003) — gives each device its
per-shard program and the cross-replica communication is written
out explicitly — `lax.pmean` over the "data" axis for gradients, losses, and
BatchNorm moments (train/steps.py and ops/norm.py take `axis_name` for exactly
this path). Same synchronous-SPMD semantics, same ICI collectives on TPU; what
changes is who writes them.

Two reasons this backend exists beyond idiom parity:

1. **Per-shard Pallas kernels.** `pallas_call` is opaque to the GSPMD
   partitioner. Inside shard_map there is no partitioner: each device runs
   the flash attention kernels (`ModelConfig.use_pallas` with `attn_res`) on
   its local batch shard, at any device count. (The default backend reaches
   the same by nesting a shard_map around attention, parallel/api.py.)
2. **A second, independently-testable implementation** of the communication
   pattern that replaced the reference's gRPC parameter-server traffic
   (image_train.py:55-67): tests assert the two backends agree, which checks
   the collective placement in both.

Scope: data parallelism only (mesh model axis must be 1 — tensor/spatial
parallelism live in the GSPMD backend, where the partitioner earns its keep).

Because every collective here is hand-written, this backend is the census
surface of the semantic analyzer (DCG008, ISSUE 11): the per-program
psum/all_gather counts in `analysis/programs.lock.jsonl` are counted from
THESE programs' jaxprs (the GSPMD backend's collectives are
partitioner-inserted and census 0 explicit). Changing the collective
pattern — a new pmean, a gather moved — is a manifest change: regenerate
with `python -m dcgan_tpu.analysis --semantic --write-manifest` and
review the census diff, or tier-1 fails on unexplained drift.

Per-shard randomness: the step key is folded with `lax.axis_index("data")`, so
each shard draws an independent z sub-batch — the same global semantics as the
GSPMD backend's single partitioned `jax.random.uniform`, though not the same
bits (the equivalence tests pin down what must match exactly: real-batch loss,
synced-BN statistics, and cross-shard parameter consistency).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dcgan_tpu.config import TrainConfig
from dcgan_tpu.parallel.api import ParallelTrain, make_multi_step_body
from dcgan_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh
from dcgan_tpu.parallel.sharding import replicated
from dcgan_tpu.train.steps import make_train_step


def make_shard_map_train(cfg: TrainConfig,
                         mesh: Optional[Mesh] = None) -> ParallelTrain:
    """Build a ParallelTrain whose step/sample are shard_map programs with
    hand-written collectives. Drop-in for make_parallel_train (same surface).
    """
    mesh = mesh or make_mesh(cfg.mesh)
    if mesh.shape[MODEL_AXIS] != 1:
        raise ValueError(
            "the shard_map backend is data-parallel only; got model axis "
            f"{mesh.shape[MODEL_AXIS]} (use the default GSPMD backend for "
            "tensor/spatial parallelism)")
    n_shards = mesh.shape[DATA_AXIS]
    if cfg.batch_size % n_shards:
        raise ValueError(
            f"global batch {cfg.batch_size} must divide over "
            f"{n_shards} data shards")
    if cfg.grad_accum > 1 and (cfg.batch_size // cfg.grad_accum) % n_shards:
        # inside shard_map the accumulation reshape is per-device, so each
        # device's local batch must itself split into grad_accum microbatches
        raise ValueError(
            f"microbatch {cfg.batch_size // cfg.grad_accum} "
            f"(batch_size/grad_accum) must divide over {n_shards} data "
            "shards")

    # --- ZeRO-2/3 hooks (ISSUE 13): the EXPLICIT form of what the gspmd
    # backend states as sharding constraints. Gradient trees leave the
    # per-shard bodies through `lax.psum_scatter` (each replica keeps the
    # summed 1/N slice of every leaf the rule engine's zero policy shards
    # — the SAME dims the NamedSharding derivation below stores mu/nu on),
    # the Adam update runs on those slices, and `lax.all_gather` rebuilds
    # full trees exactly where the stage needs them: the updates once per
    # update at stage 2, the params just-in-time per forward at stage 3.
    # Leaves the policy leaves replicated keep their pmean.
    zero = cfg.mesh.zero_stage
    zero_hooks = None
    state_shapes = None
    if zero >= 2:
        from dcgan_tpu.elastic import rules as _rules
        from dcgan_tpu.train.steps import ZeroHooks, init_train_state

        state_shapes = jax.eval_shape(
            lambda k: init_train_state(k, cfg), jax.random.key(0))
        mesh_shape = dict(mesh.shape)
        _rules.validate_zero_state(state_shapes, mesh_shape,
                                   zero_stage=zero)
        dims = {net: _rules.zero_scatter_dims(state_shapes["params"][net],
                                              mesh_shape)
                for net in ("gen", "disc")}

        def _scatter_mean(x, d):
            # psum_scatter sums; /n makes it the pmean the replicated
            # leaves keep — both are sum-then-divide, so a sharded and a
            # replicated leaf see identical reduction arithmetic
            if d < 0:
                return lax.pmean(x, DATA_AXIS)
            return lax.psum_scatter(x, DATA_AXIS, scatter_dimension=d,
                                    tiled=True) / n_shards

        def _gather(x, d):
            return x if d < 0 else lax.all_gather(x, DATA_AXIS, axis=d,
                                                  tiled=True)

        def _map(fn, tree, net):
            return jax.tree_util.tree_map(fn, tree, dims[net])

        reduce_grads = lambda g, net: _map(_scatter_mean, g, net)
        gather_updates = ((lambda u, net: _map(_gather, u, net))
                          if zero == 2 else (lambda u, net: u))
        gather_params = ((lambda p, net: _map(_gather, p, net))
                         if zero >= 3 else (lambda p, net: p))

        if cfg.comm_overlap != "off":
            # Collective overlap plane (ISSUE 20, DESIGN §6n): same math,
            # restructured wire plan. reduce_grads/gather_updates swap
            # their per-leaf collectives for one large collective per
            # dtype-grouped bucket (the plan comes from the SAME rule
            # table that placed the shards, so layouts cannot disagree);
            # each bucket's psum_scatter depends only on its own leaves'
            # cotangents, which is what lets the scheduler issue it while
            # the rest of the backward is still running. Under "prefetch"
            # (stage 3) the up-front full-tree param gather additionally
            # becomes a layer-ahead staged walk. All arms are bit-exact
            # vs "off" (tests/test_comm_overlap.py pins params to the
            # last bit); the @overlap manifest rows pin the shrunken
            # census.
            from dcgan_tpu.parallel import comm as _comm

            plans = {net: _rules.zero_bucket_plan(
                         state_shapes["params"][net], mesh_shape,
                         bucket_mb=cfg.comm_bucket_mb)
                     for net in ("gen", "disc")}
            reduce_grads = lambda g, net: _comm.bucketed_reduce(
                g, dims[net], plans[net], axis_name=DATA_AXIS,
                n_shards=n_shards)
            if zero == 2:
                gather_updates = lambda u, net: _comm.bucketed_gather(
                    u, dims[net], plans[net], axis_name=DATA_AXIS,
                    n_shards=n_shards)
            if zero >= 3 and cfg.comm_overlap == "prefetch":
                gather_params = lambda p, net: _comm.staged_gather(
                    p, lambda nm, _p=p, _net=net: jax.tree_util.tree_map(
                        _gather, _p[nm], dims[_net][nm]))

        zero_hooks = ZeroHooks(reduce_grads=reduce_grads,
                               gather_updates=gather_updates,
                               gather_params=gather_params)

    fns = make_train_step(cfg, axis_name=DATA_AXIS,
                          # the pipelined stages' generator batches are
                          # per-shard inside shard_map (the fused step
                          # derives shapes from its sharded images arg;
                          # these stages have no images arg to read)
                          local_batch=cfg.batch_size // n_shards,
                          zero_hooks=zero_hooks)
    conditional = cfg.model.num_classes > 0
    # The varying-manner checker needs `vma` annotations on every
    # ShapeDtypeStruct a pallas_call emits, which the flash kernels (written
    # to be backend-agnostic) don't carry — turn static checking off where
    # they run; the collective placement is the same either way and is
    # covered by the equivalence tests. ZeRO >= 2 likewise runs unchecked:
    # this container's check_rep tracker has no rule marking tiled
    # psum_scatter/all_gather chains replication-consistent with the
    # sharded out_specs below, and the placement is pinned by the stage
    # 1/2/3 loss-parity tests instead.
    flash = cfg.model.use_pallas and cfg.model.attn_res
    vma = not flash and zero < 2

    def smap(f, in_specs, out_specs):
        from dcgan_tpu.utils.backend import shard_map

        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check=vma)

    rep = replicated(mesh)
    img_spec = P(DATA_AXIS, None, None, None)
    z_spec = P(DATA_AXIS, None)
    lbl_spec = P(DATA_AXIS)
    # state placement: fully replicated at stage 1 (the pre-ZeRO layout,
    # byte-exact — `st` stays the P() prefix the committed fingerprints
    # were traced with); the rule engine's data-sharded tree at stage >= 2,
    # so the per-shard bodies receive local slices of every zero-sharded
    # leaf — exactly what the explicit psum_scatter/all_gather hooks above
    # produce and consume
    if zero >= 2:
        from dcgan_tpu.parallel.sharding import state_shardings

        shardings = state_shardings(state_shapes, mesh, zero_stage=zero)
        st = jax.tree_util.tree_map(lambda s: s.spec, shardings)
    else:
        shardings = None  # derived at the bottom, as before
        st = P()

    def step_body(state, images, key, labels=None):
        # independent z / gradient-penalty draws per shard
        key = jax.random.fold_in(key, lax.axis_index(DATA_AXIS))
        return fns.train_step(state, images, key, labels)

    def sample_body(state, z, labels=None):
        # Gather the shard outputs so sample() returns a replicated array —
        # the ParallelTrain contract ("replicated output for host saving"):
        # on multi-host runs a data-sharded result would not be fully
        # addressable and the trainer's device_get of the grid would fail.
        # Expressed as scatter-into-zeros + psum rather than all_gather
        # because psum's output is statically replicated for the VMA checker
        # (all_gather results are formally still device-varying).
        imgs = fns.sample(state, z, labels)
        per_shard = imgs.shape[0]
        full = jnp.zeros((per_shard * n_shards,) + imgs.shape[1:],
                         imgs.dtype)
        full = lax.dynamic_update_slice_in_dim(
            full, imgs, lax.axis_index(DATA_AXIS) * per_shard, axis=0)
        return lax.psum(full, DATA_AXIS)

    def summarize_body(state, images, key, labels=None):
        # fold like step_body: each shard's generator activations come from
        # an independent z sub-batch, matching the GSPMD backend's single
        # global draw (without folding, all shards would histogram the same
        # batch/n_shards z vectors n_shards times over)
        key = jax.random.fold_in(key, lax.axis_index(DATA_AXIS))
        return fns.summarize(state, images, key, labels)

    if conditional:
        step = jax.jit(
            smap(step_body, (st, img_spec, P(), lbl_spec), (st, P())),
            donate_argnums=(0,))
        sample = jax.jit(
            smap(sample_body, (st, z_spec, lbl_spec), P()))
        # summarize: activation_stats pmaxes min/max before binning and psums
        # the counts (utils/metrics.py), so the per-shard programs emit
        # identical global histograms — replicated outputs.
        summarize = jax.jit(
            smap(summarize_body, (st, img_spec, P(), lbl_spec), P()))
        # eval_losses: per-shard losses pmean'd inside -> replicated metrics
        eval_losses = jax.jit(
            smap(fns.eval_losses, (st, img_spec, z_spec, lbl_spec), P()))
    else:
        step = jax.jit(
            smap(step_body, (st, img_spec, P()), (st, P())),
            donate_argnums=(0,))
        sample = jax.jit(
            smap(sample_body, (st, z_spec), P()))
        summarize = jax.jit(
            smap(summarize_body, (st, img_spec, P()), P()))
        eval_losses = jax.jit(
            smap(fns.eval_losses, (st, img_spec, z_spec), P()))

    # K steps in one per-shard program (see ParallelTrain.multi_step);
    # step_body folds the shard index into each key
    multi_body = make_multi_step_body(step_body)
    scan_img = P(None, *img_spec)
    if conditional:
        multi_step = jax.jit(
            smap(multi_body, (st, scan_img, P(), P(None, *lbl_spec)),
                 (st, P())),
            donate_argnums=(0,))
    else:
        multi_step = jax.jit(
            smap(multi_body, (st, scan_img, P()), (st, P())),
            donate_argnums=(0,))

    init = jax.jit(fns.init,
                   out_shardings=shardings if zero >= 2 else rep)

    # Pipelined stage programs (ISSUE 7): per-shard bodies with the same
    # shard-index key fold as step_body (independent z per shard); the
    # fake stack is batch-sharded on axis 1, slot axis unsharded —
    # exactly what the consuming d_update's fake_spec declares. Traced
    # lazily, so these cost nothing when --pipeline_gd is off.
    fake_spec = P(None, *img_spec)

    def gen_fakes_body(state, key):
        key = jax.random.fold_in(key, lax.axis_index(DATA_AXIS))
        return fns.gen_fakes(state, key)

    def d_update_body(state, images, fakes, key):
        key = jax.random.fold_in(key, lax.axis_index(DATA_AXIS))
        return fns.d_update(state, images, fakes, key)

    def g_update_body(state, key):
        key = jax.random.fold_in(key, lax.axis_index(DATA_AXIS))
        return fns.g_update(state, key)

    gen_fakes = jax.jit(smap(gen_fakes_body, (st, P()), fake_spec))
    d_update = jax.jit(
        # state-only donation: the consumed stack has no same-shaped
        # output to alias onto (see parallel/api.py) — the trainer's
        # buffer manager frees it by reference drop instead
        smap(d_update_body, (st, img_spec, fake_spec, P()), (st, P())),
        donate_argnums=(0,))
    g_update = jax.jit(
        smap(g_update_body, (st, P()), (st, fake_spec, P())),
        donate_argnums=(0,))

    if shardings is None:
        shardings = jax.tree_util.tree_map(
            lambda _: rep, jax.eval_shape(fns.init, jax.random.key(0)))
    return ParallelTrain(mesh=mesh, cfg=cfg, shardings=shardings,
                         init=init, step=step, sample=sample,
                         summarize=summarize, eval_losses=eval_losses,
                         multi_step=multi_step, gen_fakes=gen_fakes,
                         d_update=d_update, g_update=g_update)
