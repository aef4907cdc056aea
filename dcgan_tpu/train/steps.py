"""The GAN train step: two Adam optimizers, one compiled XLA program.

Reference semantics being replaced (image_train.py:109-112, 147-194): two
independent `AdamOptimizer(2e-4, β1=0.5).minimize` ops run in *one* sess.run on
the same batch, with numpy-fed z and a device→host→device image round-trip per
step (SURVEY.md §2.4 #2, #10). Here the whole step — z sampling, G forward,
D forward ×2, both backward passes, both Adam applies, BN EMA updates — is one
pure function built for `jax.jit(fn, donate_argnums=(0,))` (the trainer and
`__graft_entry__` compile it exactly that way): zero host round-trips, and z is
drawn on-device from a threaded PRNG key instead of `np.random.uniform` feeds
(image_train.py:151-152).

Two update modes (TrainConfig.update_mode):
- "sequential" (default): D updates on the current G, then G updates against the
  *updated* D — the canonical alternating GAN step the reference intended.
- "fused": both gradients are taken at the same (pre-update) params and both
  updates applied together — the reference's actual one-sess.run semantics,
  kept behind a flag for strict-parity experiments.

TrainConfig.n_critic > 1 (canonical WGAN-GP: 5) runs that many critic updates
per generator update as a lax.scan inside the same compiled program — fresh z
per critic iteration, same real batch, critic body compiled once.

Under jit-with-sharding (parallel/), gradient all-reduce and synced-BN moments
are inserted by GSPMD; for explicit-collective execution (shard_map) pass
`axis_name` and grads/metrics are pmean'd by hand. Both replace the reference's
per-worker async parameter-server pulls/pushes (image_train.py:55-67).

Pipelined stage split (ISSUE 7, ParaGAN's separable-stage framing): the same
step semantics factored into three independently-dispatchable programs —
`gen_fakes` (G forward producing a [n_critic, B, ...] fake stack, the fill/
refill program), `d_update` (the critic update(s) CONSUMING a provided fake
stack instead of regenerating it), and `g_update` (the generator update,
which RETURNS the fake stack it generated so the next step's `d_update` can
consume it at staleness 1). Per-step FLOPs are conservation-equal to the
fused program — every consumed fake is produced exactly once: the fused
step (n_critic = 1, no grad_accum) runs G's forward once, linearised, its
D step taking the fake batch from the forward its G step pulls the loss
gradient back through (DESIGN.md §6f). The split's wins are the largest
program's peak temp memory and the stage separation itself (cross-stage
placement/overlap substrate). The stage bodies reuse the exact loss/penalty/
accumulation code paths of the fused step (n_critic critic scan, grad_accum
microbatch scan), so the two surfaces cannot drift; only the fake batch's
PROVENANCE differs — fused regenerates per step, pipelined consumes the
stack produced during the previous step. The stack lives OUTSIDE the
checkpoint pytree (trainer-held device buffer): both modes save and restore
the identical state tree.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from dcgan_tpu.config import TrainConfig, is_token_arch
from dcgan_tpu.models.dcgan import (
    discriminator_apply,
    gan_init,
    generator_apply,
    sampler_apply,
)
from dcgan_tpu.train import losses as L

Pytree = Any


def make_lr_schedule(cfg: TrainConfig, base_lr: float, *,
                     updates_per_step: int = 1):
    """Learning-rate schedule as an update-count -> lr callable.

    "constant" is the reference's fixed 2e-4 (image_train.py:11); "linear"
    and "cosine" decay to 0 over max_steps, with an optional linear warmup.
    Always returned as a callable — even for constant — so the optimizer
    state carries its count in every configuration and the checkpoint tree
    shape never depends on the schedule flags.

    `updates_per_step`: optax advances the schedule once per opt.update()
    call, and the critic updates n_critic times per generator step — the
    discriminator's schedule horizon is stretched by that factor so both
    nets decay on the same *trainer-step* timeline.
    """
    warmup = cfg.warmup_steps * updates_per_step
    decay_steps = max(1, cfg.max_steps * updates_per_step - warmup)
    if cfg.lr_schedule == "constant":
        main = optax.constant_schedule(base_lr)
    elif cfg.lr_schedule == "linear":
        main = optax.linear_schedule(base_lr, 0.0, decay_steps)
    else:  # cosine
        main = optax.cosine_decay_schedule(base_lr, decay_steps)
    if warmup:
        ramp = optax.linear_schedule(0.0, base_lr, warmup)
        return optax.join_schedules([ramp, main], [warmup])
    return main


def make_optimizer(cfg: TrainConfig, lr: Optional[float] = None, *,
                   updates_per_step: int = 1) -> optax.GradientTransformation:
    """Adam(lr=2e-4, β1=0.5, β2=0.999, ε=1e-8) — the reference's optimizer
    (image_train.py:109-112; β2/ε are TF AdamOptimizer defaults). `lr`
    overrides the base rate (TTUR per-net rates); the schedule applies on
    top of whichever base is used."""
    base_lr = cfg.learning_rate if lr is None else lr
    # Reduced-precision ladder (ISSUE 17): under bf16 params the Adam
    # FIRST moment is kept as an f32 master copy (mu_dtype) — it is a small
    # signed running mean whose bf16 rounding visibly biases updates. nu
    # (second moment) follows the param dtype: it is a variance consumed
    # through sqrt, where bf16's ~3 significant digits are plenty. mu_dtype
    # changes leaf DTYPES only, never the optimizer tree SHAPE, so the
    # checkpoint-structure contract below survives the ladder, and the
    # rule-engine specs (elastic/rules.py) shard mu like any same-shaped
    # param leaf.
    mu_dtype = jnp.float32 if cfg.precision == "bf16" else None
    adam = optax.adam(make_lr_schedule(cfg, base_lr,
                                       updates_per_step=updates_per_step),
                      b1=cfg.beta1, b2=0.999, eps=1e-8, mu_dtype=mu_dtype)
    # ALWAYS a 2-element chain: identity and clip_by_global_norm both carry
    # EmptyState, so the optimizer-state tree (and therefore the checkpoint
    # structure) is identical whatever grad_clip is — a clipped run's
    # checkpoint restores under generate/evals configs that never heard of
    # the flag (the same shape-invariance contract as ema_gen and the lr
    # schedule's count, above).
    clip = optax.clip_by_global_norm(cfg.grad_clip) if cfg.grad_clip > 0 \
        else optax.identity()
    return optax.chain(clip, adam)


def init_train_state(key, cfg: TrainConfig) -> Pytree:
    """Build the full training state pytree.

    The checkpointed logical set matches the reference's Saver contents
    (SURVEY.md §5: G/D weights, BN β/γ + running stats, Adam moments, step),
    plus an EMA copy of the generator weights.
    """
    params, bn = gan_init(key, cfg.model)
    opt_g = make_optimizer(cfg, cfg.g_learning_rate)
    opt_d = make_optimizer(cfg, cfg.d_learning_rate,
                           updates_per_step=cfg.n_critic)
    # ema_gen is ALWAYS part of the state so the checkpoint tree structure is
    # independent of cfg.g_ema_decay — a checkpoint trained with EMA on
    # restores under an eval/generate/resume config with it off (and vice
    # versa). With decay=0 it is just a live mirror (one G-param-tree write
    # per step, negligible next to the step's compute).
    return {
        "params": params,
        "bn": bn,
        "opt": {
            "gen": opt_g.init(params["gen"]),
            "disc": opt_d.init(params["disc"]),
        },
        "ema_gen": jax.tree_util.tree_map(jnp.copy, params["gen"]),
        "step": jnp.zeros((), jnp.int32),
    }


@dataclasses.dataclass(frozen=True)
class ZeroHooks:
    """ZeRO-2/3 layout hooks (ISSUE 13, arXiv:2004.13336): the three points
    where state sharding changes the weight-update computation's layout,
    injected by the parallel backends so the step bodies stay
    layout-agnostic. Every callable takes (tree, net) with net in
    {"gen", "disc"} (the EMA mirror rides the "gen" specs). With no hooks
    (zero_stage=1, the default) the step is bit-identical to the pre-ZeRO
    program — the parity contract the committed jaxpr fingerprints pin.

    reduce_grads: full per-replica gradient tree -> the optimizer's input.
        Replaces the gradient `_pmean` at EVERY site: gspmd constrains the
        grads to the rule engine's ZeRO grad specs (the partitioner lowers
        the cross-replica sum as a reduce-scatter); shard_map writes the
        `lax.psum_scatter` mean per sharded leaf explicitly (pmean for
        leaves the policy leaves replicated). The result shards exactly
        like the mu/nu moments, so Adam runs shard-local.
    gather_updates: the shard-local Adam update tree -> the resident
        params' layout. Stage 2: the ONE fused all-gather per update that
        rebuilds replicated params; stage 3: identity (params stay
        resident sharded).
    gather_params: resident params -> the full view a forward/grad needs.
        Stage 3's just-in-time all-gather (gspmd: a replication
        constraint, shard_map: explicit `lax.all_gather`); identity at
        stage 2, where params are already full between steps.

    The collective overlap plane (ISSUE 20, DESIGN §6n) swaps hook
    BODIES, never the seam: under `--comm_overlap bucket` the shard_map
    backend's reduce_grads/gather_updates pack leaves into dtype-grouped
    flat buffers (parallel/comm.py) so each hook issues one collective
    per bucket instead of one per leaf — and because each bucket's
    psum_scatter depends only on its own leaves' cotangents, the
    scheduler issues it while the rest of the backward is still running,
    instead of after the full walk. Under `--comm_overlap prefetch`
    (stage 3) gather_params becomes a layer-ahead staged walk whose
    optimization_barrier chain lets layer i+1's gather overlap layer i's
    compute. The step bodies cannot tell: every arm is bit-exact vs
    "off", and "off" leaves the original per-leaf bodies byte-identical
    (parity-pinned).
    """
    reduce_grads: Callable
    gather_updates: Callable
    gather_params: Callable


@dataclasses.dataclass(frozen=True)
class TrainStepFns:
    """Bundle of the compiled-surface functions for one TrainConfig. A
    one-network likelihood family (`make_lm_train_step`) has `train_step`
    and `init` only: the sampler, the probes and the stage programs stay
    None, and `parallel/api.py` turns them into refusals by name."""
    train_step: Callable  # (state, images, key[, labels]) -> (state, metrics)
    init: Callable        # (key,) -> state
    sample: Optional[Callable] = None  # (state, z[, labels]) -> images
                          # (EMA-stat BN)
    summarize: Optional[Callable] = None
                          # (state, images, key[, labels]) -> per-layer
                          # activation histogram/sparsity stats (on device)
    eval_losses: Optional[Callable] = None
                           # (state, images, z[, labels]) -> loss metrics,
                           # no state update — the reference's sample-batch
                           # loss probe (image_train.py:179-192)
    # pipelined stage programs (ISSUE 7; unconditional models only — the
    # trainer's --pipeline_gd validation enforces that):
    gen_fakes: Optional[Callable] = None
                          # (state, key) -> [n_critic, B, H, W, C] fake
                          # stack — fresh z per critic slot, train-mode BN
                          # (updates discarded, like the fused D branch),
                          # constrain_fake applied. The FILL program: run
                          # start, restart, and rollback refill
    d_update: Optional[Callable] = None
                          # (state, images, fakes, key) -> (state, metrics):
                          # the critic update(s) consuming a provided fake
                          # stack; touches ONLY the disc half of the state
                          # (params/opt/bn.disc) — gen/ema_gen/step ride
                          # through untouched, so the tree shape is the
                          # fused step's exactly
    g_update: Optional[Callable] = None
                          # (state, key) -> (state, fakes, metrics): the
                          # generator update against the CURRENT D
                          # (sequential semantics — the trainer dispatches
                          # it after d_update), returning the fake stack it
                          # generated from its PRE-update weights as the
                          # next step's d_update input (staleness 1);
                          # increments state["step"]


def make_train_step(cfg: TrainConfig, *, axis_name: Optional[str] = None,
                    constrain_fake: Optional[Callable] = None,
                    constrain_micro: Optional[Callable] = None,
                    attn_mesh=None, pallas_mesh=None,
                    local_batch: Optional[int] = None,
                    zero_hooks: Optional[ZeroHooks] = None) -> TrainStepFns:
    """constrain_fake, if given, is applied to every generator output that is
    fed to the discriminator during training. The parallel layer passes a
    `with_sharding_constraint` to the real-image sharding here when the mesh
    spatially shards images: without it GSPMD is free to leave the fake branch
    replicated over the "model" axis while the real branch is height-sharded,
    and the partitioner then DOUBLE-COUNTS the fake branch's contribution to
    the shared conv-kernel gradients (observed ~2x grads on the 8-device CPU
    mesh; the constraint restores f64-level agreement — see
    tests/test_parallel.py::test_sharded_step_matches_single_device[dp4xsp2]).

    constrain_micro, if given, pins the (grad_accum, micro, ...) reshapes of
    the step inputs to scan-over-microbatches shardings (leading axis
    unsharded, batch sharded on axis 1) — without it the partitioner may
    shard the scan axis after the reshape, serializing the mesh.

    local_batch: the batch size the pipelined stage programs (gen_fakes /
    g_update, ISSUE 7) draw their z at. The fused step derives every batch
    shape from its `images` argument, but gen_fakes/g_update take no images
    — so the generator-side stages need the size stated. Defaults to
    cfg.batch_size (the global batch — correct under jit-with-sharding,
    where programs see global shapes); the shard_map backend passes its
    per-device batch instead, since each shard's program sees local shapes.

    zero_hooks (ISSUE 13): the ZeroHooks bundle a backend passes under
    zero_stage >= 2. None (the default) keeps every code path bit-identical
    to the pre-ZeRO step — the hooks' identity/default forms below ARE the
    original call sites, so the committed program fingerprints only move
    when the knob does.
    """
    mcfg = cfg.model
    opt_g = make_optimizer(cfg, cfg.g_learning_rate)   # TTUR-capable:
    opt_d = make_optimizer(cfg, cfg.d_learning_rate,   # per-net base rates
                           updates_per_step=cfg.n_critic)
    wgan = cfg.loss == "wgan-gp"
    r1 = cfg.r1_gamma > 0.0
    from dcgan_tpu.ops.augment import diff_augment, parse_policy
    aug_policy = parse_policy(cfg.diffaug)

    def _aug(x, key, idx):
        # DiffAugment on every D input; off (or the eval probe's aug-free
        # path, key=None) = identity. `idx` decorrelates the per-input
        # transform streams within one step — callers never fold keys
        # themselves, so a new call site cannot reuse a stream by accident.
        if not aug_policy or key is None:
            return x
        return diff_augment(x, jax.random.fold_in(key, idx), aug_policy)

    gan_losses = {
        "gan": functools.partial(L.bce_gan_losses,
                                 label_smoothing=cfg.label_smoothing),
        "wgan-gp": L.wgan_losses,
        "hinge": L.hinge_losses}[cfg.loss]
    _cf = constrain_fake if constrain_fake is not None else (lambda x: x)

    def _pmean(x):
        return lax.pmean(x, axis_name) if axis_name is not None else x

    # --- ZeRO layout hooks (ISSUE 13): resolved ONCE here so every
    # gradient/update/forward site below reads layout-agnostic names. The
    # defaults reproduce the pre-ZeRO program exactly: reduce_grads is the
    # gradient _pmean, the two gathers are python identity (same tracer
    # out, no jaxpr change).
    if zero_hooks is None:
        def _reduce_grads(g, net):
            return _pmean(g)

        def _gather_updates(u, net):
            return u

        def _gather_params(p, net):
            return p
    else:
        _reduce_grads = zero_hooks.reduce_grads
        _gather_updates = zero_hooks.gather_updates
        _gather_params = zero_hooks.gather_params

    def _opt_arg(p):
        # optax.update's `params` argument: our chain (clip + adam) never
        # reads it, but under ZeRO the grads are SHARDS while the resident
        # params may be full (stage 2) — pass None rather than a
        # shape-mismatched tree a future transform might consume
        return None if zero_hooks is not None else p

    # --- grad_accum microbatch helpers, shared by the fused accum step and
    # the pipelined stage bodies (ISSUE 7) so the accumulate-in-f32 /
    # average-then-pmean semantics are single-sourced ----------------------

    def _split_micro(x):
        """(B, ...) -> (grad_accum, micro, ...) with the scan-axis sharding
        constraint applied (see constrain_micro above)."""
        K = cfg.grad_accum
        out = x.reshape(K, x.shape[0] // K, *x.shape[1:])
        return constrain_micro(out) if constrain_micro is not None else out

    def _zeros_f32(tree):
        # accumulate in f32 whatever the param dtype: K bf16 adds would
        # round away low-magnitude contributions
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), tree)

    def _acc(acc, grads):
        return jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32), acc, grads)

    def _avg(acc, like, net):
        return _reduce_grads(jax.tree_util.tree_map(
            lambda a, p: (a / cfg.grad_accum).astype(p.dtype), acc, like),
            net)

    def _critic_streams(iter_key, batch):
        """Per-critic-iteration randomness: fresh z against the same real
        batch, the gradient-penalty key, and the DiffAugment key. One
        definition shared by the accum and non-accum critic loops so their
        training semantics cannot silently desynchronize."""
        zk, gpk = jax.random.split(iter_key)
        aug_k = jax.random.fold_in(iter_key, 3) if aug_policy else None
        z_i = jax.random.uniform(zk, (batch, mcfg.z_dim),
                                 minval=-1.0, maxval=1.0, dtype=jnp.float32)
        return z_i, gpk, aug_k

    def _zero_metric():
        # Under shard_map (axis_name set) the critic-scan metric carry must
        # be data-axis-VARYING to match the loop body's per-device metric
        # outputs — an unvarying f32 zero fails the scan's carry-type check
        # at trace time.
        z0 = jnp.zeros((), jnp.float32)
        return lax.pcast(z0, axis_name, to="varying") if axis_name else z0

    def _d_metrics(d_loss, d_real, d_fake, gp) -> dict:
        # the discriminator half of the step's metric row — the fused
        # assembly below and the pipelined d_update stage both build from
        # this, so the two surfaces report identical keys; the gp slot
        # carries whichever penalty the config runs (WGAN-GP or R1)
        metrics = {
            "d_loss": _pmean(d_loss),
            "d_loss_real": _pmean(d_real),
            "d_loss_fake": _pmean(d_fake),
        }
        if wgan:
            metrics["gp"] = _pmean(gp)
        elif r1:
            metrics["r1"] = _pmean(gp)
        return metrics

    def _loss_metrics(d_loss, d_real, d_fake, g_loss, gp) -> dict:
        # one assembly for train_step and eval_losses so the sample/* probe
        # can never silently diverge from the training metrics. (Key ORDER
        # is irrelevant: jitted outputs flatten through the dict pytree,
        # which sorts keys.)
        metrics = _d_metrics(d_loss, d_real, d_fake, gp)
        metrics["g_loss"] = _pmean(g_loss)
        return metrics

    def _generate(g_params: Pytree, g_bn: Pytree, z: jax.Array, labels
                  ) -> Tuple[jax.Array, Pytree]:
        """G's train-mode forward: (fake fed to D, new G BN/SN state)."""
        fake, g_bn = generator_apply(g_params, g_bn, z, cfg=mcfg,
                                     train=True, labels=labels,
                                     axis_name=axis_name, attn_mesh=attn_mesh,
                                     pallas_mesh=pallas_mesh)
        return _cf(fake), g_bn

    def d_loss_fn(d_params: Pytree, g_params: Pytree, bn: Pytree,
                  images: jax.Array, z: jax.Array, gp_key,
                  labels, step=0, r1_every_step=False,
                  aug_key=None) -> Tuple[jax.Array, Tuple]:
        fake, _ = _generate(g_params, bn["gen"], z, labels)
        return _d_loss_on_fake(d_params, bn, images, fake, gp_key, labels,
                               step, r1_every_step, aug_key)

    def _d_loss_on_fake(d_params: Pytree, bn: Pytree, images: jax.Array,
                        fake: jax.Array, gp_key, labels, step=0,
                        r1_every_step=False,
                        aug_key=None) -> Tuple[jax.Array, Tuple]:
        """The D loss on an ALREADY-MATERIALIZED fake batch — the shared
        body of d_loss_fn, the fused step (which takes `fake` from G's one
        linearised forward) and the pipelined d_update stage (which
        consumes the previous step's device-resident stack), so they can
        never diverge on loss, penalty, or BN-chaining semantics."""
        # D sees real then fake, chaining BN state through both applications —
        # the functional analogue of the reference's two discriminator() calls
        # with reuse=True (image_train.py:82,85). Each D input is
        # independently DiffAugmented when the policy is on.
        _, real_logits, d_bn1 = discriminator_apply(
            d_params, bn["disc"], _aug(images, aug_key, 0),
            cfg=mcfg, train=True, labels=labels,
            axis_name=axis_name, attn_mesh=attn_mesh, pallas_mesh=pallas_mesh)
        _, fake_logits, d_bn2 = discriminator_apply(
            d_params, d_bn1, _aug(fake, aug_key, 1),
            cfg=mcfg, train=True, labels=labels,
            axis_name=axis_name, attn_mesh=attn_mesh, pallas_mesh=pallas_mesh)
        d_loss, d_real, d_fake = gan_losses(real_logits, fake_logits)[:3]
        gp = jnp.zeros((), jnp.float32)
        if wgan or r1:
            # Penalty critic runs with train=False (running BN stats):
            # batch-stat BN couples D(x_i) to every x_j in the batch, which
            # would contaminate the per-example ||grad_x D(x)|| both
            # penalties are defined on. Penalties act on the RAW inputs —
            # the Lipschitz constraint lives in image space, not in
            # DiffAugment's transformed space.
            def critic(x):
                return discriminator_apply(
                    d_params, bn["disc"], x, cfg=mcfg, train=False,
                    labels=labels, axis_name=axis_name,
                    attn_mesh=attn_mesh, pallas_mesh=pallas_mesh)[1][:, 0]
            if wgan:
                gp = L.gradient_penalty(critic, images.astype(jnp.float32),
                                        fake.astype(jnp.float32), gp_key)
                d_loss = d_loss + cfg.gp_weight * gp
            elif cfg.r1_interval == 1 or r1_every_step:
                # R1: zero-centered penalty on reals only, every step.
                # r1_every_step is the eval probe's path: unscaled gamma, so
                # the held-out d_loss is comparable across r1_interval
                # settings (the lazy form's k-scaling is a training-schedule
                # artifact, not a different regularizer)
                gp = L.r1_penalty(critic, images.astype(jnp.float32))
                d_loss = d_loss + 0.5 * cfg.r1_gamma * gp
            else:
                # lazy regularization (StyleGAN2): the penalty (an extra D
                # forward + double backward) runs only on every k-th step —
                # lax.cond executes one branch — with gamma scaled by k so
                # the time-averaged pressure matches
                gp = lax.cond(
                    step % cfg.r1_interval == 0,
                    lambda _: L.r1_penalty(critic,
                                           images.astype(jnp.float32)),
                    lambda _: jnp.zeros((), jnp.float32), None)
                d_loss = d_loss + 0.5 * cfg.r1_gamma * cfg.r1_interval * gp
        return d_loss, (d_bn2, d_real, d_fake, gp)

    def _g_loss_on_fake(fake: jax.Array, d_params: Pytree, d_bn: Pytree,
                        labels, aug_key=None) -> jax.Array:
        """G's loss on an ALREADY-GENERATED fake batch — the shared body of
        g_loss_fn and the fused step (which differentiates it by `fake`
        alone and pulls that back through G's one linearised forward)."""
        # generator gradients flow THROUGH the augmentation — the property
        # DiffAugment needs (arXiv:2006.10738)
        _, fake_logits, _ = discriminator_apply(
            d_params, d_bn, _aug(fake, aug_key, 2), cfg=mcfg,
            train=True, labels=labels, axis_name=axis_name,
            attn_mesh=attn_mesh, pallas_mesh=pallas_mesh)
        # the family's own generator loss (4th return) — single-sourced with
        # the D-side dispatch; every family's g_loss depends only on the
        # fake logits, so the real-logits slot gets a dummy (its unused
        # d-side outputs are DCE'd by XLA). BCE: non-saturating generator
        # loss (image_train.py:96).
        return gan_losses(fake_logits, fake_logits)[3]

    def g_loss_fn(g_params: Pytree, d_params: Pytree, bn: Pytree,
                  z: jax.Array, labels, aug_key=None,
                  return_fake: bool = False) -> Tuple[jax.Array, Tuple]:
        fake, g_bn = _generate(g_params, bn["gen"], z, labels)
        g_loss = _g_loss_on_fake(fake, d_params, bn["disc"], labels, aug_key)
        # return_fake (pipelined g_update only): ride the already-computed
        # fake out through the aux so the stage can hand it to the NEXT
        # step's d_update — a Python-level branch, so the fused path's
        # jaxpr is untouched
        if return_fake:
            return g_loss, (g_bn, fake)
        return g_loss, (g_bn,)

    # Scopes a reader of a profile thinks in (ISSUE 24): every program
    # below runs its phases under `d_step` / `g_step`, inside each `loss`
    # (forward and, through the transpose's name stack, backward) and
    # `adam`, then `ema`; the nets add `gen` / `disc`, their stages and
    # `attn` / `bn` / `sn`. Metadata only: no operation changes.
    def _loss_grad(fn):
        vg = jax.value_and_grad(fn, has_aux=True)

        def call(*args, **kwargs):
            with jax.named_scope("loss"):
                return vg(*args, **kwargs)
        return call

    d_grad = _loss_grad(d_loss_fn)
    d_on_fake_grad = _loss_grad(_d_loss_on_fake)
    g_grad = _loss_grad(g_loss_fn)
    # by the fake batch alone; the caller sets the scope (G's pull-back
    # shares it)
    g_on_fake_grad = jax.value_and_grad(_g_loss_on_fake)

    def _adam(opt, grads, opt_state, params: Pytree, net: str):
        """One optimizer update from already reduced / averaged gradients:
        (new params, new optimizer state)."""
        with jax.named_scope("adam"):
            updates, opt_state = opt.update(grads, opt_state,
                                            _opt_arg(params))
            return (optax.apply_updates(params,
                                        _gather_updates(updates, net)),
                    opt_state)

    def _ema_update(state: Pytree, new_gen: Pytree) -> Pytree:
        d_ema = cfg.g_ema_decay  # 0 -> ema_gen mirrors the live weights
        with jax.named_scope("ema"):
            return jax.tree_util.tree_map(
                lambda e, p: d_ema * e + (1.0 - d_ema) * p,
                state["ema_gen"], new_gen)

    def _accum_train_step(state: Pytree, images: jax.Array, z: jax.Array,
                          gp_key, aug_key, labels) -> Tuple[Pytree, dict]:
        """grad_accum > 1: K scanned microbatches per optimizer update.

        Gradients for each net are taken at the same (pre-update) params
        on every microbatch and averaged — the full-batch mean gradient at
        one microbatch's activation memory. BN state chains through the
        microbatches exactly as it chains through consecutive steps; the
        single pmean/all-reduce per net happens on the AVERAGED gradient,
        so the collective cost per optimizer update is unchanged.

        With n_critic > 1 the accumulation nests inside the scanned critic
        loop: each critic iteration draws its own fresh full z batch
        (matching the non-accum loop's semantics), splits it into K
        microbatches, and applies one Adam update from the accumulated
        gradient — n_critic Adam applies per step, each from a K-microbatch
        mean, at one microbatch's activation memory throughout.
        """
        K = cfg.grad_accum
        params, bn = state["params"], state["bn"]
        # ZeRO-3: the resident (possibly data-sharded) trees stay the
        # update targets; forwards and grads run on the gathered full view
        gen_full = _gather_params(params["gen"], "gen")

        imgs_s = _split_micro(images)
        lbls_s = _split_micro(labels) if labels is not None else None

        def _micro_xs(z_full, gpk, augk):
            """One optimizer update's worth of per-microbatch scan inputs."""
            xs = {"img": imgs_s, "z": _split_micro(z_full),
                  "gpk": jax.random.split(gpk, K)}
            if lbls_s is not None:
                xs["lbl"] = lbls_s
            if augk is not None:
                xs["augk"] = jax.random.split(augk, K)
            return xs

        # --- D: each Adam apply from K accumulated microbatch grads ---------
        def d_accum_update(d_params, d_opt_state, bn_d_start, xs):
            """Scan K microbatches at fixed d_params, apply Adam once."""
            d_full = _gather_params(d_params, "disc")

            def d_micro(carry, x):
                g_acc, bn_d = carry
                bn_in = {"gen": bn["gen"], "disc": bn_d}
                (d_loss, (d_bn_i, d_real, d_fake, gp)), grads = d_grad(
                    d_full, gen_full, bn_in, x["img"], x["z"],
                    x["gpk"], x.get("lbl"), state["step"], False,
                    x.get("augk"))
                return ((_acc(g_acc, grads), d_bn_i),
                        (d_loss, d_real, d_fake, gp))

            (g_acc, bn_d), ms = lax.scan(
                d_micro, (_zeros_f32(d_full), bn_d_start), xs)
            d_params, d_opt_state = _adam(
                opt_d, _avg(g_acc, d_full, "disc"), d_opt_state, d_params,
                "disc")
            return (d_params, d_opt_state, bn_d,
                    tuple(m.mean() for m in ms))

        with jax.named_scope("d_step"):
            if cfg.n_critic == 1:
                new_disc, d_opt, d_bn, (d_loss, d_real, d_fake, gp) = \
                    d_accum_update(params["disc"], state["opt"]["disc"],
                                   bn["disc"],
                                   _micro_xs(z, gp_key, aug_key))
            else:
                # the non-accum critic loop's semantics (fresh full z per
                # iteration against the same real batch), each iteration's
                # update accumulated over K microbatches
                def critic_iter(carry, iter_key):
                    d_params_c, d_opt_c, d_bn_c, _ = carry
                    z_i, gpk, aug_k = _critic_streams(iter_key,
                                                      images.shape[0])
                    out = d_accum_update(d_params_c, d_opt_c, d_bn_c,
                                         _micro_xs(z_i, gpk, aug_k))
                    return out, None

                zero = _zero_metric()
                (new_disc, d_opt, d_bn,
                 (d_loss, d_real, d_fake, gp)), _ = lax.scan(
                    critic_iter,
                    (params["disc"], state["opt"]["disc"], bn["disc"],
                     (zero, zero, zero, zero)),
                    jax.random.split(gp_key, cfg.n_critic))

        if cfg.update_mode == "sequential":
            g_target_disc, disc_bn_for_g = \
                _gather_params(new_disc, "disc"), d_bn
        else:  # "fused": G grads at pre-update D params (reference parity)
            g_target_disc, disc_bn_for_g = \
                _gather_params(params["disc"], "disc"), bn["disc"]

        # --- G: same accumulation against the (possibly updated) D ----------
        # the top-level z/aug streams, like the non-accum G step (with
        # n_critic > 1 the critic iterations drew their own)
        g_xs = _micro_xs(z, gp_key, aug_key)

        def g_micro(carry, x):
            g_acc, bn_g = carry
            bn_in = {"gen": bn_g, "disc": disc_bn_for_g}
            (g_loss, (g_bn_i,)), grads = g_grad(
                gen_full, g_target_disc, bn_in, x["z"],
                x.get("lbl"), x.get("augk"))
            return (_acc(g_acc, grads), g_bn_i), g_loss

        with jax.named_scope("g_step"):
            (g_gacc, g_bn), g_losses = lax.scan(
                g_micro, (_zeros_f32(gen_full), bn["gen"]), g_xs)
            new_gen, g_opt = _adam(opt_g, _avg(g_gacc, gen_full, "gen"),
                                   state["opt"]["gen"], params["gen"],
                                   "gen")

        new_state = {
            "params": {"gen": new_gen, "disc": new_disc},
            "bn": {"gen": g_bn, "disc": d_bn},
            "opt": {"gen": g_opt, "disc": d_opt},
            "step": state["step"] + 1,
        }
        new_state["ema_gen"] = _ema_update(state, new_gen)
        # metrics: microbatch means (with n_critic > 1, the LAST critic
        # iteration's — matching the non-accum loop's last-iter reporting)
        return new_state, _loss_metrics(d_loss, d_real, d_fake,
                                        g_losses.mean(), gp)

    def train_step(state: Pytree, images: jax.Array, key: jax.Array,
                   labels: Optional[jax.Array] = None
                   ) -> Tuple[Pytree, dict]:
        # the 3-way split happens only when DiffAugment is on, so every
        # stream (z, gp) is bit-identical to reference-parity runs otherwise
        if aug_policy:
            z_key, gp_key, aug_key = jax.random.split(key, 3)
        else:
            z_key, gp_key = jax.random.split(key)
            aug_key = None
        z = jax.random.uniform(
            z_key, (images.shape[0], mcfg.z_dim),
            minval=-1.0, maxval=1.0, dtype=jnp.float32)

        if cfg.grad_accum > 1:
            return _accum_train_step(state, images, z, gp_key, aug_key,
                                     labels)

        params, bn = state["params"], state["bn"]
        # ZeRO-3: resident (possibly data-sharded) trees are the update
        # targets; forwards and grads run on the gathered full view
        gen_full = _gather_params(params["gen"], "gen")

        if cfg.n_critic == 1:
            # G's ONE forward, linearised: D's step takes its fake batch
            # from it and G's step pulls its loss gradient back through it.
            # G reads nothing of D, so the fake is the same whichever D the
            # G loss is taken against (either update_mode). It runs under
            # the G step's scopes, which own G's forward and backward.
            with jax.named_scope("g_step"), jax.named_scope("loss"):
                fake, g_vjp, g_bn = jax.vjp(
                    lambda gp: _generate(gp, bn["gen"], z, labels),
                    gen_full, has_aux=True)

        # --- D step(s) ------------------------------------------------------
        with jax.named_scope("d_step"):
            if cfg.n_critic == 1:
                (d_loss, (d_bn, d_real, d_fake, gp)), d_grads = \
                    d_on_fake_grad(
                        _gather_params(params["disc"], "disc"), bn, images,
                        fake, gp_key, labels, state["step"], False, aug_key)
                new_disc, d_opt = _adam(
                    opt_d, _reduce_grads(d_grads, "disc"),
                    state["opt"]["disc"], params["disc"], "disc")
            else:
                # n_critic > 1 (canonical WGAN-GP: 5) — scanned critic
                # updates inside the same compiled program. Each iteration
                # draws fresh z (and a fresh interpolation key) against the
                # same real batch; the loop is lax.scan so XLA compiles the
                # critic body once.
                def critic_iter(carry, iter_key):
                    d_params_c, d_opt_c, d_bn_c, _ = carry
                    z_i, gpk, aug_k = _critic_streams(iter_key,
                                                      images.shape[0])
                    bn_in = {"gen": bn["gen"], "disc": d_bn_c}
                    (loss_i, (bn_i, real_i, fake_i, gp_i)), grads = d_grad(
                        _gather_params(d_params_c, "disc"), gen_full,
                        bn_in, images, z_i, gpk,
                        labels, state["step"], False, aug_k)
                    d_params_c, d_opt_c = _adam(
                        opt_d, _reduce_grads(grads, "disc"), d_opt_c,
                        d_params_c, "disc")
                    # last iteration's metrics ride the carry; note they
                    # are evaluated at that iteration's PRE-update params
                    # (one Adam step stale relative to the critic G trains
                    # against)
                    return ((d_params_c, d_opt_c, bn_i,
                             (loss_i, real_i, fake_i, gp_i)), None)

                iter_keys = jax.random.split(gp_key, cfg.n_critic)
                zero = _zero_metric()
                (new_disc, d_opt, d_bn,
                 (d_loss, d_real, d_fake, gp)), _ = lax.scan(
                    critic_iter,
                    (params["disc"], state["opt"]["disc"], bn["disc"],
                     (zero, zero, zero, zero)),
                    iter_keys)

        if cfg.update_mode == "sequential":
            g_target_disc, disc_bn_for_g = \
                _gather_params(new_disc, "disc"), d_bn
        else:  # "fused": reference parity — G grads at pre-update D params
            g_target_disc, disc_bn_for_g = \
                _gather_params(params["disc"], "disc"), bn["disc"]

        # --- G step ---------------------------------------------------------
        with jax.named_scope("g_step"):
            if cfg.n_critic == 1:
                with jax.named_scope("loss"):
                    g_loss, fake_ct = g_on_fake_grad(
                        fake, g_target_disc, disc_bn_for_g, labels, aug_key)
                    g_grads, = g_vjp(fake_ct)
            else:
                (g_loss, (g_bn,)), g_grads = g_grad(
                    gen_full, g_target_disc,
                    {"gen": bn["gen"], "disc": disc_bn_for_g}, z, labels,
                    aug_key)
            new_gen, g_opt = _adam(
                opt_g, _reduce_grads(g_grads, "gen"), state["opt"]["gen"],
                params["gen"], "gen")

        new_state = {
            "params": {"gen": new_gen, "disc": new_disc},
            "bn": {"gen": g_bn, "disc": d_bn},
            "opt": {"gen": g_opt, "disc": d_opt},
            # Unlike the reference's global_step (G-updates only, SURVEY.md
            # §2.4 #3), this counts full D+G steps.
            "step": state["step"] + 1,
        }
        new_state["ema_gen"] = _ema_update(state, new_gen)
        return new_state, _loss_metrics(d_loss, d_real, d_fake, g_loss, gp)

    # --- pipelined stage programs (ISSUE 7) --------------------------------
    # The fused step factored into three independently-dispatchable
    # programs with IDENTICAL loss/penalty/accumulation code paths (every
    # loss goes through _d_loss_on_fake / g_loss_fn above — the stage
    # surfaces cannot drift from the fused ones). Unconditional models
    # only (labels=None throughout; TrainConfig validation enforces it),
    # sequential update_mode only (the trainer dispatches g_update after
    # d_update, so G trains against the updated critic — the fused
    # sequential ordering).

    stage_batch = local_batch if local_batch is not None else cfg.batch_size

    def _fake_stack(g_params: Pytree, g_bn: Pytree, key: jax.Array,
                    n: int) -> jax.Array:
        """[n, B, H, W, C] generator batches from FIXED (params, bn) —
        fresh z per slot (the fused critic loop's per-iteration z
        semantics via _critic_streams), train-mode BN with the updates
        discarded (the fused D branch's convention), constrain_fake
        applied. lax.scan so the body compiles once whatever n is."""
        def one(carry, iter_key):
            z_i, _, _ = _critic_streams(iter_key, stage_batch)
            return carry, _generate(g_params, g_bn, z_i, None)[0]
        keys = jax.random.split(key, n)
        if n == 1:
            # no 1-trip scan (see d_update: a single-iteration while loop
            # serializes the CPU backend)
            return one((), keys[0])[1][None]
        _, stack = lax.scan(one, (), keys)
        return stack

    # Every stage folds a stage-unique tag into the per-step key INSIDE
    # its traced body, so the three stage streams are independent while
    # the trainer hands all three the same key (the fused step splits its
    # single key inside its program the same way). Folding here instead
    # of in the dispatch loop matters: a host-side fold_in is a tiny
    # device program per call — three extra per-step dispatches that
    # stretch the pipelined span on dispatch-bound hosts.
    _D_TAG, _G_TAG, _FILL_TAG = 0, 1, 2

    def gen_fakes(state: Pytree, key: jax.Array) -> jax.Array:
        """The FILL program: an [n_critic, B, ...] fake stack from the
        CURRENT generator — dispatched at run start, after a restore, and
        after a rollback invalidated the in-flight buffer."""
        return _fake_stack(_gather_params(state["params"]["gen"], "gen"),
                           state["bn"]["gen"],
                           jax.random.fold_in(key, _FILL_TAG),
                           cfg.n_critic)

    @jax.named_scope("d_step")
    def d_update(state: Pytree, images: jax.Array, fakes: jax.Array,
                 key: jax.Array) -> Tuple[Pytree, dict]:
        """The critic update(s) CONSUMING a provided fake stack (slot i
        feeds critic iteration i) instead of regenerating it — the fake
        production moves to g_update (where the G-loss forward doubles as
        slot 0), which is what makes this program's peak temp memory the
        pipeline's headroom win and decouples D's fake source from G's z.
        Touches ONLY the disc half of the state; gen/ema_gen/step ride
        through untouched, so the tree shape is exactly the fused
        step's."""
        params, bn = state["params"], state["bn"]
        iter_keys = jax.random.split(jax.random.fold_in(key, _D_TAG),
                                     cfg.n_critic)
        zero = _zero_metric()

        if cfg.grad_accum > 1:
            imgs_s = _split_micro(images)

            def critic_iter(carry, xs):
                d_params_c, d_opt_c, d_bn_c, _ = carry
                fake_i, iter_key = xs
                d_full = _gather_params(d_params_c, "disc")
                _, gpk, aug_k = _critic_streams(iter_key, stage_batch)
                xs_m = {"img": imgs_s, "fake": _split_micro(fake_i),
                        "gpk": jax.random.split(gpk, cfg.grad_accum)}
                if aug_k is not None:
                    xs_m["augk"] = jax.random.split(aug_k, cfg.grad_accum)

                def d_micro(c, x):
                    g_acc, bn_d = c
                    bn_in = {"gen": bn["gen"], "disc": bn_d}
                    (loss, (bn_i, real, fk, gp)), grads = d_on_fake_grad(
                        d_full, bn_in, x["img"], x["fake"],
                        x["gpk"], None, state["step"], False,
                        x.get("augk"))
                    return ((_acc(g_acc, grads), bn_i),
                            (loss, real, fk, gp))

                (g_acc, bn_d), ms = lax.scan(
                    d_micro, (_zeros_f32(d_full), d_bn_c), xs_m)
                d_params_c, d_opt_c = _adam(
                    opt_d, _avg(g_acc, d_full, "disc"), d_opt_c,
                    d_params_c, "disc")
                return ((d_params_c, d_opt_c, bn_d,
                         tuple(m.mean() for m in ms)), None)
        else:
            def critic_iter(carry, xs):
                d_params_c, d_opt_c, d_bn_c, _ = carry
                fake_i, iter_key = xs
                _, gpk, aug_k = _critic_streams(iter_key, stage_batch)
                bn_in = {"gen": bn["gen"], "disc": d_bn_c}
                (loss_i, (bn_i, real_i, fake_m, gp_i)), grads = \
                    d_on_fake_grad(
                        _gather_params(d_params_c, "disc"), bn_in, images,
                        fake_i, gpk, None,
                        state["step"], False, aug_k)
                d_params_c, d_opt_c = _adam(
                    opt_d, _reduce_grads(grads, "disc"), d_opt_c,
                    d_params_c, "disc")
                return ((d_params_c, d_opt_c, bn_i,
                         (loss_i, real_i, fake_m, gp_i)), None)

        carry0 = (params["disc"], state["opt"]["disc"], bn["disc"],
                  (zero, zero, zero, zero))
        if cfg.n_critic == 1:
            # direct call, no 1-trip scan — the fused step's own
            # n_critic==1 branch skips the scan too (a single-iteration
            # while loop measurably serializes the CPU backend), and the
            # SAME critic_iter body runs either way so the two paths
            # cannot drift
            (new_disc, d_opt, d_bn,
             (d_loss, d_real, d_fake, gp)), _ = critic_iter(
                carry0, (fakes[0], iter_keys[0]))
        else:
            (new_disc, d_opt, d_bn,
             (d_loss, d_real, d_fake, gp)), _ = lax.scan(
                critic_iter, carry0, (fakes, iter_keys))
        new_state = {
            "params": {"gen": params["gen"], "disc": new_disc},
            "bn": {"gen": bn["gen"], "disc": d_bn},
            "opt": {"gen": state["opt"]["gen"], "disc": d_opt},
            "ema_gen": state["ema_gen"],
            "step": state["step"],
        }
        return new_state, _d_metrics(d_loss, d_real, d_fake, gp)

    @jax.named_scope("g_step")
    def g_update(state: Pytree, key: jax.Array
                 ) -> Tuple[Pytree, jax.Array, dict]:
        """The generator update against the CURRENT critic (the trainer
        dispatches it after d_update — sequential semantics), RETURNING
        the fake stack the next step's d_update consumes at staleness 1.
        Slot 0 is the g-loss forward's own fake (from the PRE-update
        weights — computed anyway, so the steady-state step gets its next
        D input for free); n_critic > 1 generates the remaining slots
        with fresh z from the same pre-update weights. Increments
        state["step"]."""
        key = jax.random.fold_in(key, _G_TAG)
        if aug_policy:
            z_key, extra_key, aug_key = jax.random.split(key, 3)
        else:
            z_key, extra_key = jax.random.split(key)
            aug_key = None
        params, bn = state["params"], state["bn"]
        gen_full = _gather_params(params["gen"], "gen")
        disc_full = _gather_params(params["disc"], "disc")

        if cfg.grad_accum > 1:
            z = jax.random.uniform(z_key, (stage_batch, mcfg.z_dim),
                                   minval=-1.0, maxval=1.0,
                                   dtype=jnp.float32)
            xs = {"z": _split_micro(z)}
            if aug_key is not None:
                xs["augk"] = jax.random.split(aug_key, cfg.grad_accum)

            def g_micro(carry, x):
                g_acc, bn_g = carry
                bn_in = {"gen": bn_g, "disc": bn["disc"]}
                (g_loss_i, (g_bn_i, fake_i)), grads = g_grad(
                    gen_full, disc_full, bn_in, x["z"],
                    None, x.get("augk"), return_fake=True)
                return (_acc(g_acc, grads), g_bn_i), (g_loss_i, fake_i)

            (g_gacc, g_bn), (g_losses, fakes_m) = lax.scan(
                g_micro, (_zeros_f32(gen_full), bn["gen"]), xs)
            g_grads = _avg(g_gacc, gen_full, "gen")
            g_loss = g_losses.mean()
            # (K, micro, ...) -> (B, ...): the full-batch fake the next
            # d_update re-splits into its own microbatches
            fake = _cf(fakes_m.reshape(stage_batch, *fakes_m.shape[2:]))
        else:
            z = jax.random.uniform(z_key, (stage_batch, mcfg.z_dim),
                                   minval=-1.0, maxval=1.0,
                                   dtype=jnp.float32)
            (g_loss, (g_bn, fake)), g_grads = g_grad(
                gen_full, disc_full, bn, z, None, aug_key,
                return_fake=True)
            g_grads = _reduce_grads(g_grads, "gen")
        new_gen, g_opt = _adam(opt_g, g_grads, state["opt"]["gen"],
                               params["gen"], "gen")

        if cfg.n_critic > 1:
            extra = _fake_stack(gen_full, bn["gen"], extra_key,
                                cfg.n_critic - 1)
            fakes = jnp.concatenate([fake[None], extra], axis=0)
        else:
            fakes = fake[None]
        new_state = {
            "params": {"gen": new_gen, "disc": params["disc"]},
            "bn": {"gen": g_bn, "disc": bn["disc"]},
            "opt": {"gen": g_opt, "disc": state["opt"]["disc"]},
            "step": state["step"] + 1,
        }
        new_state["ema_gen"] = _ema_update(state, new_gen)
        return new_state, fakes, {"g_loss": _pmean(g_loss)}

    def sample(state: Pytree, z: jax.Array,
               labels: Optional[jax.Array] = None) -> jax.Array:
        # EMA weights when tracking is on (g_ema_decay > 0); the reference
        # samples live weights (image_train.py:181-184), which remains the
        # default. Selected by config, not key presence — ema_gen always
        # exists in the state (see init_train_state) but under decay=0 it is
        # a by-construction mirror and live weights are the clearer choice.
        g_params = (state["ema_gen"] if cfg.g_ema_decay > 0.0
                    else state["params"]["gen"])
        # ZeRO-3: the EMA mirror shards like the live G params — one
        # just-in-time gather serves both sources
        g_params = _gather_params(g_params, "gen")
        return sampler_apply(g_params, state["bn"]["gen"], z,
                             cfg=mcfg, labels=labels,
                             pallas_mesh=pallas_mesh)

    def summarize(state: Pytree, images: jax.Array, key: jax.Array,
                  labels: Optional[jax.Array] = None) -> dict:
        """Per-layer activation histograms + sparsity, reduced on device.

        The functional replacement for the reference's `_activation_summary`
        (distriubted_model.py:75-80): one extra forward of G and of D (on the
        real batch) with train-mode BN, run on a step-count cadence
        (TrainConfig.activation_summary_steps — never a per-process time gate;
        it is a mesh collective) — the hot step is untouched.
        """
        from dcgan_tpu.utils.metrics import activation_stats

        params, bn = state["params"], state["bn"]
        params = {"gen": _gather_params(params["gen"], "gen"),
                  "disc": _gather_params(params["disc"], "disc")}
        z = jax.random.uniform(key, (images.shape[0], mcfg.z_dim),
                               minval=-1.0, maxval=1.0, dtype=jnp.float32)
        g_cap: dict = {}
        d_cap: dict = {}
        fake, _ = generator_apply(params["gen"], bn["gen"], z, cfg=mcfg,
                                  train=True, labels=labels,
                                  axis_name=axis_name,
                                  attn_mesh=attn_mesh, pallas_mesh=pallas_mesh, capture=g_cap)
        d_real_prob, _, _ = discriminator_apply(
            params["disc"], bn["disc"], images, cfg=mcfg,
            train=True, labels=labels, axis_name=axis_name,
            attn_mesh=attn_mesh, pallas_mesh=pallas_mesh, capture=d_cap)
        # the reference's input/output histogram channels (image_train.py:
        # 86-89): z itself, D(x), and D(G(z)) — one extra D forward on the
        # fakes, paid only on the summary cadence
        d_fake_prob, _, _ = discriminator_apply(
            params["disc"], bn["disc"], fake, cfg=mcfg,
            train=True, labels=labels, axis_name=axis_name,
            attn_mesh=attn_mesh, pallas_mesh=pallas_mesh)
        acts = {**{f"gen/{k}": v for k, v in g_cap.items()},
                **{f"disc/{k}": v for k, v in d_cap.items()},
                "z": z, "d_real_prob": d_real_prob,
                "d_fake_prob": d_fake_prob}
        return activation_stats(acts, axis_name=axis_name)

    def eval_losses(state: Pytree, images: jax.Array, z: jax.Array,
                    labels: Optional[jax.Array] = None) -> dict:
        """Loss probe on a held-out batch with a caller-fixed z, no update —
        the reference's every-100-steps sample evaluation: it feeds the
        *sample* pipeline's batch and the fixed sample_z through the train
        graph's loss tensors without running the optimizers
        (image_train.py:179-192). Train-mode BN (batch statistics), matching
        the reference's reuse of the train graph; the returned BN state is
        discarded. WGAN-GP's interpolation uses a fixed key: a deterministic
        probe, not a training signal."""
        params, bn = state["params"], state["bn"]
        params = {"gen": _gather_params(params["gen"], "gen"),
                  "disc": _gather_params(params["disc"], "disc")}
        gp_key = jax.random.key(0)
        d_loss, (_, d_real, d_fake, gp) = d_loss_fn(
            params["disc"], params["gen"], bn, images, z, gp_key, labels,
            r1_every_step=True)
        g_loss, _ = g_loss_fn(params["gen"], params["disc"], bn, z, labels)
        return _loss_metrics(d_loss, d_real, d_fake, g_loss, gp)

    def init(key):
        return init_train_state(key, cfg)

    return TrainStepFns(train_step=train_step, sample=sample, init=init,
                        summarize=summarize, eval_losses=eval_losses,
                        gen_fakes=gen_fakes, d_update=d_update,
                        g_update=g_update)


# ---------------------------------------------------------------------------
# the one-network likelihood step (TrainConfig.loss == "lm")
# ---------------------------------------------------------------------------

def _lm_arch(cfg: TrainConfig):
    """The module of the token arch (models/<arch>.py). It gives the step
    `lm_init(key, model cfg)` (the state beside optimizer and step, with
    `params` in it), `lm_loss(params, state, ids, model cfg) -> (loss,
    aux)` with `state` the entries `LM_READS` names, `LM_MEAN` / `LM_SUM`
    (the aux entries averaged / summed over the data shards),
    `lm_metrics(aux)` (the step's counters, all scalars) and
    `lm_accumulate(state, aux)` (the state entries the step adds to): what
    an arch counts stays with the arch."""
    if not is_token_arch(cfg.model.arch):
        raise ValueError(f"arch={cfg.model.arch!r} has no likelihood step")
    return importlib.import_module(f"dcgan_tpu.models.{cfg.model.arch}")


def init_lm_state(key, cfg: TrainConfig) -> Pytree:
    """State of a token arch: what the arch keeps (parameters, and for the
    routed arch the selection biases and per-expert pair counts, for the
    looped arch the per-exit mass, for the hybrid arch the memory's
    per-channel mean), the optimizer state and the step."""
    state = _lm_arch(cfg).lm_init(key, cfg.model)
    return {
        **state,
        "opt": make_optimizer(cfg).init(state["params"]),
        "step": jnp.zeros((), jnp.int32),
    }


def make_lm_train_step(cfg: TrainConfig, *, mesh=None) -> TrainStepFns:
    """Loss, gradient and the program's Adam for a token arch, as the
    same `TrainStepFns` the two-player step returns. `train_step(state, ids
    [B, S] int32, key)`: the key is unused (nothing is drawn). Under a mesh
    with a data axis > 1 loss and gradient run per data shard inside a
    `shard_map` (each shard's rows through the whole network, as every chip
    of a deployment runs its own tokens) and are averaged across it; the
    kernels are opaque to the partitioner."""
    from jax.sharding import PartitionSpec as P

    from dcgan_tpu.utils.backend import shard_map

    arch = _lm_arch(cfg)
    mcfg = cfg.model
    opt = make_optimizer(cfg)
    n_data = 1 if mesh is None else mesh.shape["data"]

    def loss_grad(params, rest, ids):
        (_, aux), grads = jax.value_and_grad(
            lambda p: arch.lm_loss(p, rest, ids, mcfg), has_aux=True)(params)
        if n_data > 1:
            grads, *mean = lax.pmean(
                (grads, *(aux[n] for n in arch.LM_MEAN)), "data")
            aux.update(zip(arch.LM_MEAN, mean))
            aux.update(zip(arch.LM_SUM, lax.psum(
                tuple(aux[n] for n in arch.LM_SUM), "data")))
        return grads, aux

    if n_data > 1:
        loss_grad = shard_map(loss_grad, mesh=mesh,
                              in_specs=(P(), P(), P("data")),
                              out_specs=P(), check=False)

    def train_step(state: Pytree, ids: jax.Array, key: jax.Array
                   ) -> Tuple[Pytree, dict]:
        del key
        rest = {n: state[n] for n in arch.LM_READS}
        grads, aux = loss_grad(state["params"], rest, ids)
        with jax.named_scope("adam"):
            updates, opt_state = opt.update(grads, state["opt"],
                                            state["params"])
            params = optax.apply_updates(state["params"], updates)
        metrics = arch.lm_metrics(aux)
        state = {**state, "params": params, "opt": opt_state,
                 **arch.lm_accumulate(state, aux),
                 "step": state["step"] + 1}
        return state, metrics

    def init(key):
        return init_lm_state(key, cfg)

    return TrainStepFns(train_step=train_step, init=init)
