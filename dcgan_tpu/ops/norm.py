"""BatchNorm with explicit, functional EMA state.

The reference's `batch_norm` class (distriubted_model.py:15-52) keeps its running
statistics as hidden TF side-state: an ExponentialMovingAverage(decay=0.9) whose
shadow variables are captured during the *train* graph build and read back by the
inference-mode `sampler` (distriubted_model.py:42,47 — a trap: sampler silently
depends on generator having been traced first, SURVEY.md §2.4 #9).

Here the running (mean, var) are an explicit pytree threaded through apply():

    params = {"scale": gamma, "bias": beta}            # gamma ~ N(1, 0.02), beta = 0
    state  = {"mean": m, "var": v}                     # EMA with momentum 0.9

    y, new_state = batch_norm_apply(params, state, x, train=True)

Cross-replica ("synced") statistics come for free under jit-with-sharding: the
batch-axis mean/var below are *global* reductions, so GSPMD lowers them to ICI
all-reduces when the batch is sharded over the mesh. For explicit-collective code
(shard_map/pmap) pass `axis_name=` and the moments are pmean'd by hand — both
paths replace the reference's per-worker (unsynced) statistics, as required by
BASELINE.json's synced-BN config.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dcgan_tpu.utils.backend import shard_map

Pytree = dict


def batch_norm_init(key, num_features: int, *, dtype=jnp.float32,
                    scale_stddev: float = 0.02,
                    num_classes: int = 0) -> Tuple[Pytree, Pytree]:
    """Returns (params, state). gamma ~ N(1, 0.02), beta = 0 as in the reference
    (distriubted_model.py:31-34); state starts at (mean=0, var=1).

    num_classes > 0 makes the affine CONDITIONAL (the cBN of SAGAN/BigGAN):
    scale/bias become per-class tables [K, C] gathered per example at apply
    time; the running moments stay shared across classes (standard cBN)."""
    shape = (num_classes, num_features) if num_classes else (num_features,)
    params = {
        "scale": 1.0 + scale_stddev * jax.random.normal(key, shape, dtype),
        "bias": jnp.zeros(shape, dtype),
    }
    state = {
        "mean": jnp.zeros((num_features,), dtype),
        "var": jnp.ones((num_features,), dtype),
    }
    return params, state


def finish_batch_moments(state: Pytree, mean: jax.Array,
                         mean_sq: jax.Array, *, momentum: float = 0.9
                         ) -> Tuple[jax.Array, jax.Array, Pytree]:
    """The BN train-path arithmetic downstream of the (already cross-shard-
    reduced) raw moments: E[x^2]-E[x]^2 with the negative-cancellation
    clamp, and the EMA state update in the stored stat dtype. Shared by
    `batch_norm_apply` and the fused conv blocks (ops/pallas_fused.py) so
    the two paths cannot drift. Returns (mean, var, new_state) with
    mean/var in float32."""
    mean = mean.astype(jnp.float32)
    # E[x^2]-E[x]^2 can cancel slightly negative in f32; clamp so
    # rsqrt(var+eps) can never produce NaN.
    var = jnp.maximum(mean_sq.astype(jnp.float32) - jnp.square(mean), 0.0)
    stat_dtype = state["mean"].dtype
    new_state = {
        "mean": momentum * state["mean"]
                + (1.0 - momentum) * mean.astype(stat_dtype),
        "var": momentum * state["var"]
               + (1.0 - momentum) * var.astype(stat_dtype),
    }
    return mean, var, new_state


def _pallas_shard_moments(x: jax.Array, mesh) -> Tuple[jax.Array, jax.Array]:
    """channel_moments per data-shard + pmean — pallas_call is opaque to
    GSPMD (the partitioner would all-gather the batch around it), so under a
    sharded mesh the kernel runs inside a shard_map over the "data" axis with
    the cross-shard reduction written explicitly (the same nest-a-shard_map-
    in-the-gspmd-jit pattern as ring attention, ops/attention.py)."""
    from jax.sharding import PartitionSpec as P

    from dcgan_tpu.ops.pallas_kernels import channel_moments

    bspec = P("data", *([None] * (x.ndim - 1)))

    def _moments(xl):
        m, ms = channel_moments(xl.reshape(-1, xl.shape[-1]))
        return lax.pmean(m, "data"), lax.pmean(ms, "data")

    # check_vma=False: pallas_call outputs carry no vma annotations (the
    # same concession the shard_map backend makes, shard_map_backend.py:74);
    # AD still inserts the psum for replicated-input gradients
    return shard_map(_moments, mesh=mesh, in_specs=(bspec,),
                     out_specs=(P(), P()), check=False)(x)


def _pallas_shard_epilogue(x, scale, bias, mean, var, *, eps, act, leak,
                           mesh):
    """fused_bn_act per data-shard (elementwise over rows, so no collective
    is needed); shard_map's transpose inserts the psum for the replicated
    scale/bias gradients."""
    from jax.sharding import PartitionSpec as P

    from dcgan_tpu.ops.pallas_kernels import fused_bn_act

    bspec = P("data", *([None] * (x.ndim - 1)))

    def _epilogue(xl, s, b, m, v):
        return fused_bn_act(xl, s, b, m, v, eps=eps, act=act, leak=leak)

    return shard_map(_epilogue, mesh=mesh,
                     in_specs=(bspec, P(), P(), P(), P()),
                     out_specs=bspec,
                     check=False)(x, scale, bias, mean, var)


@jax.named_scope("bn")
def batch_norm_apply(params: Pytree, state: Pytree, x: jax.Array, *,
                     train: bool, momentum: float = 0.9, eps: float = 1e-5,
                     axis_name: Optional[str] = None, act: str = "none",
                     leak: float = 0.2, use_pallas: bool = False,
                     labels: Optional[jax.Array] = None,
                     pallas_mesh=None) -> Tuple[jax.Array, Pytree]:
    """Normalize `x` over all axes but the last (channel) axis, optionally
    fusing the following activation (`act` in {"none","relu","lrelu","tanh"}).

    train=True : use batch moments, return EMA-updated state
                 (the reference's moments over [0,1,2] with a [0,1] fallback for
                 2-D inputs, distriubted_model.py:36-39, generalizes to "all but
                 channels" here).
    train=False: use the running statistics; state is returned unchanged.

    use_pallas=True routes the moments reduction and the normalize+activation
    epilogue through the fused Pallas kernels (ops/pallas_kernels.py) — one
    HBM pass each way instead of one per op. Under the gspmd backend on a
    multi-device mesh pass `pallas_mesh` and the kernels run per data-shard
    inside a shard_map (pallas_call is opaque to the partitioner); with
    explicit-collective code (shard_map backend) leave it None and pass
    `axis_name` as usual.

    Conditional BN (params built with num_classes > 0): pass `labels` [B] and
    each example is scaled/shifted by its class's row of the [K, C] tables.
    The per-example affine breaks the fused kernels' per-channel-vector
    contract, so cBN always takes the jnp path.
    """
    if train:
        if use_pallas:
            if pallas_mesh is not None:
                mean, mean_sq = _pallas_shard_moments(x, pallas_mesh)
            else:
                from dcgan_tpu.ops.pallas_kernels import channel_moments

                mean, mean_sq = channel_moments(x.reshape(-1, x.shape[-1]))
        else:
            # Moments in float32 even under bfloat16 activations — bf16
            # accumulation over a 64*64*64 reduction loses too many bits for
            # stable statistics.
            reduce_axes = tuple(range(x.ndim - 1))
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=reduce_axes)
            # E[x^2] - E[x]^2 so a single fused pass feeds both moments;
            # psum-friendly.
            mean_sq = jnp.mean(jnp.square(xf), axis=reduce_axes)
        if axis_name is not None:
            mean = lax.pmean(mean, axis_name)
            mean_sq = lax.pmean(mean_sq, axis_name)
        mean, var, new_state = finish_batch_moments(
            state, mean, mean_sq, momentum=momentum)
    else:
        mean = state["mean"]
        var = state["var"]
        new_state = state

    conditional = params["scale"].ndim == 2
    if conditional:
        if labels is None:
            raise ValueError("conditional BN requires labels")
        # per-example affine: gather class rows, broadcast over spatial dims
        bshape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
        scale = params["scale"][labels].reshape(bshape).astype(x.dtype)
        bias = params["bias"][labels].reshape(bshape).astype(x.dtype)
    elif use_pallas:
        if pallas_mesh is not None:
            y = _pallas_shard_epilogue(x, params["scale"], params["bias"],
                                       mean, var, eps=eps, act=act,
                                       leak=leak, mesh=pallas_mesh)
        else:
            from dcgan_tpu.ops.pallas_kernels import fused_bn_act

            y = fused_bn_act(x, params["scale"], params["bias"], mean, var,
                             eps=eps, act=act, leak=leak)
        return y, new_state
    else:
        scale = params["scale"].astype(x.dtype)
        bias = params["bias"].astype(x.dtype)
    inv = lax.rsqrt(var.astype(x.dtype) + jnp.asarray(eps, x.dtype))
    y = (x - mean.astype(x.dtype)) * inv * scale + bias
    y = _apply_act(y, act, leak)
    return y, new_state


def _apply_act(y: jax.Array, act: str, leak: float) -> jax.Array:
    # dispatch table shared with the pallas kernels (ops/activations.py) so
    # the two BN paths cannot silently diverge — without pulling
    # jax.experimental.pallas into the default path
    from dcgan_tpu.ops.activations import ACTS, act_fwd

    if act not in ACTS:
        raise ValueError(f"unknown act {act!r}")
    return act_fwd(y, act, leak)
