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


@jax.named_scope("bn")
def batch_norm_apply(params: Pytree, state: Pytree, x: jax.Array, *,
                     train: bool, momentum: float = 0.9, eps: float = 1e-5,
                     axis_name: Optional[str] = None, act: str = "none",
                     leak: float = 0.2,
                     labels: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Pytree]:
    """Normalize `x` over all axes but the last (channel) axis, optionally
    fusing the following activation (`act` in {"none","relu","lrelu","tanh"}).

    train=True : use batch moments, return EMA-updated state
                 (the reference's moments over [0,1,2] with a [0,1] fallback for
                 2-D inputs, distriubted_model.py:36-39, generalizes to "all but
                 channels" here).
    train=False: use the running statistics; state is returned unchanged.

    One path, all of it XLA's to fuse into the neighbouring convolution
    (DESIGN.md §8b): float32 moments, optional `pmean` over `axis_name`,
    normalize, activation.

    Conditional BN (params built with num_classes > 0): pass `labels` [B] and
    each example is scaled/shifted by its class's row of the [K, C] tables.
    """
    if train:
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
        # E[x^2]-E[x]^2 can cancel slightly negative in f32; clamp so
        # rsqrt(var+eps) can never produce NaN.
        var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
        stat_dtype = state["mean"].dtype
        new_state = {
            "mean": momentum * state["mean"]
                    + (1.0 - momentum) * mean.astype(stat_dtype),
            "var": momentum * state["var"]
                   + (1.0 - momentum) * var.astype(stat_dtype),
        }
    else:
        mean = state["mean"]
        var = state["var"]
        new_state = state

    if params["scale"].ndim == 2:
        if labels is None:
            raise ValueError("conditional BN requires labels")
        # per-example affine: gather class rows, broadcast over spatial dims
        bshape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
        scale = params["scale"][labels].reshape(bshape).astype(x.dtype)
        bias = params["bias"][labels].reshape(bshape).astype(x.dtype)
    else:
        scale = params["scale"].astype(x.dtype)
        bias = params["bias"].astype(x.dtype)
    inv = lax.rsqrt(var.astype(x.dtype) + jnp.asarray(eps, x.dtype))
    y = (x - mean.astype(x.dtype)) * inv * scale + bias
    return _apply_act(y, act, leak), new_state


def _apply_act(y: jax.Array, act: str, leak: float) -> jax.Array:
    """The reference's activation set: relu (generator,
    distriubted_model.py:95-106), lrelu (discriminator, :118-121,156), tanh
    (generator output, :111)."""
    if act == "none":
        return y
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "lrelu":
        return jnp.maximum(y, leak * y)
    if act == "tanh":
        return jnp.tanh(y)
    raise ValueError(f"unknown act {act!r}")
