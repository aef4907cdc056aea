"""Linear / conv / transposed-conv / leaky-relu as pure init/apply pairs.

Reference behavior being matched (not copied — the reference is TF graph code):
- `linear`:   W ~ N(0, 0.02), b = 0                  (distriubted_model.py:160-173)
- `conv2d`:   5x5 stride-2 SAME, W ~ TruncNorm(0.02) (distriubted_model.py:176-187)
- `deconv2d`: 5x5 stride-2 SAME, W ~ N(0, 0.02)      (distriubted_model.py:190-213)
- `lrelu`:    max(x, 0.2x)                           (distriubted_model.py:156-157)

TPU notes: NHWC layout with HWIO kernels (XLA:TPU's preferred conv layout);
compute in bfloat16 with float32 params — the matmul/conv lands on the MXU, the
cast is free in the fused epilogue. All shapes are static so XLA can tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

Pytree = dict


def _stddev_init(key, shape, stddev, dtype, truncated=False):
    if truncated:
        # TF truncated_normal: resample outside 2 sigma; jax provides the same.
        return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)
    return stddev * jax.random.normal(key, shape, dtype)


# ---------------------------------------------------------------------------
# linear
# ---------------------------------------------------------------------------

def linear_init(key, in_dim: int, out_dim: int, *, stddev: float = 0.02,
                dtype=jnp.float32) -> Pytree:
    kw, _ = jax.random.split(key)
    return {
        "w": _stddev_init(kw, (in_dim, out_dim), stddev, dtype),
        "b": jnp.zeros((out_dim,), dtype),
    }


def linear_apply(params: Pytree, x: jax.Array, *, compute_dtype=None) -> jax.Array:
    w, b = params["w"], params["b"]
    if compute_dtype is not None:
        x, w = x.astype(compute_dtype), w.astype(compute_dtype)
    return x @ w + b.astype(x.dtype)


# ---------------------------------------------------------------------------
# conv2d (strided, SAME)
# ---------------------------------------------------------------------------

_CONV_DIMS = ("NHWC", "HWIO", "NHWC")


def conv2d_init(key, in_ch: int, out_ch: int, *, kernel: int = 5,
                stddev: float = 0.02, dtype=jnp.float32) -> Pytree:
    kw, _ = jax.random.split(key)
    return {
        "w": _stddev_init(kw, (kernel, kernel, in_ch, out_ch), stddev, dtype,
                          truncated=True),
        "b": jnp.zeros((out_ch,), dtype),
    }


def conv2d_apply(params: Pytree, x: jax.Array, *, stride: int = 2,
                 compute_dtype=None) -> jax.Array:
    w, b = params["w"], params["b"]
    if compute_dtype is not None:
        x, w = x.astype(compute_dtype), w.astype(compute_dtype)
    y = lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=_CONV_DIMS)
    return y + b.astype(y.dtype)


# ---------------------------------------------------------------------------
# deconv2d (transposed conv, SAME, output = input * stride)
# ---------------------------------------------------------------------------

def deconv2d_init(key, in_ch: int, out_ch: int, *, kernel: int = 5,
                  stddev: float = 0.02, dtype=jnp.float32) -> Pytree:
    kw, _ = jax.random.split(key)
    return {
        "w": _stddev_init(kw, (kernel, kernel, in_ch, out_ch), stddev, dtype),
        "b": jnp.zeros((out_ch,), dtype),
    }


def deconv2d_apply(params: Pytree, x: jax.Array, *, stride: int = 2,
                   compute_dtype=None) -> jax.Array:
    w, b = params["w"], params["b"]
    if compute_dtype is not None:
        x, w = x.astype(compute_dtype), w.astype(compute_dtype)
    y = lax.conv_transpose(
        x, w, strides=(stride, stride), padding="SAME",
        dimension_numbers=_CONV_DIMS)
    return y + b.astype(y.dtype)


# ---------------------------------------------------------------------------
# lrelu
# ---------------------------------------------------------------------------

def lrelu(x: jax.Array, leak: float = 0.2) -> jax.Array:
    return jnp.maximum(x, leak * x)
