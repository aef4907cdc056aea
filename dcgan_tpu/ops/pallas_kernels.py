"""Pallas TPU kernels for the training step's hot elementwise/reduction ops.

The MXU work (conv / conv-transpose / matmul) stays with XLA — it already
tiles those optimally. What Pallas buys here is the HBM-bandwidth-bound tail
around BatchNorm, the op the reference applies after nearly every conv
(distriubted_model.py:93-121): with BN + activation fused into two single-pass
kernels, each activation tensor crosses HBM once per direction instead of
once per op.

- `channel_moments(x)`: one pass producing per-channel (mean, mean(x^2)) — the
  batch-statistics reduction of BN's train path (the reference's
  tf.nn.moments, distriubted_model.py:36-39). Accumulates in float32 across a
  row-block grid (sequential on TPU, so in-place accumulation is safe).
- `scale_shift_act(x, scale, shift, act)`: the entire BN epilogue
  y = act(x * scale + shift) as one elementwise pass, with a custom VJP whose
  backward is itself a single Pallas pass producing dx and the per-channel
  dscale/dshift reductions together.

Both degrade to `interpret=True` off-TPU, so the same code path is exercised
by the CPU test mesh. Models opt in via ModelConfig.use_pallas; the jnp path
remains the default for a structural reason: XLA already fuses the BN
epilogue into its neighbours, so a hand-written kernel has no HBM traffic
left to remove (it lost to XLA on the previous machine; not measured on the
current one — DESIGN.md §8b), so the kernels are a capability (and the
pattern for ops XLA can't fuse), not a default. GSPMD cannot repartition an opaque kernel call, so on
multi-device meshes the kernels run per data-shard inside a shard_map — the
gspmd backend nests one around each fused BN call
(ops/norm.py::_pallas_shard_moments, VERDICT r1 #5), and the shard_map
backend's whole step already is one.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dcgan_tpu.ops.activations import ACTS, LEAK
from dcgan_tpu.ops.activations import act_fwd as _act_fwd
from dcgan_tpu.ops.activations import act_grad as _act_grad


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _row_tile(n: int) -> int:
    """Largest row-block <= 256 that divides n (shapes here are powers of
    two; a divisor always exists, so no ragged masking is needed)."""
    tile = min(n, 256)
    while n % tile:
        tile -= 1
    return tile


# ---------------------------------------------------------------------------
# channel_moments: [N, C] -> (mean [C], mean_sq [C])
# ---------------------------------------------------------------------------

def _moments_kernel(x_ref, sum_ref, sumsq_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        sumsq_ref[:] = jnp.zeros_like(sumsq_ref)

    xf = x_ref[:].astype(jnp.float32)
    sum_ref[:] += jnp.sum(xf, axis=0, keepdims=True)
    sumsq_ref[:] += jnp.sum(xf * xf, axis=0, keepdims=True)


def _moments_fwd_impl(x2d: jax.Array) -> Tuple[jax.Array, jax.Array]:
    n, c = x2d.shape
    tile = _row_tile(n)
    acc_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    sums, sumsqs = pl.pallas_call(
        _moments_kernel,
        name="bn_moments",
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((tile, c), lambda i: (i, 0))],
        out_specs=(acc_spec, acc_spec),
        out_shape=(jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        interpret=_interpret(),
    )(x2d)
    inv_n = 1.0 / n
    return sums[0] * inv_n, sumsqs[0] * inv_n


@jax.custom_vjp
def channel_moments(x2d: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-channel (E[x], E[x^2]) over axis 0 of [N, C], in one HBM pass."""
    return _moments_fwd_impl(x2d)


def _moments_vjp_fwd(x2d):
    return _moments_fwd_impl(x2d), x2d


def _moments_vjp_bwd(x2d, g):
    # d mean/dx = 1/N ; d mean_sq/dx = 2x/N — a broadcastwise epilogue XLA
    # fuses into the surrounding backward graph; no kernel needed.
    g_mean, g_msq = g
    n = x2d.shape[0]
    dx = (g_mean[None, :] + 2.0 * x2d.astype(jnp.float32) * g_msq[None, :]) / n
    return (dx.astype(x2d.dtype),)


channel_moments.defvjp(_moments_vjp_fwd, _moments_vjp_bwd)


# ---------------------------------------------------------------------------
# scale_shift_act: y = act(x * scale + shift), per-channel scale/shift
# ---------------------------------------------------------------------------

def _ssa_fwd_kernel(x_ref, scale_ref, shift_ref, y_ref, *, act, leak):
    xf = x_ref[:].astype(jnp.float32)
    u = xf * scale_ref[:] + shift_ref[:]
    y_ref[:] = _act_fwd(u, act, leak).astype(y_ref.dtype)


def _ssa_bwd_kernel(x_ref, scale_ref, shift_ref, g_ref,
                    dx_ref, dscale_ref, dshift_ref, *, act, leak):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dscale_ref[:] = jnp.zeros_like(dscale_ref)
        dshift_ref[:] = jnp.zeros_like(dshift_ref)

    xf = x_ref[:].astype(jnp.float32)
    u = xf * scale_ref[:] + shift_ref[:]
    du = g_ref[:].astype(jnp.float32) * _act_grad(u, act, leak)
    dx_ref[:] = (du * scale_ref[:]).astype(dx_ref.dtype)
    dscale_ref[:] += jnp.sum(du * xf, axis=0, keepdims=True)
    dshift_ref[:] += jnp.sum(du, axis=0, keepdims=True)


def _ssa_impl(x2d, scale, shift, act, leak):
    # Validated here — shared by the primal and the custom-VJP forward — so a
    # bad act name errors under jax.grad too (the primal wrapper is bypassed
    # when differentiating) instead of silently applying identity.
    if act not in ACTS:
        raise ValueError(f"unknown act {act!r}")
    n, c = x2d.shape
    tile = _row_tile(n)
    vec_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_ssa_fwd_kernel, act=act, leak=leak),
        name="bn_apply",
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((tile, c), lambda i: (i, 0)),
                  vec_spec, vec_spec],
        out_specs=pl.BlockSpec((tile, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), x2d.dtype),
        interpret=_interpret(),
    )(x2d, scale.reshape(1, c).astype(jnp.float32),
      shift.reshape(1, c).astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def scale_shift_act(x2d: jax.Array, scale: jax.Array, shift: jax.Array,
                    act: str = "none", leak: float = LEAK) -> jax.Array:
    """Fused y = act(x * scale + shift) over [N, C] with per-channel [C]
    scale/shift. act in {"none", "relu", "lrelu", "tanh"}."""
    return _ssa_impl(x2d, scale, shift, act, leak)


def _ssa_vjp_fwd(x2d, scale, shift, act, leak):
    return _ssa_impl(x2d, scale, shift, act, leak), (x2d, scale, shift)


def _ssa_vjp_bwd(act, leak, res, g):
    x2d, scale, shift = res
    n, c = x2d.shape
    tile = _row_tile(n)
    vec_spec = pl.BlockSpec((1, c), lambda i: (0, 0))
    dx, dscale, dshift = pl.pallas_call(
        functools.partial(_ssa_bwd_kernel, act=act, leak=leak),
        name="bn_bwd",
        grid=(n // tile,),
        in_specs=[pl.BlockSpec((tile, c), lambda i: (i, 0)),
                  vec_spec, vec_spec,
                  pl.BlockSpec((tile, c), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((tile, c), lambda i: (i, 0)),
                   vec_spec, vec_spec),
        out_shape=(jax.ShapeDtypeStruct((n, c), x2d.dtype),
                   jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        interpret=_interpret(),
    )(x2d, scale.reshape(1, c).astype(jnp.float32),
      shift.reshape(1, c).astype(jnp.float32), g)
    return (dx, dscale[0].astype(scale.dtype), dshift[0].astype(shift.dtype))


scale_shift_act.defvjp(_ssa_vjp_fwd, _ssa_vjp_bwd)


# ---------------------------------------------------------------------------
# Fused BN + activation built from the two kernels
# ---------------------------------------------------------------------------

def fused_bn_act(x: jax.Array, gamma: jax.Array, beta: jax.Array,
                 mean: jax.Array, var: jax.Array, *, eps: float,
                 act: str, leak: float = LEAK) -> jax.Array:
    """y = act((x - mean) * rsqrt(var + eps) * gamma + beta) for NHWC (or
    [N, C]) `x`, as one fused elementwise pass. mean/var may be batch moments
    (train) or running statistics (inference) — gradients flow through them
    either way via the scale/shift vectors."""
    c = x.shape[-1]
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + jnp.float32(eps))
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    y2d = scale_shift_act(x.reshape(-1, c), scale, shift, act, leak)
    return y2d.reshape(x.shape)
