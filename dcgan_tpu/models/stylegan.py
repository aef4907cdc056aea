"""StyleGAN2-lite generator — the framework's third model family.

The reference is DCGAN-only (distriubted_model.py:83-128); this family is a
deliberately small take on the StyleGAN2 synthesis architecture (Karras et
al. 2020, arXiv:1912.04958), selected with `ModelConfig(arch="stylegan")`
and scaled by the same base_size·2^k rule as the other stacks:

- a 2-layer lrelu **mapping network** z -> w (w_dim = z_dim; z is
  pixel-normalized first, the paper's input normalization);
- a **learned constant** [base, base, top_ch] input instead of a z
  projection;
- k up-blocks of 2x nearest upsample + two **modulated 3x3 convolutions**
  — per-sample styles s = 1 + affine(w) scale the input channels and the
  output is demodulated by the per-sample, per-output-channel norm
  1/sqrt(Σ (W·s)²) — the TPU-friendly activation-scaling formulation,
  mathematically identical to StyleGAN2's grouped-conv weight modulation
  for stride-1 convs (the weight-scale cancels under demodulation, so the
  framework's N(0, 0.02) init convention stands in for equalized LR);
- a **skip (tRGB) output path**: each stage emits an RGB contribution via a
  modulated-without-demodulation 1x1 conv, summed with the upsampled
  running RGB; final image through tanh (framework contract: images live
  in tanh range end to end, unlike the paper's unbounded output).

Knowing omissions vs the paper, all documented here so nobody expects
paper-exact FID: no per-layer noise injection (`generator_apply` takes no
PRNG key by framework contract — adding one would fork every caller for a
texture-detail feature), no style mixing regularization, no path-length
regularization, and Adam β₂ stays at the repo default. The discriminator
is the existing norm-free residual critic (models/resnet.py — StyleGAN2's
own D is a plain resnet; pair with `--r1_gamma`/`--r1_interval`, the
regularizer the paper trains with).

There is no BatchNorm anywhere in G — styles carry the conditioning role —
so the generator's state tree is empty: nothing to sync across replicas,
and the sampler path is identical to the train path modulo `train` having
no effect. num_classes > 0 concatenates a one-hot onto z before the
mapping network (conditioning enters through w). conditional_bn / attn_res
/ spectral_norm="gd" are rejected in config validation for this family.

Entry points match dcgan.py's signatures; models/dcgan.py dispatches on
cfg.arch so every caller (steps, parallel backends, trainer, generate,
evals, bench) is untouched — the integration-surface conventions
docs/DESIGN.md §4 describes.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dcgan_tpu.config import ModelConfig
from dcgan_tpu.ops.layers import conv2d_init, linear_apply, linear_init, \
    lrelu
from dcgan_tpu.models.resnet import _g_channels, _upsample

Pytree = dict

_CONV_DIMS = ("NHWC", "HWIO", "NHWC")


# ---------------------------------------------------------------------------
# Generator
# ---------------------------------------------------------------------------

def generator_init(key, cfg: ModelConfig) -> Tuple[Pytree, Pytree]:
    """Returns (params, state). state is {} — no BN, no persistent
    moments; the whole generator is a pure function of (params, z)."""
    k = cfg.num_up_layers
    dtype = jnp.dtype(cfg.param_dtype)
    chans = _g_channels(cfg)
    # key budget: 3 head keys (map0/map1/const) + 6 per block, consumed as
    # keys[6*i - 3 : 6*i + 3] for i in 1..k — max index 6k+2, so exactly
    # 6k+3 keys
    keys = jax.random.split(key, 6 * k + 3)

    in_dim = cfg.z_dim + (cfg.num_classes if cfg.num_classes else 0)
    params: Pytree = {
        "map0": linear_init(keys[0], in_dim, cfg.z_dim, dtype=dtype),
        "map1": linear_init(keys[1], cfg.z_dim, cfg.z_dim, dtype=dtype),
        # the learned constant input IS the signal source: unit-scale init
        # (the paper's randn), not the 0.02 weight convention
        "const": jax.random.normal(
            keys[2], (cfg.base_size, cfg.base_size, chans[0]), dtype),
    }
    for i in range(1, k + 1):
        cin, cout = chans[i - 1], chans[i]
        kk = keys[6 * i - 3:6 * i + 3]
        params[f"b{i}_style1"] = linear_init(kk[0], cfg.z_dim, cin,
                                             dtype=dtype)
        params[f"b{i}_conv1"] = conv2d_init(kk[1], cin, cout,
                                            kernel=3, dtype=dtype)
        params[f"b{i}_style2"] = linear_init(kk[2], cfg.z_dim, cout,
                                             dtype=dtype)
        params[f"b{i}_conv2"] = conv2d_init(kk[3], cout, cout,
                                            kernel=3, dtype=dtype)
        params[f"b{i}_rgb_style"] = linear_init(kk[4], cfg.z_dim, cout,
                                                dtype=dtype)
        params[f"b{i}_trgb"] = conv2d_init(kk[5], cout, cfg.c_dim,
                                           kernel=1, dtype=dtype)
    return params, {}


def _mod_conv(layer: Pytree, style_layer: Pytree, x: jax.Array,
              w_lat: jax.Array, *, demod: bool, cdt) -> jax.Array:
    """Modulated conv as activation scaling (exact for stride-1, bias-free
    conv): scale input channels by s = 1 + affine(w), convolve, then (for
    demod) divide each output channel by its per-sample modulated weight
    norm sqrt(Σ_{kh,kw,i} (W s_i)²). Bias applies after demodulation."""
    s = 1.0 + linear_apply(style_layer, w_lat, compute_dtype=cdt)  # [B, cin]
    w = layer["w"].astype(cdt)                       # [kh, kw, cin, cout]
    y = lax.conv_general_dilated(
        x * s[:, None, None, :], w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=_CONV_DIMS)
    if demod:
        # Σ over kh,kw once (style-independent), then per-sample over cin —
        # f32 throughout: a bf16 sum over kernel*cin terms loses the low
        # bits the rsqrt then amplifies
        w2 = (layer["w"].astype(jnp.float32) ** 2).sum(axis=(0, 1))
        d = lax.rsqrt((s.astype(jnp.float32) ** 2) @ w2 + 1e-8)  # [B, cout]
        y = y * d.astype(cdt)[:, None, None, :]
    return y + layer["b"].astype(cdt)


def generator_apply(params: Pytree, state: Pytree, z: jax.Array, *,
                    cfg: ModelConfig, train: bool,
                    labels: Optional[jax.Array] = None,
                    axis_name: Optional[str] = None,
                    attn_mesh=None,
                    pallas_mesh=None,
                    capture: Optional[dict] = None
                    ) -> Tuple[jax.Array, Pytree]:
    """z [B, z_dim] (-1..1) -> image [B, S, S, c_dim] in tanh range.

    `train` is accepted for signature parity but has no effect: there is no
    batch-dependent state. The returned state is always {}.
    """
    del train, axis_name, attn_mesh, pallas_mesh  # no BN / attention here
    k = cfg.num_up_layers
    cdt = jnp.dtype(cfg.compute_dtype)

    if cfg.num_classes:
        if labels is None:
            raise ValueError("conditional generator requires labels")
        onehot = jax.nn.one_hot(labels, cfg.num_classes, dtype=z.dtype)
        z = jnp.concatenate([z, onehot], axis=-1)

    # pixel-normalize z (the paper's mapping-input normalization), then the
    # 2-layer lrelu mapping network -> w
    with jax.named_scope("map"):
        zn = z.astype(cdt)
        zn = zn * lax.rsqrt(jnp.mean(zn.astype(jnp.float32) ** 2, axis=-1,
                                     keepdims=True).astype(cdt) + 1e-8)
        w_lat = lrelu(linear_apply(params["map0"], zn, compute_dtype=cdt),
                      cfg.leak)
        w_lat = lrelu(linear_apply(params["map1"], w_lat, compute_dtype=cdt),
                      cfg.leak)
    if capture is not None:
        capture["w"] = w_lat

    h = jnp.broadcast_to(params["const"].astype(cdt),
                         (z.shape[0],) + params["const"].shape)
    rgb = None
    for i in range(1, k + 1):
        with jax.named_scope(f"b{i}"):
            h = _upsample(h)
            h = lrelu(_mod_conv(params[f"b{i}_conv1"],
                                params[f"b{i}_style1"],
                                h, w_lat, demod=True, cdt=cdt), cfg.leak)
            h = lrelu(_mod_conv(params[f"b{i}_conv2"],
                                params[f"b{i}_style2"],
                                h, w_lat, demod=True, cdt=cdt), cfg.leak)
            y = _mod_conv(params[f"b{i}_trgb"], params[f"b{i}_rgb_style"],
                          h, w_lat, demod=False, cdt=cdt)
            rgb = y if rgb is None else _upsample(rgb) + y
        if capture is not None:
            capture[f"h{i}"] = h
    out = jnp.tanh(rgb.astype(jnp.float32))
    if capture is not None:
        capture[f"h{k + 1}"] = out
    return out, {}
