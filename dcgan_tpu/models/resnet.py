"""ResNet GAN generator / discriminator — the framework's second model family.

The reference is DCGAN-only (distriubted_model.py:83-128); this family is the
residual architecture of WGAN-GP (Gulrajani et al. 2017, appendix F) and
SNGAN (Miyato et al. 2018, table 3), selected with `ModelConfig(arch=
"resnet")` and scaled by the same base_size·2^k rule as the DCGAN stacks:

- generator: linear z -> [base, base, top_ch], then k residual up-blocks
  (BN -> relu -> 2x nearest upsample -> conv3x3 -> BN -> relu -> conv3x3,
  skip = upsample (+1x1 conv on channel change)), final BN -> relu ->
  conv3x3 -> tanh;
- discriminator: an "optimized" first down-block (conv3x3 -> relu ->
  conv3x3 -> avgpool; skip = avgpool -> 1x1), then residual down-blocks
  (relu -> conv3x3 -> relu -> conv3x3 [-> avgpool]), relu, global sum
  pool, linear -> 1 logit.

Everything composes with the existing machinery because the integration
surfaces are shared, not copied:

- params/state are flat dicts of {"w","b"} layers and bn*/sn_* leaves, so
  the spectral-norm wrappers (dcgan._sn_layer), the TP sharding rules
  (parallel/sharding.py keys on "w"/"proj"/"head" names), Adam/optax, and
  Orbax checkpointing all apply unchanged;
- normalization is ops/norm.batch_norm_apply — synced moments, cBN [K, C]
  tables, fused Pallas kernels, and the nested-shard_map gspmd path come
  for free;
- attn_res inserts the same SAGAN block (ops/attention.py), sequence-
  parallel under a spatial mesh, exactly as in the DCGAN stacks;
- conditioning mirrors dcgan.py: one-hot concat onto z for G, constant
  channel maps for D.

Entry points match dcgan.py's signatures; models/dcgan.py dispatches on
cfg.arch so every caller (steps, parallel, generate, evals) is untouched.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dcgan_tpu.config import ModelConfig
from dcgan_tpu.ops.attention import attn_apply, attn_init
from dcgan_tpu.ops.layers import (
    conv2d_apply,
    conv2d_init,
    linear_apply,
    linear_init,
)
from dcgan_tpu.ops.norm import batch_norm_apply, batch_norm_init

Pytree = dict


def _upsample(x: jax.Array) -> jax.Array:
    """2x nearest-neighbor upsample, NHWC."""
    return x.repeat(2, axis=1).repeat(2, axis=2)


def _avgpool(x: jax.Array) -> jax.Array:
    """2x2 average pool, NHWC (shapes here are powers of two)."""
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def _g_channels(cfg: ModelConfig):
    """Per-stage channel plan: top_ch at base_size, halving as resolution
    doubles and flooring at gf_dim (the last up-block keeps its width, the
    SNGAN/BigGAN convention), so gf_dim means the same thing in both
    families."""
    k = cfg.num_up_layers
    return [cfg.gf_dim * (2 ** max(0, k - 1 - i)) for i in range(k + 1)]


def _d_channels(cfg: ModelConfig):
    """Mirror of the generator plan: df_dim at full resolution, doubling as
    resolution halves."""
    k = cfg.num_up_layers
    return [cfg.df_dim * (2 ** i) for i in range(k)]


# ---------------------------------------------------------------------------
# Generator
# ---------------------------------------------------------------------------

def generator_init(key, cfg: ModelConfig) -> Tuple[Pytree, Pytree]:
    """Returns (params, bn_state); flat layer names (b{i}_*) keep the
    spectral-norm and sharding machinery applicable as-is."""
    k = cfg.num_up_layers
    dtype = jnp.dtype(cfg.param_dtype)
    chans = _g_channels(cfg)
    keys = jax.random.split(key, 6 * k + 4)
    bn_classes = cfg.num_classes if cfg.conditional_bn else 0

    in_dim = cfg.z_dim + (cfg.num_classes if cfg.num_classes else 0)
    params: Pytree = {
        "proj": linear_init(keys[0], in_dim,
                            chans[0] * cfg.base_size * cfg.base_size,
                            dtype=dtype),
    }
    state: Pytree = {}
    for i in range(1, k + 1):
        cin, cout = chans[i - 1], chans[i]
        kk = keys[6 * i - 5:6 * i + 1]
        bn_p, bn_s = batch_norm_init(kk[0], cin, dtype=dtype,
                                     num_classes=bn_classes)
        params[f"b{i}_bn1"], state[f"b{i}_bn1"] = bn_p, bn_s
        params[f"b{i}_conv1"] = conv2d_init(kk[1], cin, cout, kernel=3,
                                            dtype=dtype)
        bn_p, bn_s = batch_norm_init(kk[2], cout, dtype=dtype,
                                     num_classes=bn_classes)
        params[f"b{i}_bn2"], state[f"b{i}_bn2"] = bn_p, bn_s
        params[f"b{i}_conv2"] = conv2d_init(kk[3], cout, cout, kernel=3,
                                            dtype=dtype)
        if cin != cout:
            params[f"b{i}_skip"] = conv2d_init(kk[4], cin, cout, kernel=1,
                                               dtype=dtype)
    bn_p, bn_s = batch_norm_init(keys[6 * k + 1], chans[k], dtype=dtype,
                                 num_classes=bn_classes)
    params["bn_out"], state["bn_out"] = bn_p, bn_s
    params["out_conv"] = conv2d_init(keys[6 * k + 2], chans[k], cfg.c_dim,
                                     kernel=3, dtype=dtype)
    if cfg.attn_res:
        i = int(round(math.log2(cfg.attn_res / cfg.base_size)))
        params["attn"] = attn_init(keys[6 * k + 3], chans[i], dtype=dtype)
    if cfg.spectral_norm == "gd":
        from dcgan_tpu.models.dcgan import _sn_state_init

        _sn_state_init(jax.random.fold_in(key, 0x53AE), params, state)
    return params, state


def generator_apply(params: Pytree, state: Pytree, z: jax.Array, *,
                    cfg: ModelConfig, train: bool,
                    labels: Optional[jax.Array] = None,
                    axis_name: Optional[str] = None,
                    attn_mesh=None,
                    pallas_mesh=None,
                    capture: Optional[dict] = None
                    ) -> Tuple[jax.Array, Pytree]:
    """z [B, z_dim] (-1..1) -> image [B, S, S, c_dim] in tanh range."""
    from dcgan_tpu.models.dcgan import _sn_layer

    k = cfg.num_up_layers
    cdt = jnp.dtype(cfg.compute_dtype)
    chans = _g_channels(cfg)
    new_state: Pytree = {}
    sn = cfg.spectral_norm == "gd"

    def layer(name):
        return _sn_layer(params, state, new_state, name, train) if sn \
            else params[name]

    def bn(name, x, act):
        y, new_state[name] = batch_norm_apply(
            params[name], state[name], x, train=train,
            momentum=cfg.bn_momentum, eps=cfg.bn_eps, axis_name=axis_name,
            act=act, labels=bn_labels)
        return y

    if cfg.num_classes:
        if labels is None:
            raise ValueError("conditional generator requires labels")
        onehot = jax.nn.one_hot(labels, cfg.num_classes, dtype=z.dtype)
        z = jnp.concatenate([z, onehot], axis=-1)
    bn_labels = labels if cfg.conditional_bn else None

    with jax.named_scope("proj"):
        h = linear_apply(layer("proj"), z.astype(cdt), compute_dtype=cdt)
        h = h.reshape(-1, cfg.base_size, cfg.base_size, chans[0])
    if cfg.attn_res == cfg.base_size:
        h = _attn(cfg, params, state, new_state, h, cdt, attn_mesh, sn,
                  train, pallas_mesh=pallas_mesh)
    if capture is not None:
        capture["h0"] = h

    for i in range(1, k + 1):
        with jax.named_scope(f"b{i}"):
            r = bn(f"b{i}_bn1", h, "relu")
            r = _upsample(r)
            r = conv2d_apply(layer(f"b{i}_conv1"), r, stride=1,
                             compute_dtype=cdt)
            r = bn(f"b{i}_bn2", r, "relu")
            r = conv2d_apply(layer(f"b{i}_conv2"), r, stride=1,
                             compute_dtype=cdt)
            s = _upsample(h)
            if f"b{i}_skip" in params:
                s = conv2d_apply(layer(f"b{i}_skip"), s, stride=1,
                                 compute_dtype=cdt)
            h = r + s
        if cfg.attn_res == cfg.base_size * (2 ** i) and i < k:
            h = _attn(cfg, params, state, new_state, h, cdt, attn_mesh, sn,
                      train, pallas_mesh=pallas_mesh)
        if capture is not None:
            capture[f"h{i}"] = h

    with jax.named_scope("out_conv"):
        h = bn("bn_out", h, "relu")
        h = conv2d_apply(layer("out_conv"), h, stride=1, compute_dtype=cdt)
    out = jnp.tanh(h.astype(jnp.float32))
    if capture is not None:
        capture[f"h{k + 1}"] = out
    return out, new_state


def _attn(cfg, params, state, new_state, h, cdt, attn_mesh, sn, train,
          pallas_mesh=None):
    from dcgan_tpu.models.dcgan import _sn_attn

    p = _sn_attn(params["attn"], state, new_state, train) if sn \
        else params["attn"]
    return attn_apply(p, h, compute_dtype=cdt, num_heads=cfg.attn_heads,
                      seq_strategy=cfg.attn_seq_strategy,
                      seq_mesh=attn_mesh, use_pallas=cfg.use_pallas,
                      pallas_mesh=pallas_mesh)


# ---------------------------------------------------------------------------
# Discriminator
# ---------------------------------------------------------------------------

def discriminator_init(key, cfg: ModelConfig) -> Tuple[Pytree, Pytree]:
    """Returns (params, state). No BN anywhere (the SNGAN/WGAN-GP critic is
    norm-free — WGAN-GP's penalty is per-example, and SN replaces BN's
    conditioning role), so `state` carries only sn_* leaves when spectral
    norm is on — which also makes the whole family valid under loss=
    'wgan-gp' without cross-example coupling."""
    k = cfg.num_up_layers
    dtype = jnp.dtype(cfg.param_dtype)
    chans = _d_channels(cfg)
    keys = jax.random.split(key, 3 * k + 3)

    cin0 = cfg.c_dim + (cfg.num_classes if cfg.num_classes else 0)
    params: Pytree = {}
    state: Pytree = {}
    in_ch = cin0
    for i in range(k):
        out_ch = chans[i]
        params[f"b{i}_conv1"] = conv2d_init(keys[3 * i], in_ch, out_ch,
                                            kernel=3, dtype=dtype)
        params[f"b{i}_conv2"] = conv2d_init(keys[3 * i + 1], out_ch, out_ch,
                                            kernel=3, dtype=dtype)
        if in_ch != out_ch:
            params[f"b{i}_skip"] = conv2d_init(keys[3 * i + 2], in_ch,
                                               out_ch, kernel=1, dtype=dtype)
        in_ch = out_ch
    params["head"] = linear_init(keys[3 * k], in_ch, 1, dtype=dtype)
    if cfg.attn_res:
        i = int(round(math.log2(cfg.output_size / cfg.attn_res)))
        params["attn"] = attn_init(keys[3 * k + 1], chans[i - 1],
                                   dtype=dtype)
    if cfg.spectral_norm in ("d", "gd"):
        from dcgan_tpu.models.dcgan import _sn_state_init

        _sn_state_init(jax.random.fold_in(key, 0xD15C), params, state)
    return params, state


def discriminator_apply(params: Pytree, state: Pytree, image: jax.Array, *,
                        cfg: ModelConfig, train: bool,
                        labels: Optional[jax.Array] = None,
                        axis_name: Optional[str] = None,
                        attn_mesh=None,
                        pallas_mesh=None,
                        capture: Optional[dict] = None
                        ) -> Tuple[jax.Array, jax.Array, Pytree]:
    """image -> (sigmoid(logit), logit [B, 1], new_state)."""
    from dcgan_tpu.models.dcgan import _sn_layer

    k = cfg.num_up_layers
    cdt = jnp.dtype(cfg.compute_dtype)
    new_state: Pytree = {}
    sn = cfg.spectral_norm in ("d", "gd")

    def layer(name):
        return _sn_layer(params, state, new_state, name, train) if sn \
            else params[name]

    h = image.astype(cdt)
    if cfg.num_classes:
        if labels is None:
            raise ValueError("conditional discriminator requires labels")
        onehot = jax.nn.one_hot(labels, cfg.num_classes, dtype=h.dtype)
        maps = jnp.broadcast_to(onehot[:, None, None, :],
                                h.shape[:3] + (cfg.num_classes,))
        h = jnp.concatenate([h, maps], axis=-1)

    for i in range(k):
        with jax.named_scope(f"b{i}"):
            # block 0 is the "optimized" form (no pre-activation on raw
            # pixels); later blocks pre-activate (relu first)
            r = h if i == 0 else jax.nn.relu(h)
            r = conv2d_apply(layer(f"b{i}_conv1"), r, stride=1,
                             compute_dtype=cdt)
            r = jax.nn.relu(r)
            r = conv2d_apply(layer(f"b{i}_conv2"), r, stride=1,
                             compute_dtype=cdt)
            r = _avgpool(r)
            s = _avgpool(h)
            if f"b{i}_skip" in params:
                s = conv2d_apply(layer(f"b{i}_skip"), s, stride=1,
                                 compute_dtype=cdt)
            h = r + s
        if cfg.attn_res and cfg.attn_res == cfg.output_size >> (i + 1):
            h = _attn(cfg, params, state, new_state, h, cdt, attn_mesh, sn,
                      train, pallas_mesh=pallas_mesh)
        if capture is not None:
            capture[f"h{i}"] = h

    with jax.named_scope("head"):
        h = jax.nn.relu(h)
        h = h.sum(axis=(1, 2))                       # global sum pool
        logit = linear_apply(layer("head"), h, compute_dtype=cdt)
    logit = logit.astype(jnp.float32)
    if capture is not None:
        capture["logit"] = logit
    return jax.nn.sigmoid(logit), logit, new_state
