"""DCGAN generator / discriminator / sampler as pure init/apply functions.

Capability-parity targets in the reference (behavior matched, architecture
re-designed functional — nothing is copied):

- `generator(z)`  distriubted_model.py:83-111 — linear z -> gf*8*4*4, reshape to
  [B,4,4,gf*8], then stride-2 5x5 deconv stages through gf*{4,2,1} with BN+relu,
  final deconv to c_dim + tanh. Batch size was hard-coded in every output_shape
  (distriubted_model.py:93-109); here shapes follow the input batch.
- `discriminator(image, reuse)`  distriubted_model.py:114-128 — stride-2 5x5 conv
  stages through df*{1,2,4,8}, BN on all but stage 0, lrelu(0.2), flatten,
  linear -> 1 logit; returns (sigmoid(logit), logit). TF's `reuse=True` variable
  sharing is simply passing the same params pytree — no variable scopes exist.
- `sampler(z)`  distriubted_model.py:131-153 — generator with train=False BN
  (running EMA statistics). Here that's `generator_apply(..., train=False)` on
  explicit state rather than TF side-state (SURVEY.md §2.4 #9).

Extensions beyond the reference (BASELINE.json configs):
- output_size 128 (or any base_size*2^k) deepens both stacks automatically;
- num_classes > 0 activates class conditioning (the reference's `y` argument is
  accepted-but-ignored, distriubted_model.py:83 / SURVEY.md §2.4 #7): one-hot
  labels concat onto z for G and broadcast as constant channel maps onto the
  image for D;
- attn_res > 0 inserts a SAGAN self-attention block (ops/attention.py) into
  both stacks at that feature-map resolution; `attn_mesh` routes it through
  sequence-parallel ring attention when the spatial mesh shards image height;
- spectral_norm "d"/"gd" divides every D (and G) weight by its power-iterated
  largest singular value each apply (ops/spectral.py) — the SN-GAN/SAGAN
  Lipschitz control, with the iteration vectors as explicit sn_* state leaves;
- conditional_bn makes the generator's BN affine per-class [K, C] tables
  (SAGAN/BigGAN cBN) on top of the z-concat conditioning.

Params/state are plain nested dicts so `jax.tree_util` / optax / checkpointing
all work without a framework dependency.
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
    deconv2d_apply,
    deconv2d_init,
    linear_apply,
    linear_init,
    lrelu,
)
from dcgan_tpu.ops.norm import batch_norm_apply, batch_norm_init
from dcgan_tpu.ops.spectral import spectral_normalize, spectral_u_init

Pytree = dict

_ATTN_SUBLAYERS = ("query", "key", "value", "out")


def _sn_state_init(key, params: Pytree, state: Pytree) -> None:
    """Power-iteration u vectors for every weight in `params` (one level of
    nesting for the attention block), written into `state` as sn_* leaves —
    the explicit-state mirror of torch's hidden SN buffers."""
    j = 0
    for name in sorted(params):
        p = params[name]
        if "w" in p:
            state[f"sn_{name}"] = spectral_u_init(
                jax.random.fold_in(key, j), p["w"].shape[-1])
            j += 1
        elif name == "attn":
            for sub in _ATTN_SUBLAYERS:
                state[f"sn_attn_{sub}"] = spectral_u_init(
                    jax.random.fold_in(key, j), p[sub]["w"].shape[-1])
                j += 1


def _sn_layer(params: Pytree, state: Pytree, new_state: Pytree, name: str,
              train: bool) -> Pytree:
    """params[name] with its weight spectrally normalized; advances the
    layer's u into new_state (train=True) or carries it unchanged."""
    w_sn, u = spectral_normalize(params[name]["w"], state[f"sn_{name}"],
                                 train=train)
    new_state[f"sn_{name}"] = u
    return {**params[name], "w": w_sn}


def _sn_attn(params_attn: Pytree, state: Pytree, new_state: Pytree,
             train: bool) -> Pytree:
    out = dict(params_attn)
    for sub in _ATTN_SUBLAYERS:
        w_sn, u = spectral_normalize(params_attn[sub]["w"],
                                     state[f"sn_attn_{sub}"], train=train)
        new_state[f"sn_attn_{sub}"] = u
        out[sub] = {**params_attn[sub], "w": w_sn}
    return out


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


# ---------------------------------------------------------------------------
# Generator
# ---------------------------------------------------------------------------

def generator_init(key, cfg: ModelConfig) -> Tuple[Pytree, Pytree]:
    """Returns (params, bn_state) for the generator."""
    if cfg.arch == "resnet":
        from dcgan_tpu.models import resnet

        return resnet.generator_init(key, cfg)
    if cfg.arch == "stylegan":
        from dcgan_tpu.models import stylegan

        return stylegan.generator_init(key, cfg)
    k = cfg.num_up_layers
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 2 * k + 2)

    in_dim = cfg.z_dim + (cfg.num_classes if cfg.num_classes else 0)
    top_ch = cfg.gf_dim * (2 ** (k - 1))
    params: Pytree = {
        "proj": linear_init(keys[0], in_dim, top_ch * cfg.base_size * cfg.base_size,
                            dtype=dtype),
    }
    state: Pytree = {}
    bn_classes = cfg.num_classes if cfg.conditional_bn else 0
    bn_p, bn_s = batch_norm_init(keys[1], top_ch, dtype=dtype,
                                 num_classes=bn_classes)
    params["bn0"], state["bn0"] = bn_p, bn_s

    in_ch = top_ch
    for i in range(1, k + 1):
        out_ch = cfg.c_dim if i == k else cfg.gf_dim * (2 ** (k - 1 - i))
        params[f"deconv{i}"] = deconv2d_init(
            keys[2 * i], in_ch, out_ch, kernel=cfg.kernel_size, dtype=dtype)
        if i < k:
            bn_p, bn_s = batch_norm_init(keys[2 * i + 1], out_ch, dtype=dtype,
                                         num_classes=bn_classes)
            params[f"bn{i}"], state[f"bn{i}"] = bn_p, bn_s
        in_ch = out_ch
    if cfg.attn_res:
        # channels of the stage whose output feature map is attn_res:
        # stage 0 (base_size) has top_ch; stage i (base_size*2^i) has
        # gf_dim * 2^(k-1-i). keys[2k+1] is unused above (stage k has no BN).
        i = int(round(math.log2(cfg.attn_res / cfg.base_size)))
        ch = top_ch if i == 0 else cfg.gf_dim * (2 ** (k - 1 - i))
        params["attn"] = attn_init(keys[2 * k + 1], ch, dtype=dtype)
    if cfg.spectral_norm == "gd":
        # u keys derive from a fold_in of the net key so existing layer init
        # streams (keys[...]) are untouched whatever the flag
        _sn_state_init(jax.random.fold_in(key, 0x53AE), params, state)
    return params, state


@jax.named_scope("gen")
def generator_apply(params: Pytree, state: Pytree, z: jax.Array, *,
                    cfg: ModelConfig, train: bool,
                    labels: Optional[jax.Array] = None,
                    axis_name: Optional[str] = None,
                    attn_mesh=None,
                    pallas_mesh=None,
                    capture: Optional[dict] = None
                    ) -> Tuple[jax.Array, Pytree]:
    """z [B, z_dim] (-1..1) -> image [B, S, S, c_dim] in tanh range.

    train=True uses batch BN statistics and returns updated EMA state;
    train=False is the reference's `sampler` path (running stats, state
    unchanged).

    `capture`, when a dict, receives every post-activation tensor keyed
    "h0".."hk" — the functional replacement for the reference's
    `_activation_summary` calls inside the layer stack
    (distriubted_model.py:75-80,94-110); callers turn them into
    histogram/sparsity summaries (utils/metrics.py).
    """
    if cfg.arch == "resnet":
        from dcgan_tpu.models import resnet

        return resnet.generator_apply(
            params, state, z, cfg=cfg, train=train, labels=labels,
            axis_name=axis_name, attn_mesh=attn_mesh,
            pallas_mesh=pallas_mesh, capture=capture)
    if cfg.arch == "stylegan":
        from dcgan_tpu.models import stylegan

        return stylegan.generator_apply(
            params, state, z, cfg=cfg, train=train, labels=labels,
            axis_name=axis_name, attn_mesh=attn_mesh,
            pallas_mesh=pallas_mesh, capture=capture)
    k = cfg.num_up_layers
    cdt = _cdtype(cfg)
    new_state: Pytree = {}
    sn = cfg.spectral_norm == "gd"

    def layer(name):
        return _sn_layer(params, state, new_state, name, train) if sn \
            else params[name]

    def attn_params():
        return _sn_attn(params["attn"], state, new_state, train) if sn \
            else params["attn"]

    if cfg.num_classes:
        if labels is None:
            raise ValueError("conditional generator requires labels")
        onehot = jax.nn.one_hot(labels, cfg.num_classes, dtype=z.dtype)
        z = jnp.concatenate([z, onehot], axis=-1)

    with jax.named_scope("proj"):
        top_ch = cfg.gf_dim * (2 ** (k - 1))
        h = linear_apply(layer("proj"), z.astype(cdt), compute_dtype=cdt)
        h = h.reshape(-1, cfg.base_size, cfg.base_size, top_ch)
        bn_labels = labels if cfg.conditional_bn else None
        h, new_state["bn0"] = batch_norm_apply(
            params["bn0"], state["bn0"], h, train=train,
            momentum=cfg.bn_momentum, eps=cfg.bn_eps, axis_name=axis_name,
            act="relu", labels=bn_labels)
    if cfg.attn_res == cfg.base_size:
        h = attn_apply(attn_params(), h, compute_dtype=cdt,
                       num_heads=cfg.attn_heads,
                       seq_strategy=cfg.attn_seq_strategy,
                       seq_mesh=attn_mesh, use_pallas=cfg.use_pallas,
                       pallas_mesh=pallas_mesh)
    if capture is not None:
        capture["h0"] = h

    for i in range(1, k + 1):
        with jax.named_scope(f"deconv{i}"):
            h = deconv2d_apply(layer(f"deconv{i}"), h, compute_dtype=cdt)
            if i < k:
                h, new_state[f"bn{i}"] = batch_norm_apply(
                    params[f"bn{i}"], state[f"bn{i}"], h, train=train,
                    momentum=cfg.bn_momentum, eps=cfg.bn_eps,
                    axis_name=axis_name, act="relu", labels=bn_labels)
        if i < k:
            if cfg.attn_res == cfg.base_size * (2 ** i):
                h = attn_apply(attn_params(), h, compute_dtype=cdt,
                               num_heads=cfg.attn_heads,
                               seq_strategy=cfg.attn_seq_strategy,
                               seq_mesh=attn_mesh,
                               use_pallas=cfg.use_pallas,
                               pallas_mesh=pallas_mesh)
            if capture is not None:
                capture[f"h{i}"] = h

    out = jnp.tanh(h.astype(jnp.float32))
    if capture is not None:
        capture[f"h{k}"] = out
    return out, new_state


def sampler_apply(params: Pytree, state: Pytree, z: jax.Array, *,
                  cfg: ModelConfig,
                  labels: Optional[jax.Array] = None,
                  pallas_mesh=None) -> jax.Array:
    """Inference-mode generation (reference `sampler`, distriubted_model.py:131)."""
    img, _ = generator_apply(params, state, z, cfg=cfg, train=False,
                             labels=labels, pallas_mesh=pallas_mesh)
    return img


# ---------------------------------------------------------------------------
# Discriminator
# ---------------------------------------------------------------------------

def discriminator_init(key, cfg: ModelConfig) -> Tuple[Pytree, Pytree]:
    """Returns (params, bn_state) for the discriminator.

    Stage 0 has no BN, matching the reference (distriubted_model.py:118; its
    `d_bn0` is created but never used — SURVEY.md §2.4 #7 — we don't create one).
    """
    if cfg.arch in ("resnet", "stylegan"):
        # the stylegan family pairs its G with the same norm-free residual
        # critic (StyleGAN2's own D is a plain resnet; pair with --r1_gamma)
        from dcgan_tpu.models import resnet

        return resnet.discriminator_init(key, cfg)
    k = cfg.num_up_layers
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 2 * k + 2)

    params: Pytree = {}
    state: Pytree = {}
    in_ch = cfg.c_dim + (cfg.num_classes if cfg.num_classes else 0)
    for i in range(k):
        out_ch = cfg.df_dim * (2 ** i)
        params[f"conv{i}"] = conv2d_init(
            keys[2 * i], in_ch, out_ch, kernel=cfg.kernel_size, dtype=dtype)
        if i > 0:
            bn_p, bn_s = batch_norm_init(keys[2 * i + 1], out_ch, dtype=dtype)
            params[f"bn{i}"], state[f"bn{i}"] = bn_p, bn_s
        in_ch = out_ch

    flat = cfg.base_size * cfg.base_size * cfg.df_dim * (2 ** (k - 1))
    params["head"] = linear_init(keys[-1], flat, 1, dtype=dtype)
    if cfg.attn_res:
        # stage i's output feature map is output_size / 2^(i+1) with
        # df_dim * 2^i channels. keys[2k] is unused above: conv keys are the
        # even indices 0..2k-2, BN keys the odd 3..2k-1, head takes 2k+1.
        i = int(round(math.log2(cfg.output_size / cfg.attn_res))) - 1
        params["attn"] = attn_init(keys[2 * k], cfg.df_dim * (2 ** i),
                                   dtype=dtype)
    if cfg.spectral_norm in ("d", "gd"):
        _sn_state_init(jax.random.fold_in(key, 0x53AE), params, state)
    return params, state


@jax.named_scope("disc")
def discriminator_apply(params: Pytree, state: Pytree, image: jax.Array, *,
                        cfg: ModelConfig, train: bool,
                        labels: Optional[jax.Array] = None,
                        axis_name: Optional[str] = None,
                        attn_mesh=None,
                        pallas_mesh=None,
                        capture: Optional[dict] = None
                        ) -> Tuple[jax.Array, jax.Array, Pytree]:
    """image [B, S, S, c] -> (sigmoid(logit), logit [B, 1], new_bn_state).

    `capture` (dict) receives post-activation tensors "h0".."h{k-1}" plus the
    final "logit" — see generator_apply.
    """
    if cfg.arch in ("resnet", "stylegan"):
        from dcgan_tpu.models import resnet

        return resnet.discriminator_apply(
            params, state, image, cfg=cfg, train=train, labels=labels,
            axis_name=axis_name, attn_mesh=attn_mesh,
            pallas_mesh=pallas_mesh, capture=capture)
    k = cfg.num_up_layers
    cdt = _cdtype(cfg)
    new_state: Pytree = {}
    sn = cfg.spectral_norm in ("d", "gd")

    def layer(name):
        return _sn_layer(params, state, new_state, name, train) if sn \
            else params[name]

    def attn_params():
        return _sn_attn(params["attn"], state, new_state, train) if sn \
            else params["attn"]

    h = image.astype(cdt)
    if cfg.num_classes:
        if labels is None:
            raise ValueError("conditional discriminator requires labels")
        onehot = jax.nn.one_hot(labels, cfg.num_classes, dtype=h.dtype)
        maps = jnp.broadcast_to(onehot[:, None, None, :],
                                h.shape[:3] + (cfg.num_classes,))
        h = jnp.concatenate([h, maps], axis=-1)

    for i in range(k):
        with jax.named_scope(f"conv{i}"):
            h = conv2d_apply(layer(f"conv{i}"), h, compute_dtype=cdt)
            if i > 0:
                # BN + lrelu (stage 0 keeps the reference's no-BN shape)
                h, new_state[f"bn{i}"] = batch_norm_apply(
                    params[f"bn{i}"], state[f"bn{i}"], h, train=train,
                    momentum=cfg.bn_momentum, eps=cfg.bn_eps,
                    axis_name=axis_name, act="lrelu", leak=cfg.leak)
            else:
                h = lrelu(h, cfg.leak)
        if cfg.attn_res and cfg.attn_res == cfg.output_size >> (i + 1):
            h = attn_apply(attn_params(), h, compute_dtype=cdt,
                           num_heads=cfg.attn_heads,
                           seq_strategy=cfg.attn_seq_strategy,
                           seq_mesh=attn_mesh, use_pallas=cfg.use_pallas,
                           pallas_mesh=pallas_mesh)
        if capture is not None:
            capture[f"h{i}"] = h

    with jax.named_scope("head"):
        h = h.reshape(h.shape[0], -1)
        logit = linear_apply(layer("head"), h, compute_dtype=cdt)
    logit = logit.astype(jnp.float32)
    if capture is not None:
        capture["logit"] = logit
    return jax.nn.sigmoid(logit), logit, new_state


# ---------------------------------------------------------------------------
# Whole-GAN convenience
# ---------------------------------------------------------------------------

def gan_init(key, cfg: ModelConfig) -> Tuple[Pytree, Pytree]:
    """Initialize both networks.

    Returns (params, state) with params = {"gen": ..., "disc": ...} — the
    structural replacement for the reference's fragile substring split of one
    flat variable list (`'d_' in name` / `'g_' in name`, image_train.py:107-108,
    SURVEY.md §2.4 #6).
    """
    kg, kd = jax.random.split(key)
    g_params, g_state = generator_init(kg, cfg)
    d_params, d_state = discriminator_init(kd, cfg)
    return ({"gen": g_params, "disc": d_params},
            {"gen": g_state, "disc": d_state})
