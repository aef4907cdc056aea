"""What the token archs share (models/mla_moe.py, models/loop_lm.py,
models/sambay.py): the
matmul of the precision policy, RMSNorm, rotary (and the pair-to-halves
relayout of interleaved rotary dimensions), SwiGLU, dense masked
attention for short sequences, the chunked head + loss, and what a block
keeps across its recomputation.

Precision policy (ops/layers.py's): float32 parameters, matmul operands in
`compute_dtype` with float32 accumulation; softmax, the norms' statistics
and the loss in float32. `cfg` is either arch's model config: the pieces
read `compute_dtype`, `param_dtype` and `rms_norm_eps` of it and nothing
else.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from dcgan_tpu.ops.pallas_attention import FLASH_LSE_NAME, FLASH_OUT_NAME

Pytree = Any

#: tokens of one chunk of the head + loss (logits of one chunk live at a time)
LOSS_CHUNK = 2048


#: what a recomputed block keeps beside its input: the flash forward's two
#: outputs as the kernel wrote them (float32 o^T, one more activation of the
#: block's width, and the log-sum-exp; the backward's `delta` reads float32
#: o^T, so a rounded copy would be another result)
KEPT_NAMES = (FLASH_OUT_NAME, FLASH_LSE_NAME)
_KEEP = jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES)


def recomputed(block, keep=KEPT_NAMES):
    """`block` as a function whose backward pass recomputes it from its
    inputs, the attention kernel's outputs excepted: projections, rotary,
    norms and the feed-forward run again, the flash forward does not (its
    outputs are the residuals its backward takes, ops/pallas_attention.py).
    With the dense fallback nothing carries the names and the input is all
    that is kept. `keep`: the `checkpoint_name`s kept, for an arch whose
    blocks hold another kernel beside attention (models/sambay.py)."""
    policy = _KEEP if keep == KEPT_NAMES else \
        jax.checkpoint_policies.save_only_these_names(*keep)
    return jax.checkpoint(block, policy=policy)


def dtypes(cfg):
    return jnp.dtype(cfg.compute_dtype), jnp.dtype(cfg.param_dtype)


def normal(key, shape, dtype, std=0.02):
    return std * jax.random.normal(key, shape, dtype)


def swiglu_init(key, h: int, width: int, dt) -> Pytree:
    ks = jax.random.split(key, 3)
    return {"gate": {"w": normal(ks[0], (h, width), dt)},
            "up": {"w": normal(ks[1], (h, width), dt)},
            "down": {"w": normal(ks[2], (width, h), dt)}}


def mm(x, w, cd, out=jnp.float32):
    """x @ w with operands in the compute dtype and float32 accumulation."""
    return jnp.dot(x.astype(cd), w.astype(cd),
                   preferred_element_type=jnp.float32).astype(out)


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rotary_tables(seq_len: int, dim: int, theta: float):
    """cos, sin [S, dim] (float32) for the half-split rotation."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def pairs_to_halves(x):
    """The last axis read as pairs (2i, 2i+1) and laid out as halves (the
    first members, then the second): a static reshape-transpose, so its
    transpose is one too (no strided slices, no scatter-add). Applied to a
    weight's columns it de-interleaves what the product gives."""
    d = x.shape[-1]
    return jnp.swapaxes(x.reshape(*x.shape[:-1], d // 2, 2), -1, -2
                        ).reshape(x.shape)


def apply_rotary(x, cos, sin, interleave: bool):
    """Rotary embedding over the last axis of x [..., S, d] (float32). With
    `interleave` the stored dimensions are pairs (2i, 2i+1), de-interleaved
    to halves before the rotation; without, the halves are rotated as they
    lie (the `rotate_half` form)."""
    x = x.astype(jnp.float32)
    if interleave:
        x = pairs_to_halves(x)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def dense_causal_attention(q, k, v, scale: float):
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    n = q.shape[1]
    keep = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def swiglu_apply(p: Pytree, x, cd):
    g = mm(x, p["gate"]["w"], cd)
    u = mm(x, p["up"]["w"], cd)
    return mm(jax.nn.silu(g) * u, p["down"]["w"], cd)


def head_loss(h, norm_scale, head_w, targets, weights, cfg,
              chunk: int = LOSS_CHUNK, norm=None):
    """Sum over positions of weight x cross-entropy of
    `RMSNorm(h) @ head_w` against `targets`, over [B, S, H] / [B, S], in
    chunks of `chunk` tokens so that one chunk's logits live at a time
    (each chunk recomputed in the backward pass). `norm_scale` None: `h`
    is normed already, or `norm` (a function of one chunk [chunk, H]) is
    the final norm in RMSNorm's place (models/sambay.py: LayerNorm with
    bias). `weights` is [B, S], or [K, B, S] for K weighted
    sums of the same cross-entropies (the result is [K]); a weight may
    carry gradient."""
    cd, _ = dtypes(cfg)
    n = targets.size
    lead = weights.shape[:weights.ndim - targets.ndim]
    chunk = chunk if n % chunk == 0 else n
    h = h.reshape(n // chunk, chunk, h.shape[-1])
    targets = targets.reshape(n // chunk, chunk)
    weights = weights.reshape(lead + (n // chunk, chunk))
    if lead:
        weights = jnp.moveaxis(weights, -2, 0)

    @jax.checkpoint
    def one(hc, tc, wc):
        if norm is not None:
            hc = norm(hc)
        elif norm_scale is not None:
            hc = rms_norm(hc, norm_scale, cfg.rms_norm_eps)
        logits = mm(hc, head_w, cd)
        with jax.named_scope("loss"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            hit = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
            return jnp.sum(wc * (lse - hit), axis=-1)

    with jax.named_scope("head"):
        def body(total, xs):
            return total + one(*xs), None
        total, _ = jax.lax.scan(body, jnp.zeros(lead, jnp.float32),
                                (h, targets, weights))
    return total
