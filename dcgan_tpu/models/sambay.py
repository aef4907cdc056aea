"""A decoder-hybrid-decoder causal language model: Mamba scans, window,
full and cross differential attention and gated memory units in one stack.

The one-network family of `SambaYModelConfig` (arch "sambay"), after
Phi-4-mini-flash-reasoning (microsoft; SambaY with differential attention,
"Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation", arXiv:2507.06607; the scan is Mamba-1's, arXiv:2312.00752;
differential attention arXiv:2410.05258), pure init/apply like the other
families. Over x [B, S, H] (float32) every layer is

    x = x + Mixer(LN1(x));  x = x + W2(silu(g) * u),  [g, u] = W1 LN2(x)

(`LN` is LayerNorm with gain and bias; no bias in the SwiGLU), with one of
five mixers, `cfg.layer_types[i]`:

- `mamba`: `[u, z] = W_in h`; `u = silu(conv(u))` (depthwise, causal, K
  taps over `t-K+1..t`, with bias); `[r, B, C] = W_x u`; `dt = softplus(W_dt
  r + b_dt)`; `A = -exp(A_log)`; the selective scan `s_t = exp(dt_t A) s_{t-1}
  + (dt_t u_t) B_t^T`, `y_t = s_t C_t + D u_t` from `s_0 = 0`; out
  `= W_out(y * silu(z))`. The MEMORY layer (`cfg.memory_layer`, the last
  Mamba layer before the full-attention layer) also hands on `m = y`,
  before the gate.
- `attn_win`, `attn_full` (differential attention): `[q, k, v] = W_qkv h +
  b`; the heads pair up: query pair `p` is `(q1, q2)`, two heads side by
  side, and reads key/value pair `p // (pairs / kv pairs)`: `(k1, k2)` and
  ONE value set `v` two heads wide. `a_j = softmax(q_j k_j^T / sqrt(d) +
  mask) v`; `lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`,
  `lambda_init = 0.8 - 0.6 exp(-0.3 i)` at layer index `i`; `o =
  RMSNorm(a_1 - lambda a_2) (1 - lambda_init)` (a learned gain two heads
  wide); out `= W_o o + b_o`. Causal; in `attn_win` position `i` sees
  `i - window + 1..i`. The ONE `attn_full` layer hands `k1, k2, v` on.
- `attn_cross`: `q = W_q h + b` only; the full layer's `k1, k2, v`; causal;
  its own lambdas, norm and `W_o`.
- `gmu` (gated memory unit): out `= W_out(silu(W_in h) * m)`, `m` the
  memory layer's.

`x_0 = E[ids]` (no scale, no positional encoding anywhere); after the last
layer a final LayerNorm; logits `= h E^T` (the head is the embedding, ONE
leaf with two uses); the loss is the mean next-token cross-entropy over
positions 0..S-2.

Two tensors cross blocks: `m` and the shared `(k1, k2, v)`. A block under
`token_ops.recomputed` therefore takes and returns more than the residual
stream, and a producer's gradient is the sum over its own layer's use and
every consumer's, which the autodiff of the checkpointed blocks forms. Per
attention layer ONE `flash_attention` call over the folded rows `[q1; q2]`
(the matching keys repeated, `[v; v]`), q/k one head wide, v two; the
subtraction, the norm and the lambdas are XLA's. Kept across a block's
recomputation beside its inputs (`m` and the shared keys/values are inputs
of their consumers): the flash forward's outputs and the scan's output and
chunk-boundary states, so the recomputation runs neither kernel's forward
(PERF.md section 6, PR 33 has the sizes).

Precision policy and the shared pieces: models/token_ops.py. `dt`, `exp`,
the scan's state, the norms, the softmax and the loss are float32.

Scopes (`jax.named_scope`, PERF.md section 3): `embed`; `block<i>` with one
of `mamba` (`in_proj`, `conv`, `dt_proj`, `scan`, `out_proj`), `attn_win` /
`attn_full` / `attn_cross` (`qkv_proj`, `attn`, `diff`, `o_proj`), `gmu`,
and `mlp`; `head` (with `loss` inside it). Kernels: `ssm_scan_fwd` /
`ssm_scan_bwd`, `flash_fwd` / `flash_dq_dkv`, `flash_fwd_win` /
`flash_dq_dkv_win`.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from dcgan_tpu.config import SambaYModelConfig
from dcgan_tpu.models.token_ops import (KEPT_NAMES, dtypes, head_loss, mm,
                                        normal, recomputed, rms_norm,
                                        swiglu_apply, swiglu_init)
from dcgan_tpu.ops.pallas_attention import flash_attention
from dcgan_tpu.ops.pallas_scan import (SCAN_OUT_NAME, SCAN_STATE_NAME,
                                       causal_conv, selective_scan)

Pytree = Any

#: the gain norm after the subtraction (differential attention's), its own
SUBLN_EPS = 1e-5
#: what a recomputed block keeps beside its inputs: the attention kernel's
#: outputs and the scan's
KEPT = KEPT_NAMES + (SCAN_OUT_NAME, SCAN_STATE_NAME)


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


# --- init ---------------------------------------------------------------------

def _ln_init(h: int, dt) -> Pytree:
    return {"scale": jnp.ones((h,), dt), "bias": jnp.zeros((h,), dt)}


def _linear(key, fan_in: int, fan_out: int, dt, bias: bool = False) -> Pytree:
    p = {"w": normal(key, (fan_in, fan_out), dt)}
    if bias:
        p["b"] = jnp.zeros((fan_out,), dt)
    return p


def _mamba_init(key, cfg: SambaYModelConfig, dt) -> Pytree:
    h, di, n = cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state
    r, taps = cfg.dt_rank, cfg.mamba_d_conv
    ks = jax.random.split(key, 6)
    # dt starts in 0.001..0.1 (log-uniform): the bias is its inverse softplus
    step = jnp.exp(jax.random.uniform(ks[5], (di,), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    return {"in_proj": _linear(ks[0], h, 2 * di, dt),
            "conv": {"w": normal(ks[1], (taps, di), dt, std=taps ** -0.5),
                     "b": jnp.zeros((di,), dt)},
            "x_proj": _linear(ks[2], di, r + 2 * n, dt),
            "dt_proj": {"w": normal(ks[3], (r, di), dt, std=r ** -0.5),
                        "b": (step + jnp.log(-jnp.expm1(-step))).astype(dt)},
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                (di, n)).astype(dt),
            "D": jnp.ones((di,), dt),
            "out_proj": _linear(ks[4], di, h, dt)}


def _attn_init(key, cfg: SambaYModelConfig, dt, cross: bool) -> Pytree:
    h, d = cfg.hidden_size, cfg.head_dim
    kv = cfg.num_key_value_heads * d
    ks = jax.random.split(key, 6)
    first = {"q_proj": _linear(ks[0], h, h, dt, bias=True)} if cross else \
        {"qkv_proj": _linear(ks[0], h, h + 2 * kv, dt, bias=True)}
    return {**first,
            "o_proj": _linear(ks[1], h, h, dt, bias=True),
            **{name: normal(k, (d,), dt, std=0.1) for name, k in
               zip(("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"),
                   ks[2:])},
            "subln": {"scale": jnp.ones((2 * d,), dt)}}


def _gmu_init(key, cfg: SambaYModelConfig, dt) -> Pytree:
    k1, k2 = jax.random.split(key)
    return {"in_proj": _linear(k1, cfg.hidden_size, cfg.d_inner, dt),
            "out_proj": _linear(k2, cfg.d_inner, cfg.hidden_size, dt)}


def sambay_init(key, cfg: SambaYModelConfig) -> Pytree:
    """The parameters: the tied embedding, one block per layer (`norm1`,
    `mixer`, `norm2`, `mlp`), the final norm. No head leaf: the head is the
    embedding."""
    _, dt = dtypes(cfg)
    h, n = cfg.hidden_size, cfg.num_hidden_layers
    ks = jax.random.split(key, 2 * n + 1)
    params = {"embed": {"table": normal(ks[0], (cfg.vocab_size, h), dt)}}
    for i, kind in enumerate(cfg.layer_types):
        mixer = (_mamba_init(ks[1 + i], cfg, dt) if kind == "mamba" else
                 _gmu_init(ks[1 + i], cfg, dt) if kind == "gmu" else
                 _attn_init(ks[1 + i], cfg, dt, cross=kind == "attn_cross"))
        params[f"block{i}"] = {
            "norm1": _ln_init(h, dt), "mixer": mixer,
            "norm2": _ln_init(h, dt),
            "mlp": swiglu_init(ks[1 + n + i], h, cfg.intermediate_size, dt)}
    params["final_norm"] = _ln_init(h, dt)
    return params


# --- pieces ---------------------------------------------------------------------

def layer_norm(x, p: Pytree, eps: float):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) \
        * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _bias(p: Pytree):
    return p["b"].astype(jnp.float32)


def mamba_apply(p: Pytree, x, cfg: SambaYModelConfig):
    """The Mamba mixer over x [B, S, H] (normed): (out [B, S, H], the scan
    output y [B, S, d_inner] before the gate, the mean of dt)."""
    cd, _ = dtypes(cfg)
    di, n, r = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank
    with jax.named_scope("in_proj"):
        uz = mm(x, p["in_proj"]["w"], cd)
        u, z = uz[..., :di], uz[..., di:]
    with jax.named_scope("conv"):
        u = jax.nn.silu(causal_conv(u, p["conv"]["w"], p["conv"]["b"]))
    with jax.named_scope("dt_proj"):
        rbc = mm(u, p["x_proj"]["w"], cd)
        dt = jax.nn.softplus(mm(rbc[..., :r], p["dt_proj"]["w"], cd)
                             + _bias(p["dt_proj"]))
    with jax.named_scope("scan"):
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        y = selective_scan(u, dt, a, rbc[..., r:r + n], rbc[..., r + n:]) \
            + p["D"].astype(jnp.float32) * u
    with jax.named_scope("out_proj"):
        return mm(y * jax.nn.silu(z), p["out_proj"]["w"], cd), y, jnp.mean(dt)


def _pairs(a, n_pairs: int):
    """[B, S, n_pairs * 2 * d] -> the two heads of every pair, each
    [B, n_pairs, S, d]."""
    b, s, _ = a.shape
    a = jnp.moveaxis(a.reshape(b, s, n_pairs, 2, -1), 1, 3)   # [B,P,2,S,d]
    return a[:, :, 0], a[:, :, 1]


def diff_attention(p: Pytree, q, kv, cfg: SambaYModelConfig, index: int,
                   window):
    """Differential attention of the projected queries q [B, S, H] over the
    key/value set `kv` = (k1, k2 [B, kv pairs, S, d], v [B, kv pairs, S,
    2d]), then `W_o`: (out [B, S, H], lambda)."""
    cd, _ = dtypes(cfg)
    b, s, h = q.shape
    d, n_pairs = cfg.head_dim, cfg.num_attention_heads // 2
    k1, k2, v = kv
    rep = n_pairs // k1.shape[1]
    with jax.named_scope("attn"):
        q1, q2 = _pairs(q.astype(cd), n_pairs)
        each = lambda a: jnp.repeat(a, rep, axis=1)      # pair p reads p // rep
        fold = lambda a, c: jnp.concatenate([a, c], axis=1).reshape(
            b * 2 * n_pairs, s, a.shape[-1])
        o = flash_attention(fold(q1, q2), fold(each(k1), each(k2)),
                            fold(each(v), each(v)), float(d) ** -0.5, True,
                            window)
        o = o.reshape(b, 2, n_pairs, s, 2 * d)
    with jax.named_scope("diff"):
        f32 = lambda name: p[name].astype(jnp.float32)
        init = lambda_init(index)
        lam = jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1"))) \
            - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + init
        o = rms_norm(o[:, 0] - lam * o[:, 1], p["subln"]["scale"],
                     SUBLN_EPS) * (1.0 - init)
        o = jnp.moveaxis(o, 1, 2).reshape(b, s, h)
    with jax.named_scope("o_proj"):
        return mm(o, p["o_proj"]["w"], cd) + _bias(p["o_proj"]), lam


def self_attn_apply(p: Pytree, x, cfg: SambaYModelConfig, index: int, window):
    """`attn_win` / `attn_full` over x [B, S, H] (normed): (out, lambda,
    this layer's (k1, k2, v) in the compute type)."""
    cd, _ = dtypes(cfg)
    h, n_kv = cfg.hidden_size, cfg.num_key_value_heads // 2
    width = cfg.num_key_value_heads * cfg.head_dim
    with jax.named_scope("qkv_proj"):
        qkv = mm(x, p["qkv_proj"]["w"], cd) + _bias(p["qkv_proj"])
        k1, k2 = _pairs(qkv[..., h:h + width].astype(cd), n_kv)
        b, s, _ = x.shape
        v = jnp.moveaxis(qkv[..., h + width:].astype(cd).reshape(
            b, s, n_kv, -1), 1, 2)
    out, lam = diff_attention(p, qkv[..., :h], (k1, k2, v), cfg, index, window)
    return out, lam, (k1, k2, v)


def cross_attn_apply(p: Pytree, x, kv, cfg: SambaYModelConfig, index: int):
    cd, _ = dtypes(cfg)
    with jax.named_scope("qkv_proj"):
        q = mm(x, p["q_proj"]["w"], cd) + _bias(p["q_proj"])
    return diff_attention(p, q, kv, cfg, index, None)


def gmu_apply(p: Pytree, x, m, cfg: SambaYModelConfig):
    cd, _ = dtypes(cfg)
    return mm(jax.nn.silu(mm(x, p["in_proj"]["w"], cd)) * m,
              p["out_proj"]["w"], cd)


def block_apply(p: Pytree, x, m, kv, *, cfg: SambaYModelConfig, index: int):
    """Layer `index` over the residual stream x [B, S, H] (float32), with
    the memory `m` and the shared keys/values `kv` where its kind reads them
    (None otherwise). Returns (x, what the layer hands on: `m` from the
    memory layer, `kv` from the full layer, else None, its counters). It
    names its own scopes (`block<i>/..`): a scope set where the recomputed
    block is CALLED is repeated in the backward pass's paths."""
    cd, _ = dtypes(cfg)
    kind, eps = cfg.layer_types[index], cfg.layer_norm_eps
    hands, stats = None, {}
    with jax.named_scope(f"block{index}"):
        with jax.named_scope(kind):
            a = layer_norm(x, p["norm1"], eps)
            if kind == "mamba":
                out, y, stats["dt"] = mamba_apply(p["mixer"], a, cfg)
                if index == cfg.memory_layer:
                    hands = y
            elif kind == "gmu":
                out = gmu_apply(p["mixer"], a, m, cfg)
            elif kind == "attn_cross":
                out, stats["lam"] = cross_attn_apply(p["mixer"], a, kv, cfg,
                                                     index)
            else:
                window = cfg.sliding_window if kind == "attn_win" else None
                out, stats["lam"], own = self_attn_apply(p["mixer"], a, cfg,
                                                         index, window)
                if kind == "attn_full":
                    hands = own
            x = x + out
        with jax.named_scope("mlp"):
            return x + swiglu_apply(p["mlp"], layer_norm(x, p["norm2"], eps),
                                    cd), hands, stats


def trunk(params: Pytree, ids, cfg: SambaYModelConfig):
    """The residual stream after the last layer over ids [B, S], before the
    final norm, with the memory `m` and the layers' counters (`dt`: each
    scan's mean step, `lam`: each attention layer's lambda)."""
    with jax.named_scope("embed"):
        x = params["embed"]["table"][ids].astype(jnp.float32)
    m, kv, dts, lams = None, None, [], []
    for i, kind in enumerate(cfg.layer_types):
        block = recomputed(functools.partial(block_apply, cfg=cfg, index=i),
                           keep=KEPT)
        x, hands, stats = block(
            params[f"block{i}"], x, m if kind == "gmu" else None,
            kv if kind == "attn_cross" else None)
        if kind == "mamba" and i == cfg.memory_layer:
            m = hands
        elif kind == "attn_full":
            kv = hands
        dts += [stats["dt"]] if "dt" in stats else []
        lams += [stats["lam"]] if "lam" in stats else []
    return x, m, {"dt": dts, "lam": lams}


def sambay_loss(params: Pytree, ids, cfg: SambaYModelConfig
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """The objective of one batch of ids [B, S] (int32): the mean next-token
    cross-entropy over positions 0..S-2. Returns (loss, {"loss", "dt_mean":
    mean of dt over the scans, "mem_rms" and "mem_abs" [d_inner]: the root
    mean square of the memory `m` and its per-channel mean |m|,
    "diff_lambda": mean of the attention layers' lambda, "attn_kept": the
    attention outputs the step keeps across its recomputation})."""
    b, s = ids.shape
    x, m, stats = trunk(params, ids, cfg)
    mask = jnp.broadcast_to(jnp.arange(s)[None, :] < s - 1, (b, s)
                            ).astype(jnp.float32)
    norm = functools.partial(layer_norm, p=params["final_norm"],
                             eps=cfg.layer_norm_eps)
    total = head_loss(x, None, params["embed"]["table"].T,
                      jnp.roll(ids, -1, axis=1), mask, cfg, norm=norm)
    loss = total / (b * (s - 1))
    mean = lambda xs: sum(xs) / len(xs)
    return loss, {
        "loss": loss, "dt_mean": mean(stats["dt"]),
        "diff_lambda": mean(stats["lam"]),
        "mem_rms": jnp.sqrt(jnp.mean(jnp.square(m))),
        "mem_abs": jnp.mean(jnp.abs(m), axis=(0, 1)),
        "attn_kept": jnp.float32(len(stats["lam"]))}


# --- what the likelihood step asks of a token arch (train/steps.py) ------------

#: state entries the loss reads beside the parameters (none); aux entries
#: averaged over the data shards (`attn_kept`, a constant of the program, is
#: not)
LM_READS = ()
LM_MEAN = ("loss", "dt_mean", "diff_lambda", "mem_rms", "mem_abs")
LM_SUM = ()


def lm_init(key, cfg: SambaYModelConfig) -> Pytree:
    """The state beside optimizer and step: the parameters and the
    per-channel mean |m| of the memory, summed over the steps."""
    return {"params": sambay_init(key, cfg),
            "mem_abs": jnp.zeros((cfg.d_inner,), jnp.float32)}


def lm_loss(params: Pytree, state: Pytree, ids, cfg: SambaYModelConfig):
    del state
    return sambay_loss(params, ids, cfg)


def lm_metrics(aux: Dict[str, Any]) -> Dict[str, jax.Array]:
    return {"loss": aux["loss"], "dt_mean": aux["dt_mean"],
            "mem_rms": aux["mem_rms"], "diff_lambda": aux["diff_lambda"],
            # attention outputs kept across the recomputation, a chip
            "attn_outputs_kept": aux["attn_kept"]}


def lm_accumulate(state: Pytree, aux: Dict[str, Any]) -> Pytree:
    return {"mem_abs": state["mem_abs"] + aux["mem_abs"]}
