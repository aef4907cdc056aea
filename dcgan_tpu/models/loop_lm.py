"""A looped causal language model: one stack of layers run several times.

The one-network family of `LoopModelConfig` (arch "loop_lm"), after Ouro
(ByteDance, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741), pure init/apply like the other families:

- layer `l` of the stack, its parameters shared by every pass, over x
  [B, S, H] (float32): `a = RMSNorm(x; attn_norm)`; `q, k, v = a Wq, a Wk,
  a Wv` (no bias; heads x head_dim); rotary on q and k over the WHOLE head
  in the `rotate_half` form; causal softmax attention; `o Wo`;
  `x += RMSNorm(o Wo; attn_out_norm)` (the sandwich: a branch's output is
  normed before it is added); `b = RMSNorm(x; ffn_norm)`;
  `x += RMSNorm(SwiGLU(b); ffn_out_norm)`;
- the loop: `x_0 = E[ids]`; for `t = 1..T` (`T = total_ut_steps`):
  `x_t = RMSNorm(Stack(x_{t-1}); final_norm)`: the final norm is inside the
  loop and the next pass starts from the normed state;
  `lambda_t = sigmoid(x_t w_e + b_e)` per token (the exit gate, float32);
  `logits_t = x_t W_head` (one head for every exit, untied from `E`);
- the exit distribution per token: `p_1 = lambda_1`, `p_t = lambda_t
  prod_{j<t} (1 - lambda_j)`, and the last exit takes what is left,
  `p_T = prod_{j<T} (1 - lambda_j)`;
- the loss: with `l_t(i)` the cross-entropy of `logits_t` at position `i`
  against token `i+1`, `mean_i [sum_t p_t(i) l_t(i) - loss_beta H(p(i))]`
  over positions 0..S-2, `H` the entropy of the T-way distribution.
  Gradients reach the gate through `p` and through `H`.

Every weight of the stack is used T times in one step: the passes are ONE
rolled `lax.scan` over `t` with the stack's parameters closed over it, so
the program holds one copy of the stack and the scan's backward accumulates
each leaf's gradient over its T uses. Each block is recomputed in the
backward pass (`token_ops.recomputed`): T x layers block inputs are kept,
not layers, and with each the flash forward's outputs (float32 o^T and
log-sum-exp), so the recomputation runs no attention kernel.

Not here: the second training stage that fits the gate to the measured gain
of each pass, and inference with early exit (`early_exit_threshold`) and a
cache per pass: no token arch has a serving path.

Precision policy and the shared pieces: models/token_ops.py. The gate, the
exit distribution and its entropy are float32.

Scopes (`jax.named_scope`, PERF.md section 3): `embed`, `loop` (the stack
of one pass and the final norm), `block<i>` with `attn_block` (`qkv_proj`,
`rope`, `attn`, `o_proj`) and `ffn`, `exit` (gate, distribution, entropy),
`head` (with `loss` inside it). Kernels: `flash_fwd` / `flash_dq_dkv`
(causal).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from dcgan_tpu.config import LoopModelConfig
from dcgan_tpu.models.token_ops import (apply_rotary, dense_causal_attention,
                                        dtypes, head_loss, mm, normal,
                                        recomputed, rms_norm, rotary_tables,
                                        swiglu_apply, swiglu_init)
from dcgan_tpu.ops.pallas_attention import flash_attention

Pytree = Any

#: tokens of one chunk of the head + loss: at 49,152 rows of vocabulary a
#: chunk's float32 logits are 96 MiB (the step's temporaries: 6.39 GB at 512,
#: 6.73 GB at 1,024; compile, PR 31)
LOSS_CHUNK = 512
#: floor under a probability inside `p log p`
_TINY = 1e-30


# --- init ---------------------------------------------------------------------

def _block_init(key, cfg: LoopModelConfig, dt) -> Pytree:
    h, width = cfg.hidden_size, cfg.num_attention_heads * cfg.head_dim
    ks = jax.random.split(key, 5)
    ones = lambda: {"scale": jnp.ones((h,), dt)}
    return {"attn_norm": ones(),
            "q_proj": {"w": normal(ks[0], (h, width), dt)},
            "k_proj": {"w": normal(ks[1], (h, width), dt)},
            "v_proj": {"w": normal(ks[2], (h, width), dt)},
            "o_proj": {"w": normal(ks[3], (width, h), dt)},
            "attn_out_norm": ones(),
            "ffn_norm": ones(),
            "ffn": swiglu_init(ks[4], h, cfg.intermediate_size, dt),
            "ffn_out_norm": ones()}


def loop_init(key, cfg: LoopModelConfig) -> Pytree:
    """The parameters: embedding, ONE stack of blocks, the loop's final
    norm, the exit gate (`[H, 1]` and a bias) and the head."""
    _, dt = dtypes(cfg)
    n, h = cfg.num_hidden_layers, cfg.hidden_size
    ks = jax.random.split(key, n + 3)
    params = {"embed": {"table": normal(ks[0], (cfg.vocab_size, h), dt)}}
    for i in range(n):
        params[f"block{i}"] = _block_init(ks[1 + i], cfg, dt)
    params["final_norm"] = {"scale": jnp.ones((h,), dt)}
    params["exit_gate"] = {"w": normal(ks[n + 1], (h, 1), dt),
                           "b": jnp.zeros((1,), dt)}
    params["lm_head"] = {"w": normal(ks[n + 2], (h, cfg.vocab_size), dt)}
    return params


# --- pieces ---------------------------------------------------------------------

def attn_apply(p: Pytree, x, cfg: LoopModelConfig, rope):
    """Multi-head causal attention over x [B, S, H] (already normed)."""
    cd, _ = dtypes(cfg)
    b, s, _ = x.shape
    nh, d = cfg.num_attention_heads, cfg.head_dim
    heads = lambda a: jnp.swapaxes(a.reshape(b, s, nh, d), 1, 2)
    with jax.named_scope("qkv_proj"):
        q = heads(mm(x, p["q_proj"]["w"], cd))              # [B, nh, S, d]
        k = heads(mm(x, p["k_proj"]["w"], cd))
        v = heads(mm(x, p["v_proj"]["w"], cd, out=cd))
    with jax.named_scope("rope"):
        cos, sin = rope
        q = apply_rotary(q, cos, sin, False).astype(cd)
        k = apply_rotary(k, cos, sin, False).astype(cd)
    with jax.named_scope("attn"):
        fold = lambda a: a.reshape(b * nh, s, d)
        scale = float(d) ** -0.5
        if cfg.use_pallas:
            o = flash_attention(fold(q), fold(k), fold(v), scale, True)
        else:
            o = dense_causal_attention(fold(q), fold(k), fold(v), scale)
        o = jnp.swapaxes(o.reshape(b, nh, s, d), 1, 2).reshape(b, s, nh * d)
    with jax.named_scope("o_proj"):
        return mm(o, p["o_proj"]["w"], cd)


def block_apply(p: Pytree, x, cfg: LoopModelConfig, rope, name: str):
    """One sandwich-norm residual block over x [B, S, H] (float32). It
    names its own scopes (`loop/<name>/..`): a scope set where the
    recomputed block is CALLED is repeated in the backward pass's paths
    (`loop/block0/loop/block0/checkpoint/..`), and a reader that sums the
    paths ending in `loop` would count those operations twice."""
    cd, _ = dtypes(cfg)
    eps = cfg.rms_norm_eps
    with jax.named_scope("loop"), jax.named_scope(name):
        with jax.named_scope("attn_block"):
            o = attn_apply(p, rms_norm(x, p["attn_norm"]["scale"], eps), cfg,
                           rope)
            x = x + rms_norm(o, p["attn_out_norm"]["scale"], eps)
        with jax.named_scope("ffn"):
            m = swiglu_apply(p["ffn"],
                             rms_norm(x, p["ffn_norm"]["scale"], eps), cd)
            return x + rms_norm(m, p["ffn_out_norm"]["scale"], eps)


def loop_loss(params: Pytree, ids, cfg: LoopModelConfig
              ) -> Tuple[jax.Array, Dict[str, Any]]:
    """The objective of one batch of ids [B, S] (int32). Returns (loss,
    {"loss", "loss_ut": [T] each exit's mean cross-entropy, "exit_mass":
    [T] sum over the scored positions of p_t, "exit_entropy",
    "exit_mean_step": mean of sum_t t p_t, "attn_kept": the attention
    outputs the step keeps across its recomputation})."""
    b, s = ids.shape
    steps = cfg.total_ut_steps
    scored = b * (s - 1)
    rope = rotary_tables(s, cfg.head_dim, cfg.rope_theta)
    # every block is recomputed in the backward pass: its input and its
    # attention kernel's outputs are kept of it, once per pass
    blocks = {f"block{i}": recomputed(functools.partial(
        block_apply, cfg=cfg, rope=rope, name=f"block{i}"))
        for i in range(cfg.num_hidden_layers)}
    gate = params["exit_gate"]
    mask = jnp.broadcast_to(jnp.arange(s)[None, :] < s - 1, (b, s)
                            ).astype(jnp.float32)
    nxt = jnp.roll(ids, -1, axis=1)
    with jax.named_scope("embed"):
        x0 = params["embed"]["table"][ids].astype(jnp.float32)

    def one_pass(carry, t):
        x, left = carry                 # left: prod_{j<t} (1 - lambda_j)
        for name, block in blocks.items():
            x = block(params[name], x)
        with jax.named_scope("loop"):
            x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
        with jax.named_scope("exit"):
            lam = jax.nn.sigmoid(jnp.dot(
                x, gate["w"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)[..., 0]
                + gate["b"].astype(jnp.float32))
            p = jnp.where(t == steps - 1, left, lam * left)
            left = left * (1.0 - lam)
            plogp = jnp.sum(mask * p * jnp.log(jnp.maximum(p, _TINY)))
            mass = jnp.sum(mask * p)
        weighted, plain = head_loss(
            x, None, params["lm_head"]["w"], nxt, jnp.stack([mask * p, mask]),
            cfg, chunk=LOSS_CHUNK)
        return (x, left), (weighted, plain, plogp, mass)

    # rolled: `unroll=True` on this scan alone steps in 600.4 ms against
    # 609.1, compiles in 50 s against 36 and reserves 2.1 GB more, 15.2 of
    # the chip's 16.9 GB held (my chip runs, PR 31; PERF.md section 6)
    _, (weighted, plain, plogp, mass) = jax.lax.scan(
        one_pass, (x0, jnp.ones((b, s), jnp.float32)), jnp.arange(steps))
    entropy = -jnp.sum(plogp) / scored
    loss = jnp.sum(weighted) / scored - cfg.loss_beta * entropy
    return loss, {
        "loss": loss, "loss_ut": plain / scored, "exit_mass": mass,
        "exit_entropy": entropy,
        "exit_mean_step": jnp.sum(
            jnp.arange(1, steps + 1, dtype=jnp.float32) * mass) / scored,
        "attn_kept": jnp.float32(
            steps * len(blocks) if cfg.use_pallas else 0)}


# --- what the likelihood step asks of a token arch (train/steps.py) ------------

#: state entries the loss reads beside the parameters (none); aux entries
#: averaged / summed over the data shards (`attn_kept`, a constant of the
#: program and the same on every shard, is neither)
LM_READS = ()
LM_MEAN = ("loss", "loss_ut", "exit_entropy", "exit_mean_step")
LM_SUM = ("exit_mass",)


def lm_init(key, cfg: LoopModelConfig) -> Pytree:
    """The state beside optimizer and step: the parameters and the per-exit
    mass the step accumulates."""
    return {"params": loop_init(key, cfg),
            "exit_mass": jnp.zeros((cfg.total_ut_steps,), jnp.float32)}


def lm_loss(params: Pytree, state: Pytree, ids, cfg: LoopModelConfig):
    del state
    return loop_loss(params, ids, cfg)


def lm_metrics(aux: Dict[str, Any]) -> Dict[str, jax.Array]:
    return {"loss": aux["loss"],
            **{f"loss_ut{t + 1}": aux["loss_ut"][t]
               for t in range(aux["loss_ut"].shape[0])},
            "exit_entropy": aux["exit_entropy"],
            "exit_mean_step": aux["exit_mean_step"],
            # attention outputs kept across the recomputation, a chip
            "attn_outputs_kept": aux["attn_kept"]}


def lm_accumulate(state: Pytree, aux: Dict[str, Any]) -> Pytree:
    return {"exit_mass": state["exit_mass"] + aux["exit_mass"]}
