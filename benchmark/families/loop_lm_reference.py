"""Plain reference of the `loop_lm` family's likelihood step.

Float32 `jax.numpy` at matmul precision "highest", written from the layer
equations of Ouro (ByteDance, "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741) under the key names of the model's public
`config.json`; imports nothing of `dcgan_tpu` and takes nothing the program
made. One looped sequence model, one loss, its gradient, Adam:

- RMSNorm `x / sqrt(mean(x^2) + eps) * g`;
- layer `l` (shared by every pass): `a = RMSNorm(x; g1)`; `q, k, v = a Wq,
  a Wk, a Wv` (no bias), heads x head_dim; rotary over the whole head
  (`rotate_half`: the halves rotated as they lie), frequencies
  `theta^(-2i/d)`; scores `q k^T / sqrt(d)`, causal, softmax, `P v`, `Wo`;
  `x += RMSNorm(o; g2)`; `b = RMSNorm(x; g3)`; `x += RMSNorm((silu(b Wg) *
  (b Wu)) Wd; g4)`. Dense masked attention in QUERY CHUNKS (every chunk
  against all keys, the chunk checkpointed), so the [heads, S, S] scores
  never exist at once;
- the loop: `x_0 = E[ids]`; for `t = 1..T`: `x_t = RMSNorm(Stack(x_{t-1});
  g_f)`, `lambda_t = sigmoid(x_t w_e + b_e)`, `logits_t = x_t W_head`;
- `p_1 = lambda_1`, `p_t = lambda_t prod_{j<t} (1 - lambda_j)`, `p_T =
  prod_{j<T} (1 - lambda_j)`;
- loss `mean_i [sum_t p_t(i) l_t(i) - beta H(p(i))]` over positions
  0..S-2, `l_t(i)` the cross-entropy of `logits_t` at `i` against token
  `i+1`, `H` the entropy of the T-way distribution; Adam with bias
  correction, no decay, no clipping.

COMPUTED IN BLOCKS, by hand: a Python loop over passes and layers calls one
compiled block forward, keeps every block's input (T x layers of them), and
the backward pass walks the same loop in reverse with one compiled block
VJP, ADDING each layer's gradient over its T uses into one accumulator; the
exits are walked in reverse too, the cotangent of `prod (1 - lambda_j)`
carried from exit to exit. Nothing here relies on how a tracer
differentiates a weight that is used several times.

Departures from the published description, each shared with the program:
(1) the final norm is applied inside the loop and the next pass starts from
the normed state; (2) the sandwich norm: each branch's output is normed
before it is added; (3) `beta` is a constant (0.1) and the loss is taken
per token; the second training stage that fits the gate to the measured
gain of each pass is left out; (4) one document per sequence (no document
mask); (5) each block and each loss chunk is recomputed in the backward
pass, which changes memory, not values.

`operand` rounds the operands of every matmul first ("bfloat16", or "fp8" =
e4m3 with a per-tensor scale, straight-through): the witness and the
control. The planted faults: `passes` (fewer passes than the configuration
states; the last of them takes the remainder), `last_pass_only` (the
truncated backward: the state entering the last pass is behind a
stop-gradient and the earlier exits' losses do not reach the stack),
`gate_grad` false (the exit distribution a constant in the backward pass),
`causal` false.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

Tree = Dict[str, Any]
HIGHEST = lax.Precision.HIGHEST
QUERY_CHUNK = 128
LOSS_CHUNK = 1024


def _round(x, operand: str):
    if operand == "float32":
        return x
    if operand == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if operand == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)) / 448.0, 1e-12)
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x + lax.stop_gradient(q * scale - x)
    raise ValueError(f"unknown operand type {operand!r}")


def _mm(x, w, operand):
    return jnp.matmul(_round(x, operand), _round(w, operand),
                      precision=HIGHEST)


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(x, theta: float):
    """x [..., S, d]: positions 0..S-1, frequencies theta^(-2i/d), the two
    halves of d a rotation's pair."""
    s, d = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(q, k, v, causal, operand):
    """softmax(q k^T / sqrt(d)) v over [N, S, d], masked where `causal`,
    a chunk of queries at a time."""
    n, s, d = q.shape
    chunk = QUERY_CHUNK if s % QUERY_CHUNK == 0 else s
    kr, vr = _round(k, operand), _round(v, operand)

    @jax.checkpoint
    def one(args):
        qc, row0 = args                                  # [N, chunk, d]
        sc = jnp.einsum("nqd,nkd->nqk", _round(qc, operand), kr,
                        precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        rows = row0 + jnp.arange(chunk)[:, None]
        keep = (jnp.arange(s)[None, :] <= rows) | ~causal
        p = jax.nn.softmax(jnp.where(keep[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,nkd->nqd", _round(p, operand), vr,
                          precision=HIGHEST)

    qs = jnp.moveaxis(q.reshape(n, s // chunk, chunk, d), 1, 0)
    out = lax.map(one, (qs, jnp.arange(0, s, chunk)))
    return jnp.moveaxis(out, 0, 1).reshape(n, s, d)


def block(p: Tree, x, causal, *, m: dict, operand: str):
    """One layer of the stack over x [B, S, H]."""
    eps, nh, d = m["rms_norm_eps"], m["num_attention_heads"], m["head_dim"]
    b, s, _ = x.shape
    a = rms_norm(x, p["attn_norm"]["scale"], eps)
    heads = lambda y: jnp.swapaxes(y.reshape(b, s, nh, d), 1, 2)
    fold = lambda y: y.reshape(b * nh, s, d)
    q = rotary(heads(_mm(a, p["q_proj"]["w"], operand)), m["rope_theta"])
    k = rotary(heads(_mm(a, p["k_proj"]["w"], operand)), m["rope_theta"])
    v = heads(_mm(a, p["v_proj"]["w"], operand))
    o = attention(fold(q), fold(k), fold(v), causal, operand)
    o = jnp.swapaxes(o.reshape(b, nh, s, d), 1, 2).reshape(b, s, nh * d)
    # departure (2): the sandwich norm
    x = x + rms_norm(_mm(o, p["o_proj"]["w"], operand),
                     p["attn_out_norm"]["scale"], eps)
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    f = p["ffn"]
    y = _mm(jax.nn.silu(_mm(h, f["gate"]["w"], operand))
            * _mm(h, f["up"]["w"], operand), f["down"]["w"], operand)
    return x + rms_norm(y, p["ffn_out_norm"]["scale"], eps)


def cross_entropy(x, head, targets, operand):
    """logsumexp - target logit of `x head` per position, [N]: a chunk of
    positions at a time, the chunk checkpointed."""
    n = targets.size
    chunk = LOSS_CHUNK if n % LOSS_CHUNK == 0 else n

    @jax.checkpoint
    def one(args):
        xc, tc = args
        logits = _mm(xc, head, operand)
        lse = jax.nn.logsumexp(logits, axis=-1)
        return lse - jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]

    return lax.map(one, (x.reshape(n // chunk, chunk, -1),
                         targets.reshape(n // chunk, chunk))).reshape(n)


def exit_step(hp: Tree, x, left, ids, is_last, gate_grad, *, m: dict,
              operand: str):
    """One exit over the normed state x [B, S, H]; `left` [B, S] is
    `prod_{j<t} (1 - lambda_j)`. Returns ((this exit's share of the loss,
    `left` for the next exit), readings)."""
    b, s = ids.shape
    scored = b * (s - 1)
    gate = hp["exit_gate"]
    lam = jax.nn.sigmoid(
        jnp.matmul(x, gate["w"], precision=HIGHEST)[..., 0] + gate["b"])
    lam = jnp.where(gate_grad, lam, lax.stop_gradient(lam))
    p = jnp.where(is_last, left, lam * left)     # the last takes what is left
    mask = (jnp.arange(s)[None, :] < s - 1).astype(jnp.float32)
    ce = cross_entropy(x, hp["lm_head"]["w"], jnp.roll(ids, -1, axis=1),
                       operand).reshape(b, s)
    plogp = jnp.sum(mask * p * jnp.log(jnp.maximum(p, 1e-30)))
    share = (jnp.sum(mask * p * ce) + m["loss_beta"] * plogp) / scored
    readings = {"loss_ut": jnp.sum(mask * ce) / scored,
                "mass": jnp.sum(mask * p),
                "entropy": -plogp / scored}
    return (share, left * (1.0 - lam)), readings


def adam(params: Tree, mom: Tree, var: Tree, grads: Tree, n, *, t: dict):
    """Adam with bias correction at step `n` (1-based), no decay."""
    b1, b2, eps, lr = t["beta1"], t["beta2"], t["adam_eps"], t["learning_rate"]
    mom = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, mom, grads)
    var = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, var, grads)
    c1 = 1 - b1 ** n.astype(jnp.float32)
    c2 = 1 - b2 ** n.astype(jnp.float32)
    params = jax.tree.map(
        lambda p, a, v: p - lr * (a / c1) / (jnp.sqrt(v / c2) + eps),
        params, mom, var)
    return params, mom, var


def init_state(model_state: Tree) -> Tree:
    """The reference's training state around benchmark-made weights. Adam's
    moments wait on the HOST between steps, one entry per top-level group
    of the parameters (none yet: zero): on the device they would take 4.9
    GB beside parameters, gradient and the kept block inputs."""
    return {"params": model_state["params"], "moments": {}, "t": 0}


def make_gradient(m: dict, operand: str):
    """`gradient(params, ids, *, causal, gate_grad, passes, last_pass_only)
    -> (grads, losses, exit mass [T])`: the loss and its gradient, in
    blocks (the module's docstring)."""
    blk = functools.partial(block, m=m, operand=operand)
    ext = functools.partial(exit_step, m=m, operand=operand)
    eps = m["rms_norm_eps"]
    layers = [f"block{i}" for i in range(m["num_hidden_layers"])]
    heads = ("exit_gate", "lm_head")

    block_fwd = jax.jit(blk)
    norm_fwd = jax.jit(lambda g, x: rms_norm(x, g, eps))
    exit_fwd = jax.jit(ext)
    embed_fwd = jax.jit(lambda table, ids: table[ids])

    @jax.jit
    def block_bwd(p, x, causal, dy):
        return jax.vjp(lambda p, x: blk(p, x, causal), p, x)[1](dy)

    @jax.jit
    def norm_bwd(g, x, dy):
        return jax.vjp(lambda g, x: rms_norm(x, g, eps), g, x)[1](dy)

    @jax.jit
    def exit_bwd(hp, x, left, ids, is_last, gate_grad, dleft):
        _, vjp, _ = jax.vjp(
            lambda hp, x, left: ext(hp, x, left, ids, is_last, gate_grad),
            hp, x, left, has_aux=True)
        return vjp((jnp.ones(()), dleft))

    embed_bwd = jax.jit(lambda table, ids, dx:
                        jnp.zeros_like(table).at[ids].add(dx))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    zeros = jax.jit(lambda tree: jax.tree.map(jnp.zeros_like, tree))

    def gradient(params: Tree, ids, *, causal, gate_grad,
                 passes: int, last_pass_only: bool):
        hp = {n: params[n] for n in heads}
        g_f = params["final_norm"]["scale"]
        # forward: every block's input is kept
        x = embed_fwd(params["embed"]["table"], ids)
        left = jnp.ones(ids.shape, jnp.float32)
        kept, total, read = [], 0.0, []
        for t in range(passes):
            inputs = []
            for name in layers:
                inputs.append(x)
                x = block_fwd(params[name], x, causal)
            stack_out, x = x, norm_fwd(g_f, x)      # departure (1)
            last = jnp.bool_(t == passes - 1)
            (share, left_out), r = exit_fwd(hp, x, left, ids, last, gate_grad)
            kept.append((inputs, stack_out, x, left, last))
            left, total = left_out, total + share
            read.append(r)
        losses = {"loss": total,
                  **{f"loss_ut{t + 1}": r["loss_ut"]
                     for t, r in enumerate(read)},
                  "exit_entropy": sum(r["entropy"] for r in read),
                  "exit_mean_step": sum((t + 1) * r["mass"]
                                        for t, r in enumerate(read))
                  / (ids.shape[0] * (ids.shape[1] - 1))}
        mass = jnp.stack([r["mass"] for r in read]
                         + [jnp.zeros(())] * (m["total_ut_steps"] - passes))
        # backward: the same loop in reverse; a layer's gradient is the SUM
        # over its uses
        grads = zeros(params)
        dx = jnp.zeros_like(x)          # from the pass that follows: none
        dleft = jnp.zeros_like(left)
        for t in reversed(range(passes)):
            inputs, stack_out, x_t, left_t, last = kept.pop()
            dhp, dx_exit, dleft = exit_bwd(hp, x_t, left_t, ids, last,
                                           gate_grad, dleft)
            for n in heads:
                grads[n] = add(grads[n], dhp[n])
            if last_pass_only and t < passes - 1:
                continue                # the stack sees the last pass alone
            dg, dx = norm_bwd(g_f, stack_out, dx + dx_exit)
            grads["final_norm"]["scale"] = add(
                grads["final_norm"]["scale"], dg)
            for name, x_in in zip(reversed(layers), reversed(inputs)):
                dp, dx = block_bwd(params[name], x_in, causal, dx)
                grads[name] = add(grads[name], dp)
            if last_pass_only:
                dx = jnp.zeros_like(dx)
        grads["embed"]["table"] = embed_bwd(params["embed"]["table"], ids, dx)
        return grads, losses, mass

    return gradient


def make_step(m: dict, t: dict, operand: str):
    """`step(state, ids, sw, read=None, last=False) -> (state, losses,
    exit mass, read(grads))`: the gradient in blocks, then the update one
    top-level group of the parameters at a time (a block, the embedding,
    the head), each in place, its moments brought from the host and, unless
    this is the `last` step, taken back there. `read` sees the whole
    gradient before the update consumes it. `sw`: the switches of
    `switches`."""
    gradient = make_gradient(m, operand)
    update = jax.jit(functools.partial(adam, t=t), donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda tree: jax.tree.map(jnp.zeros_like, tree))

    def step(state: Tree, ids, sw: Tree, read=None, last: bool = False):
        grads, losses, mass = gradient(state["params"], ids, **sw)
        reading = read(grads) if read is not None else None
        like = jax.tree.leaves(grads)[0].sharding
        n = state["t"] + 1
        params, moments = {}, {}
        for name in sorted(grads):
            g = grads.pop(name)
            parked = state["moments"].get(name)
            mom, var = (zeros(g), zeros(g)) if parked is None else \
                jax.device_put(parked, like)
            params[name], mom, var = update(state["params"][name], mom, var,
                                            g, jnp.int32(n))
            moments[name] = None if last else jax.device_get((mom, var))
            del g, mom, var
        new = {**state, "params": params, "moments": moments, "t": n}
        return new, losses, mass, reading

    return step


def switches(m: dict, *, causal: bool = True, gate_grad: bool = True,
             passes: int = 0, last_pass_only: bool = False) -> Tree:
    """The keyword arguments of `gradient`: the model as it is, or a
    fault."""
    return {"causal": jnp.bool_(causal), "gate_grad": jnp.bool_(gate_grad),
            "passes": passes or m["total_ut_steps"],
            "last_pass_only": last_pass_only}
