"""Plain reference of the `mla_moe` family's likelihood step.

Float32 `jax.numpy` at matmul precision "highest", written from the layer
equations of DeepSeek-V3 (arXiv:2412.19437) under the key names of the
model's public `config.json`; imports nothing of `dcgan_tpu` and takes
nothing the program made. One sequence model, one loss, its gradient, Adam:

- RMSNorm `x / sqrt(mean(x^2) + eps) * g`; pre-norm residual blocks;
- latent attention: `c_q = RMSNorm(x W_qa)`, `q = c_q W_qb` (heads x
  [nope | rope]); `[c_kv | k_rope] = x W_kva`, `c_kv = RMSNorm(c_kv)`,
  `[k_nope | v] = c_kv W_kvb`; rotary on every head's `q_rope` and on the
  one `k_rope` all heads share (stored dims are pairs when
  `rope_interleave`); scores `q k^T / sqrt(nope + rope)`, causal, softmax,
  `P v`, `W_o`. Dense masked attention in QUERY CHUNKS (every chunk against
  all keys, the chunk checkpointed), so the [heads, S, S] scores never
  exist at once;
- dense SwiGLU in the leading layers; in every later layer
  `s = sigmoid(x W_r)`, the top k of `s + b`, weights `s` of the selected
  over their sum over ALL selected (+1e-20) times the scaling factor, a
  LOOP OVER THE EXPERTS HELD with a mask (no sort, no grouped product), and
  the shared expert once;
- the multi-token module: `W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]`, one
  expert block, its own final norm, the trunk's head;
- loss: mean next-token cross-entropy over positions 0..S-2, plus
  `mtp_loss_weight` times the module's over 0..S-3; Adam with bias
  correction, no decay, no clipping.

Departures from the published description, each shared with the program:
(1) ONE CHIP'S SHARE: only `experts_held` experts from `first_expert` exist
here; routing and normalization are over all `n_routed_experts`, and what
the absent experts would add is left out (benchmark/configs/*.json state the
deployment); (2) the vocabulary is the slice held (`vocab_size` rows); (3)
the router's bias `b` is a constant (its balancing update is in no public
config); (4) the multi-token module runs over all S positions with the
inputs rolled (`t_{i+1}` wraps at the end): positions S-2 and S-1 have no
target and weight 0, and causality keeps them from every scored position;
they do count in the module's per-expert pair counts; (5) each block and
each loss chunk is recomputed in the backward pass (`jax.checkpoint`), which
changes memory, not values.

`operand` rounds the operands of every matmul first ("bfloat16", or "fp8" =
e4m3 with a per-tensor scale, straight-through): the witness and the
control. The planted faults are run-time switches of the same compiled
program: `mtp_weight` 0 (the multi-token loss left out), `held_norm` (the
weights normalized over the HELD selected experts only), `causal` false.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

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


def rotary(x, theta: float, interleave: bool):
    """x [..., S, d]: positions 0..S-1, frequencies theta^(-2i/d)."""
    s, d = x.shape[-2:]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
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
    return jnp.moveaxis(out, 0, 1).reshape(n, s, v.shape[-1])


def mla(p, x, m, sw, operand):
    """x [B, S, H] (normed)."""
    b, s, _ = x.shape
    nh, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                      m["qk_rope_head_dim"], m["v_head_dim"])
    eps, rank = m["rms_norm_eps"], m["kv_lora_rank"]
    cq = rms_norm(_mm(x, p["q_a"]["w"], operand), p["q_norm"]["scale"], eps)
    q = _mm(cq, p["q_b"]["w"], operand).reshape(b, s, nh, dn + dr)
    ckv = _mm(x, p["kv_a"]["w"], operand)
    k_rope = ckv[..., rank:]
    ckv = rms_norm(ckv[..., :rank], p["kv_norm"]["scale"], eps)
    kv = _mm(ckv, p["kv_b"]["w"], operand).reshape(b, s, nh, dn + dv)
    q, kv = jnp.swapaxes(q, 1, 2), jnp.swapaxes(kv, 1, 2)   # [B, nh, S, .]
    rope = functools.partial(rotary, theta=m["rope_theta"],
                             interleave=m["rope_interleave"])
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(rope(k_rope)[:, None], (b, nh, s, dr))], axis=-1)
    fold = lambda a: a.reshape(b * nh, s, a.shape[-1])
    o = attention(fold(q), fold(k), fold(kv[..., dn:]), sw["causal"], operand)
    o = jnp.swapaxes(o.reshape(b, nh, s, dv), 1, 2).reshape(b, s, nh * dv)
    return _mm(o, p["o_proj"]["w"], operand)


def swiglu(gate, up, down, x, operand):
    return _mm(jax.nn.silu(_mm(x, gate, operand)) * _mm(x, up, operand),
               down, operand)


def moe(p, bias, x, m, sw, operand):
    """The share of this chip over x [T, H] (normed): (y, counts [held])."""
    k, held, first = (m["num_experts_per_tok"], m["experts_held"],
                      m["first_expert"])
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"]["w"], precision=HIGHEST))
    _, idx = lax.top_k(s + bias[None, :], k)
    w = jnp.take_along_axis(s, idx, axis=-1)                    # [T, k]
    here = (idx >= first) & (idx < first + held)
    if m["norm_topk_prob"]:
        over_all = jnp.sum(w, axis=-1, keepdims=True)
        over_held = jnp.sum(jnp.where(here, w, 0.0), axis=-1, keepdims=True)
        w = w / (jnp.where(sw["held_norm"], over_held, over_all) + 1e-20)
    w = w * m["routed_scaling_factor"]

    @jax.checkpoint
    def one(y, xs):
        e, gate, up, down = xs
        mine = idx == e + first                                 # [T, k]
        w_e = jnp.sum(jnp.where(mine, w, 0.0), axis=-1, keepdims=True)
        return y + w_e * swiglu(gate, up, down, x, operand), jnp.sum(mine)

    ex = p["experts"]
    y, counts = lax.scan(one, jnp.zeros_like(x),
                         (jnp.arange(held), ex["gate"], ex["up"], ex["down"]))
    sh = p["shared"]
    y = y + swiglu(sh["gate"]["w"], sh["up"]["w"], sh["down"]["w"], x, operand)
    return y, counts.astype(jnp.int32)


def block(p, bias, x, sw, m, operand):
    eps = m["rms_norm_eps"]
    b, s, h = x.shape
    x = x + mla(p["mla"], rms_norm(x, p["attn_norm"]["scale"], eps), m, sw,
                operand)
    xn = rms_norm(x, p["ffn_norm"]["scale"], eps)
    if "dense_ffn" in p:
        d = p["dense_ffn"]
        return x + swiglu(d["gate"]["w"], d["up"]["w"], d["down"]["w"], xn,
                          operand), jnp.zeros((0,), jnp.int32)
    y, counts = moe(p["moe"], bias, xn.reshape(b * s, h), m, sw, operand)
    return x + y.reshape(b, s, h), counts


def cross_entropy(h, g, head, targets, weights, eps, operand):
    """Sum of weight x (logsumexp - target logit) of `RMSNorm(h) head`."""
    n = targets.size
    chunk = LOSS_CHUNK if n % LOSS_CHUNK == 0 else n

    @jax.checkpoint
    def one(args):
        hc, tc, wc = args
        logits = _mm(rms_norm(hc, g, eps), head, operand)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(wc * (lse - hit))

    parts = lax.map(one, (h.reshape(n // chunk, chunk, -1),
                          targets.reshape(n // chunk, chunk),
                          weights.reshape(n // chunk, chunk)))
    return jnp.sum(parts)


def loss_fn(params: Tree, bias: Tree, ids, m: dict, sw: Tree,
            operand: str = "float32"):
    """(total, (loss, loss_mtp, {layer: counts}))."""
    b, s = ids.shape
    eps = m["rms_norm_eps"]
    table = params["embed"]["table"]
    blk = jax.checkpoint(functools.partial(block, m=m, operand=operand))
    x = table[ids]
    counts = {}
    for i in range(m["num_hidden_layers"]):
        name = f"block{i}"
        x, c = blk(params[name], bias.get(name, jnp.zeros(())), x, sw)
        if name in bias:
            counts[name] = c
    pos = jnp.arange(s)[None, :]
    ones = lambda keep: jnp.broadcast_to(keep, (b, s)).astype(jnp.float32)
    head = params["lm_head"]["w"]
    nxt = jnp.roll(ids, -1, axis=1)
    loss = cross_entropy(x, params["final_norm"]["scale"], head, nxt,
                         ones(pos < s - 1), eps, operand) / (b * (s - 1))
    loss_mtp = jnp.zeros(())
    if m["num_nextn_predict_layers"]:
        p = params["mtp"]
        merged = jnp.concatenate(
            [rms_norm(table[nxt], p["enorm"]["scale"], eps),
             rms_norm(x, p["hnorm"]["scale"], eps)], axis=-1)
        hm, c = blk(p["block"], bias["mtp"],
                    _mm(merged, p["eh_proj"]["w"], operand), sw)
        counts["mtp"] = c
        loss_mtp = cross_entropy(
            hm, p["final_norm"]["scale"], head, jnp.roll(ids, -2, axis=1),
            ones(pos < s - 2), eps, operand) / (b * (s - 2))
    return loss + sw["mtp_weight"] * loss_mtp, (loss, loss_mtp, counts)


def switches(m: dict, *, mtp: bool = True, held_norm: bool = False,
             causal: bool = True) -> Tree:
    """The run-time switches of `loss_fn`: the model as it is, or a fault."""
    return {"mtp_weight": jnp.float32(m["mtp_loss_weight"] if mtp else 0.0),
            "held_norm": jnp.bool_(held_norm), "causal": jnp.bool_(causal)}


def init_state(model_state: Tree) -> Tree:
    """The reference's training state around benchmark-made weights. Adam's
    moments live on the HOST, one entry per top-level group of the
    parameters (none yet: zero): on the device they would take 5.4 GB of a
    16 GB chip beside the parameters, their gradient and the float32
    backward pass's temporaries, which the runtime keeps reserved for as
    long as the gradient's program is loaded."""
    return {"params": model_state["params"],
            "moe_bias": model_state["moe_bias"], "moments": {}, "t": 0}


def loss_and_grads(params: Tree, bias: Tree, ids, sw: Tree, *, m: dict,
                   operand: str = "float32"):
    (_, (loss, loss_mtp, counts)), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, bias, ids, m, sw, operand)
    return grads, {"loss": loss, "loss_mtp": loss_mtp}, counts


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


def make_step(m: dict, t: dict, operand: str):
    """`step(state, ids, sw, read=None, last=False) -> (state, losses,
    counts, read(grads))`: the gradient as one compiled program, then the
    update one top-level group of the parameters at a time (a block, the
    embedding, the head), each in place, its moments brought from the host
    and, unless this is the `last` step, taken back there. `read` sees the
    whole gradient before the update consumes it."""
    grads_fn = jax.jit(functools.partial(loss_and_grads, m=m,
                                         operand=operand))
    update = jax.jit(functools.partial(adam, t=t), donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda tree: jax.tree.map(jnp.zeros_like, tree))

    def step(state: Tree, ids, sw: Tree, read=None, last: bool = False):
        grads, losses, counts = grads_fn(state["params"], state["moe_bias"],
                                         ids, sw)
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
        return new, losses, counts, reading

    return step


# --- what is read from a state (the program's too: pure tree arithmetic) -------

def leaves(tree: Tree, prefix: str = "") -> Dict[str, Any]:
    out = {}
    for name in sorted(tree):
        path = f"{prefix}/{name}" if prefix else name
        if isinstance(tree[name], dict):
            out.update(leaves(tree[name], path))
        else:
            out[path] = tree[name]
    return out


def norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


SAMPLE = 65536


def sample(x):
    """A fixed strided subset of a leaf's elements (at most SAMPLE): where
    the gradient VECTOR is compared, it is compared on these coordinates,
    so that 2.7 GB of gradient need not cross to the host and back."""
    flat = x.reshape(-1)
    return flat[::max(1, flat.size // SAMPLE)][:SAMPLE].astype(jnp.float32)
