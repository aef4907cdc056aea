"""Plain reference of the GAN train step, independent of the program.

Float32 `jax.numpy`/`lax`, matmul precision "highest", no kernels: the DCGAN
stacks (Radford et al. 2015) with the optional SAGAN self-attention block,
spectral normalization, batch normalization on batch moments, the BCE and
hinge losses and two Adam updates, composed as one sequential step (D on the
current G, then G against the updated D). It imports nothing of `dcgan_tpu`
and takes nothing the program made: weights come from `benchmark.weights`,
inputs from the harness, and z is drawn from the step's key by the rule the
program documents (uniform(-1, 1) from the first half of `split(key)`).

`operand` is the control's knob (see benchmark/check.py): "float32" is the
reference proper; "bfloat16" and "fp8" round the operands of every matmul
and convolution to that type first (fp8 = e4m3 with a per-tensor scale),
which is what a program computing below its stated precision would do.

Attention never materializes [B, S, S] at once: queries go through in
chunks (`lax.map` over a checkpointed chunk), every chunk against all keys,
so 256 images of 4,096 tokens fit beside the activations. The batch axis is
never split, so batch-norm moments are over the whole (global) batch, and
under a data-sharded input the same code runs across chips.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Tree = Dict[str, Any]
_DIMS = ("NHWC", "HWIO", "NHWC")
_SCORE_BYTES = 1 << 30  # per-device budget for one chunk's score matrix


# --- operand rounding (the control's knob) ---------------------------------

def _round(x: jax.Array, operand: str) -> jax.Array:
    if operand == "float32":
        return x
    if operand == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if operand == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)) / 448.0, 1e-12)
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        # straight-through: the rounding has no gradient of its own
        return x + lax.stop_gradient(q * scale - x)
    raise ValueError(f"unknown operand type {operand!r}")


def _matmul(x, w, operand):
    return jnp.matmul(_round(x, operand), _round(w, operand),
                      precision=lax.Precision.HIGHEST)


def _conv(x, w, operand):
    return lax.conv_general_dilated(
        _round(x, operand), _round(w, operand), (2, 2), "SAME",
        dimension_numbers=_DIMS, precision=lax.Precision.HIGHEST)


def _deconv(x, w, operand):
    return lax.conv_transpose(
        _round(x, operand), _round(w, operand), (2, 2), "SAME",
        dimension_numbers=_DIMS, precision=lax.Precision.HIGHEST)


# --- layers -----------------------------------------------------------------

def _unit(x, eps=1e-12):
    return x / (jnp.linalg.norm(x) + eps)


def _spectral(w, u):
    """w / sigma with one power-iteration step from u (Miyato et al. 2018);
    u and v carry no gradient, sigma = v^T W u does."""
    w2 = w.reshape(-1, w.shape[-1])
    ws = lax.stop_gradient(w2)
    hp = lax.Precision.HIGHEST
    v = _unit(jnp.matmul(ws, u, precision=hp))
    u1 = _unit(jnp.matmul(ws.T, v, precision=hp))
    v1 = lax.stop_gradient(_unit(jnp.matmul(ws, u1, precision=hp)))
    u1 = lax.stop_gradient(u1)
    sigma = jnp.dot(v1, jnp.matmul(w2, u1, precision=hp), precision=hp)
    return w / sigma, u1


def _batch_norm(p, s, x, mcfg):
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.maximum(jnp.mean(jnp.square(x), axis=axes) - jnp.square(mean),
                      0.0)
    y = (x - mean) * lax.rsqrt(var + mcfg["bn_eps"]) * p["scale"] + p["bias"]
    m = mcfg["bn_momentum"]
    return y, {"mean": m * s["mean"] + (1 - m) * mean,
               "var": m * s["var"] + (1 - m) * var}


def _attention(p, x, mcfg, operand, n_shards):
    """SAGAN block: x + gamma * out(softmax(q k^T / sqrt(d)) v), one head
    group per `attn_heads`, over the flattened H*W sequence."""
    B, H, W, C = x.shape
    S = H * W
    heads = mcfg["attn_heads"]
    seq = x.reshape(B, S, C)
    q = _matmul(seq, p["query"]["w"], operand) + p["query"]["b"]
    k = _matmul(seq, p["key"]["w"], operand) + p["key"]["b"]
    v = _matmul(seq, p["value"]["w"], operand) + p["value"]["b"]
    scale = 1.0 / math.sqrt(q.shape[-1] // heads)

    def split(t):  # [B, S, h*d] -> [B, h, S, d]
        return t.reshape(B, S, heads, -1).transpose(0, 2, 1, 3)

    q, k, v = split(q), split(k), split(v)
    chunk = S
    while chunk > 1 and (B // n_shards) * heads * chunk * S * 4 > _SCORE_BYTES:
        chunk //= 2
    kr, vr = _round(k, operand), _round(v, operand)

    @jax.checkpoint
    def one(qc):  # [B, h, c, d] against all keys
        s = jnp.einsum("bhqd,bhkd->bhqk", _round(qc, operand), kr,
                       precision=lax.Precision.HIGHEST) * scale
        pr = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkv->bhqv", _round(pr, operand), vr,
                          precision=lax.Precision.HIGHEST)

    qs = q.reshape(B, heads, S // chunk, chunk, -1).transpose(2, 0, 1, 3, 4)
    out = lax.map(one, qs)                       # [S/c, B, h, c, dv]
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, S, -1)
    out = _matmul(out, p["out"]["w"], operand) + p["out"]["b"]
    return x + p["gamma"] * out.reshape(B, H, W, C)


def _sn_params(params, state, new_state, name, on):
    if not on:
        return params[name]
    w, u = _spectral(params[name]["w"], state[f"sn_{name}"])
    new_state[f"sn_{name}"] = u
    return {**params[name], "w": w}


def _sn_attn(params, state, new_state, on):
    if not on:
        return params["attn"]
    out = dict(params["attn"])
    for sub in ("query", "key", "value", "out"):
        w, u = _spectral(params["attn"][sub]["w"], state[f"sn_attn_{sub}"])
        new_state[f"sn_attn_{sub}"] = u
        out[sub] = {**params["attn"][sub], "w": w}
    return out


def num_stages(mcfg) -> int:
    return int(round(math.log2(mcfg["output_size"] / mcfg["base_size"])))


def generator(params, state, z, mcfg, operand, n_shards=1):
    """z [B, z_dim] -> (image [B, S, S, c] in tanh range, new state)."""
    k = num_stages(mcfg)
    sn = mcfg["spectral_norm"] == "gd"
    new: Tree = {}
    top = mcfg["gf_dim"] * 2 ** (k - 1)
    base = mcfg["base_size"]
    pj = _sn_params(params, state, new, "proj", sn)
    h = (_matmul(z, pj["w"], operand) + pj["b"]).reshape(-1, base, base, top)
    h, new["bn0"] = _batch_norm(params["bn0"], state["bn0"], h, mcfg)
    h = jnp.maximum(h, 0.0)
    if mcfg["attn_res"] == base:
        h = _attention(_sn_attn(params, state, new, sn), h, mcfg, operand,
                       n_shards)
    for i in range(1, k + 1):
        dc = _sn_params(params, state, new, f"deconv{i}", sn)
        h = _deconv(h, dc["w"], operand) + dc["b"]
        if i < k:
            h, new[f"bn{i}"] = _batch_norm(params[f"bn{i}"], state[f"bn{i}"],
                                           h, mcfg)
            h = jnp.maximum(h, 0.0)
            if mcfg["attn_res"] == base * 2 ** i:
                h = _attention(_sn_attn(params, state, new, sn), h, mcfg,
                               operand, n_shards)
    return jnp.tanh(h), new


def discriminator(params, state, x, mcfg, operand, n_shards=1):
    """image [B, S, S, c] -> (logit [B, 1], new state)."""
    k = num_stages(mcfg)
    sn = mcfg["spectral_norm"] in ("d", "gd")
    new: Tree = {}
    h = x
    for i in range(k):
        cv = _sn_params(params, state, new, f"conv{i}", sn)
        h = _conv(h, cv["w"], operand) + cv["b"]
        if i > 0:
            h, new[f"bn{i}"] = _batch_norm(params[f"bn{i}"], state[f"bn{i}"],
                                           h, mcfg)
        h = jnp.maximum(h, mcfg["leak"] * h)
        if mcfg["attn_res"] and mcfg["attn_res"] == mcfg["output_size"] >> (i + 1):
            h = _attention(_sn_attn(params, state, new, sn), h, mcfg, operand,
                           n_shards)
    hd = _sn_params(params, state, new, "head", sn)
    logit = _matmul(h.reshape(h.shape[0], -1), hd["w"], operand) + hd["b"]
    return logit, new


# --- losses and the optimizer ------------------------------------------------

def _bce(logits, target):
    return jnp.mean(jnp.maximum(logits, 0.0) - logits * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def d_loss_of(loss, real, fake):
    if loss == "hinge":
        return (jnp.mean(jnp.maximum(1.0 - real, 0.0))
                + jnp.mean(jnp.maximum(1.0 + fake, 0.0)))
    if loss == "gan":
        return _bce(real, 1.0) + _bce(fake, 0.0)
    raise ValueError(f"reference has no loss {loss!r}")


def g_loss_of(loss, fake):
    if loss == "hinge":
        return -jnp.mean(fake)
    if loss == "gan":
        return _bce(fake, 1.0)
    raise ValueError(f"reference has no loss {loss!r}")


def _adam(params, grads, opt, lr, tcfg):
    b1, b2, eps = tcfg["beta1"], tcfg["beta2"], tcfg["adam_eps"]
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, m, v)
    return new, {"m": m, "v": v, "t": t}


def leaf_norms(tree, prefix="") -> Dict[str, jax.Array]:
    """{"gen/deconv1/w": ||leaf||, ...} for a nested dict of arrays."""
    out = {}
    for name in sorted(tree):
        path = f"{prefix}/{name}" if prefix else name
        if isinstance(tree[name], dict):
            out.update(leaf_norms(tree[name], path))
        else:
            out[path] = jnp.sqrt(jnp.sum(jnp.square(
                tree[name].astype(jnp.float32))))
    return out


def leaves(tree, prefix="") -> Dict[str, jax.Array]:
    """{"gen/deconv1/w": leaf, ...} for a nested dict of arrays."""
    out = {}
    for name in sorted(tree):
        path = f"{prefix}/{name}" if prefix else name
        if isinstance(tree[name], dict):
            out.update(leaves(tree[name], path))
        else:
            out[path] = tree[name]
    return out


def first_gradient(opt: Tree, tcfg: dict) -> Dict[str, jax.Array]:
    """The first step's gradient as Adam got it, out of the state after that
    one step: from zero moments `m` is (1 - beta1) x the gradient."""
    return {path: m / (1.0 - tcfg["beta1"])
            for net in ("disc", "gen")
            for path, m in leaves(opt[net]["m"], net).items()}


def stat_changes(bn: Tree, bn0: Tree) -> Dict[str, jax.Array]:
    """Per leaf of the model state beside the weights (batch-norm moving
    moments, power-iteration vectors): its change since `bn0`."""
    before = leaves(bn0)
    return {path: x.astype(jnp.float32) - before[path].astype(jnp.float32)
            for path, x in leaves(bn).items()}


def diff_norms(a: Dict[str, jax.Array], b: Dict[str, jax.Array]
               ) -> Dict[str, jax.Array]:
    """Per leaf ||a - b|| of two flat dicts of leaves (`b`'s names)."""
    return {n: jnp.sqrt(jnp.sum(jnp.square(
        a[n].astype(jnp.float32) - b[n].astype(jnp.float32)))) for n in b}


def init_state(model_state: Tree) -> Tree:
    """Training state around benchmark-made weights: Adam moments at zero,
    the generator's average starting at the weights."""
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    p = model_state["params"]
    return {"params": p, "bn": model_state["bn"],
            "opt": {n: {"m": zeros(p[n]), "v": zeros(p[n]),
                        "t": jnp.zeros((), jnp.int32)} for n in ("gen", "disc")},
            "ema_gen": p["gen"]}


def train_step(state: Tree, images: jax.Array, key: jax.Array, *, mcfg: dict,
               tcfg: dict, operand: str = "float32", n_shards: int = 1
               ) -> Tuple[Tree, Dict[str, jax.Array], Dict[str, jax.Array]]:
    """One sequential GAN step. Returns (state, losses, gradient leaf norms)."""
    z_key, _ = jax.random.split(key)
    z = jax.random.uniform(z_key, (images.shape[0], mcfg["z_dim"]),
                           jnp.float32, -1.0, 1.0)
    params, bn = state["params"], state["bn"]
    loss = tcfg["loss"]
    fw = dict(mcfg=mcfg, operand=operand, n_shards=n_shards)

    def d_loss_fn(d_params):
        fake, _ = generator(params["gen"], bn["gen"], z, **fw)
        real_logit, s1 = discriminator(d_params, bn["disc"], images, **fw)
        fake_logit, s2 = discriminator(d_params, {**bn["disc"], **s1}, fake,
                                       **fw)
        return d_loss_of(loss, real_logit, fake_logit), {**bn["disc"], **s1,
                                                         **s2}

    (d_loss, d_bn), d_grads = jax.value_and_grad(d_loss_fn, has_aux=True)(
        params["disc"])
    new_disc, d_opt = _adam(params["disc"], d_grads, state["opt"]["disc"],
                            tcfg["d_learning_rate"], tcfg)

    def g_loss_fn(g_params):
        fake, g_bn = generator(g_params, bn["gen"], z, **fw)
        fake_logit, _ = discriminator(new_disc, d_bn, fake, **fw)
        return g_loss_of(loss, fake_logit), {**bn["gen"], **g_bn}

    (g_loss, g_bn), g_grads = jax.value_and_grad(g_loss_fn, has_aux=True)(
        params["gen"])
    new_gen, g_opt = _adam(params["gen"], g_grads, state["opt"]["gen"],
                           tcfg["g_learning_rate"], tcfg)
    decay = tcfg["g_ema_decay"]
    new_state = {
        "params": {"gen": new_gen, "disc": new_disc},
        "bn": {"gen": g_bn, "disc": d_bn},
        "opt": {"gen": g_opt, "disc": d_opt},
        "ema_gen": jax.tree.map(lambda e, p: decay * e + (1 - decay) * p,
                                state["ema_gen"], new_gen),
    }
    norms = {**leaf_norms(d_grads, "disc"), **leaf_norms(g_grads, "gen")}
    return new_state, {"d_loss": d_loss, "g_loss": g_loss}, norms


def make_step(mcfg: dict, tcfg: dict, operand: str = "float32",
              n_shards: int = 1):
    """The jitted reference step for one configuration."""
    return jax.jit(functools.partial(train_step, mcfg=mcfg, tcfg=tcfg,
                                     operand=operand, n_shards=n_shards),
                   donate_argnums=(0,))


def delta_norms(params: Tree, params0: Tree) -> Dict[str, jax.Array]:
    """Per-leaf norm of the parameters' change."""
    return leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                   - b.astype(jnp.float32), params, params0))
