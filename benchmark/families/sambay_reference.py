"""Plain reference of the `sambay` family's likelihood step.

Float32 `jax.numpy` at matmul precision "highest", written from the layer
equations of Phi-4-mini-flash-reasoning (microsoft; SambaY with
differential attention, "Decoder-Hybrid-Decoder Architecture for Efficient
Reasoning with Long Generation", arXiv:2507.06607; Mamba-1,
arXiv:2312.00752; differential attention, arXiv:2410.05258) under the key
names of the model's public `config.json`; imports nothing of `dcgan_tpu`
and takes nothing the program made. One sequence model, one loss, its
gradient, Adam:

- LayerNorm `(x - mean) / sqrt(var + eps) * g + b`; every layer `x = x +
  Mixer(LN1(x)); x = x + (silu(h Wg) * (h Wu)) Wd`, `h = LN2(x)`;
- Mamba: `[u, z] = h W_in`; `u = silu(conv(u))`, the depthwise causal
  convolution as K SHIFTED ADDS; `[r, B, C] = u W_x`; `dt = softplus(r W_dt
  + b_dt)`; `A = -exp(A_log)`; the recurrence `s_t = exp(dt_t A) s_{t-1} +
  (dt_t u_t) B_t^T`, `y_t = s_t C_t + D u_t` as a plain `lax.scan` over
  TIME with the state [B, d_inner, N] as its carry; out `= (y silu(z))
  W_out`. The memory layer also hands on `m = y`;
- differential attention: `[q, k, v] = h W_qkv + b`; query pair `p` is two
  heads `(q1, q2)`, key/value pair `p // rep` is `(k1, k2)` and a value set
  two heads wide, `v = [v1 | v2]`. The four maps are written out, as in the
  published `flashdiff` form: `a_11 = P1 v1`, `a_12 = P1 v2`, `a_21 = P2
  v1`, `a_22 = P2 v2`, `P_j = softmax(q_j k_j^T / sqrt(d) + mask)`; `a_1 =
  [a_11 | a_12]`, `a_2 = [a_21 | a_22]`; `lambda = exp(lq1 . lk1) - exp(lq2
  . lk2) + lambda_init`; `o = RMSNorm(a_1 - lambda a_2) (1 - lambda_init)`;
  out `= o W_o + b_o`. Dense masked attention in QUERY CHUNKS of 128 (every
  chunk against all keys, the chunk checkpointed), the mask the triangle or,
  in a window layer, the band `i - window < j <= i`;
- cross-attention: `q = h W_q + b`, the full layer's `k1, k2, v`; the gated
  memory unit: `(silu(h W_in) * m) W_out`;
- `x_0 = E[ids]`; a final LayerNorm; logits `= h E^T`; mean next-token
  cross-entropy over positions 0..S-2; Adam with bias correction, no decay,
  no clipping.

COMPUTED IN BLOCKS, by hand: a Python loop over the layers calls one
compiled forward per KIND of layer and keeps every layer's input; the
backward pass walks the layers in reverse with one compiled VJP per kind.
The two tensors that cross layers are handled by hand: the cotangent of
`m` that every gated memory unit sends back is ADDED UP and given to the
memory layer as the cotangent of its second output, and likewise the
cotangents of `(k1, k2, v)` from every cross-attention layer to the
full-attention layer; the tied embedding's gradient is the head's plus the
look-up's. Nothing here relies on how a tracer differentiates a value that
is used in several places.

Departures from the published description, each shared with the program:
(1) what `config.json` does not give (the Mamba sizes, which layer is of
which kind, differential attention's constants) is the family's
convention, listed in the configuration file's `assumed`; (2) the
vocabulary is the slice held; (3) one document per sequence; (4) each
block, each chunk of queries, each chunk of the loss and each stretch of
256 steps of the scan is recomputed in the backward pass, which changes
memory, not values.

`operand` rounds the operands of every matmul first ("bfloat16", or "fp8" =
e4m3 with a per-tensor scale, straight-through): the witness and the
control. The planted faults (`switches`): `window` false (the window layer
sees the whole triangle), `second_map` false (`lambda = 0`), `softplus`
false (`dt = r W_dt + b_dt` as it is), `m_grad` false (the gated memory
units' cotangent of `m` is dropped: the memory layer's scan gets no
gradient from them), `kv_grad` false (the cross layers' cotangent of the
shared keys and values is dropped).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

Tree = Dict[str, Any]
HIGHEST = lax.Precision.HIGHEST
QUERY_CHUNK = 128
LOSS_CHUNK = 1024
SCAN_STRETCH = 256
SUBLN_EPS = 1e-5


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


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def lambda_init(index):
    return 0.8 - 0.6 * jnp.exp(-0.3 * index)


def kinds_of(m: dict):
    """The kind of every layer, and the index of the memory layer (the last
    Mamba layer before the one full-attention layer)."""
    kinds = list(m["layer_types"])
    full = kinds.index("attn_full")
    return kinds, max(i for i in range(full) if kinds[i] == "mamba")


# --- the mixers -----------------------------------------------------------------

def conv(u, w, b):
    """Depthwise causal convolution as K shifted adds: tap k of w [K, D]
    reads u at `t - (K - 1) + k`, zeros before the sequence."""
    taps, s = w.shape[0], u.shape[1]
    out = jnp.zeros_like(u) + b
    for k in range(taps):
        shift = taps - 1 - k
        out = out + w[k] * jnp.pad(u, ((0, 0), (shift, 0), (0, 0)))[:, :s]
    return out


def scan(u, dt, a, bm, cm):
    """y [B, S, D] of `s_t = exp(dt_t A) s_{t-1} + (dt_t u_t) B_t^T`, `y_t =
    s_t C_t` from a zero state: one step of time at a time."""
    b, s, d = u.shape

    def step(state, xs):
        u_t, dt_t, b_t, c_t = xs                       # [B, D] x2, [B, N] x2
        state = jnp.exp(dt_t[..., None] * a) * state \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def stretch(state, xs):
        return lax.scan(step, state, xs)

    n = SCAN_STRETCH if s % SCAN_STRETCH == 0 else s
    split = lambda x: jnp.moveaxis(x, 1, 0).reshape(
        (s // n, n) + x.shape[:1] + x.shape[2:])
    _, y = lax.scan(stretch, jnp.zeros((b, d, a.shape[1]), jnp.float32),
                    tuple(split(x) for x in (u, dt, bm, cm)))
    return jnp.moveaxis(y.reshape(s, b, d), 0, 1)


def mamba(p, h, sw, m: dict, operand):
    """(out [B, S, H], the scan's output y before the gate)."""
    n = m["mamba_d_state"]
    di = m["mamba_expand"] * m["hidden_size"]
    r = m["mamba_dt_rank"] or -(-m["hidden_size"] // 16)
    uz = _mm(h, p["in_proj"]["w"], operand)
    u, z = uz[..., :di], uz[..., di:]
    u = jax.nn.silu(conv(u, p["conv"]["w"], p["conv"]["b"]))
    rbc = _mm(u, p["x_proj"]["w"], operand)
    raw = _mm(rbc[..., :r], p["dt_proj"]["w"], operand) + p["dt_proj"]["b"]
    dt = jnp.where(sw["softplus"], jax.nn.softplus(raw), raw)
    y = scan(u, dt, -jnp.exp(p["A_log"]), rbc[..., r:r + n],
             rbc[..., r + n:]) + p["D"] * u
    return _mm(y * jax.nn.silu(z), p["out_proj"]["w"], operand), y


def attention_maps(q1, q2, k1, k2, v, window, operand):
    """(a_1, a_2) over [N, S, d] queries and keys and v [N, S, 2d]: the four
    maps `P_j v_i`, a chunk of queries at a time; key `j` is seen by query
    `i` where `i - window < j <= i`."""
    n, s, d = q1.shape
    chunk = QUERY_CHUNK if s % QUERY_CHUNK == 0 else s
    k1, k2 = _round(k1, operand), _round(k2, operand)
    v1, v2 = _round(v[..., :d], operand), _round(v[..., d:], operand)

    def probs(qc, k, keep):
        sc = jnp.einsum("nqd,nkd->nqk", _round(qc, operand), k,
                        precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        return _round(jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1),
                      operand)

    @jax.checkpoint
    def one(args):
        q1c, q2c, row0 = args                            # [N, chunk, d]
        rows = row0 + jnp.arange(chunk)[:, None]
        cols = jnp.arange(s)[None, :]
        keep = ((cols <= rows) & (cols > rows - window))[None]
        pv = lambda p_, v_: jnp.einsum("nqk,nkd->nqd", p_, v_,
                                       precision=HIGHEST)
        p1, p2 = probs(q1c, k1, keep), probs(q2c, k2, keep)
        a_11, a_12 = pv(p1, v1), pv(p1, v2)
        a_21, a_22 = pv(p2, v1), pv(p2, v2)
        return (jnp.concatenate([a_11, a_12], axis=-1),
                jnp.concatenate([a_21, a_22], axis=-1))

    chunks = lambda q: jnp.moveaxis(q.reshape(n, s // chunk, chunk, d), 1, 0)
    a1, a2 = lax.map(one, (chunks(q1), chunks(q2), jnp.arange(0, s, chunk)))
    whole = lambda a: jnp.moveaxis(a, 0, 1).reshape(n, s, 2 * d)
    return whole(a1), whole(a2)


def _pairs(a, n_pairs):
    """[B, S, n_pairs * 2 * d] -> the two heads of every pair, each
    [B, n_pairs, S, d]."""
    b, s, _ = a.shape
    a = jnp.moveaxis(a.reshape(b, s, n_pairs, 2, -1), 1, 3)
    return a[:, :, 0], a[:, :, 1]


def diff_attention(p, q, kv, index, window, sw, m: dict, operand):
    """q [B, S, H] (projected) over `kv` = (k1, k2 [B, kv pairs, S, d], v
    [B, kv pairs, S, 2d])."""
    b, s, h = q.shape
    n_pairs = m["num_attention_heads"] // 2
    d = h // m["num_attention_heads"]
    k1, k2, v = kv
    rep = n_pairs // k1.shape[1]
    q1, q2 = _pairs(q, n_pairs)
    each = lambda a: jnp.repeat(a, rep, axis=1).reshape(
        b * n_pairs, s, a.shape[-1])
    fold = lambda a: a.reshape(b * n_pairs, s, d)
    a1, a2 = attention_maps(fold(q1), fold(q2), each(k1), each(k2), each(v),
                            window, operand)
    init = lambda_init(index)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init
    lam = lam * sw["second_map"]
    o = rms_norm(a1 - lam * a2, p["subln"]["scale"], SUBLN_EPS) * (1.0 - init)
    o = jnp.moveaxis(o.reshape(b, n_pairs, s, 2 * d), 1, 2).reshape(b, s, h)
    return _mm(o, p["o_proj"]["w"], operand) + p["o_proj"]["b"], lam


def self_attention(p, h, index, window, sw, m: dict, operand):
    """(out, this layer's (k1, k2, v), lambda)."""
    b, s, width = h.shape
    n_kv = m["num_key_value_heads"] // 2
    kvw = m["num_key_value_heads"] * (width // m["num_attention_heads"])
    qkv = _mm(h, p["qkv_proj"]["w"], operand) + p["qkv_proj"]["b"]
    k1, k2 = _pairs(qkv[..., width:width + kvw], n_kv)
    v = jnp.moveaxis(qkv[..., width + kvw:].reshape(b, s, n_kv, -1), 1, 2)
    out, lam = diff_attention(p, qkv[..., :width], (k1, k2, v), index, window,
                              sw, m, operand)
    return out, (k1, k2, v), lam


def swiglu(f, h, operand):
    return _mm(jax.nn.silu(_mm(h, f["gate"]["w"], operand))
               * _mm(h, f["up"]["w"], operand), f["down"]["w"], operand)


# --- a layer of each kind: (p, x, what it reads, index, window, sw) ------------

def _finish(p, x, out, m, operand):
    x = x + out
    return x + swiglu(p["mlp"], layer_norm(x, p["norm2"],
                                           m["layer_norm_eps"]), operand)


def mamba_layer(p, x, sw, *, m, operand):
    """-> (x, the scan's output y)."""
    out, y = mamba(p["mixer"], layer_norm(x, p["norm1"], m["layer_norm_eps"]),
                   sw, m, operand)
    return (_finish(p, x, out, m, operand), y)


def attn_layer(p, x, index, window, sw, *, m, operand):
    """-> ((x, (k1, k2, v)), lambda)."""
    out, kv, lam = self_attention(
        p["mixer"], layer_norm(x, p["norm1"], m["layer_norm_eps"]), index,
        window, sw, m, operand)
    return (_finish(p, x, out, m, operand), kv), lam


def cross_layer(p, x, kv, index, sw, *, m, operand):
    """-> (x, lambda)."""
    h = layer_norm(x, p["norm1"], m["layer_norm_eps"])
    q = _mm(h, p["mixer"]["q_proj"]["w"], operand) + p["mixer"]["q_proj"]["b"]
    out, lam = diff_attention(p["mixer"], q, kv, index, x.shape[1], sw, m,
                              operand)
    return _finish(p, x, out, m, operand), lam


def gmu_layer(p, x, mem, *, m, operand):
    h = layer_norm(x, p["norm1"], m["layer_norm_eps"])
    out = _mm(jax.nn.silu(_mm(h, p["mixer"]["in_proj"]["w"], operand)) * mem,
              p["mixer"]["out_proj"]["w"], operand)
    return _finish(p, x, out, m, operand)


def head_loss(table, norm, x, ids, *, m, operand):
    """Mean next-token cross-entropy of `LN(x) E^T` over positions 0..S-2, a
    chunk of positions at a time."""
    b, s = ids.shape
    n = b * s
    chunk = LOSS_CHUNK if n % LOSS_CHUNK == 0 else n
    weights = jnp.broadcast_to(jnp.arange(s)[None, :] < s - 1,
                               (b, s)).astype(jnp.float32)

    @jax.checkpoint
    def one(args):
        xc, tc, wc = args
        logits = _mm(layer_norm(xc, norm, m["layer_norm_eps"]), table.T,
                     operand)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum(wc * (lse - hit))

    parts = lax.map(one, (x.reshape(n // chunk, chunk, -1),
                          jnp.roll(ids, -1, axis=1).reshape(n // chunk, chunk),
                          weights.reshape(n // chunk, chunk)))
    return jnp.sum(parts) / (b * (s - 1))


# --- the gradient, in blocks ----------------------------------------------------

def make_gradient(m: dict, operand: str):
    """`gradient(params, ids, sw) -> (grads, losses, readings)`: the loss
    and its gradient, in blocks (the module's docstring). `readings`:
    {"mem_abs": the per-channel mean |m| [d_inner], "dt_mean", "mem_rms",
    "diff_lambda"}."""
    kinds, memory = kinds_of(m)
    kw = dict(m=m, operand=operand)
    fns = {"mamba": functools.partial(mamba_layer, **kw),
           "attn": functools.partial(attn_layer, **kw),
           "attn_cross": functools.partial(cross_layer, **kw),
           "gmu": functools.partial(gmu_layer, **kw)}
    fwd = {k: jax.jit(f) for k, f in fns.items()}
    head = functools.partial(head_loss, **kw)
    head_grad = jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2)))
    embed_fwd = jax.jit(lambda table, ids: table[ids])
    embed_bwd = jax.jit(lambda dtable, ids, dx: dtable.at[ids].add(dx),
                        donate_argnums=(0,))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    zeros = jax.jit(lambda tree: jax.tree.map(jnp.zeros_like, tree))

    @jax.jit
    def mamba_bwd(p, x, sw, dx, dy):
        return jax.vjp(lambda p, x: fns["mamba"](p, x, sw), p, x)[1]((dx, dy))

    @jax.jit
    def attn_bwd(p, x, index, window, sw, dx, dkv):
        _, vjp, _ = jax.vjp(
            lambda p, x: fns["attn"](p, x, index, window, sw), p, x,
            has_aux=True)
        return vjp((dx, dkv))

    @jax.jit
    def cross_bwd(p, x, kv, index, sw, dx):
        _, vjp, _ = jax.vjp(
            lambda p, x, kv: fns["attn_cross"](p, x, kv, index, sw), p, x, kv,
            has_aux=True)
        return vjp(dx)

    @jax.jit
    def gmu_bwd(p, x, mem, dx):
        return jax.vjp(fns["gmu"], p, x, mem)[1](dx)

    memory_reading = jax.jit(lambda y: {
        "mem_abs": jnp.mean(jnp.abs(y), axis=(0, 1)),
        "mem_rms": jnp.sqrt(jnp.mean(y * y))})

    def gradient(params: Tree, ids, sw: Tree):
        s = ids.shape[1]
        window = lambda kind: jnp.int32(
            m["sliding_window"] if kind == "attn_win" and sw["window"] else s)
        # forward: every layer's input is kept
        x = embed_fwd(params["embed"]["table"], ids)
        inputs, mem, kv, lams = [], None, None, []
        for i, kind in enumerate(kinds):
            p = params[f"block{i}"]
            inputs.append(x)
            if kind == "mamba":
                x, y = fwd["mamba"](p, x, sw["mamba"])
                if i == memory:
                    mem = y
                del y
            elif kind == "gmu":
                x = fwd["gmu"](p, x, mem)
            elif kind == "attn_cross":
                x, lam = fwd["attn_cross"](p, x, kv, jnp.float32(i),
                                           sw["attn"])
                lams.append(lam)
            else:
                (x, own), lam = fwd["attn"](p, x, jnp.float32(i),
                                            window(kind), sw["attn"])
                lams.append(lam)
                if kind == "attn_full":
                    kv = own
                del own
        table = params["embed"]["table"]
        loss, (dtable, dnorm, dx) = head_grad(table, params["final_norm"], x,
                                              ids)
        readings = {**memory_reading(mem),
                    "diff_lambda": sum(lams) / len(lams)}
        # backward: the same loop in reverse; the cotangents of `m` and of
        # the shared keys/values are summed over their readers by hand
        grads = {"final_norm": dnorm}
        dmem, dkv = zeros(mem), zeros(kv)
        for i in reversed(range(len(kinds))):
            kind, p, x_in = kinds[i], params[f"block{i}"], inputs.pop()
            if kind == "gmu":
                dp, dx, d = gmu_bwd(p, x_in, mem, dx)
                if sw["m_grad"]:
                    dmem = add(dmem, d)
            elif kind == "attn_cross":
                dp, dx, d = cross_bwd(p, x_in, kv, jnp.float32(i), sw["attn"],
                                      dx)
                if sw["kv_grad"]:
                    dkv = add(dkv, d)
            elif kind == "mamba":
                dy = dmem if i == memory else zeros(dmem)
                dp, dx = mamba_bwd(p, x_in, sw["mamba"], dx, dy)
            else:
                d = dkv if kind == "attn_full" else zeros(dkv)
                dp, dx = attn_bwd(p, x_in, jnp.float32(i), window(kind),
                                  sw["attn"], dx, d)
            grads[f"block{i}"] = dp
        # the tied leaf: the head's use plus the look-up's
        grads["embed"] = {"table": embed_bwd(dtable, ids, dx)}
        return grads, {"loss": loss}, readings

    return gradient


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
    of the parameters (none yet: zero): on the device they would take 5.6
    GB beside parameters, gradient and the kept block inputs."""
    return {"params": model_state["params"], "moments": {}, "t": 0}


def make_step(m: dict, t: dict, operand: str):
    """`step(state, ids, sw, read=None, last=False) -> (state, losses,
    readings, read(grads))`: the gradient in blocks, then the update one
    top-level group of the parameters at a time (a block, the embedding),
    each in place, its moments brought from the host and, unless this is
    the `last` step, taken back there. `read` sees the whole gradient
    before the update consumes it. `sw`: the switches of `switches`."""
    gradient = make_gradient(m, operand)
    update = jax.jit(functools.partial(adam, t=t), donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda tree: jax.tree.map(jnp.zeros_like, tree))

    def step(state: Tree, ids, sw: Tree, read=None, last: bool = False):
        grads, losses, readings = gradient(state["params"], ids, sw)
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
        return new, losses, readings, reading

    return step


def switches(*, window: bool = True, second_map: bool = True,
             softplus: bool = True, m_grad: bool = True,
             kv_grad: bool = True) -> Tree:
    """The switches of `gradient`: the model as it is, or a fault. `mamba`
    and `attn` are run-time values of the compiled layers; the others steer
    the loop."""
    return {"mamba": {"softplus": jnp.bool_(softplus)},
            "attn": {"second_map": jnp.float32(1.0 if second_map else 0.0)},
            "window": window, "m_grad": m_grad, "kv_grad": kv_grad}


def parameter_count(m: dict) -> Dict[str, int]:
    """Parameters by part, from the configuration's sizes alone (the test of
    the configuration file holds its `held` and `published` counts to
    this)."""
    h, inter = m["hidden_size"], m["intermediate_size"]
    di, n = m["mamba_expand"] * h, m["mamba_d_state"]
    r = m["mamba_dt_rank"] or math.ceil(h / 16)
    d = h // m["num_attention_heads"]
    kv = m["num_key_value_heads"] * d
    rest = 3 * h * inter + 4 * h                     # SwiGLU, two LayerNorms
    attn_tail = h * h + h + 4 * d + 2 * d            # W_o, lambdas, gain
    per = {"mamba": h * 2 * di + m["mamba_d_conv"] * di + di
           + di * (r + 2 * n) + r * di + di + di * n + di + di * h + rest,
           "attn_win": h * (h + 2 * kv) + h + 2 * kv + attn_tail + rest,
           "attn_cross": h * h + h + attn_tail + rest,
           "gmu": 2 * h * di + rest}
    per["attn_full"] = per["attn_win"]
    layers = sum(per[k] for k in m["layer_types"])
    return {**per, "layers": layers, "embedding": m["vocab_size"] * h,
            "final_norm": 2 * h,
            "total": layers + m["vocab_size"] * h + 2 * h}
