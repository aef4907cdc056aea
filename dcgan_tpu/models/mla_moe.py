"""A causal token trunk: latent attention, routed experts, a multi-token head.

The one-network family of `TokenModelConfig` (arch "mla_moe"), after
DeepSeek-V3 (arXiv:2412.19437), pure init/apply like the image families:

- pre-norm residual blocks `x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))`;
- latent attention (MLA): queries through a low-rank pair `q_a`/`q_b` with an
  RMSNorm between, keys and values from ONE compressed vector per token
  (`kv_a` -> RMSNorm -> `kv_b`), a rotary part decoupled from it (`q_rope` per
  head, one `k_rope` shared by all heads), causal softmax over
  `[nope | rope]` scores. Training materializes `k_nope` and `v` (absorbing
  `kv_b` into the query is the decode-time form);
- layer 0..first_k_dense_replace-1: a dense SwiGLU; every later layer: a
  sigmoid router over ALL `n_routed_experts`, top-k of score + bias,
  weights normalized over all selected experts, plus a shared expert;
- ONE CHIP'S SHARE of the experts: the layer is told `experts_held` and
  `first_expert`, routes over every expert, and computes `w_i E_i(x)` for
  the selected experts it holds. What absent experts would add is left out
  and that partial result goes on. No token is dropped at any imbalance:
  the (token, expert) pairs are sorted by expert and the grouped matmuls
  (megablox `gmm`, a Pallas kernel whose grid follows the pairs that are
  really here) visit only the tiles that hold pairs. The buffer they run
  over is sized from the share of the experts held (`moe_buffer_rows`:
  MOE_BUFFER_FACTOR times the pairs expected, 16,384 rows for 65,536
  pairs at 16 of 256): rows are gathered straight from the tokens and
  their results added into their tokens' rows, so what is moved follows
  the pairs that are here. A step in which more pairs arrive than the
  buffer has rows takes the worst-case buffer of every pair instead (a
  `lax.cond` on the count; the counter `compact` says which ran). Where
  the sized buffer would be the worst case (every expert held, the tiny
  preset) there is no branch. On one chip the layer runs without an
  exchange; nothing stands in for the absent chips;
- a multi-token module after the last trunk layer: `eh_proj([RMSNorm(Emb(
  t_{i+1})) ; RMSNorm(h_i)])`, one expert block, its own final norm, the
  trunk's head: logits for `t_{i+2}`.

Precision policy (ops/layers.py's): float32 parameters, matmul operands in
`compute_dtype` with float32 accumulation; the router, softmax, the norms'
statistics and the loss in float32; the residual stream in float32. The
pieces every token arch uses (the matmul, RMSNorm, rotary, SwiGLU, the
chunked head + loss) are models/token_ops.py's.

Scopes (`jax.named_scope`, PERF.md section 3): `embed`, `block<i>`, `mla`
(`q_proj`, `kv_proj`, `rope`, `attn`, `o_proj`), `dense_ffn`, `moe`
(`route`, `dispatch`, `experts`, `shared`, `combine`), `mtp`, `head` (with
`loss` inside it). Kernels: `flash_fwd` / `flash_dq_dkv` (causal), `gmm` /
`tgmm`.
"""

from __future__ import annotations

import functools
import importlib
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from dcgan_tpu.config import TokenModelConfig
from dcgan_tpu.models.token_ops import (apply_rotary, dense_causal_attention,
                                        dtypes, head_loss, mm, normal,
                                        recomputed, rms_norm, rotary_tables,
                                        swiglu_apply, swiglu_init)
from dcgan_tpu.ops.pallas_attention import flash_attention

Pytree = Any

# the package's `gmm` attribute is its custom-vjp function; the kernels
# (`gmm`, `tgmm`) are in the submodule of the same name
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

#: rows of one grouped-matmul tile: 128 keeps the rows the kernel computes
#: under twice the pairs routed when 16 experts see ~256 pairs each
GMM_TILE_M = 128
#: the grouped buffer of an expert layer holds this many times the pairs
#: expected at the share of the experts held (`moe_buffer_rows`); a layer
#: where more arrive takes the worst-case buffer for that step
MOE_BUFFER_FACTOR = 4

# --- init ---------------------------------------------------------------------

def _mla_init(key, cfg: TokenModelConfig, dt) -> Pytree:
    h, nh = cfg.hidden_size, cfg.num_attention_heads
    ks = jax.random.split(key, 5)
    return {
        "q_a": {"w": normal(ks[0], (h, cfg.q_lora_rank), dt)},
        "q_norm": {"scale": jnp.ones((cfg.q_lora_rank,), dt)},
        "q_b": {"w": normal(ks[1], (cfg.q_lora_rank, nh * cfg.qk_head_dim),
                             dt)},
        "kv_a": {"w": normal(
            ks[2], (h, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dt)},
        "kv_norm": {"scale": jnp.ones((cfg.kv_lora_rank,), dt)},
        "kv_b": {"w": normal(
            ks[3], (cfg.kv_lora_rank,
                    nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dt)},
        "o_proj": {"w": normal(ks[4], (nh * cfg.v_head_dim, h), dt)},
    }


def _moe_init(key, cfg: TokenModelConfig, dt) -> Pytree:
    h, f, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.experts_held
    ks = jax.random.split(key, 5)
    return {
        "router": {"w": normal(ks[0], (h, cfg.n_routed_experts), dt)},
        "experts": {"gate": normal(ks[1], (held, h, f), dt),
                    "up": normal(ks[2], (held, h, f), dt),
                    "down": normal(ks[3], (held, f, h), dt)},
        "shared": swiglu_init(ks[4], h, f * cfg.n_shared_experts, dt),
    }


def _block_init(key, cfg: TokenModelConfig, dt, dense: bool) -> Pytree:
    k_attn, k_ffn = jax.random.split(key)
    h = cfg.hidden_size
    block = {"attn_norm": {"scale": jnp.ones((h,), dt)},
             "mla": _mla_init(k_attn, cfg, dt),
             "ffn_norm": {"scale": jnp.ones((h,), dt)}}
    if dense:
        block["dense_ffn"] = swiglu_init(k_ffn, h, cfg.intermediate_size, dt)
    else:
        block["moe"] = _moe_init(k_ffn, cfg, dt)
    return block


def moe_layer_names(cfg: TokenModelConfig) -> Tuple[str, ...]:
    """The expert layers, by the name their block carries in the state."""
    names = tuple(f"block{i}" for i in range(cfg.first_k_dense_replace,
                                             cfg.num_hidden_layers))
    return names + (("mtp",) if cfg.num_nextn_predict_layers else ())


def token_init(key, cfg: TokenModelConfig) -> Tuple[Pytree, Pytree]:
    """(params, router biases). The bias `b` of `topk(score + b)` takes no
    gradient and no optimizer state, so it lives beside the parameters, one
    [n_routed_experts] vector per expert layer."""
    _, dt = dtypes(cfg)
    n = cfg.num_hidden_layers
    ks = jax.random.split(key, n + 4)
    h = cfg.hidden_size
    params = {"embed": {"table": normal(ks[0], (cfg.vocab_size, h), dt)}}
    for i in range(n):
        params[f"block{i}"] = _block_init(
            ks[1 + i], cfg, dt, dense=i < cfg.first_k_dense_replace)
    params["final_norm"] = {"scale": jnp.ones((h,), dt)}
    params["lm_head"] = {"w": normal(ks[n + 1], (h, cfg.vocab_size), dt)}
    if cfg.num_nextn_predict_layers:
        params["mtp"] = {
            "enorm": {"scale": jnp.ones((h,), dt)},
            "hnorm": {"scale": jnp.ones((h,), dt)},
            "eh_proj": {"w": normal(ks[n + 2], (2 * h, h), dt)},
            "block": _block_init(ks[n + 3], cfg, dt, dense=False),
            "final_norm": {"scale": jnp.ones((h,), dt)},
        }
    bias = {name: jnp.zeros((cfg.n_routed_experts,), jnp.float32)
            for name in moe_layer_names(cfg)}
    return params, bias


# --- pieces ---------------------------------------------------------------------

def mla_apply(p: Pytree, x, cfg: TokenModelConfig, rope):
    """Latent attention over x [B, S, H] (already normed, float32)."""
    cd, _ = dtypes(cfg)
    b, s, _ = x.shape
    nh, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    with jax.named_scope("q_proj"):
        cq = rms_norm(mm(x, p["q_a"]["w"], cd), p["q_norm"]["scale"],
                      cfg.rms_norm_eps)
        q = mm(cq, p["q_b"]["w"], cd).reshape(b, s, nh, dn + dr)
    with jax.named_scope("kv_proj"):
        ckv = mm(x, p["kv_a"]["w"], cd)
        k_rope = ckv[..., cfg.kv_lora_rank:]                    # [B, S, dr]
        ckv = rms_norm(ckv[..., :cfg.kv_lora_rank], p["kv_norm"]["scale"],
                       cfg.rms_norm_eps)
        kv = mm(ckv, p["kv_b"]["w"], cd).reshape(b, s, nh, dn + dv)
    with jax.named_scope("rope"):
        cos, sin = rope
        q = jnp.swapaxes(q, 1, 2)                               # [B, nh, S, .]
        kv = jnp.swapaxes(kv, 1, 2)
        q_rope = apply_rotary(q[..., dn:], cos, sin, cfg.rope_interleave)
        k_rope = apply_rotary(k_rope, cos, sin, cfg.rope_interleave)
        q = jnp.concatenate([q[..., :dn], q_rope], axis=-1).astype(cd)
        k = jnp.concatenate(
            [kv[..., :dn],
             jnp.broadcast_to(k_rope[:, None], (b, nh, s, dr))],
            axis=-1).astype(cd)
        v = kv[..., dn:].astype(cd)
    with jax.named_scope("attn"):
        fold = lambda a: a.reshape(b * nh, s, a.shape[-1])
        scale = float(dn + dr) ** -0.5
        if cfg.use_pallas:
            o = flash_attention(fold(q), fold(k), fold(v), scale, True)
        else:
            o = dense_causal_attention(fold(q), fold(k), fold(v), scale)
        o = jnp.swapaxes(o.reshape(b, nh, s, dv), 1, 2).reshape(b, s, nh * dv)
    with jax.named_scope("o_proj"):
        return mm(o, p["o_proj"]["w"], cd)


@jax.custom_vjp
def _permute(a, perm, inv):
    """a[perm] for a permutation `perm` with inverse `inv`: the cotangent is
    a gather too (g[inv]), where a plain gather's transpose is a scatter."""
    return a[perm]


def _permute_fwd(a, perm, inv):
    return a[perm], (perm, inv)


def _permute_bwd(res, g):
    perm, inv = res
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def route(router_w, bias, x, cfg: TokenModelConfig):
    """Sigmoid scores over all experts, top-k of score + bias, weights the
    scores themselves (without the bias) of the selected, divided by their
    sum over ALL selected experts, times the scaling factor. Float32.
    Returns (expert index [T, k], weight [T, k])."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(bias)[None, :],
                           cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling_factor


def _gmm_tile_m(m: int) -> int:
    return math.gcd(m, GMM_TILE_M)


def _gmm_tiling(m: int, k: int, n: int) -> tuple:
    """Whole-width tiles in k and n where they fit the scoped VMEM (an
    expert's matrices are small), so a tile of rows is one grid step."""
    cap = lambda d: d if d <= 1024 else 1024
    return (_gmm_tile_m(m), cap(k), cap(n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, out_dtype):
    """Grouped matmul of sorted rows [M, K] against [G, K, N]: the megablox
    Pallas kernels (`gmm`, and `tgmm` for the weights' gradient), whose grid
    is sized by the tiles that hold rows of a group, so the work follows the
    pairs that are really here. Rows past the last group are not written
    (the caller masks them). The library's own VJP reuses the forward's
    tiling for the transposed product; this one sizes each call's tiles."""
    (m, k), n = lhs.shape, rhs.shape[2]
    return _megablox.gmm(lhs, rhs, group_sizes, out_dtype,
                         _gmm_tiling(m, k, n),
                         interpret=jax.default_backend() != "tpu")


def _gmm_fwd(lhs, rhs, group_sizes, out_dtype):
    return _gmm(lhs, rhs, group_sizes, out_dtype), (lhs, rhs, group_sizes)


def _gmm_bwd(out_dtype, res, grad):
    lhs, rhs, group_sizes = res
    (m, k), n = lhs.shape, rhs.shape[2]
    interpret = jax.default_backend() != "tpu"
    d_lhs = _megablox.gmm(grad, rhs, group_sizes, lhs.dtype,
                          _gmm_tiling(m, n, k), transpose_rhs=True,
                          interpret=interpret)
    d_rhs = _megablox.tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                           _gmm_tiling(m, k, n), interpret=interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def moe_buffer_rows(m: int, cfg: TokenModelConfig) -> int:
    """Rows of the grouped buffer for `m` pairs routed: MOE_BUFFER_FACTOR
    times the pairs expected at the share of the experts held here, in whole
    tiles, and never more than the worst case `m`."""
    want = -(-MOE_BUFFER_FACTOR * m * cfg.experts_held // cfg.n_routed_experts)
    return min(m, -(-want // GMM_TILE_M) * GMM_TILE_M)


def _tile_rows(sizes, tm: int):
    """Rows the grouped kernels compute at tiles of `tm` rows: every tile a
    non-empty group touches."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    return jnp.sum(tiles) * tm


def _held_experts(xs, e, sizes, filled, cd):
    """The held experts' SwiGLU over sorted rows xs [R, H] (`e` in the
    compute dtype); rows past the pairs that are here come out zero."""
    g = _gmm(xs, e["gate"], sizes, cd)
    u = _gmm(xs, e["up"], sizes, cd)
    act = jnp.where(filled, jax.nn.silu(g.astype(jnp.float32))
                    * u.astype(jnp.float32), 0).astype(cd)
    return jnp.where(filled, _gmm(act, e["down"], sizes, cd), 0)


def _routed_whole(cd, x, w_here, e, shared, order, sizes):
    """`shared` plus the weighted held experts over a buffer of ALL m pairs,
    the absent ones sorted behind the pairs that are here: right at any
    imbalance, and m rows moved whatever arrives."""
    (t, h), k = x.shape, w_here.shape[1]
    m = t * k
    with jax.named_scope("dispatch"):
        inv = jnp.zeros((m,), jnp.int32).at[order].set(
            jnp.arange(m, dtype=jnp.int32), unique_indices=True)
        filled = (jnp.arange(m) < jnp.sum(sizes))[:, None]
        xs = _permute(jnp.repeat(x.astype(cd), k, axis=0), order, inv)
        xs = jnp.where(filled, xs, 0)
    with jax.named_scope("experts"):
        ys = _held_experts(xs, e, sizes, filled, cd)
    with jax.named_scope("combine"):
        back = _permute(ys, inv, order).reshape(t, k, h)
        return jnp.einsum("tk,tkh->th", w_here,
                          back.astype(jnp.float32)) + shared


def _routed_sized(c, cd, x, w_here, e, shared, order, sizes):
    """The same sum over a buffer of the first `c` sorted pairs, for a layer
    with at most `c` pairs here: rows gathered straight from the tokens,
    their results added into their tokens' rows."""
    (t, h), k = x.shape, w_here.shape[1]
    with jax.named_scope("dispatch"):
        rows = order[:c]
        tok = rows // k
        filled = (jnp.arange(c) < jnp.sum(sizes))[:, None]
        xs = jnp.where(filled, x.astype(cd)[tok], 0)
    with jax.named_scope("experts"):
        ys = _held_experts(xs, e, sizes, filled, cd)
    with jax.named_scope("combine"):
        w_c = w_here.reshape(t * k).at[rows].get(unique_indices=True)
        return shared.at[tok].add(w_c[:, None] * ys.astype(jnp.float32))


def _routed_branches(c, cd):
    """(whole, sized): `lax.cond` takes the second where its predicate
    holds."""
    return (functools.partial(_routed_whole, cd),
            functools.partial(_routed_sized, c, cd))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed(c, cd, fits, x, w_here, e, shared, order, sizes):
    """`_routed_sized` where the pairs here fit `c` rows (`fits`), else
    `_routed_whole`. Its own VJP, so that each branch keeps its
    intermediates to itself: differentiating the `cond` would have the
    branch taken write zeros for everything the other would have kept."""
    whole, sized = _routed_branches(c, cd)
    return jax.lax.cond(fits, sized, whole,
                        x, w_here, e, shared, order, sizes)


def _routed_fwd(c, cd, fits, x, w_here, e, shared, order, sizes):
    # `shared` is only added to: its cotangent is the result's, so neither
    # it nor that cotangent has to pass through the backward's `cond`
    return (_routed(c, cd, fits, x, w_here, e, shared, order, sizes),
            (fits, x, w_here, e, order, sizes))


def _routed_bwd(c, cd, res, g):
    fits, *diff, order, sizes = res

    def back(branch):
        return lambda: jax.vjp(lambda *d: branch(
            *d, jnp.zeros_like(g), order, sizes), *diff)[1](g)
    whole, sized = _routed_branches(c, cd)
    grads = jax.lax.cond(fits, back(sized), back(whole))
    return (None, *grads, g, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


def moe_apply(p: Pytree, bias, x, cfg: TokenModelConfig):
    """The expert layer's share of this chip over tokens x [T, H] (normed,
    float32): (y [T, H] float32, counters). `y` holds the selected experts
    that are held here, weighted, plus the shared expert once."""
    cd, _ = dtypes(cfg)
    t, _ = x.shape
    k, held = cfg.num_experts_per_tok, cfg.experts_held
    m = t * k
    c = moe_buffer_rows(m, cfg)
    with jax.named_scope("route"):
        idx, w = route(p["router"]["w"], bias, x, cfg)
        local = idx - cfg.first_expert
        here = (local >= 0) & (local < held)
        local = jnp.where(here, local, held).reshape(m)   # absent -> last
        w_here = jnp.where(here, w, 0.0)
    with jax.named_scope("dispatch"):
        order = jnp.argsort(local, stable=True)
        sizes = jnp.bincount(local, length=held + 1)[:held].astype(jnp.int32)
    with jax.named_scope("experts"):
        e = {n: a.astype(cd) for n, a in p["experts"].items()}
    with jax.named_scope("shared"):
        shared = swiglu_apply(p["shared"], x, cd)
    rows = _tile_rows(sizes, _gmm_tile_m(m))
    if c == m:
        y = _routed_whole(cd, x, w_here, e, shared, order, sizes)
        compact = jnp.zeros((), jnp.float32)
    else:
        fits = jnp.sum(sizes) <= c
        y = _routed(c, cd, fits, x, w_here, e, shared, order, sizes)
        compact = fits.astype(jnp.float32)
        rows = jnp.where(fits, _tile_rows(sizes, _gmm_tile_m(c)), rows)
    return y, {"counts": sizes, "rows": rows, "compact": compact}


def block_apply(p: Pytree, bias, x, cfg: TokenModelConfig, rope):
    """One pre-norm residual block over x [B, S, H] (float32):
    (x, counters or None)."""
    cd, _ = dtypes(cfg)
    b, s, h = x.shape
    with jax.named_scope("mla"):
        x = x + mla_apply(p["mla"],
                          rms_norm(x, p["attn_norm"]["scale"],
                                   cfg.rms_norm_eps), cfg, rope)
    xn = rms_norm(x, p["ffn_norm"]["scale"], cfg.rms_norm_eps)
    if "dense_ffn" in p:
        with jax.named_scope("dense_ffn"):
            return x + swiglu_apply(p["dense_ffn"], xn, cd), None
    with jax.named_scope("moe"):
        y, counters = moe_apply(p["moe"], bias, xn.reshape(b * s, h), cfg)
    return x + y.reshape(b, s, h), counters


def token_loss(params: Pytree, bias: Pytree, ids, cfg: TokenModelConfig
               ) -> Tuple[jax.Array, Dict[str, Any]]:
    """The likelihood of one batch of ids [B, S] (int32): mean next-token
    cross-entropy of the trunk over positions 0..S-2, plus
    `mtp_loss_weight` times that of the multi-token module over 0..S-3.
    Returns (loss, {"loss", "loss_mtp", "counts": {layer: [held]},
    "rows": rows the grouped kernels computed, "compact": the share of the
    expert layers that ran over the sized buffer, "attn_kept": the attention
    outputs the step keeps across its recomputation})."""
    b, s = ids.shape
    rope = rotary_tables(s, cfg.qk_rope_head_dim, cfg.rope_theta)
    # every block is recomputed in the backward pass: its input and its
    # attention kernel's outputs are kept of it (10.9 GB of state leave
    # 5 GB for activations)
    block = recomputed(functools.partial(block_apply, cfg=cfg, rope=rope))
    table = params["embed"]["table"]
    with jax.named_scope("embed"):
        x = table[ids].astype(jnp.float32)
    counters = {}
    for i in range(cfg.num_hidden_layers):
        name = f"block{i}"
        with jax.named_scope(name):
            x, c = block(params[name], bias.get(name), x)
        if c is not None:
            counters[name] = c
    pos = jnp.arange(s)[None, :]
    nxt = jnp.roll(ids, -1, axis=1)
    loss = head_loss(x, params["final_norm"]["scale"], params["lm_head"]["w"],
                     nxt, jnp.broadcast_to(pos < s - 1, (b, s)), cfg
                     ) / (b * (s - 1))
    loss_mtp = jnp.zeros((), jnp.float32)
    if cfg.num_nextn_predict_layers:
        cd, _ = dtypes(cfg)
        p = params["mtp"]
        with jax.named_scope("mtp"):
            # position i sees h_i and the embedding of token i+1, and is
            # scored on token i+2. The last two positions have no target
            # (their rolled inputs wrap round; causal, so nothing earlier
            # sees them) and carry weight 0.
            with jax.named_scope("embed"):
                e = table[nxt].astype(jnp.float32)
            merged = jnp.concatenate(
                [rms_norm(e, p["enorm"]["scale"], cfg.rms_norm_eps),
                 rms_norm(x, p["hnorm"]["scale"], cfg.rms_norm_eps)], axis=-1)
            hm = mm(merged, p["eh_proj"]["w"], cd)
            hm, c = block(p["block"], bias["mtp"], hm)
            counters["mtp"] = c
            loss_mtp = head_loss(
                hm, p["final_norm"]["scale"], params["lm_head"]["w"],
                jnp.roll(ids, -2, axis=1),
                jnp.broadcast_to(pos < s - 2, (b, s)), cfg) / (b * (s - 2))
    total = loss + cfg.mtp_loss_weight * loss_mtp
    return total, {
        "loss": loss, "loss_mtp": loss_mtp,
        "counts": {n: c["counts"] for n, c in counters.items()},
        "rows": sum(c["rows"] for c in counters.values()),
        "compact": sum(c["compact"] for c in counters.values())
        / max(len(counters), 1),
        # one a block: the trunk's layers and the multi-token module
        "attn_kept": jnp.float32(
            cfg.num_hidden_layers + bool(cfg.num_nextn_predict_layers)
            if cfg.use_pallas else 0)}


# --- what the likelihood step asks of a token arch (train/steps.py) ------------

#: state entries the loss reads beside the parameters; aux entries
#: averaged / summed over the data shards (`attn_kept`, a constant of the
#: program and the same on every shard, is neither)
LM_READS = ("moe_bias",)
LM_MEAN = ("loss", "loss_mtp", "compact")
LM_SUM = ("counts", "rows")


def lm_init(key, cfg: TokenModelConfig) -> Pytree:
    """The state beside optimizer and step: the parameters, the routers'
    selection biases (no gradient, no optimizer state; the step leaves them
    as they are) and the per-expert pair counts the step accumulates."""
    params, bias = token_init(key, cfg)
    return {
        "params": params,
        "moe_bias": bias,
        "moe_counts": {n: jnp.zeros((cfg.experts_held,), jnp.int32)
                       for n in bias},
    }


def lm_loss(params: Pytree, state: Pytree, ids, cfg: TokenModelConfig):
    return token_loss(params, state["moe_bias"], ids, cfg)


def lm_metrics(aux: Dict[str, Any]) -> Dict[str, jax.Array]:
    per_expert = jnp.concatenate(list(aux["counts"].values()))
    pairs = jnp.sum(per_expert)
    return {
        "loss": aux["loss"], "loss_mtp": aux["loss_mtp"],
        # (token, expert) pairs routed to experts held here, all layers
        "moe_pairs_here": pairs.astype(jnp.float32),
        # the fullest held expert's pairs over the mean
        "moe_load_max": jnp.max(per_expert) * per_expert.size
        / jnp.maximum(pairs, 1).astype(jnp.float32),
        # rows the grouped kernels computed (whole tiles)
        "moe_rows_computed": aux["rows"].astype(jnp.float32),
        # share of the expert layers whose pairs fit the sized buffer
        "moe_compact_share": aux["compact"],
        # attention outputs kept across the recomputation, a chip
        "attn_outputs_kept": aux["attn_kept"],
    }


def lm_accumulate(state: Pytree, aux: Dict[str, Any]) -> Pytree:
    return {"moe_counts": {n: state["moe_counts"][n] + c
                           for n, c in aux["counts"].items()}}
