"""Self-attention over the spatial sequence, with two sequence-parallel
execution strategies (ring and all-to-all/Ulysses).

The reference has no attention anywhere — it is a pure-conv DCGAN whose
largest spatial extent is 64x64 (distriubted_model.py:7,83-128), and SURVEY.md
§2.5 records sequence/context parallelism as structurally absent. This module
is the framework's first-class long-context machinery anyway: images flatten
to a sequence of H*W spatial positions, a SAGAN-style self-attention block
(Zhang et al. 2018, arXiv:1805.08318, optionally multi-head) attends over
that sequence, and when the sequence is sharded over a mesh axis the
attention runs in one of two explicit-collective forms:

- **ring** (`ring_attention`, arXiv:2310.01889): each device keeps its query
  block resident and rotates key/value blocks around the axis with
  `lax.ppermute`, folding each incoming block into a numerically stable
  online softmax. n-1 neighbor hops on ICI; peak memory O(S_local^2); no
  device ever materializes the full sequence; any head count.
- **ulysses** (`ulysses_attention`, arXiv:2309.14509): one `lax.all_to_all`
  trades sequence sharding for head sharding, each device runs ordinary (or
  flash) attention over the FULL sequence for its share of heads, a second
  all_to_all trades back. Two collectives total; needs num_heads divisible
  by the axis size; per-device memory is bounded by the flash path, not the
  strategy.

Design notes:
- `attn_apply` is identity at initialization: the residual gate `gamma` starts
  at 0 (the SAGAN recipe), so inserting the block into a DCGAN stack does not
  perturb the reference dynamics until training moves gamma.
- Projections are 1x1 convs expressed as channel matmuls: query/key to C/8,
  value to C/2, output back to C — the SAGAN channel plan. Heads are an
  apply-time split of the same projections (checkpoint-compatible).
- Logits are scaled by 1/sqrt(d_head) (standard scaled dot-product; SAGAN's
  paper omits the scale — documented divergence, it only re-scales what
  gamma=0 already gates) and accumulated in float32 regardless of compute
  dtype.
- Both strategies are exact: equivalence against dense attention (and each
  other) is asserted to f32 tolerance in tests/test_attention.py on an
  8-virtual-device mesh, gradients included (ppermute, all_to_all, and the
  scan recurrence are differentiable as-is).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dcgan_tpu.ops.layers import linear_apply, linear_init
from dcgan_tpu.utils.backend import shard_map

Pytree = dict

# Measurement generation of the DENSE attention path (full_attention and
# the ring fold below) — the counterpart of pallas_attention.ATTN_GEN for
# configs that never execute the flash kernels. bench.py stamps whichever
# generation matches the config's execution form, so a flash-only change
# (tile retune, block layout) never retires the capture history of dense
# configs whose code is byte-identical. Gen 2 = the shared bf16-operand /
# f32-accumulation precision policy (it changed BOTH forms).
DENSE_ATTN_GEN = 2


def attn_init(key, ch: int, *, dtype=jnp.float32) -> Pytree:
    """Parameters for one self-attention block over `ch`-channel feature maps.

    SAGAN channel plan: query/key project to ch//8, value to ch//2, output
    back to ch; `gamma` (the residual gate) starts at 0 so the block is the
    identity at init.
    """
    if ch < 8:
        raise ValueError(f"attention needs >= 8 channels, got {ch}")
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "query": linear_init(kq, ch, ch // 8, dtype=dtype),
        "key": linear_init(kk, ch, ch // 8, dtype=dtype),
        "value": linear_init(kv, ch, ch // 2, dtype=dtype),
        "out": linear_init(ko, ch // 2, ch, dtype=dtype),
        "gamma": jnp.zeros((), dtype),
    }


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   *, scale: float) -> jax.Array:
    """softmax(q k^T * scale) v over the whole sequence. [B,S,d] each.

    Precision policy (all execution forms share it): matmul OPERANDS keep
    their input dtype — bf16 rides the MXU fast path instead of being
    upcast into 4x-slower f32 matmuls — while scores/softmax/accumulation
    are float32 via `preferred_element_type`. The probability matrix is
    cast back to the value dtype for the PV matmul (the flash-attention
    recipe, arXiv:2205.14135 §3.1). float32 inputs take the exact float32
    path unchanged — the policy is dtype-gated, not a global downcast.
    """
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkv->bqv", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str, n_shards: int, scale: float) -> jax.Array:
    """Exact attention over a sequence sharded along `axis_name`.

    Per-device blocks q,k,v: [B, S_local, d]. The device keeps q resident and
    receives each of the `n_shards` k/v blocks in turn over a `ppermute` ring,
    maintaining the online-softmax statistics (running max m, normalizer l,
    unnormalized accumulator acc) so the result equals full softmax attention
    over the global sequence (arXiv:2310.01889's blockwise recurrence).

    Communication: exactly n_shards-1 neighbor exchanges of the local k/v
    blocks — O(S_local * d) per hop on ICI; nothing ever all-gathers. The
    resident block folds before the scan, so no hop's result is discarded.
    """
    if n_shards == 1:
        return full_attention(q, k, v, scale=scale)
    fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def fold(k_blk, v_blk, m, l, acc):
        # same precision policy as full_attention: operands in input dtype,
        # scores/stats/accumulator f32 via preferred_element_type
        s = jnp.einsum("bqd,bkd->bqk", q, k_blk,
                       preferred_element_type=jnp.float32) * scale
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # exp(-inf - -inf) cannot occur: m_new is finite from the first fold
        # on, and there m = -inf only on the correction side
        # (corr = exp(-inf - finite) = 0, which correctly discards the empty
        # accumulator).
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqk,bkv->bqv", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # Build the accumulators out of q/v arithmetic (not jnp.zeros) so they
    # inherit the operands' device-varying axes — the scan carry then
    # type-checks under shard_map's VMA tracking over ANY enclosing mesh
    # (the ring axis alone, or ring + a batch axis).
    zero_q = q[..., 0].astype(jnp.float32) * 0.0    # [B, S]
    m, l, acc = fold(k, v, zero_q - jnp.inf, zero_q,
                     zero_q[..., None] * v[:, :1, :].astype(jnp.float32))

    def body(carry, _):
        k_blk, v_blk, m, l, acc = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm=fwd)
        v_blk = lax.ppermute(v_blk, axis_name, perm=fwd)
        m, l, acc = fold(k_blk, v_blk, m, l, acc)
        return (k_blk, v_blk, m, l, acc), None

    (_, _, _, l, acc), _ = lax.scan(
        body, (k, v, m, l, acc), None, length=n_shards - 1)
    return acc / l[..., None]


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str, n_shards: int, num_heads: int,
                      scale: float, use_pallas: bool = False) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses, arXiv:2309.14509).

    Per-device blocks q,k,v: [B, S_local, h*d] sharded on the sequence. One
    `all_to_all` re-shards from sequence-split to head-split — each device
    then holds the FULL sequence for h/n_shards heads and runs ordinary
    attention locally — and a second all_to_all restores sequence sharding.
    Two collectives total, each moving the activations once, vs the ring's
    n-1 k/v hops: better when heads divide nicely and the fabric does fast
    all-to-alls; the ring wins when h < n or per-hop overlap matters. Both
    are exact; tests pin them against dense attention and each other.
    """
    if num_heads % n_shards:
        raise ValueError(
            f"ulysses needs num_heads ({num_heads}) divisible by the "
            f"sequence-parallel axis ({n_shards}); use the ring strategy "
            "or adjust attn_heads")
    B, S_loc, _ = q.shape

    def to_heads(t):
        # [B, S_loc, h, d] --all_to_all--> [B, S_loc*n, h/n, d]
        t = t.reshape(B, S_loc, num_heads, t.shape[-1] // num_heads)
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    S = S_loc * n_shards
    h_loc = num_heads // n_shards

    def fold(t):  # heads into batch for the local attention
        return t.transpose(0, 2, 1, 3).reshape(B * h_loc, S, t.shape[-1])

    if use_pallas:
        # local attention over the full sequence is exactly the regime the
        # flash kernels exist for (no [S, S] score matrix per device)
        from dcgan_tpu.ops.pallas_attention import flash_attention

        out = flash_attention(fold(qh), fold(kh), fold(vh), scale)
    else:
        out = full_attention(fold(qh), fold(kh), fold(vh), scale=scale)
    # downcast BEFORE the return collective: the f32 accumulation is local,
    # and shipping f32 under a bf16 compute dtype would double the bytes of
    # one of the strategy's two activation moves
    out = out.astype(v.dtype)
    out = out.reshape(B, h_loc, S, -1).transpose(0, 2, 1, 3)
    # [B, S, h/n, dv] --all_to_all--> [B, S_loc, h, dv], heads re-merged
    out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                         tiled=True)
    return out.reshape(B, S_loc, -1)


def _project(params: Pytree, x: jax.Array, cdt) -> Tuple[jax.Array, ...]:
    q = linear_apply(params["query"], x, compute_dtype=cdt)
    k = linear_apply(params["key"], x, compute_dtype=cdt)
    v = linear_apply(params["value"], x, compute_dtype=cdt)
    return q, k, v


@jax.named_scope("attn")
def attn_apply(params: Pytree, x: jax.Array, *, compute_dtype=None,
               num_heads: int = 1, seq_mesh=None, seq_axis: str = "model",
               batch_axis: str = "data", seq_strategy: str = "ring",
               use_pallas: bool = False, pallas_mesh=None) -> jax.Array:
    """x [B,H,W,C] -> x + gamma * attention(x) (same shape/dtype).

    pallas_mesh: a pure data-parallel Mesh the CALLER's jit partitions
    over. pallas_call is opaque to the GSPMD partitioner, so on such a
    mesh the flash path runs per data-shard inside a nested shard_map —
    attention is batch-local, so the wrapper needs no collectives. Ignored unless
    use_pallas is set and no sequence mesh applies.

    num_heads > 1 splits the existing query/key/value projections into heads
    (folded into the batch dim around the attention proper, so every
    execution form below — dense, flash, ring — is head-agnostic). Head
    count is an apply-time knob: parameter shapes do not change, so the same
    checkpoint serves any divisor head count.

    seq_mesh=None: attention over the full flattened H*W sequence (under a
    data-parallel jit the batch dim shards and nothing else changes).
    use_pallas=True routes this dense path through the flash-attention Pallas
    kernels (ops/pallas_attention.py) — O(S) HBM traffic, no [S, S] score
    matrix ever materialized.

    seq_mesh=<Mesh>: sequence-parallel execution — the flattened sequence is
    sharded over `seq_axis` (the mesh layout MeshConfig.spatial produces:
    batch over "data", image height over "model") and attention runs as an
    explicit `shard_map` nested inside the caller's jit. The surrounding
    convs stay under the GSPMD partitioner (halo exchanges); only the
    attention — whose all-to-all token mixing the partitioner would
    otherwise lower to a full k/v all-gather — is written by hand, in one of
    two strategies (`seq_strategy`):

    - "ring": ppermute k/v around the axis with an online-softmax fold
      (`ring_attention`) — any head count, n-1 neighbor hops.
    - "ulysses": one all_to_all to head sharding, local full attention, one
      all_to_all back (`ulysses_attention`) — needs num_heads divisible by
      the axis size.
    """
    B, H, W, C = x.shape
    cdt = compute_dtype
    seq = x.reshape(B, H * W, C)
    q, k, v = _project(params, seq, cdt)
    if num_heads > 1 and (q.shape[-1] % num_heads
                          or v.shape[-1] % num_heads):
        raise ValueError(
            f"num_heads={num_heads} does not divide the projection dims "
            f"(qk {q.shape[-1]}, v {v.shape[-1]})")
    scale = 1.0 / ((q.shape[-1] // num_heads) ** 0.5)

    seq_parallel = seq_mesh is not None and seq_mesh.shape[seq_axis] > 1
    if seq_parallel:
        n = seq_mesh.shape[seq_axis]
        if (H * W) % n:
            raise ValueError(
                f"sequence {H}x{W} does not shard over {n} devices")
        if seq_strategy not in ("ring", "ulysses"):
            raise ValueError(f"unknown seq_strategy {seq_strategy!r}")
        spec = P(batch_axis, seq_axis, None)

    if seq_parallel and seq_strategy == "ulysses":
        # heads stay unfolded: the all_to_all itself is the head split.
        # check_vma only without pallas: pallas_call outputs carry no vma
        # annotations (same constraint as shard_map_backend)
        f = shard_map(
            functools.partial(ulysses_attention, axis_name=seq_axis,
                              n_shards=n, num_heads=num_heads, scale=scale,
                              use_pallas=use_pallas),
            mesh=seq_mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check=not use_pallas)
        out = f(q, k, v)
    else:
        if num_heads > 1:
            q, k, v = (_split_heads(t, num_heads) for t in (q, k, v))
        if seq_parallel:
            if use_pallas:
                # ring x flash: the per-hop fold runs the flash kernels, so
                # no device ever materializes even its LOCAL
                # [S_local, S_local] score block — the composition for
                # sequences whose shards are themselves long
                # (ops/pallas_attention.py::ring_flash_attention)
                from dcgan_tpu.ops.pallas_attention import (
                    ring_flash_attention,
                )

                ring_fn = functools.partial(
                    ring_flash_attention, scale=scale, axis_name=seq_axis,
                    n_shards=n)
            else:
                ring_fn = functools.partial(
                    ring_attention, axis_name=seq_axis, n_shards=n,
                    scale=scale)
            ring = shard_map(
                ring_fn, mesh=seq_mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check=not use_pallas)
            out = ring(q, k, v)
        elif use_pallas:
            from dcgan_tpu.ops.pallas_attention import flash_attention

            if pallas_mesh is not None and \
                    pallas_mesh.shape.get(batch_axis, 1) > 1:
                # data-parallel gspmd mesh: run the kernels per batch
                # shard inside a nested shard_map. Heads ride the batch
                # dim batch-major, so when B divides the data-axis size
                # each shard holds whole batches' head groups — but
                # correctness does NOT depend on that alignment: every
                # [b, head] row is independent in flash_attention, so a
                # split that lands mid-head-group is merely a layout, not
                # a semantics, difference. check_vma off: pallas outputs
                # carry no vma annotations.
                spec = P(batch_axis, None, None)
                out = shard_map(
                    # scale closed over: custom_vjp nondiff args must stay
                    # positional
                    lambda qs, ks, vs: flash_attention(qs, ks, vs, scale),
                    mesh=pallas_mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check=False)(q, k, v)
            else:
                out = flash_attention(q, k, v, scale)
        else:
            out = full_attention(q, k, v, scale=scale)
        if num_heads > 1:
            out = _merge_heads(out, num_heads)

    out = linear_apply(params["out"], out.astype(v.dtype), compute_dtype=cdt)
    gamma = params["gamma"].astype(x.dtype)
    return x + gamma * out.reshape(B, H, W, C).astype(x.dtype)


def _split_heads(t: jax.Array, h: int) -> jax.Array:
    """[B, S, h*d] -> [B*h, S, d] (heads ride the batch dim)."""
    B, S, D = t.shape
    return t.reshape(B, S, h, D // h).transpose(0, 2, 1, 3) \
        .reshape(B * h, S, D // h)


def _merge_heads(t: jax.Array, h: int) -> jax.Array:
    """[B*h, S, d] -> [B, S, h*d]."""
    Bh, S, d = t.shape
    return t.reshape(Bh // h, h, S, d).transpose(0, 2, 1, 3) \
        .reshape(Bh // h, S, h * d)
