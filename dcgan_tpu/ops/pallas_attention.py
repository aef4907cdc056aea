"""Pallas TPU flash attention: blocked online-softmax forward + flash backward.

The attention block (ops/attention.py) is the framework's long-context hot op
— images flatten to an H*W token sequence and DCGAN's conv stacks turn into
SAGAN stacks (ModelConfig.attn_res). XLA lowers dense attention as materialize
-softmax-matmul: the [S, S] score matrix crosses HBM twice per direction. This
module is the memory-optimal form (Flash Attention, arXiv:2205.14135,
expressed as TPU Pallas kernels): scores live only as [TQ, TK] VMEM tiles, an
online softmax folds each tile into running (max, normalizer, accumulator)
statistics, and the backward recomputes tiles from the saved log-sum-exp
instead of reading a stored probability matrix. O(S) HBM traffic in S instead
of O(S^2) — the property that makes sequence length a free axis.

Layout notes (TPU):
- Forward blocks are [TQ, d] / [TK, d] with TQ = 256, TK = 1024 (chip-swept,
  see BLOCK_Q/BLOCK_K below — NOT the 128 MXU edge: the systolic array stays
  busy either way, and wide k-tiles quarter the serialized online-softmax
  iterations); `q @ k^T` and `p @ v` land on the MXU in the input dtype
  with f32 accumulation (`preferred_element_type`). Grid (B, S/TQ), both
  axes parallel; the kernel loops over k-tiles with `lax.fori_loop`.
- The backward is ONE kernel, `flash_dq_dkv`: each [TQ, TK] score tile is
  rebuilt once (s, p = exp(s - lse), dp, ds) and feeds all three gradient
  products. Grid (B, S/TK): the batch axis is parallel, the k-tile axis is
  SEQUENTIAL ("arbitrary") because dQ is a whole-sequence f32 accumulator
  [S, dk] in VMEM scratch that every k-tile adds to, cast to the gradient
  dtype into its resident output block at the last k-tile. The kernel loops
  over q-tiles (BWD_BLOCK_Q = 1024) with dK^T / dV^T carried as values.
- Every product over the tile is a PLAIN matmul: dQ = ds @ k, and dK^T =
  q^T @ ds, dV^T = do^T @ p with q^T / do^T handed in already transposed
  ([B, d, S], sequence on the lane axis — `_bwd_stats` builds them once).
  Contracting over the tile's leading axis instead (ds^T @ q) sends the
  whole [TQ, TK] tile through the transpose unit: 38 ms an instance where
  this form takes 23 (v5e, batch 256, S 4096, d 8/32; PERF.md section 6).
  dK^T / dV^T leave the kernel as [B, d, S] and XLA transposes them back.
- Residents per batch row: q^T, do^T, lse, delta — all lane-dense along S,
  so none pads; the dQ accumulator and its output block are the only
  [S, d] (lane-padded) residents, which keeps S = 65536 compiling at
  batch > 1 like the two-kernel backward it replaces.
- Head widths that have RUN on the v5e: q/k 8 with v 32 (SAGAN: d_qk = C/8,
  d_v = C/2; they ride the lane axis zero-padded, which wastes lanes but not
  HBM), 64/64 (tools/bench_attention.py), and since PR 27 q/k 192 with v 128
  at S = 8192, causal, heads folded into the batch axis (the latent-attention
  trunk of models/mla_moe.py; PERF.md section 6 has the times). Other widths
  compile from the same code and have not been timed.
- `causal=True` (a static argument) computes the lower triangle only: the
  forward's k-loop ends at the q-tile's diagonal, the backward's q-loop
  starts at the k-tile's, and only the tiles the diagonal crosses build a
  mask. Tiles above the diagonal are skipped, not masked after the fact.
  The non-causal call traces the same kernel bodies it always did.
- Off-TPU the kernels run under `interpret=True`, so the CPU test mesh
  exercises the identical code path (tests/test_flash_backward.py and
  tests/test_pallas_attention.py assert exactness against
  ops/attention.py::full_attention, gradients included).

Composition: `ops/attention.py::attn_apply(use_pallas=True)` routes its dense
path here (single chip, or per-shard under the shard_map backend — pallas_call
is opaque to the GSPMD partitioner, same constraint as ops/pallas_kernels.py).
Under a spatial mesh the same flag routes the ring strategy through
`ring_flash_attention` (bottom of this module): ring hops bound the
per-device sequence, flash tiles bound the per-hop fold, so neither level
ever materializes a score matrix — the nesting for sequences whose shards
are themselves long.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tile sizes. The per-tile softmax state update is loop-carried, so tile
# COUNT — not matmul rate — dominates at the head dims this model uses;
# large k-tiles amortize that serialization. (256, 1024) won a tile sweep on
# the previous machine against the naive MXU-edge 128/128 (timings not
# measured on the current one; DESIGN.md §8). Overridable via
# the DCGAN_FLASH_TQ / DCGAN_FLASH_TK env vars — read at TRACE time, and
# the resolved tiles are baked into the jit-compiled program (they are not
# part of the jit cache key), so set them before the first call for a given
# shape; sweeps use a fresh process per grid point (bench_attention.py).
BLOCK_Q = 256
BLOCK_K = 1024
# The backward's q-tile: it carries no softmax state from tile to tile, so a
# taller tile only amortizes the per-iteration relayouts (lse/delta to
# columns, the q^T/do^T tiles back to rows). 1024 beat 256 and 512 at every
# shape swept on the v5e (S 1024-65536, d 8/32 and 64/64; PERF.md section 6).
BWD_BLOCK_Q = 1024

# Measurement generation: bump on ANY change that alters attention-kernel
# performance characteristics (tile defaults, precision policy, block
# layouts). tools/bench_attention.py stamps it into every timing row and
# tools/capture_all.py publishes only the highest generation present per
# sequence length — so crossover tables never mix measurements of
# different kernel code. Gen 2 = bf16-operand policy + (256, 1024) tiles +
# lane-major backward stats. Gen 3 = one backward kernel (flash_dq_dkv).
# Gen 4 = the static `causal` argument (non-causal programs unchanged).
ATTN_GEN = 4

_NEG_INF = -1e30  # finite stand-in for -inf: keeps exp()/max() NaN-free


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params(inner: str):
    """The batch axis is always parallel. The forward's q-tile programs are
    independent too (softmax state is loop-carried INSIDE a program);
    the backward's k-tile axis is "arbitrary": dQ accumulates across it."""
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", inner),
        # both kernels hold full-sequence residents (double-buffered across
        # the batch grid axis); the default VMEM budget is tighter than the
        # hardware's — claim most of the 128 MiB explicitly
        vmem_limit_bytes=100 * 1024 * 1024)


def _tile(s: int, which: str, default: int) -> int:
    """Largest tile <= the configured target dividing s, subject to the
    Mosaic constraint that non-full block dims be multiples of 8 (sequence
    lengths here are powers of two times small factors, so such a divisor
    exists for every supported shape; if none does, the full sequence is
    always a legal block)."""
    raw = os.environ.get(f"DCGAN_FLASH_{which}", default)
    try:
        target = int(raw)
    except ValueError:
        raise ValueError(
            f"DCGAN_FLASH_{which}={raw!r} is not an integer") from None
    if target < 1:
        raise ValueError(f"DCGAN_FLASH_{which}={target} must be >= 1")
    if target >= s:
        return s
    for b in range(min(s, target), 7, -1):
        if s % b == 0 and b % 8 == 0:
            return b
    return s


def _blocks(s: int, block_q: int = BLOCK_Q) -> tuple:
    return _tile(s, "TQ", block_q), _tile(s, "TK", BLOCK_K)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _causal_keep(row0, col0, tq: int, tk: int):
    """[TQ, TK] mask of a score tile whose first row is query `row0` and
    first column is key `col0`: True where the key is not after the query."""
    rows = row0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    cols = col0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return cols <= rows


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, tk,
                causal=False):
    # Precision policy (shared with ops/attention.py::full_attention):
    # matmul operands stay in the INPUT dtype — bf16 rides the MXU fast
    # path — while scores/stats/accumulator are f32 via
    # preferred_element_type; p is cast back to the operand dtype for the
    # PV matmul (the flash-attention recipe). f32 inputs take the exact
    # f32 path unchanged.
    q = q_ref[0]                                        # [TQ, d]
    mmdt = q.dtype
    tq = q.shape[0]
    dv = v_ref.shape[-1]
    n_k = k_ref.shape[1] // tk
    row0 = pl.program_id(1) * tq if causal else None

    def body(j, carry, masked=False):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * tk, tk), :]
        vb = v_ref[0, pl.ds(j * tk, tk), :]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = jnp.where(_causal_keep(row0, j * tk, tq, tk), s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(mmdt), vb,
                                   preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((tq, 1), jnp.float32)
    acc0 = jnp.zeros((tq, dv), jnp.float32)
    if causal:
        # k-tiles wholly on or below the diagonal of this q-tile, then the
        # ones the diagonal crosses; the rest are never touched. Key 0 is in
        # the first tile and no query precedes it, so every row's running
        # max is a real score from the first tile on.
        n_full = (row0 + 1) // tk
        n_here = (row0 + tq + tk - 1) // tk
        carry = lax.fori_loop(0, n_full, body, (m0, l0, acc0))
        m, l, acc = lax.fori_loop(
            n_full, n_here, functools.partial(body, masked=True), carry)
    else:
        m, l, acc = lax.fori_loop(0, n_k, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # log-sum-exp per row — the single vector the backward needs to
    # reconstruct p tiles without storing them. Kept [S, 1] (not [S]):
    # Mosaic requires block last-two dims (8, 128)-divisible or full, which
    # a trailing singleton satisfies and a flat [B, S] block cannot.
    lse_ref[0] = m + jnp.log(l)


def _fwd_impl(q, k, v, scale, causal=False):
    B, S, dk = q.shape
    dv = v.shape[-1]
    tq, tk = _blocks(S)
    kernel = functools.partial(_fwd_kernel, scale=scale, tk=tk)
    if causal:
        kernel = functools.partial(kernel, causal=True)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(B, S // tq),
        in_specs=[pl.BlockSpec((1, tq, dk), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, S, dk), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, S, dv), lambda b, i: (b, 0, 0))],
        out_specs=(pl.BlockSpec((1, tq, dv), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, tq, 1), lambda b, i: (b, i, 0))),
        out_shape=(jax.ShapeDtypeStruct((B, S, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, S, 1), jnp.float32)),
        compiler_params=_compiler_params("parallel"),
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_dkv_kernel(k_ref, v_ref, qT_ref, doT_ref, lse_ref, delta_ref,
                   dq_ref, dkT_ref, dvT_ref, dq_acc, *, scale, tq,
                   causal=False):
    # same operand-dtype / f32-accumulation policy as the forward. One
    # k-tile per program; q^T/do^T/lse/delta enter as full-sequence
    # residents with the sequence on the LANE axis (a [S, d] or [S, 1]
    # layout would lane-pad up to 128x and scale VMEM residency with S,
    # which walled compilation at large S/batch).
    j = pl.program_id(1)
    kb = k_ref[0]                                        # [TK, dk]
    vb = v_ref[0]                                        # [TK, dv]
    mmdt = kb.dtype
    n_q = qT_ref.shape[2] // tq

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def body(i, carry, masked=False):
        dkT, dvT = carry
        rows = pl.ds(pl.multiple_of(i * tq, tq), tq)
        qT = qT_ref[0, :, rows]                          # [dk, TQ]
        doT = doT_ref[0, :, rows]                        # [dv, TQ]
        lse = lse_ref[0, 0, rows][:, None]               # [TQ, 1]
        delta = delta_ref[0, 0, rows][:, None]
        s = jax.lax.dot_general(qT.T, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            tk = kb.shape[0]
            s = jnp.where(_causal_keep(i * tq, j * tk, tq, tk), s, _NEG_INF)
        p = jnp.exp(s - lse)                             # [TQ, TK]
        dp = jax.lax.dot_general(doT.T, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(mmdt)             # [TQ, TK]
        dq_acc[rows, :] += jnp.dot(ds, kb,
                                   preferred_element_type=jnp.float32)
        dkT = dkT + jnp.dot(qT, ds, preferred_element_type=jnp.float32)
        dvT = dvT + jnp.dot(doT, p.astype(mmdt),
                            preferred_element_type=jnp.float32)
        return dkT, dvT

    zeros = (jnp.zeros(dkT_ref.shape[1:], jnp.float32),
             jnp.zeros(dvT_ref.shape[1:], jnp.float32))
    if causal:
        # q-tiles the diagonal crosses within this k-tile, then the ones
        # wholly on or below it; q-tiles above the k-tile are skipped (their
        # rows of the dQ accumulator take nothing from it)
        col0 = j * kb.shape[0]
        i_first = col0 // tq
        i_full = jnp.minimum((col0 + kb.shape[0] + tq - 2) // tq, n_q)
        carry = lax.fori_loop(i_first, i_full,
                              functools.partial(body, masked=True), zeros)
        dkT, dvT = lax.fori_loop(i_full, n_q, body, carry)
    else:
        dkT, dvT = lax.fori_loop(0, n_q, body, zeros)
    dkT_ref[0] = (dkT * scale).astype(dkT_ref.dtype)
    dvT_ref[0] = dvT.astype(dvT_ref.dtype)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_stats(q, out, lse, g):
    """The hop-invariant backward inputs, computed once per backward pass
    (the ring backward reuses them across every hop), all with the sequence
    on the LANE axis — the kernel holds them full-sequence, and a [S, d]
    block lane-pads up to 128x (8 MiB at S=16384 where 64 KiB is the data):

    - qT [B, dk, S]: q transposed, so dK^T = q^T @ ds is a plain matmul.
    - doT [B, dv, S]: the f32 cotangent cast to the matmul operand dtype
      ONCE — under bf16 it halves its HBM traffic and VMEM residency — and
      transposed like q.
    - lse, and delta_i = rowsum(dO_i * O_i), the softmax-jacobian correction
      term (one fused elementwise reduction, XLA handles it): [B, 1, S].
    """
    B, S, _ = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * out, axis=-1)
    return (jnp.swapaxes(q, 1, 2), jnp.swapaxes(g.astype(q.dtype), 1, 2),
            lse.reshape(B, 1, S), delta.reshape(B, 1, S))


def _bwd_impl(scale, causal, res, g):
    q, k, v, out, lse = res
    return _bwd_core(scale, k, v, *_bwd_stats(q, out, lse, g), causal=causal)


def _bwd_core(scale, k, v, qT, doT, lse, delta, grad_dtype=None,
              causal=False):
    """The backward pallas_call: dQ, dK and dV from one pass over the score
    tiles. grad_dtype overrides the gradient output dtype (the ring
    backward asks for f32 so per-hop contributions are not rounded to bf16
    before the cross-hop accumulation)."""
    B, S, dk = k.shape
    dv = v.shape[-1]
    tq, tk = _blocks(S, BWD_BLOCK_Q)

    def resident(d):
        return pl.BlockSpec((1, d, S), lambda b, j: (b, 0, 0))

    kernel = functools.partial(_dq_dkv_kernel, scale=scale, tq=tq)
    if causal:
        kernel = functools.partial(kernel, causal=True)
    dq, dkT, dvT = pl.pallas_call(
        kernel,
        name="flash_dq_dkv",
        grid=(B, S // tk),
        in_specs=[pl.BlockSpec((1, tk, dk), lambda b, j: (b, j, 0)),
                  pl.BlockSpec((1, tk, dv), lambda b, j: (b, j, 0)),
                  resident(dk), resident(dv), resident(1), resident(1)],
        out_specs=(pl.BlockSpec((1, S, dk), lambda b, j: (b, 0, 0)),
                   pl.BlockSpec((1, dk, tk), lambda b, j: (b, 0, j)),
                   pl.BlockSpec((1, dv, tk), lambda b, j: (b, 0, j))),
        out_shape=(jax.ShapeDtypeStruct((B, S, dk), grad_dtype or qT.dtype),
                   jax.ShapeDtypeStruct((B, dk, S), grad_dtype or k.dtype),
                   jax.ShapeDtypeStruct((B, dv, S), grad_dtype or v.dtype)),
        scratch_shapes=[pltpu.VMEM((S, dk), jnp.float32)],
        compiler_params=_compiler_params("arbitrary"),
        interpret=_interpret(),
    )(k, v, qT, doT, lse, delta)
    return dq, jnp.swapaxes(dkT, 1, 2), jnp.swapaxes(dvT, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale: float, causal: bool = False) -> jax.Array:
    """softmax(q k^T * scale) v over [B, S, d] blocks without ever
    materializing the [S, S] score matrix in HBM. Returns float32 (matching
    ops/attention.py::full_attention's accumulation contract). `causal`
    (static): query i sees keys 0..i only, and the tiles above the diagonal
    are not computed."""
    out, _ = _fwd_impl(q, k, v, scale, causal)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal):
    out, lse = _fwd_impl(q, k, v, scale, causal)
    return out, (q, k, v, out, lse)


flash_attention.defvjp(_flash_vjp_fwd, _bwd_impl)


# ---------------------------------------------------------------------------
# ring x flash composition: sequence-parallel attention whose per-hop fold
# runs the flash kernels — for the regime where each device's S_local block
# itself outgrows what a dense [S_local, S_local] fold should materialize.
# ---------------------------------------------------------------------------

def ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         scale: float, axis_name: str,
                         n_shards: int) -> jax.Array:
    """Exact attention over a sequence sharded along `axis_name`, with every
    per-block fold running the flash kernels instead of a dense
    [S_local, S_local] einsum.

    Same contract as ops/attention.py::ring_attention (q/k/v [B, S_local, d]
    per device, n_shards-1 ppermute hops, f32 result), but the hop fold is
    `_fwd_impl` — each block contributes a normalized partial (out_b, lse_b)
    and partials merge associatively: lse = logaddexp(lse_a, lse_b),
    out = out_a*exp(lse_a-lse) + out_b*exp(lse_b-lse). The backward
    re-rotates (k, v) around the ring and reuses `_bwd_core` per hop with
    the GLOBAL lse (p = exp(s - lse_global) gives each block's true global
    probabilities), accumulating dq locally while (dk, dv) ride the ring
    with their blocks and land home after the full cycle.
    """
    if n_shards == 1:
        return flash_attention(q, k, v, scale)
    return _ring_flash(q, k, v, scale, axis_name, n_shards)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash(q, k, v, scale, axis_name, n_shards):
    out, _ = _ring_flash_fwd_pass(q, k, v, scale, axis_name, n_shards)
    return out


def _ring_flash_fwd_pass(q, k, v, scale, axis_name, n_shards):
    fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    # resident block first (no hop result is discarded), then n-1 rotations
    out, lse = _fwd_impl(q, k, v, scale)

    def hop(carry, _):
        k_blk, v_blk, out, lse = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm=fwd)
        v_blk = lax.ppermute(v_blk, axis_name, perm=fwd)
        out_b, lse_b = _fwd_impl(q, k_blk, v_blk, scale)
        lse_new = jnp.logaddexp(lse, lse_b)
        out = (out * jnp.exp(lse - lse_new)
               + out_b * jnp.exp(lse_b - lse_new))
        return (k_blk, v_blk, out, lse_new), None

    (_, _, out, lse), _ = lax.scan(
        hop, (k, v, out, lse), None, length=n_shards - 1)
    return out, lse


def _ring_flash_vjp_fwd(q, k, v, scale, axis_name, n_shards):
    out, lse = _ring_flash_fwd_pass(q, k, v, scale, axis_name, n_shards)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(scale, axis_name, n_shards, res, g):
    q, k, v, out, lse = res
    fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    # hop-invariant backward inputs computed ONCE (q^T, the operand-dtype
    # cotangent transposed, lse and delta lane-major) — only the backward
    # kernel re-runs per hop
    stats = _bwd_stats(q, out, lse, g)

    def hop(carry, _):
        # (k, v) and their accumulated gradients travel TOGETHER: each
        # device adds its contribution to the passing block, and after the
        # full n_shards-rotation cycle every (dk, dv) sits on the block's
        # home device, complete. dq accumulates locally. Per-hop gradient
        # terms come out of the kernels ALREADY f32 (grad_dtype) so the
        # cross-hop accumulation never rounds through bf16.
        k_blk, v_blk, dk_c, dv_c, dq = carry
        dq_h, dk_h, dv_h = _bwd_core(scale, k_blk, v_blk, *stats,
                                     grad_dtype=jnp.float32)
        dq = dq + dq_h
        dk_c = dk_c + dk_h
        dv_c = dv_c + dv_h
        k_blk = lax.ppermute(k_blk, axis_name, perm=fwd)
        v_blk = lax.ppermute(v_blk, axis_name, perm=fwd)
        dk_c = lax.ppermute(dk_c, axis_name, perm=fwd)
        dv_c = lax.ppermute(dv_c, axis_name, perm=fwd)
        return (k_blk, v_blk, dk_c, dv_c, dq), None

    zeros = (jnp.zeros(k.shape, jnp.float32),
             jnp.zeros(v.shape, jnp.float32))
    (_, _, dk_c, dv_c, dq), _ = lax.scan(
        hop, (k, v) + zeros + (jnp.zeros(q.shape, jnp.float32),),
        None, length=n_shards)
    # after n rotations the blocks (and their grads) are home again
    return (dq.astype(q.dtype), dk_c.astype(k.dtype),
            dv_c.astype(v.dtype))


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)
