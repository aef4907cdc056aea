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
- The forward, `flash_fwd`, works in the TRANSPOSED domain, one kernel for
  every shape. Grid (B, S/TQ), both axes parallel; inputs q^T [B, dk, S]
  (block (1, dk, TQ)), k [B, S, dk] and v^T [B, dv, S] resident per batch
  row; a `lax.fori_loop` over k-tiles inside. Each fold builds the score
  tile as s^T = k_tile q^T, [TK, TQ] with keys on sublanes and queries on
  lanes (a plain matmul, no transposed contraction). The running max and
  sum are [1, TQ] ROWS: their reductions fold vregs elementwise down the
  sublane axis (one 8-to-1 fold per 128 queries) where a [TQ, 1] column
  folds 128 lanes per 8 rows through the cross-lane unit, and they
  broadcast back along sublanes. The value product is acc^T [dv, TQ] +=
  v^T_tile @ p^T: the p^T tile is the MXU's STATIONARY operand (its
  weights) and the value head pushes only its dv rows against each weight
  tile, where `acc += p v` pushed all TQ rows of p against weight tiles
  that used dv of their 128 columns. Query columns are independent, so a
  wide q-tile and several folds per loop iteration let one fold's products
  overlap another's exp (FWD_BLOCK_Q / FWD_BLOCK_K / FWD_UNROLL below).
- Lane-dense along S: the v^T resident and the outputs o^T [B, dv, S] f32
  and lse [B, 1, S] (a [B, S, 32] f32 and a [B, S, 1] array lane-pad 4x and
  128x in HBM). Lane-padded: the k resident [S, dk]. The q-tile rides the
  LANE axis, so on the chip TQ and TK are multiples of 128 (or the whole
  sequence); every power-of-two S gives such tiles.
- Precision policy, shared with ops/attention.py::full_attention: matmul
  operands in the input dtype (bf16 on the MXU's fast path), scores, exp,
  max, sum and accumulator f32 (`preferred_element_type`), `scale` applied
  in f32 to the scores, p^T cast to the operand dtype for the value product.
- The backward is ONE kernel, `flash_dq_dkv`: each [TQ, TK] score tile is
  rebuilt once (s, p = exp(s - lse), dp, ds) and feeds all three gradient
  products. Grid (B, S/TK): the batch axis is parallel, the k-tile axis is
  SEQUENTIAL ("arbitrary") because dQ is a whole-sequence f32 accumulator
  [S, dk] in VMEM scratch that every k-tile adds to, cast to the gradient
  dtype into its resident output block at the last k-tile. The kernel loops
  over q-tiles (BWD_BLOCK_Q = 1024) with dK^T / dV^T carried as values.
- Every product over the tile is a PLAIN matmul: dQ = ds @ k, and dK^T =
  q^T @ ds, dV^T = do^T @ p with q^T / do^T handed in already transposed
  ([B, d, S], sequence on the lane axis). Contracting over the tile's
  leading axis instead (ds^T @ q) sends the whole [TQ, TK] tile through
  the transpose unit: 38 ms an instance where this form takes 23 (v5e,
  batch 256, S 4096, d 8/32; PERF.md section 6). dK^T / dV^T leave the
  kernel as [B, d, S] and XLA transposes them back.
- Residents per batch row: q^T, do^T, lse, delta — all lane-dense along S,
  so none pads; the dQ accumulator and its output block are the only
  [S, d] (lane-padded) residents, which keeps S = 65536 compiling at
  batch > 1 like the two-kernel backward it replaced.
- The hand-off: the forward's q^T, o^T and lse [B, 1, S] are saved as
  residuals in the layouts the backward takes, so `_bwd_inputs` transposes
  only the cotangent and forms delta from g^T and o^T. The [B, S, d] <->
  [B, d, S] swaps at the edges of `flash_attention` are XLA's.
- Head widths that have RUN on the v5e: q/k 8 with v 32 (SAGAN: d_qk = C/8,
  d_v = C/2; they ride the lane axis zero-padded, which wastes lanes but not
  HBM), 64/64 (tools/bench_attention.py), and since PR 27 q/k 192 with v 128
  at S = 8192, causal, heads folded into the batch axis (the latent-attention
  trunk of models/mla_moe.py; PERF.md section 6 has the times), and since
  PR 31 q/k/v 128/128/128 at 16 heads, S = 4096, causal (the looped trunk of
  models/loop_lm.py): 0.946 ms the forward alone, 2.389 ms forward +
  backward (tools/bench_attention.py, one TPU v5 lite; PERF.md section 6),
  and since PR 33 q/k 64 with v 128 at 40 folded rows, S = 8192 (the
  differential attention of models/sambay.py: a pair's two maps share one
  value set two heads wide): causal 6.80 ms the forward alone, 17.24 ms
  forward + backward; with `window=512` 2.91 / 6.31 ms.
  Other widths compile from the same code and have not been timed.
- `causal=True` (a static argument) computes the lower triangle only: the
  forward's k-loop ends at the q-tile's diagonal, the backward's q-loop
  starts at the k-tile's, and only the tiles the diagonal crosses build a
  mask. Tiles above the diagonal are skipped, not masked after the fact.
  The non-causal call traces kernel bodies with no mask in them.
- `window=w` (a static argument, with `causal=True`): query i sees keys
  i - w + 1..i, the position itself counted. The forward's k-loop starts at
  the tile of the q-tile's first row less w - 1 and ends at its diagonal;
  the backward's q-loop runs from the k-tile's diagonal to the tile of its
  last column plus w - 1. Tiles outside the band are skipped, not masked;
  tiles that the band's two edges cross build a mask, tiles wholly inside
  it none. `window=None` traces the kernel bodies it traced before the
  argument existed (tests/test_flash_window.py pins the lowered text), and
  `window >= S` IS the causal call, bit for bit. The windowed call runs
  under its own kernel names (`flash_fwd_win`, `flash_dq_dkv_win`) at its
  own tiles, WIN_BLOCK_Q x WIN_BLOCK_K = 512 x 512 for both kernels: with
  the causal forward's 2,048-wide q-tile a 512-key band would compute
  2,560 keys a tile for 512 useful. Swept at 40 rows x 8,192, q/k 64, v
  128, w 512 (forward / forward + backward, ms; my chip run, PR 33): TQ 128
  with TK 128 / 256 / 512: 5.49 / 13.72, 4.67 / 10.47, 4.42 / 8.86; TQ 256:
  4.36 / 11.34, 3.68 / 7.72, 3.83 / 7.46; TQ 512: 3.63 / 10.79, 3.14 /
  7.43, **2.91 / 6.31**. At 512 x 512 a q-tile folds two k-tiles, both
  masked (half the scores computed are inside the band); smaller tiles
  waste fewer scores and lose more to the per-tile overheads.
- Off-TPU the kernels run under `interpret=True`, so the CPU test mesh
  exercises the identical code path (tests/test_flash_forward.py,
  tests/test_flash_backward.py and tests/test_pallas_attention.py assert
  exactness against ops/attention.py::full_attention, gradients included).

Composition: `ops/attention.py::attn_apply(use_pallas=True)` routes its dense
path here (single chip, or per-shard under the shard_map backend and under the
gspmd backend's nested shard_map — pallas_call is opaque to the GSPMD
partitioner).
Under a spatial mesh the same flag routes the ring strategy through
`ring_flash_attention` (bottom of this module): ring hops bound the
per-device sequence, flash tiles bound the per-hop fold, so neither level
ever materializes a score matrix — the nesting for sequences whose shards
are themselves long.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tile sizes, all chip-swept on the v5e (PERF.md section 6; CHANGES.md PR 28
# has the tables). Overridable via the DCGAN_FLASH_TQ / DCGAN_FLASH_TK env
# vars — read at TRACE time, and the resolved tiles are baked into the
# jit-compiled program (they are not part of the jit cache key), so set them
# before the first call for a given shape (tools/bench_attention.py makes a
# new jitted function per grid point).
#
# The forward: its q-tile rides the LANE axis, and every query column of a
# score tile is independent of the others, so a WIDE q-tile gives the
# scheduler independent work to put beside each fold's serial chain (max,
# exp, sum, value product) and amortizes the k/v tile loads; a SHORT k-tile
# keeps one fold's tile small, and FWD_UNROLL folds share one loop iteration
# (one basic block), so one fold's s^T product overlaps the exp and the
# value product of the fold before it. 2048 / 256 won at every shape swept
# (S 1,024-16,384 at 8/32 and 64/64; 192/128 at 8,192 causal or not);
# 512 / 1024 with one fold an iteration is 35 % slower at 8/32, a q-tile of
# 256 (the width the row form this kernel replaced had) 70 %. Folds an
# iteration, sagan128's step at batch 256: 1 -> 1,161.5 images/s, 2 ->
# 1,179.1, 4 -> 1,200.2, 8 -> 1,206.6; but the kernel is traced once per
# call site with every fold written out, and at 8 that costs 1.3 s of every
# start (4 % of a warm set-up) where 4 costs nothing over the row form.
FWD_BLOCK_Q = 2048
FWD_BLOCK_K = 256
FWD_UNROLL = 4
# The backward's q-tile: it carries no softmax state from tile to tile, so a
# taller tile only amortizes the per-iteration relayouts (lse/delta to
# columns, the q^T/do^T tiles back to rows). 1024 beat 256 and 512 at every
# shape swept on the v5e (S 1024-65536, d 8/32 and 64/64; PERF.md section 6).
# Its k-tile (one grid step) is BLOCK_K. BLOCK_Q is only `_blocks`' default:
# both kernels pass their own q-tile.
BLOCK_Q = 256
BLOCK_K = 1024
BWD_BLOCK_Q = 1024
# The windowed call's tiles (`window`), forward and backward alike: the
# module's docstring has the sweep and why the causal tiles do not serve.
WIN_BLOCK_Q = 512
WIN_BLOCK_K = 512

# Measurement generation: bump on ANY change that alters attention-kernel
# performance characteristics (tile defaults, precision policy, block
# layouts). tools/bench_attention.py stamps it into every timing row and
# tools/capture_all.py publishes only the highest generation present per
# sequence length — so crossover tables never mix measurements of
# different kernel code. Gen 2 = bf16-operand policy + (256, 1024) tiles +
# lane-major backward stats. Gen 3 = one backward kernel (flash_dq_dkv).
# Gen 4 = the static `causal` argument (non-causal programs unchanged).
# Gen 5 = the forward in the transposed domain (s^T = k q^T, o^T and lse out
# lane-dense, residuals handed to the backward as they are), every shape.
ATTN_GEN = 5

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


def _blocks(s: int, block_q: int = BLOCK_Q, block_k: int = BLOCK_K) -> tuple:
    return _tile(s, "TQ", block_q), _tile(s, "TK", block_k)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(qT_ref, k_ref, vT_ref, oT_ref, lse_ref, *, scale, tk, unroll,
                causal=False, window=None):
    # Precision policy (shared with ops/attention.py::full_attention):
    # matmul operands stay in the INPUT dtype — bf16 rides the MXU fast
    # path — while scores/stats/accumulator are f32 via
    # preferred_element_type, `scale` is applied in f32 to the scores, and
    # p^T is cast back to the operand dtype for the value product (the
    # flash-attention recipe). f32 inputs take the exact f32 path unchanged.
    qT = qT_ref[0]                                      # [dk, TQ]
    mmdt = qT.dtype
    tq = qT.shape[1]
    dv = vT_ref.shape[1]
    n_k = k_ref.shape[1] // tk
    q0 = pl.program_id(1) * tq if causal else None

    def fold(j, carry, masked):
        m, l, acc = carry                               # [1,TQ] x2, [dv,TQ]
        keys = pl.ds(pl.multiple_of(j * tk, tk), tk)
        kb = k_ref[0, keys, :]                          # [TK, dk]
        vTb = vT_ref[0, :, keys]                        # [dv, TK]
        # keys on sublanes, queries on lanes
        sT = jnp.dot(kb, qT, preferred_element_type=jnp.float32) * scale
        if masked:
            key = j * tk + lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
            query = q0 + lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
            keep = key <= query
            if window is not None:
                keep = keep & (key > query - window)
            sT = jnp.where(keep, sT, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sT, axis=0, keepdims=True))
        pT = jnp.exp(sT - m_new)                        # [TK, TQ]
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(pT, axis=0, keepdims=True)
        # the tile is the STATIONARY operand: dv rows pushed per weight tile
        acc = acc * corr + jnp.dot(vTb, pT.astype(mmdt),
                                   preferred_element_type=jnp.float32)
        return m_new, l, acc

    def loop(lo, hi, carry, masked=False):
        # `unroll` folds per iteration; lo and hi are whole multiples of it
        def group(g, carry):
            for u in range(unroll):
                carry = fold(g * unroll + u, carry, masked)
            return carry
        return lax.fori_loop(lo // unroll, hi // unroll, group, carry)

    carry = (jnp.full((1, tq), _NEG_INF, jnp.float32),
             jnp.zeros((1, tq), jnp.float32),
             jnp.zeros((dv, tq), jnp.float32))
    if window is not None:
        # the band: k-tiles from the one that holds the first query's first
        # key to the diagonal's. Tiles that the band's lower edge crosses
        # (masked), tiles wholly inside the band (plain), tiles the
        # diagonal crosses (masked); where the window is shorter than a
        # q-tile and a k-tile together no tile is plain. A column whose
        # keys of the first tile are all masked carries _NEG_INF and sums
        # of ones until its first real score, whose `corr` is exp(-1e30) =
        # 0: what was gathered before it is wiped.
        n_here = (q0 + tq + tk - 1) // tk
        lo = jnp.maximum(q0 - (window - 1), 0) // tk
        plain_lo = jnp.clip((q0 + tq - window + tk - 1) // tk, lo, n_here)
        plain_hi = jnp.clip((q0 + 1) // tk, plain_lo, n_here)
        carry = loop(lo, plain_lo, carry, masked=True)
        carry = loop(plain_lo, plain_hi, carry)
        m, l, acc = loop(plain_hi, n_here, carry, masked=True)
    elif causal:
        # k-tiles wholly on or below the diagonal of this q-tile, then the
        # ones the diagonal crosses; the rest are never touched. Key 0 is in
        # the first tile and no query precedes it, so every column's running
        # max is a real score from the first tile on.
        n_full = (q0 + 1) // tk
        n_here = (q0 + tq + tk - 1) // tk
        carry = loop(0, n_full, carry)
        m, l, acc = loop(n_full, n_here, carry, masked=True)
    else:
        m, l, acc = loop(0, n_k, carry)
    oT_ref[0] = (acc / l).astype(oT_ref.dtype)
    # log-sum-exp per query — the single vector the backward needs to
    # reconstruct p tiles without storing them; a lane-dense [1, TQ] row
    lse_ref[0] = m + jnp.log(l)


def _fwd_unroll(tq: int, tk: int) -> int:
    """Folds per loop iteration: a divisor of TQ/TK, so that the whole
    sequence's tile count and the causal loops' bounds (a q-tile's first and
    last key tile) are all whole multiples of it; 1 where TK does not
    divide TQ."""
    return math.gcd(FWD_UNROLL, tq // tk) if tq % tk == 0 else 1


def _fwd_core(qT, k, vT, scale, causal=False, window=None):
    """The forward pallas_call: q^T [B, dk, S], k [B, S, dk], v^T [B, dv, S]
    -> (o^T [B, dv, S] f32, lse [B, 1, S] f32); q^T, o^T and lse are the
    layouts `_bwd_core` takes its residents in."""
    B, dk, S = qT.shape
    dv = vT.shape[1]
    if window is not None:
        # its own tiles, one fold an iteration: the band's loop bounds are
        # no multiples of a group of folds
        tq, tk = _blocks(S, WIN_BLOCK_Q, WIN_BLOCK_K)
        kernel = functools.partial(_fwd_kernel, scale=scale, tk=tk, unroll=1,
                                   causal=True, window=window)
    else:
        tq, tk = _blocks(S, FWD_BLOCK_Q, FWD_BLOCK_K)
        kernel = functools.partial(_fwd_kernel, scale=scale, tk=tk,
                                   unroll=_fwd_unroll(tq, tk))
        if causal:
            kernel = functools.partial(kernel, causal=True)
    return pl.pallas_call(
        kernel,
        name="flash_fwd" if window is None else "flash_fwd_win",
        grid=(B, S // tq),
        in_specs=[pl.BlockSpec((1, dk, tq), lambda b, i: (b, 0, i)),
                  pl.BlockSpec((1, S, dk), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, dv, S), lambda b, i: (b, 0, 0))],
        out_specs=(pl.BlockSpec((1, dv, tq), lambda b, i: (b, 0, i)),
                   pl.BlockSpec((1, 1, tq), lambda b, i: (b, 0, i))),
        out_shape=(jax.ShapeDtypeStruct((B, dv, S), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, S), jnp.float32)),
        compiler_params=_compiler_params("parallel"),
        interpret=_interpret(),
    )(qT, k, vT)


def _fwd_impl(q, k, v, scale, causal=False, window=None):
    """The forward over row-major [B, S, d] blocks, XLA's swaps at its
    edges: (out [B, S, dv] f32, lse [B, 1, S])."""
    outT, lse = _fwd_core(jnp.swapaxes(q, 1, 2), k, jnp.swapaxes(v, 1, 2),
                          scale, causal, window)
    return jnp.swapaxes(outT, 1, 2), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _causal_keep(row0, col0, tq: int, tk: int, window=None):
    """[TQ, TK] mask of a score tile whose first row is query `row0` and
    first column is key `col0`: True where the key is not after the query
    (and, with a window, among the query's last `window` keys)."""
    rows = row0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    cols = col0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    if window is None:
        return cols <= rows
    return (cols <= rows) & (cols > rows - window)


def _dq_dkv_kernel(k_ref, v_ref, qT_ref, doT_ref, lse_ref, delta_ref,
                   dq_ref, dkT_ref, dvT_ref, dq_acc, *, scale, tq,
                   causal=False, window=None):
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
            s = jnp.where(_causal_keep(i * tq, j * tk, tq, tk, window), s,
                          _NEG_INF)
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
    if window is not None:
        # the band seen from this k-tile: q-tiles from its diagonal's to the
        # one that holds the last query its last key reaches: the diagonal
        # crosses the first (masked), then tiles wholly inside the band
        # (plain), then those the band's lower edge crosses (masked)
        col0, tk = j * kb.shape[0], kb.shape[0]
        masked = functools.partial(body, masked=True)
        i_first = col0 // tq
        i_last = jnp.minimum((col0 + tk + window - 2) // tq + 1, n_q)
        plain_lo = jnp.clip((col0 + tk + tq - 2) // tq, i_first, i_last)
        plain_hi = jnp.clip((col0 + window - tq) // tq + 1, plain_lo, i_last)
        carry = lax.fori_loop(i_first, plain_lo, masked, zeros)
        carry = lax.fori_loop(plain_lo, plain_hi, body, carry)
        dkT, dvT = lax.fori_loop(plain_hi, i_last, masked, carry)
    elif causal:
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


def _bwd_inputs(qT, outT, lse, g):
    """The hop-invariant backward inputs, computed once per backward pass
    (the ring backward reuses them across every hop), all with the sequence
    on the LANE axis — the kernel holds them full-sequence, and a [S, d]
    block lane-pads up to 128x (8 MiB at S=16384 where 64 KiB is the data).
    The forward left q^T [B, dk, S], o^T [B, dv, S] and lse [B, 1, S] that
    way already; what is made here:

    - doT [B, dv, S]: the f32 cotangent g [B, S, dv] transposed, and cast to
      the matmul operand dtype ONCE — under bf16 it halves its HBM traffic
      and VMEM residency.
    - delta_i = sum_d(dO_i * O_i), the softmax-jacobian correction term (one
      fused elementwise reduction over g^T and o^T, XLA handles it):
      [B, 1, S].
    """
    gT = jnp.swapaxes(g, 1, 2).astype(jnp.float32)
    delta = jnp.sum(gT * outT, axis=1, keepdims=True)
    return qT, gT.astype(qT.dtype), lse, delta


def _bwd_stats(q, out, lse, g):
    """`_bwd_inputs` for a caller that holds `_fwd_impl`'s row-major q and
    out (tests/test_flash_backward.py pins the ring's float32 contract
    through the pair); the VJPs below keep q^T and o^T and skip the swaps."""
    return _bwd_inputs(jnp.swapaxes(q, 1, 2), jnp.swapaxes(out, 1, 2), lse, g)


def _bwd_impl(scale, causal, window, res, g):
    qT, k, v, outT, lse = res
    return _bwd_core(scale, k, v, *_bwd_inputs(qT, outT, lse, g),
                     causal=causal, window=_band(window, causal, k.shape[1]))


def _bwd_core(scale, k, v, qT, doT, lse, delta, grad_dtype=None,
              causal=False, window=None):
    """The backward pallas_call: dQ, dK and dV from one pass over the score
    tiles. grad_dtype overrides the gradient output dtype (the ring
    backward asks for f32 so per-hop contributions are not rounded to bf16
    before the cross-hop accumulation)."""
    B, S, dk = k.shape
    dv = v.shape[-1]
    if window is not None:
        tq, tk = _blocks(S, WIN_BLOCK_Q, WIN_BLOCK_K)
    else:
        tq, tk = _blocks(S, BWD_BLOCK_Q)

    def resident(d):
        return pl.BlockSpec((1, d, S), lambda b, j: (b, 0, 0))

    kernel = functools.partial(_dq_dkv_kernel, scale=scale, tq=tq)
    if window is not None:
        kernel = functools.partial(kernel, causal=True, window=window)
    elif causal:
        kernel = functools.partial(kernel, causal=True)
    dq, dkT, dvT = pl.pallas_call(
        kernel,
        name="flash_dq_dkv" if window is None else "flash_dq_dkv_win",
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


def _band(window, causal: bool, seq: int):
    """The window the kernels are given: None where it hides nothing (no
    window, or one as long as the sequence: the plain causal kernels then,
    bit for bit)."""
    if window is None:
        return None
    if not causal:
        raise ValueError("a window is the causal mask's: pass causal=True")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    return None if window >= seq else int(window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale: float, causal: bool = False,
                    window=None) -> jax.Array:
    """softmax(q k^T * scale) v over [B, S, d] blocks without ever
    materializing the [S, S] score matrix in HBM. Returns float32 (matching
    ops/attention.py::full_attention's accumulation contract). `causal`
    (static): query i sees keys 0..i only, and the tiles above the diagonal
    are not computed. `window` (static, with `causal`): query i sees keys
    i - window + 1..i, the position itself counted; tiles outside the band
    are skipped, and the call runs kernels of its own name and tiles
    (`flash_fwd_win`, `flash_dq_dkv_win`)."""
    out, _ = _fwd_impl(q, k, v, scale, causal,
                       _band(window, causal, q.shape[1]))
    return out


#: `checkpoint_name`s of the forward kernel's two outputs. A caller that
#: recomputes a block under `jax.checkpoint` keeps them by a policy on
#: these names (models/token_ops.py::recomputed), and the recomputation
#: then holds no forward kernel; under no `jax.checkpoint` a name lowers
#: to nothing.
FLASH_OUT_NAME = "flash_outT"
FLASH_LSE_NAME = "flash_lse"


def _flash_vjp_fwd(q, k, v, scale, causal, window):
    # the [B, S, d] <-> [B, d, S] swaps at the edges are XLA's; the
    # residuals stay in the kernels' layout
    qT = jnp.swapaxes(q, 1, 2)
    outT, lse = _fwd_core(qT, k, jnp.swapaxes(v, 1, 2), scale, causal,
                          _band(window, causal, q.shape[1]))
    # named on the kernel's own outputs, ahead of the swap: named any later
    # the kernel would still be live in a recomputation that kept them
    outT = checkpoint_name(outT, FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return jnp.swapaxes(outT, 1, 2), (qT, k, v, outT, lse)


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
    `_fwd_core` — each block contributes a normalized partial (out_b, lse_b)
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
    out, _ = _ring_flash_vjp_fwd(q, k, v, scale, axis_name, n_shards)
    return out


def _ring_flash_vjp_fwd(q, k, v, scale, axis_name, n_shards):
    fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    # q^T, the rotating v^T block and the partials stay in the kernel's
    # layout across the hops ([B, dv, S] partials merge with [B, 1, S]
    # weights by plain broadcasting); one swap back at the end. Resident
    # block first (no hop result is discarded), then n-1 rotations.
    qT = jnp.swapaxes(q, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    outT, lse = _fwd_core(qT, k, vT, scale)

    def hop(carry, _):
        k_blk, vT_blk, outT, lse = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm=fwd)
        vT_blk = lax.ppermute(vT_blk, axis_name, perm=fwd)
        outT_b, lse_b = _fwd_core(qT, k_blk, vT_blk, scale)
        lse_new = jnp.logaddexp(lse, lse_b)
        outT = (outT * jnp.exp(lse - lse_new)
                + outT_b * jnp.exp(lse_b - lse_new))
        return (k_blk, vT_blk, outT, lse_new), None

    (_, _, outT, lse), _ = lax.scan(
        hop, (k, vT, outT, lse), None, length=n_shards - 1)
    return jnp.swapaxes(outT, 1, 2), (qT, k, v, outT, lse)


def _ring_flash_vjp_bwd(scale, axis_name, n_shards, res, g):
    qT, k, v, outT, lse = res
    fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    # hop-invariant backward inputs computed ONCE (the operand-dtype
    # cotangent transposed, delta) — only the backward kernel re-runs per
    # hop
    stats = _bwd_inputs(qT, outT, lse, g)

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
        hop, (k, v) + zeros + (jnp.zeros(k.shape, jnp.float32),),
        None, length=n_shards)
    # after n rotations the blocks (and their grads) are home again
    return (dq.astype(qT.dtype), dk_c.astype(k.dtype),
            dv_c.astype(v.dtype))


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)
