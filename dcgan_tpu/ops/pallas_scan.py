"""Pallas TPU selective scan (Mamba-1, arXiv:2312.00752) and the causal
depthwise convolution in front of it.

The recurrence, per batch row, channel `d` and state `n`, over time `t`:

    s_t[d, n] = exp(dt_t[d] A[d, n]) s_{t-1}[d, n] + dt_t[d] u_t[d] B_t[n]
    y_t[d]    = sum_n s_t[d, n] C_t[n]                         (s_0 = 0)

with input-dependent `dt`, `B`, `C`. Written with XLA's `associative_scan`,
or a `lax.scan` whose gradient keeps `s`, the states are one tensor
[L, D, N] in HBM (2.7 GB float32 at L 8,192, D 5,120, N 16); here the state
lives in VMEM and crosses HBM only at chunk boundaries.

Layout (TPU): a state tile is [N, 128]: the N states on SUBLANES, 128
channels on LANES, so `dt_t` and `u_t` are rows broadcast down the sublanes
and the sum over `n` is a sublane reduction. `B_t[n]` and `C_t[n]` would be
columns broadcast along the lanes, a cross-lane move per step: the wrapper
hands them in already broadcast, [B, L, N, 128] (XLA's, 67 MB each at the
shipped size, read once per chunk), and takes their gradients back the same
way, [B, L, N, 128] partial sums over the channels of a lane, the last 128
summed by XLA. It is VPU and EUP work (about eight elementwise operations
and one `exp` per channel, state and step), no MXU: the matmul peak does not
bound this kernel and neither does HBM (its roofline share reads low by
nature; benchmark/layer_metrics/ssm_scan_roofline.py).

- `ssm_scan_fwd`: grid (B, L/T, D/Dblk), time chunks and channel blocks
  sequential; the running state of every channel block waits in a VMEM
  scratch [D/Dblk, N, Dblk] between chunks. The channel block is the
  INNER axis so that a chunk's B and C tiles are fetched once. Each program
  runs T steps, eight to a loop iteration (one sublane tile of `u`, `dt`,
  `y`), writes `y` and the state at the START of its chunk (the boundary
  states, [B, L/T, N, D]: 21 MB at T 128).
- `ssm_scan_bwd`: the same grid with the chunks in REVERSE. A program
  rebuilds its chunk's T states from the boundary state into a VMEM scratch
  (4 MB), then walks the chunk backwards with the state's cotangent
  `g_t = C_t dy_t + exp(dt_{t+1} A) g_{t+1}` carried like the state:
  du, ddt per step; dB, dC per step as [N, 128] partial sums accumulated
  over the channel blocks in the resident output tile; dA as one partial
  per chunk, summed by XLA.
- Everything float32. Padding: L is padded to a multiple of T with dt = 0
  (the state passes through unchanged, the padded rows of `y` are dropped).
- Off the TPU the kernels run under `interpret=True`
  (tests/test_selective_scan.py holds outputs and all five gradients to a
  `lax.scan`).
- The forward's two outputs carry `checkpoint_name`s (SCAN_OUT_NAME,
  SCAN_STATE_NAME): a caller that recomputes a block keeps them by policy
  (models/token_ops.py::recomputed) and the recomputation holds no scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: time steps of one grid step (one boundary state is kept per chunk) and
#: channels of one program (a multiple of 128 on the chip). Swept on the v5e
#: at L 8,192, D 5,120, N 16, forward / forward + backward in ms: (64, 512)
#: 2.00 / 6.60, (128, 512) 1.87 / 6.27, (64, 256) 2.40 / 7.58, (64, 1024)
#: 1.79 / 6.25, (32, 512) 2.40 / 7.28 (my chip run, PR 33)
SCAN_CHUNK = 128
SCAN_BLOCK_D = 512
_LANES = 128
_ROWS = 8          # steps of one loop iteration: a float32 sublane tile

SCAN_OUT_NAME = "ssm_scan_y"
SCAN_STATE_NAME = "ssm_scan_states"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params():
    if _interpret():
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _block_d(d: int) -> int:
    for b in (SCAN_BLOCK_D, 256, _LANES):
        if b <= SCAN_BLOCK_D and d % b == 0:
            return b
    return d


def _lane_width(dblk: int) -> int:
    return _LANES if dblk % _LANES == 0 else dblk


def _rows_to_tile(rows, lw):
    """Eight [1, lw] rows as one [8, lw] tile (row i on sublane i)."""
    at = lax.broadcasted_iota(jnp.int32, (_ROWS, lw), 0)
    tile = jnp.zeros((_ROWS, lw), jnp.float32)
    for i, r in enumerate(rows):
        tile = jnp.where(at == i, r, tile)
    return tile


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(u_ref, dt_ref, at_ref, bb_ref, cb_ref, y_ref, s0_ref, s_scr,
                *, t_chunk, lw):
    c, j = pl.program_id(1), pl.program_id(2)
    dblk = u_ref.shape[2]
    groups = [slice(g * lw, (g + 1) * lw) for g in range(dblk // lw)]

    @pl.when(c == 0)
    def _():
        s_scr[j] = jnp.zeros(s_scr.shape[1:], jnp.float32)

    s0_ref[0, 0] = s_scr[j]
    at = [at_ref[:, g] for g in groups]                     # [N, lw] each

    def eight(i8, s):
        r0 = pl.multiple_of(i8 * _ROWS, _ROWS)
        rows = pl.ds(r0, _ROWS)
        u8 = [u_ref[0, rows, g] for g in groups]            # [8, lw] each
        dt8 = [dt_ref[0, rows, g] for g in groups]
        ys = [[] for _ in groups]
        s = list(s)
        for i in range(_ROWS):
            bb, cb = bb_ref[0, r0 + i], cb_ref[0, r0 + i]   # [N, lw]
            for g in range(len(groups)):
                dt_r = dt8[g][i:i + 1]                      # [1, lw]
                s[g] = s[g] * jnp.exp(dt_r * at[g]) \
                    + (dt_r * u8[g][i:i + 1]) * bb
                ys[g].append(jnp.sum(s[g] * cb, axis=0, keepdims=True))
        for g, lanes in enumerate(groups):
            y_ref[0, rows, lanes] = _rows_to_tile(ys[g], lw)
        return tuple(s)

    s = lax.fori_loop(0, t_chunk // _ROWS, eight,
                      tuple(s_scr[j, :, g] for g in groups))
    for g, lanes in enumerate(groups):
        s_scr[j, :, lanes] = s[g]


def _geometry(u, a, chunk):
    b, l, d = u.shape
    n = a.shape[1]
    t_chunk = min(chunk, -(-l // _ROWS) * _ROWS)
    if t_chunk % _ROWS:
        raise ValueError(f"chunk must be a multiple of {_ROWS}, got {chunk}")
    lp = -(-l // t_chunk) * t_chunk
    dblk = _block_d(d)
    return b, l, lp, d, n, t_chunk, dblk, _lane_width(dblk)


def _pad_time(x, lp):
    return x if x.shape[1] == lp else jnp.pad(
        x, ((0, 0), (0, lp - x.shape[1])) + ((0, 0),) * (x.ndim - 2))


def _lanes(x, lw):
    """[B, L, N] -> [B, L, N, lw]: every element along a lane row."""
    return jnp.broadcast_to(x[..., None], x.shape + (lw,))


def _specs(t_chunk, dblk, n, lw, time):
    """BlockSpecs of the per-step tensors: `time(c)` is the chunk a grid
    step works on."""
    seq = pl.BlockSpec((1, t_chunk, dblk), lambda b, c, j: (b, time(c), j))
    a_t = pl.BlockSpec((n, dblk), lambda b, c, j: (0, j))
    bc = pl.BlockSpec((1, t_chunk, n, lw), lambda b, c, j: (b, time(c), 0, 0))
    state = pl.BlockSpec((1, 1, n, dblk), lambda b, c, j: (b, time(c), 0, j))
    return seq, a_t, bc, state


def _scan_fwd(u, dt, a, bm, cm, chunk):
    """(y [B, L', D], boundary states [B, L'/T, N, D]), L' the padded
    length."""
    b, l, lp, d, n, t_chunk, dblk, lw = _geometry(u, a, chunk)
    u, dt = _pad_time(u, lp), _pad_time(dt, lp)
    bb, cb = _lanes(_pad_time(bm, lp), lw), _lanes(_pad_time(cm, lp), lw)
    seq, a_t, bc, state = _specs(t_chunk, dblk, n, lw, lambda c: c)
    y, s0 = pl.pallas_call(
        functools.partial(_fwd_kernel, t_chunk=t_chunk, lw=lw),
        name="ssm_scan_fwd",
        grid=(b, lp // t_chunk, d // dblk),
        in_specs=[seq, seq, a_t, bc, bc],
        out_specs=(seq, state),
        out_shape=(jax.ShapeDtypeStruct((b, lp, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, lp // t_chunk, n, d),
                                        jnp.float32)),
        scratch_shapes=[pltpu.VMEM((d // dblk, n, dblk), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(u, dt, a.T, bb, cb)
    return y, s0


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(u_ref, dt_ref, at_ref, bb_ref, cb_ref, dy_ref, s0_ref,
                du_ref, ddt_ref, da_ref, dbb_ref, dcb_ref, g_scr, s_scr,
                *, t_chunk, lw):
    c, j = pl.program_id(1), pl.program_id(2)
    dblk = u_ref.shape[2]
    groups = [slice(g * lw, (g + 1) * lw) for g in range(dblk // lw)]
    n_groups = len(groups)
    n_eights = t_chunk // _ROWS

    @pl.when(c == 0)                       # the LAST chunk: nothing follows
    def _():
        g_scr[j] = jnp.zeros(g_scr.shape[1:], jnp.float32)

    @pl.when(j == 0)                       # dB, dC: summed over the blocks
    def _():
        dbb_ref[...] = jnp.zeros(dbb_ref.shape, jnp.float32)
        dcb_ref[...] = jnp.zeros(dcb_ref.shape, jnp.float32)

    at = [at_ref[:, g] for g in groups]

    # the chunk's states again, from its boundary state: s_scr[t + 1] = s_t
    s_scr[0] = s0_ref[0, 0]

    def rebuild(i8, s):
        r0 = pl.multiple_of(i8 * _ROWS, _ROWS)
        rows = pl.ds(r0, _ROWS)
        u8 = [u_ref[0, rows, g] for g in groups]
        dt8 = [dt_ref[0, rows, g] for g in groups]
        s = list(s)
        for i in range(_ROWS):
            bb = bb_ref[0, r0 + i]
            for g, lanes in enumerate(groups):
                dt_r = dt8[g][i:i + 1]
                s[g] = s[g] * jnp.exp(dt_r * at[g]) \
                    + (dt_r * u8[g][i:i + 1]) * bb
                s_scr[r0 + i + 1, :, lanes] = s[g]
        return tuple(s)

    lax.fori_loop(0, n_eights, rebuild,
                  tuple(s0_ref[0, 0, :, g] for g in groups))

    def eight(k, carry):
        gs, das = list(carry[:n_groups]), list(carry[n_groups:])
        r0 = pl.multiple_of((n_eights - 1 - k) * _ROWS, _ROWS)
        rows = pl.ds(r0, _ROWS)
        u8 = [u_ref[0, rows, g] for g in groups]
        dt8 = [dt_ref[0, rows, g] for g in groups]
        dy8 = [dy_ref[0, rows, g] for g in groups]
        dus = [[None] * _ROWS for _ in groups]
        ddts = [[None] * _ROWS for _ in groups]
        for i in reversed(range(_ROWS)):
            bb, cb = bb_ref[0, r0 + i], cb_ref[0, r0 + i]
            db = jnp.zeros(bb.shape, jnp.float32)
            dc = jnp.zeros(bb.shape, jnp.float32)
            for g, lanes in enumerate(groups):
                dt_r, u_r = dt8[g][i:i + 1], u8[g][i:i + 1]
                dy_r = dy8[g][i:i + 1]
                s_t = s_scr[r0 + i + 1, :, lanes]
                s_prev = s_scr[r0 + i, :, lanes]
                grad = gs[g] + cb * dy_r               # dL/ds_t, whole
                dc = dc + dy_r * s_t
                db = db + grad * (dt_r * u_r)
                ga = grad * jnp.exp(dt_r * at[g])      # to s_{t-1}
                gas = ga * s_prev
                gb = jnp.sum(grad * bb, axis=0, keepdims=True)
                ddts[g][i] = jnp.sum(gas * at[g], axis=0, keepdims=True) \
                    + u_r * gb
                dus[g][i] = dt_r * gb
                das[g] = das[g] + gas * dt_r
                gs[g] = ga
            dbb_ref[0, r0 + i] += db
            dcb_ref[0, r0 + i] += dc
        for g, lanes in enumerate(groups):
            du_ref[0, rows, lanes] = _rows_to_tile(dus[g], lw)
            ddt_ref[0, rows, lanes] = _rows_to_tile(ddts[g], lw)
        return tuple(gs) + tuple(das)

    zeros = tuple(jnp.zeros(at[0].shape, jnp.float32) for _ in groups)
    out = lax.fori_loop(0, n_eights, eight,
                        tuple(g_scr[j, :, g] for g in groups) + zeros)
    for g, lanes in enumerate(groups):
        g_scr[j, :, lanes] = out[g]
        da_ref[0, 0, :, lanes] = out[n_groups + g]


def _scan_bwd(u, dt, a, bm, cm, s0, dy, chunk):
    b, l, lp, d, n, t_chunk, dblk, lw = _geometry(u, a, chunk)
    n_chunks = lp // t_chunk
    u, dt, dy = _pad_time(u, lp), _pad_time(dt, lp), _pad_time(dy, lp)
    bb, cb = _lanes(_pad_time(bm, lp), lw), _lanes(_pad_time(cm, lp), lw)
    seq, a_t, bc, state = _specs(t_chunk, dblk, n, lw,
                                 lambda c: n_chunks - 1 - c)
    du, ddt, da, dbb, dcb = pl.pallas_call(
        functools.partial(_bwd_kernel, t_chunk=t_chunk, lw=lw),
        name="ssm_scan_bwd",
        grid=(b, n_chunks, d // dblk),
        in_specs=[seq, seq, a_t, bc, bc, seq, state],
        out_specs=(seq, seq, state, bc, bc),
        out_shape=(jax.ShapeDtypeStruct((b, lp, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, lp, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, n_chunks, n, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, lp, n, lw), jnp.float32),
                   jax.ShapeDtypeStruct((b, lp, n, lw), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((d // dblk, n, dblk), jnp.float32),
                        pltpu.VMEM((t_chunk + 1, n, dblk), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
    )(u, dt, a.T, bb, cb, dy, s0)
    return (du[:, :l], ddt[:, :l], jnp.sum(da, axis=(0, 1)).T,
            jnp.sum(dbb, axis=-1)[:, :l], jnp.sum(dcb, axis=-1)[:, :l])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def selective_scan(u: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, chunk: int = SCAN_CHUNK) -> jax.Array:
    """y [B, L, D] of the recurrence in the module's docstring over u, dt
    [B, L, D], a [D, N] (negative), b, c [B, L, N], all float32, from a zero
    state. `chunk` (static): the steps of one grid step, a multiple of 8."""
    y, _ = _scan_fwd(u, dt, a, b, c, chunk)
    return y[:, :u.shape[1]]


def _scan_vjp_fwd(u, dt, a, b, c, chunk):
    y, s0 = _scan_fwd(u, dt, a, b, c, chunk)
    # named on the kernel's own outputs (ops/pallas_attention.py does the
    # same): a recomputation that keeps both holds no forward kernel
    y = checkpoint_name(y, SCAN_OUT_NAME)
    s0 = checkpoint_name(s0, SCAN_STATE_NAME)
    return y[:, :u.shape[1]], (u, dt, a, b, c, s0)


def _scan_vjp_bwd(chunk, res, dy):
    u, dt, a, b, c, s0 = res
    return _scan_bwd(u, dt, a, b, c, s0, dy.astype(jnp.float32), chunk)


selective_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def causal_conv(u: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """Depthwise causal convolution over time of u [B, L, D] with taps w
    [K, D] (tap k reads `t - (K - 1) + k`, zeros before the sequence) and a
    bias [D]: K shifted multiply-adds, one fused elementwise pass of XLA's."""
    k, l = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(u.dtype)
    for i in range(k):
        out = out + w[i].astype(u.dtype) * padded[:, i:i + l]
    return out
