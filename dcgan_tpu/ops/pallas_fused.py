"""Fused Pallas conv/deconv building blocks for the G/D stacks (ISSUE 17).

ops/pallas_kernels.py fuses the HBM-bound tail AROUND BatchNorm (moments +
normalize/act epilogue) but leaves the conv itself with XLA, so under
`use_pallas` each stage still writes its conv output to HBM once for the
moments pass and once more for the epilogue. These kernels pull the GEMM
into the same pass: each D stage (`conv ⊕ bias ⊕ BN-moments`, then the
shared `scale_shift_act` epilogue) and G stage (`deconv ⊕ bias ⊕ ...`)
becomes Pallas end to end, so a stage's activation tensor crosses HBM once
per direction — the program-interior win PR 6's trace digest located
(14.25 ms compute vs 43.6 ms idle) and ParaGAN (arXiv:2411.03999) frames.

Formulation: im2col. Patch extraction stays with XLA
(`lax.conv_general_dilated_patches` — differentiable, so JAX transposes it
into the dx scatter for free), producing [M, Cin*kh*kw] rows whose GEMM
against the [Cin*kh*kw, Cout] reshaped kernel IS the conv; a transposed
conv is the identical GEMM over `lhs_dilation`-expanded patches (verified
bit-exact against `lax.conv_transpose` — the JAX default does NOT flip the
kernel taps, tests/test_pallas_fused.py). The Pallas kernel then fuses
GEMM + bias + the per-channel moment reduction (train) or the whole
BN-affine + activation epilogue (inference, stats known) into one VMEM-
resident pass, accumulating in float32 over a (row-block, k-block) grid —
the TPU grid is sequential, so in-place accumulation into the resident
output block is safe (same idiom as `_moments_kernel`).

VJP strategy: forward is the fused Pallas pass; backward's GEMMs
(dpatches = du @ w2d.T, dw2d = patches.T @ du) stay with XLA — it already
tiles transposed matmuls optimally (the pallas_kernels.py philosophy), and
the moments/epilogue cotangent is a broadcastwise expression XLA fuses
into them. Cross-shard moment reduction happens OUTSIDE the kernel
(lax.pmean under an axis_name, or per data-shard inside a nested
shard_map under the gspmd backend's `pallas_mesh` — pallas_call is opaque
to GSPMD, the ops/norm.py pattern), so both parallel backends pick the
blocks up without touching step structure.

Everything degrades to `interpret=True` off-TPU: tier-1 pins numerical
parity (forward AND gradients) against the unfused conv+BN reference on
the CPU mesh without a TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from dcgan_tpu.ops.activations import ACTS, LEAK
from dcgan_tpu.ops.activations import act_fwd as _act_fwd
from dcgan_tpu.ops.activations import act_grad as _act_grad
from dcgan_tpu.ops.pallas_kernels import _interpret, _row_tile

Pytree = dict

_CONV_DIMS = ("NHWC", "HWIO", "NHWC")


#: widest contraction dim taken as ONE block when no lane-aligned block
#: divides it (K = 1600 at the first celeba64/dcgan128 D stage: 0.8 MiB of
#: bf16 patch tile); anything wider is zero-padded to the lane tiling instead
_WHOLE_K_MAX = 2048


def _k_padded(n: int) -> int:
    """The contraction dim the kernels are handed for a true K of n: n
    itself when `_k_tile` can block it, else n rounded up to the 128 lanes
    (zero patch columns against zero weight rows add nothing to the GEMM,
    hence nothing to the moments)."""
    if n % 128 == 0 or n <= _WHOLE_K_MAX:
        return n
    return -(-n // 128) * 128


def _k_tile(n: int) -> int:
    """Contraction block for K = Cin*kh*kw (1600..25600 at the 64-256px
    stages): the largest multiple of 128 up to 512 that divides n, else the
    whole n. The TPU lowering takes a block's last dim only as a multiple
    of the 128 lanes or as the whole array dim, so "largest divisor <= 512"
    (400 at K = 1600, 3200 and 6400) is refused by the chip's compiler.
    Streaming K in blocks keeps the weight tile (tk x Cout) VMEM-resident
    instead of the whole [K, Cout] matrix (13 MiB f32 at the deepest 256px
    stage); callers bound the whole-n case with `_k_padded`."""
    for tile in (512, 384, 256, 128):
        if n % tile == 0:
            return tile
    return n


def w_to_gemm(w: jax.Array) -> jax.Array:
    """[kh, kw, Cin, Cout] HWIO kernel -> [Cin*kh*kw, Cout] GEMM operand.
    conv_general_dilated_patches orders the patch features channel-major
    (Cin slowest, then kh, kw) — hence the (2, 0, 1, 3) transpose."""
    kh, kw, cin, cout = w.shape
    return jnp.transpose(w, (2, 0, 1, 3)).reshape(kh * kw * cin, cout)


def _transpose_pads(k: int, s: int) -> Tuple[int, int]:
    # lax.conv_transpose's SAME padding arithmetic (jax.lax internal), so
    # the dilated-patch formulation matches it exactly (tests pin 0 error)
    pad_len = k + s - 2
    pad_a = k - 1 if s > k - 1 else int(np.ceil(pad_len / 2))
    return pad_a, pad_len - pad_a


def conv_patches(x: jax.Array, kernel: int, stride: int,
                 transpose: bool) -> Tuple[jax.Array, Tuple[int, int, int]]:
    """im2col rows for a strided (or transposed) SAME conv.

    Returns (patches2d [N*Ho*Wo, Cin*k*k], (N, Ho, Wo))."""
    if transpose:
        pads = [_transpose_pads(kernel, stride)] * 2
        p = lax.conv_general_dilated_patches(
            x, (kernel, kernel), (1, 1), pads,
            lhs_dilation=(stride, stride), dimension_numbers=_CONV_DIMS)
    else:
        p = lax.conv_general_dilated_patches(
            x, (kernel, kernel), (stride, stride), "SAME",
            dimension_numbers=_CONV_DIMS)
    n, ho, wo, f = p.shape
    return p.reshape(n * ho * wo, f), (n, ho, wo)


# ---------------------------------------------------------------------------
# Kernel 1: GEMM + bias + per-channel moments (train-path forward)
# ---------------------------------------------------------------------------

def _gemm_bias_moments_kernel(p_ref, w_ref, b_ref, y_ref, sum_ref,
                              sumsq_ref, *, k_blocks, out_dtype):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        sumsq_ref[:] = jnp.zeros_like(sumsq_ref)

    @pl.when(j == 0)
    def _():
        y_ref[:] = jnp.zeros_like(y_ref)

    y_ref[:] += jnp.dot(p_ref[:], w_ref[:],
                        preferred_element_type=jnp.float32)

    @pl.when(j == k_blocks - 1)
    def _():
        u = y_ref[:] + b_ref[:]
        y_ref[:] = u
        # moments of the value the model will actually SEE (the conv output
        # after its cast to compute dtype) — bit-parity with the unfused
        # path, which reduces the stored activation
        uc = u.astype(out_dtype).astype(jnp.float32)
        sum_ref[:] += jnp.sum(uc, axis=0, keepdims=True)
        sumsq_ref[:] += jnp.sum(uc * uc, axis=0, keepdims=True)


def _gbm_impl(p2d, w2d, b, out_dtype):
    m, k = p2d.shape
    c = w2d.shape[1]
    tm, tk = _row_tile(m), _k_tile(k)
    acc_spec = pl.BlockSpec((1, c), lambda i, j: (0, 0))
    y, sums, sumsqs = pl.pallas_call(
        functools.partial(_gemm_bias_moments_kernel, k_blocks=k // tk,
                          out_dtype=jnp.dtype(out_dtype)),
        name="fused_conv_stats",
        grid=(m // tm, k // tk),
        in_specs=[pl.BlockSpec((tm, tk), lambda i, j: (i, j)),
                  pl.BlockSpec((tk, c), lambda i, j: (j, 0)),
                  acc_spec],
        out_specs=(pl.BlockSpec((tm, c), lambda i, j: (i, 0)),
                   acc_spec, acc_spec),
        out_shape=(jax.ShapeDtypeStruct((m, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)),
        interpret=_interpret(),
    )(p2d, w2d, b.reshape(1, c).astype(jnp.float32))
    inv_m = 1.0 / m
    return y, sums[0] * inv_m, sumsqs[0] * inv_m


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gemm_bias_moments(p2d: jax.Array, w2d: jax.Array, b: jax.Array,
                      out_dtype=jnp.float32):
    """One fused pass: u = p2d @ w2d + b (f32 accumulation) together with
    the per-channel (E[u], E[u^2]) the BN train path needs. Returns
    (u [M, C] float32, mean [C], mean_sq [C]); callers cast u to their
    compute dtype (the moments already describe the cast value)."""
    return _gbm_impl(p2d, w2d, b, out_dtype)


def _gbm_vjp_fwd(p2d, w2d, b, out_dtype):
    out = _gbm_impl(p2d, w2d, b, out_dtype)
    return out, (p2d, w2d, b, out[0])


def _gbm_vjp_bwd(out_dtype, res, g):
    # d mean/du = 1/M, d mean_sq/du = 2u/M — folded into the GEMM
    # cotangent so backward stays two XLA matmuls + one fused epilogue
    p2d, w2d, b, u = res
    gu, g_mean, g_msq = g
    m = u.shape[0]
    du = gu.astype(jnp.float32) + (g_mean[None, :]
                                   + 2.0 * u * g_msq[None, :]) / m
    dp = jnp.dot(du, w2d.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
    dw = jnp.dot(p2d.astype(jnp.float32).T, du,
                 preferred_element_type=jnp.float32)
    # db cast to the bias's own dtype: a f32 cotangent for a bf16 param
    # would promote its Adam nu leaf to f32 across the step, breaking
    # state-carry dtype invariance (and with it donation aliasing)
    db = jnp.sum(du, axis=0)
    return (dp.astype(p2d.dtype), dw.astype(w2d.dtype), db.astype(b.dtype))


gemm_bias_moments.defvjp(_gbm_vjp_fwd, _gbm_vjp_bwd)


# ---------------------------------------------------------------------------
# Kernel 2: GEMM + bias + BN affine + activation (inference-path forward —
# running stats are known, so the whole stage fuses into ONE kernel)
# ---------------------------------------------------------------------------

def _gemm_bias_scale_act_kernel(p_ref, w_ref, b_ref, scale_ref, shift_ref,
                                y_ref, acc_ref, *, k_blocks, act, leak):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(p_ref[:], w_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(j == k_blocks - 1)
    def _():
        u = acc_ref[:] + b_ref[:]
        v = u * scale_ref[:] + shift_ref[:]
        y_ref[:] = _act_fwd(v, act, leak).astype(y_ref.dtype)


def _gbsa_impl(p2d, w2d, b, scale, shift, act, leak, out_dtype):
    if act not in ACTS:
        raise ValueError(f"unknown act {act!r}")
    m, k = p2d.shape
    c = w2d.shape[1]
    tm, tk = _row_tile(m), _k_tile(k)
    vec_spec = pl.BlockSpec((1, c), lambda i, j: (0, 0))
    y, _ = pl.pallas_call(
        functools.partial(_gemm_bias_scale_act_kernel, k_blocks=k // tk,
                          act=act, leak=leak),
        name="fused_conv_apply",
        grid=(m // tm, k // tk),
        in_specs=[pl.BlockSpec((tm, tk), lambda i, j: (i, j)),
                  pl.BlockSpec((tk, c), lambda i, j: (j, 0)),
                  vec_spec, vec_spec, vec_spec],
        out_specs=(pl.BlockSpec((tm, c), lambda i, j: (i, 0)),
                   pl.BlockSpec((tm, c), lambda i, j: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((m, c), jnp.dtype(out_dtype)),
                   # f32 accumulator rides as a second output block (grid-
                   # resident across the k sweep; discarded) so the kernel
                   # needs no scratch allocation in interpret mode
                   jax.ShapeDtypeStruct((m, c), jnp.float32)),
        interpret=_interpret(),
    )(p2d, w2d, b.reshape(1, c).astype(jnp.float32),
      scale.reshape(1, c).astype(jnp.float32),
      shift.reshape(1, c).astype(jnp.float32))
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def gemm_bias_scale_act(p2d: jax.Array, w2d: jax.Array, b: jax.Array,
                        scale: jax.Array, shift: jax.Array,
                        act: str = "none", leak: float = LEAK,
                        out_dtype=jnp.float32) -> jax.Array:
    """Fully fused inference stage: act((p2d @ w2d + b) * scale + shift)
    in one Pallas pass. Differentiable — the R1/WGAN-GP penalty critics run
    with train=False BN and ARE differentiated — with an XLA backward that
    recomputes u (one matmul) instead of storing it."""
    return _gbsa_impl(p2d, w2d, b, scale, shift, act, leak, out_dtype)


def _gbsa_vjp_fwd(p2d, w2d, b, scale, shift, act, leak, out_dtype):
    y = _gbsa_impl(p2d, w2d, b, scale, shift, act, leak, out_dtype)
    return y, (p2d, w2d, b, scale, shift)


def _gbsa_vjp_bwd(act, leak, out_dtype, res, g):
    p2d, w2d, b, scale, shift = res
    sf = scale.astype(jnp.float32)
    u = jnp.dot(p2d.astype(jnp.float32), w2d.astype(jnp.float32),
                preferred_element_type=jnp.float32) \
        + b.astype(jnp.float32)[None, :]
    v = u * sf[None, :] + shift.astype(jnp.float32)[None, :]
    dv = g.astype(jnp.float32) * _act_grad(v, act, leak)
    du = dv * sf[None, :]
    dscale = jnp.sum(dv * u, axis=0)
    dshift = jnp.sum(dv, axis=0)
    dp = jnp.dot(du, w2d.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
    dw = jnp.dot(p2d.astype(jnp.float32).T, du,
                 preferred_element_type=jnp.float32)
    db = jnp.sum(du, axis=0)
    return (dp.astype(p2d.dtype), dw.astype(w2d.dtype), db.astype(b.dtype),
            dscale.astype(scale.dtype), dshift.astype(shift.dtype))


gemm_bias_scale_act.defvjp(_gbsa_vjp_fwd, _gbsa_vjp_bwd)


# ---------------------------------------------------------------------------
# fp8 ladder rung (TrainConfig.precision="fp8", ISSUE 17): simulated-
# quantization matmul/conv operands — amax-scaled float8_e4m3fn round-trip,
# so the CPU mesh exercises the numerics without fp8 MXU support. Shared by
# the unfused layers (ops/layers.py) and the fused blocks below.
# ---------------------------------------------------------------------------

from dcgan_tpu.ops.layers import _fake_quant_fp8 as fake_quant_fp8  # noqa: E402
# (one definition, in ops/layers.py — the import-light home the unfused
# conv/deconv paths share; re-exported here for the fused blocks and tests)


# ---------------------------------------------------------------------------
# The fused stage: conv/deconv ⊕ bias ⊕ BN ⊕ act, both-backend routing
# ---------------------------------------------------------------------------

def _shard_gemm_moments(p2d, w2d, b, out_dtype, mesh):
    """gemm_bias_moments per data-shard + pmean under the gspmd backend's
    pallas_mesh (pallas_call is opaque to GSPMD — the ops/norm.py
    `_pallas_shard_moments` pattern, check_vma=False for the same reason)."""
    from jax.sharding import PartitionSpec as P

    from dcgan_tpu.utils.backend import shard_map

    def _body(pl_, w_, b_):
        u, mean, msq = gemm_bias_moments(pl_, w_, b_, out_dtype)
        return u, lax.pmean(mean, "data"), lax.pmean(msq, "data")

    return shard_map(_body, mesh=mesh,
                     in_specs=(P("data", None), P(), P()),
                     out_specs=(P("data", None), P(), P()),
                     check=False)(p2d, w2d, b)


def _shard_gemm_scale_act(p2d, w2d, b, scale, shift, act, leak, out_dtype,
                          mesh):
    from jax.sharding import PartitionSpec as P

    from dcgan_tpu.utils.backend import shard_map

    def _body(pl_, w_, b_, s_, t_):
        return gemm_bias_scale_act(pl_, w_, b_, s_, t_, act, leak,
                                   out_dtype)

    return shard_map(_body, mesh=mesh,
                     in_specs=(P("data", None), P(), P(), P(), P()),
                     out_specs=P("data", None),
                     check=False)(p2d, w2d, b, scale, shift)


def fused_conv_bn_act(conv_params: Pytree, bn_params: Pytree,
                      bn_state: Pytree, x: jax.Array, *, transpose: bool,
                      kernel: int, stride: int = 2, train: bool,
                      momentum: float = 0.9, eps: float = 1e-5,
                      act: str, leak: float = LEAK,
                      axis_name: Optional[str] = None, pallas_mesh=None,
                      compute_dtype=None,
                      quant: str = "") -> Tuple[jax.Array, Pytree]:
    """One G/D stage as fused Pallas passes: conv (transpose=False, the D
    `conv⊕BN⊕lrelu` block) or deconv (transpose=True, the G
    `deconv⊕BN⊕relu` block), returning (y, new_bn_state) with exactly
    `batch_norm_apply`'s state contract so the model loops swap it in
    behind ModelConfig.pallas_fused without touching step structure.

    train=True : pass 1 fuses GEMM+bias+moments; the cross-shard pmean and
    BN's EMA/var arithmetic run between passes (they are [C]-sized); pass 2
    is the shared `scale_shift_act` epilogue kernel.
    train=False: the running stats are known ahead of the GEMM, so the
    whole stage collapses into the single gemm_bias_scale_act kernel.
    """
    from dcgan_tpu.ops.norm import finish_batch_moments
    from dcgan_tpu.ops.pallas_kernels import scale_shift_act

    cdt = jnp.dtype(compute_dtype) if compute_dtype is not None else x.dtype
    w, b = conv_params["w"], conv_params["b"]
    x = x.astype(cdt)
    w2d = w_to_gemm(w.astype(cdt))
    p2d, (n, ho, wo) = conv_patches(x, kernel, stride, transpose)
    k_pad = _k_padded(p2d.shape[1]) - p2d.shape[1]
    if k_pad:
        p2d = jnp.pad(p2d, ((0, 0), (0, k_pad)))
        w2d = jnp.pad(w2d, ((0, k_pad), (0, 0)))
    if quant == "fp8":
        p2d, w2d = fake_quant_fp8(p2d), fake_quant_fp8(w2d)
    c = w2d.shape[1]
    gamma, beta = bn_params["scale"], bn_params["bias"]

    if train:
        if pallas_mesh is not None:
            u, mean, mean_sq = _shard_gemm_moments(p2d, w2d, b, cdt,
                                                   pallas_mesh)
        else:
            u, mean, mean_sq = gemm_bias_moments(p2d, w2d, b, cdt)
            if axis_name is not None:
                mean = lax.pmean(mean, axis_name)
                mean_sq = lax.pmean(mean_sq, axis_name)
        mean, var, new_state = finish_batch_moments(
            bn_state, mean, mean_sq, momentum=momentum)
        inv = lax.rsqrt(var + jnp.float32(eps))
        scale = gamma.astype(jnp.float32) * inv
        shift = beta.astype(jnp.float32) - mean * scale
        u = u.astype(cdt)
        if pallas_mesh is not None:
            from dcgan_tpu.ops.norm import _pallas_shard_epilogue

            # reuse the BN epilogue's per-shard wrapper (elementwise over
            # rows; shard_map transpose inserts the replicated-grad psums)
            y2d = _pallas_shard_epilogue(
                u, gamma, beta, mean, var, eps=eps, act=act, leak=leak,
                mesh=pallas_mesh)
        else:
            y2d = scale_shift_act(u, scale, shift, act, leak)
        return y2d.reshape(n, ho, wo, c), new_state

    mean = bn_state["mean"].astype(jnp.float32)
    var = bn_state["var"].astype(jnp.float32)
    inv = lax.rsqrt(var + jnp.float32(eps))
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean * scale
    if pallas_mesh is not None:
        y2d = _shard_gemm_scale_act(p2d, w2d, b, scale, shift, act, leak,
                                    cdt, pallas_mesh)
    else:
        y2d = gemm_bias_scale_act(p2d, w2d, b, scale, shift, act, leak, cdt)
    return y2d.reshape(n, ho, wo, c), bn_state


# ---------------------------------------------------------------------------
# Analytic cost model (tools/step_profile.py PALLAS_FUSED=1 rows)
# ---------------------------------------------------------------------------

def fused_sites(cfg, batch: int):
    """The fused-block launches of one G forward + one D forward at `cfg`
    (plain-dcgan arch): one descriptor per interior stage, exactly the
    model loops' gating (G stages 1..k-1, D stages 1..k-1; the boundary
    stages stay unfused). A site's kernel is the GEMM [M, K] @ [K, C]
    with M = batch * out_res**2 patch rows and K = in_ch * kernel**2 —
    the same formulation `conv_patches`/`w_to_gemm` lower, so the
    analytic rows below decompose the program that actually runs."""
    k = cfg.num_up_layers
    ks = cfg.kernel_size
    sites = []
    for i in range(1, k):
        out_res = cfg.base_size * (2 ** i)
        in_ch = cfg.gf_dim * (2 ** (k - i))
        sites.append({"name": f"gen/deconv{i}", "transpose": True,
                      "act": "relu", "in_res": cfg.base_size * 2 ** (i - 1),
                      "out_res": out_res, "in_ch": in_ch,
                      "m": batch * out_res * out_res,
                      "k": in_ch * ks * ks,
                      "c": cfg.gf_dim * (2 ** (k - 1 - i))})
    for i in range(1, k):
        out_res = cfg.output_size >> (i + 1)
        in_ch = cfg.df_dim * (2 ** (i - 1))
        sites.append({"name": f"disc/conv{i}", "transpose": False,
                      "act": "lrelu", "in_res": cfg.output_size >> i,
                      "out_res": out_res, "in_ch": in_ch,
                      "m": batch * out_res * out_res,
                      "k": in_ch * ks * ks, "c": cfg.df_dim * (2 ** i)})
    return sites


def kernel_cost(m: int, k: int, c: int, *, train: bool,
                compute_dtype=jnp.float32):
    """Analytic flops / HBM bytes / peak-VMEM model of one fused forward
    launch, per-part so the conservation check (step_profile) can pin
    fused == sum-of-parts. The GEMM dominates (2MKC); the fused win is
    the BYTES column — train mode reads the patch matrix once and never
    round-trips the pre-BN activation through HBM, inference collapses
    the whole stage into one kernel. `peak_temp_mib` is the VMEM-resident
    working set of one grid step: the operand tiles plus the f32
    accumulator/moment blocks the sequential-k grid revisits."""
    isz = jnp.dtype(compute_dtype).itemsize
    parts = {"gemm": 2 * m * k * c, "bias": m * c}
    if train:
        # kernel 1's moment accumulation (u^2 + the two sums) and the
        # scale_shift_act epilogue pass (scale*u + shift, act compare)
        parts["moments"] = 3 * m * c
        parts["epilogue"] = 4 * m * c
        # u is written f32 (accumulator dtype), moments are 2x [C] f32;
        # the epilogue pass re-reads u and writes the cast activation
        hbm = (m * k * isz + k * c * isz + c * 4        # patches, w, b
               + m * c * 4 + 2 * c * 4                  # u, mean, mean_sq
               + m * c * 4 + m * c * isz)               # epilogue r/w
    else:
        # single-kernel stage: scale+shift fold the running stats, one
        # activation, output written once in compute dtype
        parts["scale_act"] = 3 * m * c
        hbm = (m * k * isz + k * c * isz + 3 * c * 4    # + scale, shift
               + m * c * isz)
    tm, tk = _row_tile(m), _k_tile(_k_padded(k))
    vmem = (tm * tk + tk * c) * isz + tm * c * 4 + 2 * c * 4
    return {"flops": sum(parts.values()), "flops_parts": parts,
            "bytes": hbm, "peak_temp_mib": round(vmem / 2**20, 3)}
