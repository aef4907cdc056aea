"""Fused Pallas conv⊕BN⊕act blocks (ops/pallas_fused.py, ISSUE 17) —
interpret-mode execution on the CPU test mesh. The core parity tests
(forward AND custom-VJP gradients against the unfused conv+BN reference)
deliberately carry no `slow` marker: the ISSUE's acceptance gate requires
them in tier-1, so a fused-kernel numerics regression fails the smoke
tier, not just the nightly. Model-integration and shard-path tests ride
the slow tier like the rest of the Pallas suite (tests/test_pallas.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dcgan_tpu.config import ModelConfig
from dcgan_tpu.ops.layers import conv2d_apply, conv2d_init, deconv2d_apply, \
    deconv2d_init
from dcgan_tpu.ops.norm import batch_norm_apply, batch_norm_init
from dcgan_tpu.ops.pallas_fused import (
    _k_tile,
    conv_patches,
    fused_conv_bn_act,
    fused_sites,
    gemm_bias_moments,
    gemm_bias_scale_act,
    kernel_cost,
    w_to_gemm,
)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(key), shape, dtype)


def _gbm_ref(p2d, w2d, b, out_dtype=jnp.float32):
    """jnp reference for gemm_bias_moments: f32-accumulated GEMM + bias,
    moments of the value AFTER the compute-dtype cast round-trip (the
    kernel's documented contract — moments describe what the model sees)."""
    u = jnp.dot(p2d.astype(jnp.float32), w2d.astype(jnp.float32)) \
        + b.astype(jnp.float32)[None, :]
    uc = u.astype(out_dtype).astype(jnp.float32)
    return u, jnp.mean(uc, axis=0), jnp.mean(uc * uc, axis=0)


def _act_ref(v, act, leak=0.2):
    if act == "relu":
        return jnp.maximum(v, 0.0)
    if act == "lrelu":
        return jnp.maximum(v, leak * v)
    if act == "tanh":
        return jnp.tanh(v)
    return v


class TestKTile:
    def test_divides_and_lane_aligned_or_whole(self):
        """What the TPU lowering takes as a block's last dim: a multiple of
        the 128 lanes, or the whole dim (tests/test_tpu_compile.py holds
        the kernels themselves to the chip's compiler)."""
        for n in [1, 7, 25, 150, 512, 800, 1600, 3200, 6400, 12800, 999]:
            t = _k_tile(n)
            assert n % t == 0 and (t == n or (t % 128 == 0 and t <= 512))

    def test_exact_power_hits_512(self):
        assert _k_tile(4096) == 512


class TestConvPatches:
    """The im2col formulation IS the conv: patches @ w_to_gemm(w) must
    match lax.conv (strided SAME) and lax.conv_transpose (the JAX default
    — no kernel flip) exactly, kernel/stride combinations the models use."""

    @pytest.mark.parametrize("kernel", [4, 5])
    def test_strided_conv(self, kernel):
        x = _rand(0, (2, 8, 8, 6))
        w = _rand(1, (kernel, kernel, 6, 10)) * 0.1
        p2d, (n, ho, wo) = conv_patches(x, kernel, 2, transpose=False)
        got = jnp.dot(p2d, w_to_gemm(w)).reshape(n, ho, wo, 10)
        want = lax.conv_general_dilated(
            x, w, window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        assert got.shape == want.shape == (2, 4, 4, 10)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("kernel", [4, 5])
    def test_transposed_conv(self, kernel):
        x = _rand(2, (2, 4, 4, 6))
        w = _rand(3, (kernel, kernel, 6, 10)) * 0.1
        p2d, (n, ho, wo) = conv_patches(x, kernel, 2, transpose=True)
        got = jnp.dot(p2d, w_to_gemm(w)).reshape(n, ho, wo, 10)
        want = lax.conv_transpose(
            x, w, strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        assert got.shape == want.shape == (2, 8, 8, 10)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


class TestGemmBiasMoments:
    def test_forward_matches_reference(self):
        p2d = _rand(0, (64, 30))
        w2d = _rand(1, (30, 12)) * 0.1
        b = _rand(2, (12,)) * 0.1
        u, mean, msq = gemm_bias_moments(p2d, w2d, b)
        ru, rm, rs = _gbm_ref(p2d, w2d, b)
        assert u.dtype == jnp.float32
        np.testing.assert_allclose(u, ru, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(mean, rm, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(msq, rs, rtol=1e-5, atol=1e-6)

    def test_moments_describe_cast_value(self):
        # under a bf16 policy the moments must match the bf16 round-trip of
        # u, NOT raw-f32 u — bit-parity with the unfused path, which reduces
        # the stored (cast) activation
        p2d = _rand(3, (32, 18))
        w2d = _rand(4, (18, 8))
        b = _rand(5, (8,))
        _, mean, msq = gemm_bias_moments(p2d, w2d, b, jnp.bfloat16)
        _, rm, rs = _gbm_ref(p2d, w2d, b, jnp.bfloat16)
        np.testing.assert_allclose(mean, rm, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(msq, rs, rtol=1e-6, atol=1e-6)

    def test_grad_matches_autodiff(self):
        p2d = _rand(6, (32, 18))
        w2d = _rand(7, (18, 8)) * 0.1
        b = _rand(8, (8,)) * 0.1
        cu, cm, cs = _rand(9, (32, 8)), _rand(10, (8,)), _rand(11, (8,))

        def via_kernel(p, w, bb):
            u, m, s = gemm_bias_moments(p, w, bb)
            return jnp.sum(u * cu) + jnp.sum(m * cm) + jnp.sum(s * cs)

        def via_ref(p, w, bb):
            u, m, s = _gbm_ref(p, w, bb)
            return jnp.sum(u * cu) + jnp.sum(m * cm) + jnp.sum(s * cs)

        gk = jax.grad(via_kernel, argnums=(0, 1, 2))(p2d, w2d, b)
        gr = jax.grad(via_ref, argnums=(0, 1, 2))(p2d, w2d, b)
        for a, e in zip(gk, gr):
            np.testing.assert_allclose(a, e, rtol=1e-4, atol=1e-4)

    def test_bf16_cotangents_keep_param_dtype(self):
        # regression: the VJP once returned a f32 `db` for a bf16 bias,
        # which promoted the bias's Adam nu leaf to f32 across the step —
        # breaking lax.scan carry dtype invariance and donation aliasing.
        # All three cotangents must come back in their operand's dtype.
        p2d = _rand(12, (16, 10), jnp.bfloat16)
        w2d = _rand(13, (10, 4), jnp.bfloat16)
        b = _rand(14, (4,), jnp.bfloat16)

        def loss(p, w, bb):
            u, m, s = gemm_bias_moments(p, w, bb, jnp.bfloat16)
            return jnp.sum(u) + jnp.sum(m) + jnp.sum(s)

        dp, dw, db = jax.grad(loss, argnums=(0, 1, 2))(p2d, w2d, b)
        assert dp.dtype == jnp.bfloat16
        assert dw.dtype == jnp.bfloat16
        assert db.dtype == jnp.bfloat16


class TestGemmBiasScaleAct:
    @pytest.mark.parametrize("act", ["none", "relu", "lrelu", "tanh"])
    def test_forward_matches_reference(self, act):
        p2d = _rand(0, (32, 18))
        w2d = _rand(1, (18, 8)) * 0.1
        b, scale, shift = _rand(2, (8,)), _rand(3, (8,)), _rand(4, (8,))
        y = gemm_bias_scale_act(p2d, w2d, b, scale, shift, act)
        u = jnp.dot(p2d, w2d) + b[None, :]
        want = _act_ref(u * scale[None, :] + shift[None, :], act)
        np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)

    def test_out_dtype(self):
        p2d = _rand(5, (16, 10))
        w2d = _rand(6, (10, 4))
        b = s = t = jnp.zeros((4,))
        y = gemm_bias_scale_act(p2d, w2d, b, s, t, "relu", 0.2, jnp.bfloat16)
        assert y.dtype == jnp.bfloat16

    @pytest.mark.parametrize("act", ["relu", "lrelu"])
    def test_grad_matches_autodiff(self, act):
        args = (_rand(7, (16, 10)), _rand(8, (10, 4)) * 0.1,
                _rand(9, (4,)), _rand(10, (4,)), _rand(11, (4,)))
        cot = _rand(12, (16, 4))

        def via_kernel(p, w, bb, sc, sh):
            return jnp.sum(gemm_bias_scale_act(p, w, bb, sc, sh, act) * cot)

        def via_ref(p, w, bb, sc, sh):
            u = jnp.dot(p, w) + bb[None, :]
            return jnp.sum(_act_ref(u * sc[None, :] + sh[None, :], act) * cot)

        gk = jax.grad(via_kernel, argnums=(0, 1, 2, 3, 4))(*args)
        gr = jax.grad(via_ref, argnums=(0, 1, 2, 3, 4))(*args)
        for a, e in zip(gk, gr):
            np.testing.assert_allclose(a, e, rtol=1e-4, atol=1e-4)

    def test_bf16_cotangents_keep_param_dtype(self):
        args = tuple(_rand(20 + i, s, jnp.bfloat16) for i, s in
                     enumerate([(16, 10), (10, 4), (4,), (4,), (4,)]))

        def loss(p, w, bb, sc, sh):
            return jnp.sum(gemm_bias_scale_act(p, w, bb, sc, sh, "lrelu",
                                               0.2, jnp.bfloat16)
                           .astype(jnp.float32))

        grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
        assert all(g.dtype == jnp.bfloat16 for g in grads)


def _stage_params(key, in_ch, out_ch, *, transpose, kernel=5):
    k1, k2 = jax.random.split(jax.random.key(key))
    init = deconv2d_init if transpose else conv2d_init
    conv_p = init(k1, in_ch, out_ch, kernel=kernel)
    bn_p, bn_s = batch_norm_init(k2, out_ch)
    return conv_p, bn_p, bn_s


def _unfused_stage(conv_p, bn_p, bn_s, x, *, transpose, act, train,
                   cdt=None, quant=""):
    apply = deconv2d_apply if transpose else conv2d_apply
    y = apply(conv_p, x, compute_dtype=cdt, quant=quant)
    return batch_norm_apply(bn_p, bn_s, y, train=train, act=act)


class TestFusedConvBnAct:
    """The fused stage vs the unfused conv/deconv + batch_norm_apply
    composition the model loops replace — output AND new-state parity,
    both directions, both train modes."""

    @pytest.mark.parametrize("transpose,act,in_ch", [
        (False, "lrelu", 6), (True, "relu", 6),
        # K = 96*25 = 2400: wider than one block may be and with no
        # lane-aligned divisor, so the stage zero-pads K to 2432
        pytest.param(False, "lrelu", 96, id="padded-K")])
    def test_train_parity(self, transpose, act, in_ch):
        from dcgan_tpu.ops import pallas_fused

        assert (pallas_fused._k_padded(in_ch * 25) != in_ch * 25) \
            == (in_ch == 96)
        x = _rand(0, (2, 8, 8, in_ch))
        conv_p, bn_p, bn_s = _stage_params(1, in_ch, 10, transpose=transpose)
        y, ns = fused_conv_bn_act(conv_p, bn_p, bn_s, x,
                                  transpose=transpose, kernel=5,
                                  train=True, act=act)
        ry, rns = _unfused_stage(conv_p, bn_p, bn_s, x,
                                 transpose=transpose, act=act, train=True)
        assert y.shape == ry.shape
        np.testing.assert_allclose(y, ry, rtol=1e-4, atol=1e-4)
        for k in ("mean", "var"):
            np.testing.assert_allclose(ns[k], rns[k], rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("transpose,act", [(False, "lrelu"),
                                               (True, "relu")])
    def test_infer_parity_and_state_identity(self, transpose, act):
        x = _rand(2, (2, 8, 8, 6))
        conv_p, bn_p, bn_s = _stage_params(3, 6, 10, transpose=transpose)
        # non-trivial running stats so the single-kernel fold is exercised
        bn_s = {"mean": _rand(4, (10,)) * 0.1,
                "var": 1.0 + 0.1 * jnp.abs(_rand(5, (10,)))}
        y, ns = fused_conv_bn_act(conv_p, bn_p, bn_s, x,
                                  transpose=transpose, kernel=5,
                                  train=False, act=act)
        ry, _ = _unfused_stage(conv_p, bn_p, bn_s, x,
                               transpose=transpose, act=act, train=False)
        np.testing.assert_allclose(y, ry, rtol=1e-4, atol=1e-4)
        assert ns is bn_s  # inference must not touch BN state

    @pytest.mark.parametrize("transpose,act", [(False, "lrelu"),
                                               (True, "relu")])
    def test_train_grads_match_unfused(self, transpose, act):
        x = _rand(6, (2, 8, 8, 6))
        conv_p, bn_p, bn_s = _stage_params(7, 6, 10, transpose=transpose)

        def fused_loss(cp, bp):
            y, _ = fused_conv_bn_act(cp, bp, bn_s, x, transpose=transpose,
                                     kernel=5, train=True, act=act)
            return jnp.sum(y * y)

        def ref_loss(cp, bp):
            y, _ = _unfused_stage(cp, bp, bn_s, x, transpose=transpose,
                                  act=act, train=True)
            return jnp.sum(y * y)

        gf = jax.grad(fused_loss, argnums=(0, 1))(conv_p, bn_p)
        gr = jax.grad(ref_loss, argnums=(0, 1))(conv_p, bn_p)
        # BN analytically cancels the conv-bias gradient (a bias shift
        # moves the batch mean BN subtracts): that leaf's true value is 0
        # and what each path returns is its own f32 cancellation noise,
        # whose size follows the compiler's summation order (~2e-3 in the
        # unfused path under the installed XLA). Comparing the two noises
        # with each other pins nothing, so each is held to 0 instead, at
        # 1e-2 — two orders under the real-signal leaves (w, gamma, beta,
        # O(0.1..1)), which stay pinned against the reference.
        for g in (gf, gr):
            np.testing.assert_allclose(g[0].pop("b"), 0.0, atol=1e-2)
        jax.tree.map(lambda a, e: np.testing.assert_allclose(
            a, e, rtol=2e-3, atol=2e-3), gf, gr)

    def test_bf16_compute_dtype(self):
        x = _rand(8, (2, 8, 8, 6), jnp.bfloat16)
        conv_p, bn_p, bn_s = _stage_params(9, 6, 10, transpose=False)
        conv_p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), conv_p)
        y, ns = fused_conv_bn_act(conv_p, bn_p, bn_s, x, transpose=False,
                                  kernel=5, train=True, act="lrelu",
                                  compute_dtype=jnp.bfloat16)
        assert y.dtype == jnp.bfloat16
        # BN stat state stays in its stored (f32) dtype under bf16 compute
        assert ns["mean"].dtype == bn_s["mean"].dtype
        ry, _ = _unfused_stage(conv_p, bn_p, bn_s, x, transpose=False,
                               act="lrelu", train=True, cdt=jnp.bfloat16)
        np.testing.assert_allclose(y.astype(jnp.float32),
                                   ry.astype(jnp.float32),
                                   rtol=0.1, atol=0.05)

    def test_fp8_quant_finite_and_close(self):
        # amax scaling means even large operands survive the e4m3 trip
        x = _rand(10, (2, 8, 8, 6)) * 50.0
        conv_p, bn_p, bn_s = _stage_params(11, 6, 10, transpose=False)
        y, _ = fused_conv_bn_act(conv_p, bn_p, bn_s, x, transpose=False,
                                 kernel=5, train=True, act="lrelu",
                                 quant="fp8")
        assert bool(jnp.all(jnp.isfinite(y)))
        ry, _ = _unfused_stage(conv_p, bn_p, bn_s, x, transpose=False,
                               act="lrelu", train=True, quant="fp8")
        np.testing.assert_allclose(y, ry, rtol=0.05, atol=0.05)


class TestConfigValidation:
    def test_requires_use_pallas(self):
        with pytest.raises(ValueError, match="requires use_pallas"):
            ModelConfig(pallas_fused=True)

    def test_dcgan_arch_only(self):
        with pytest.raises(ValueError, match="arch='dcgan' only"):
            ModelConfig(arch="resnet", use_pallas=True, pallas_fused=True)

    def test_rejects_conditional_bn(self):
        with pytest.raises(ValueError, match="conditional_bn"):
            ModelConfig(use_pallas=True, pallas_fused=True,
                        conditional_bn=True, num_classes=4)

    def test_quant_values(self):
        with pytest.raises(ValueError, match="quant"):
            ModelConfig(quant="int4")


class TestCostModel:
    def _cfg64(self):
        return ModelConfig(output_size=64, base_size=4, gf_dim=16, df_dim=16)

    def test_site_census_and_geometry(self):
        cfg = self._cfg64()
        k = cfg.num_up_layers
        sites = fused_sites(cfg, batch=8)
        # interior stages only: G 1..k-1 plus D 1..k-1, boundaries unfused
        assert len(sites) == 2 * (k - 1)
        g1 = next(s for s in sites if s["name"] == "gen/deconv1")
        assert g1["transpose"] and g1["act"] == "relu"
        assert g1["out_res"] == cfg.base_size * 2
        assert g1["m"] == 8 * g1["out_res"] ** 2
        assert g1["k"] == g1["in_ch"] * cfg.kernel_size ** 2
        d1 = next(s for s in sites if s["name"] == "disc/conv1")
        assert not d1["transpose"] and d1["act"] == "lrelu"
        assert d1["in_res"] == cfg.output_size // 2
        assert d1["out_res"] == cfg.output_size // 4

    @pytest.mark.parametrize("train", [True, False])
    def test_parts_conservation(self, train):
        cost = kernel_cost(1024, 150, 32, train=train)
        assert cost["flops"] == sum(cost["flops_parts"].values())
        assert cost["flops_parts"]["gemm"] == 2 * 1024 * 150 * 32
        assert cost["peak_temp_mib"] > 0

    def test_train_costs_more_hbm_than_infer(self):
        tr = kernel_cost(1024, 150, 32, train=True)
        inf = kernel_cost(1024, 150, 32, train=False)
        assert tr["bytes"] > inf["bytes"]

    def test_bf16_shrinks_streaming_bytes(self):
        f32 = kernel_cost(1024, 150, 32, train=False)
        bf16 = kernel_cost(1024, 150, 32, train=False,
                           compute_dtype=jnp.bfloat16)
        assert bf16["bytes"] < f32["bytes"]


# ---------------------------------------------------------------------------
# shard paths + full-model integration: slow tier (multi-device interpret
# runs), same placement as tests/test_pallas.py's integration classes
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestShardPaths:
    def test_axis_name_pmean_matches_global(self):
        from jax.sharding import Mesh, PartitionSpec as P

        from dcgan_tpu.utils.backend import shard_map

        x = _rand(0, (4, 8, 8, 6))
        conv_p, bn_p, bn_s = _stage_params(1, 6, 10, transpose=False)
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

        def body(xs):
            y, ns = fused_conv_bn_act(conv_p, bn_p, bn_s, xs,
                                      transpose=False, kernel=5, train=True,
                                      act="lrelu", axis_name="data")
            return y, ns

        y, ns = shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=(P("data"), P()), check=False)(x)
        ry, rns = fused_conv_bn_act(conv_p, bn_p, bn_s, x, transpose=False,
                                    kernel=5, train=True, act="lrelu")
        np.testing.assert_allclose(y, ry, rtol=1e-4, atol=1e-4)
        for k in ("mean", "var"):
            np.testing.assert_allclose(ns[k], rns[k], rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("train", [True, False])
    def test_pallas_mesh_matches_global(self, train):
        # the gspmd backend's routing: pallas_call is opaque to GSPMD, so
        # the stage runs per data-shard under a nested shard_map + pmean
        from jax.sharding import Mesh

        x = _rand(2, (4, 8, 8, 6))
        conv_p, bn_p, bn_s = _stage_params(3, 6, 10, transpose=False)
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        y, ns = fused_conv_bn_act(conv_p, bn_p, bn_s, x, transpose=False,
                                  kernel=5, train=train, act="lrelu",
                                  pallas_mesh=mesh)
        ry, rns = fused_conv_bn_act(conv_p, bn_p, bn_s, x, transpose=False,
                                    kernel=5, train=train, act="lrelu")
        np.testing.assert_allclose(y, ry, rtol=1e-4, atol=1e-4)
        if train:
            for k in ("mean", "var"):
                np.testing.assert_allclose(ns[k], rns[k],
                                           rtol=1e-4, atol=1e-5)


@pytest.mark.slow
class TestModelIntegration:
    """ModelConfig.pallas_fused routes every interior stage through the
    fused blocks — whole-net parity against the unfused model."""

    def _cfgs(self):
        # f32 compute: the default bf16 compute dtype rounds the GEMM and
        # conv formulations differently (~bf16-eps output drift), which is
        # precision-policy territory (tests/test_precision.py) — THIS test
        # pins the fused blocks' routing/formulation at full precision
        base = dict(output_size=16, base_size=4, gf_dim=8, df_dim=8, z_dim=8,
                    compute_dtype="float32")
        return (ModelConfig(**base),
                ModelConfig(**base, use_pallas=True, pallas_fused=True))

    def test_generator_parity(self):
        from dcgan_tpu.models.dcgan import generator_apply, generator_init

        plain, fused = self._cfgs()
        params, state = generator_init(jax.random.key(0), plain)
        z = _rand(1, (4, 8))
        for train in (True, False):
            y0, s0 = generator_apply(params, state, z, cfg=plain,
                                     train=train)
            y1, s1 = generator_apply(params, state, z, cfg=fused,
                                     train=train)
            np.testing.assert_allclose(y1, y0, rtol=1e-4, atol=1e-4)
            jax.tree.map(lambda a, e: np.testing.assert_allclose(
                a, e, rtol=1e-4, atol=1e-5), s1, s0)

    def test_discriminator_parity(self):
        from dcgan_tpu.models.dcgan import discriminator_apply, \
            discriminator_init

        plain, fused = self._cfgs()
        params, state = discriminator_init(jax.random.key(2), plain)
        img = jnp.tanh(_rand(3, (4, 16, 16, 3)))
        for train in (True, False):
            p0, l0, s0 = discriminator_apply(params, state, img, cfg=plain,
                                             train=train)
            p1, l1, s1 = discriminator_apply(params, state, img, cfg=fused,
                                             train=train)
            np.testing.assert_allclose(l1, l0, rtol=1e-4, atol=1e-4)
            jax.tree.map(lambda a, e: np.testing.assert_allclose(
                a, e, rtol=1e-4, atol=1e-5), s1, s0)

    def test_generator_grads_parity(self):
        from dcgan_tpu.models.dcgan import generator_apply, generator_init

        plain, fused = self._cfgs()
        params, state = generator_init(jax.random.key(4), plain)
        z = _rand(5, (4, 8))

        def loss(p, cfg):
            y, _ = generator_apply(p, state, z, cfg=cfg, train=True)
            return jnp.mean(y * y)

        g0 = jax.grad(loss)(params, plain)
        g1 = jax.grad(loss)(params, fused)
        jax.tree.map(lambda a, e: np.testing.assert_allclose(
            a, e, rtol=5e-3, atol=5e-4), g1, g0)
