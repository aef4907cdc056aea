"""Flash-attention Pallas kernels (ops/pallas_attention.py): exactness vs the
dense reference, forward and backward, plus the attn_apply(use_pallas=True)
routing, a full train step on the flash path, and the meshes `use_pallas`
composes with. Off-TPU the kernels run in interpret mode — the same code
path the chip compiles."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig
from dcgan_tpu.utils.backend import shard_map
from dcgan_tpu.ops.attention import (
    attn_apply,
    attn_init,
    full_attention,
    ring_attention,
)
from dcgan_tpu.ops.pallas_attention import flash_attention
from dcgan_tpu.train import make_train_step


def qkv(B=2, S=256, d=8, dv=32, seed=0):
    k0 = jax.random.key(seed)
    return tuple(
        jax.random.normal(jax.random.fold_in(k0, i), (B, S, dim))
        for i, dim in enumerate((d, d, dv)))


class TestFlashAttention:
    @pytest.mark.parametrize("S", [128, 192, 256])
    def test_forward_matches_dense(self, S):
        q, k, v = qkv(S=S)
        scale = q.shape[-1] ** -0.5
        ref = full_attention(q, k, v, scale=scale)
        out = flash_attention(q, k, v, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6)

    def test_gradients_match_dense(self):
        q, k, v = qkv()
        scale = q.shape[-1] ** -0.5

        def dense(q, k, v):
            return jnp.sum(full_attention(q, k, v, scale=scale) ** 2)

        def flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, scale) ** 2)

        g_ref = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=2e-5)

    def test_extreme_logits_stay_finite(self):
        # the online softmax must survive rows whose max logit is huge
        q, k, v = qkv(S=128)
        q = q * 100.0
        out = flash_attention(q, k, v, q.shape[-1] ** -0.5)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_bf16_inputs(self):
        q, k, v = (t.astype(jnp.bfloat16) for t in qkv(S=128))
        scale = q.shape[-1] ** -0.5
        out = flash_attention(q, k, v, scale)
        ref = full_attention(q, k, v, scale=scale)
        assert out.dtype == jnp.float32  # f32 accumulation contract
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-2)

    def test_bad_tile_env_raises(self, monkeypatch):
        q, k, v = qkv(S=128)
        for bad in ("0", "-8", "garbage"):
            monkeypatch.setenv("DCGAN_FLASH_TQ", bad)
            with pytest.raises(ValueError, match="DCGAN_FLASH_TQ"):
                flash_attention(q, k, v, 0.1)

    @pytest.mark.parametrize("tq,tk", [("64", "32"), ("256", "128")])
    def test_tuned_tile_sizes_stay_exact(self, tq, tk, monkeypatch):
        # DCGAN_FLASH_TQ/TK are the chip-tuning knobs (read per call); any
        # divisor config must be bit-compatible with the default tiling
        q, k, v = qkv(S=256)
        scale = q.shape[-1] ** -0.5
        ref = full_attention(q, k, v, scale=scale)
        monkeypatch.setenv("DCGAN_FLASH_TQ", tq)
        monkeypatch.setenv("DCGAN_FLASH_TK", tk)
        out = flash_attention(q, k, v, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6)
        # all three: dq sums over k-tiles across grid steps, dk/dv over
        # q-tiles inside one (the fused backward, flash_dq_dkv)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            full_attention(q, k, v, scale=scale) ** 2), argnums=(0, 1, 2))(
                q, k, v)
        g_fl = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, scale) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=2e-5)


class TestRingFlash:
    """ring x flash composition (ops/pallas_attention.py::
    ring_flash_attention): sequence-parallel ring hops whose per-block fold
    runs the flash kernels — exactness vs full attention and vs the dense
    ring, forward and gradients, on the 8-virtual-device mesh."""

    def _mesh_and_spec(self, n):
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(1, n),
                    ("data", "model"))
        return mesh, P("data", "model", None)

    def _smap(self, fn, n):
        mesh, spec = self._mesh_and_spec(n)
        # check=False: pallas_call outputs carry no vma annotations
        # (same constraint as attn_apply's seq-parallel pallas routing)
        return shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check=False)

    def test_forward_matches_dense_and_ring(self):
        import functools

        from dcgan_tpu.ops.pallas_attention import ring_flash_attention

        q, k, v = qkv(S=256, d=16, dv=32)
        scale = q.shape[-1] ** -0.5
        n = 8
        rf = self._smap(functools.partial(
            ring_flash_attention, scale=scale, axis_name="model",
            n_shards=n), n)
        ring = self._smap(functools.partial(
            ring_attention, axis_name="model", n_shards=n, scale=scale), n)
        dense = full_attention(q, k, v, scale=scale)
        np.testing.assert_allclose(np.asarray(rf(q, k, v)),
                                   np.asarray(dense), atol=2e-5)
        np.testing.assert_allclose(np.asarray(rf(q, k, v)),
                                   np.asarray(ring(q, k, v)), atol=2e-5)

    def test_gradients_match_dense(self):
        import functools

        from dcgan_tpu.ops.pallas_attention import ring_flash_attention

        q, k, v = qkv(S=128, d=8, dv=16)
        scale = q.shape[-1] ** -0.5
        n = 4
        rf = self._smap(functools.partial(
            ring_flash_attention, scale=scale, axis_name="model",
            n_shards=n), n)

        g_rf = jax.grad(lambda q, k, v: jnp.sum(rf(q, k, v) ** 2),
                        argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(
                full_attention(q, k, v, scale=scale) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_rf):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=5e-5)

    def test_single_shard_is_flash(self):
        from dcgan_tpu.ops.pallas_attention import ring_flash_attention

        q, k, v = qkv(S=128)
        scale = q.shape[-1] ** -0.5
        out = ring_flash_attention(q, k, v, scale=scale, axis_name="model",
                                   n_shards=1)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(full_attention(q, k, v, scale=scale)), atol=2e-6)

    def test_attn_apply_routes_ring_through_flash(self):
        mesh, _ = self._mesh_and_spec(8)
        params = attn_init(jax.random.key(0), 16)
        params = dict(params, gamma=jnp.asarray(0.7))
        x = jax.random.normal(jax.random.key(1), (2, 16, 16, 16))
        dense_ring = attn_apply(params, x, seq_mesh=mesh,
                                seq_strategy="ring")
        flash_ring = attn_apply(params, x, seq_mesh=mesh,
                                seq_strategy="ring", use_pallas=True)
        np.testing.assert_allclose(np.asarray(flash_ring),
                                   np.asarray(dense_ring), atol=1e-5)


class TestFusedAttnApply:
    def test_use_pallas_matches_dense_block(self):
        params = attn_init(jax.random.key(0), 16)
        params = dict(params, gamma=jnp.asarray(0.5))
        x = jax.random.normal(jax.random.key(1), (2, 16, 16, 16))
        dense = attn_apply(params, x)
        fused = attn_apply(params, x, use_pallas=True)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                                   atol=1e-5)

    def test_train_step_on_fused_path(self):
        cfg = TrainConfig(
            model=ModelConfig(output_size=16, gf_dim=8, df_dim=8, attn_res=8,
                              compute_dtype="float32", use_pallas=True),
            batch_size=8, mesh=MeshConfig(data=1))
        fns = make_train_step(cfg)
        state = fns.init(jax.random.key(0))
        xs = jnp.asarray(np.tanh(np.random.default_rng(0).normal(
            size=(8, 16, 16, 3))).astype(np.float32))
        state, metrics = jax.jit(fns.train_step)(state, xs, jax.random.key(1))
        assert int(state["step"]) == 1
        for v in metrics.values():
            assert np.isfinite(float(v))

    def test_fused_step_matches_unfused(self):
        base = ModelConfig(output_size=16, gf_dim=8, df_dim=8, attn_res=8,
                           compute_dtype="float32")
        xs = jnp.asarray(np.tanh(np.random.default_rng(0).normal(
            size=(8, 16, 16, 3))).astype(np.float32))
        results = []
        for use_pallas in (False, True):
            cfg = TrainConfig(model=dataclasses.replace(
                base, use_pallas=use_pallas), batch_size=8,
                mesh=MeshConfig(data=1))
            fns = make_train_step(cfg)
            state = fns.init(jax.random.key(0))
            state, metrics = jax.jit(fns.train_step)(state, xs,
                                                     jax.random.key(1))
            results.append((state, metrics))
        (_, m_ref), (_, m_fused) = results
        for k in m_ref:
            np.testing.assert_allclose(float(m_fused[k]), float(m_ref[k]),
                                       rtol=1e-4, err_msg=k)


def _lowered_step(mesh_cfg, debug_info=False, **model_kw):
    """The gspmd train step of a 16 px model on `mesh_cfg` over the 8
    virtual devices, lowered (nothing compiles or runs)."""
    from dcgan_tpu.parallel import make_parallel_train

    cfg = TrainConfig(
        model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                          compute_dtype="float32", **model_kw),
        batch_size=16, mesh=mesh_cfg)
    pt = make_parallel_train(cfg)
    state = jax.eval_shape(lambda k: pt.init(k), jax.random.key(0))
    images = jax.ShapeDtypeStruct((16, 16, 16, 3), jnp.float32)
    return pt.programs["train_step"].lower(
        state, images, jax.random.key(1)).as_text(debug_info=debug_info)


class TestGspmdMeshes:
    """What parallel/api.py's guard admits: `use_pallas` selects the flash
    kernels where the model has attention and nothing else, so only
    attention constrains the mesh."""

    @pytest.mark.parametrize("mesh_cfg", [
        pytest.param(MeshConfig(), id="data"),
        pytest.param(MeshConfig(model=2, spatial=True), id="data-x-spatial"),
    ])
    def test_attention_runs_the_flash_kernels_per_shard(self, mesh_cfg):
        import re

        text = _lowered_step(mesh_cfg, debug_info=True, use_pallas=True,
                             attn_res=8)
        kernels = {loc.split("/")[-2]
                   for loc in re.findall(r'loc\("([^"]+)"', text)
                   if loc.endswith("/pallas_call")}
        assert kernels == {"flash_fwd", "flash_dq_dkv"}

    def test_model_axis_with_attention_is_refused(self):
        with pytest.raises(ValueError,
                           match="data-parallel or spatial mesh"):
            _lowered_step(MeshConfig(model=2), use_pallas=True, attn_res=8)

    @pytest.mark.parametrize("mesh_cfg", [
        pytest.param(MeshConfig(), id="data"),
        pytest.param(MeshConfig(model=2), id="data-x-model"),
        pytest.param(MeshConfig(model=2, spatial=True), id="data-x-spatial"),
    ])
    def test_without_attention_the_flag_is_a_no_op(self, mesh_cfg):
        assert _lowered_step(mesh_cfg, use_pallas=True) \
            == _lowered_step(mesh_cfg)
