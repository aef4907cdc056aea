"""Self-attention + sequence-parallel ring attention (ops/attention.py).

The reference has no attention (pure-conv DCGAN, SURVEY.md §2.5); these tests
cover the framework's long-context machinery: exactness of the ring recurrence
against full attention (forward and gradients) on the 8-virtual-device mesh,
identity-at-init of the SAGAN block, model wiring at every legal attn_res, and
single-device-vs-sharded equivalence of the full train step with ring
attention under a spatial mesh.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# tier-1 but for the tests marked slow one by one: each of those pays a
# multi-device or whole-model compile of 10-30 s on a CPU

from jax.sharding import Mesh, PartitionSpec as P

from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig
from dcgan_tpu.utils.backend import shard_map
from dcgan_tpu.models.dcgan import (
    discriminator_apply,
    gan_init,
    generator_apply,
)
from dcgan_tpu.ops.attention import (
    attn_apply,
    attn_init,
    full_attention,
    ring_attention,
)
from dcgan_tpu.parallel import make_mesh, make_parallel_train
from dcgan_tpu.train import make_train_step

ATTN_TINY = ModelConfig(output_size=16, gf_dim=8, df_dim=8, attn_res=8,
                        compute_dtype="float32")


def qkv(B=2, S=64, d=16, dv=32):
    k = jax.random.key(0)
    return tuple(
        jax.random.normal(jax.random.fold_in(k, i), (B, S, dim))
        for i, dim in enumerate((d, d, dv)))


def ring_mesh(n):
    return Mesh(np.asarray(jax.devices()).reshape(8 // n, n),
                ("data", "model"))


def max_abs_diff(a, b):
    d = jax.tree_util.tree_map(lambda x, y: float(jnp.max(jnp.abs(x - y))),
                               a, b)
    return max(jax.tree_util.tree_leaves(d))


class TestRingAttention:
    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_matches_full_attention(self, n):
        q, k, v = qkv()
        scale = q.shape[-1] ** -0.5
        full = full_attention(q, k, v, scale=scale)
        mesh = ring_mesh(n)
        spec = P(None, "model", None)
        ring = jax.jit(shard_map(
            functools.partial(ring_attention, axis_name="model", n_shards=n,
                              scale=scale),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))(q, k, v)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                                   atol=2e-6)

    def test_gradients_match_full_attention(self):
        q, k, v = qkv()
        scale = q.shape[-1] ** -0.5
        mesh = ring_mesh(4)
        spec = P(None, "model", None)

        def loss_full(q, k, v):
            return jnp.sum(full_attention(q, k, v, scale=scale) ** 2)

        def loss_ring(q, k, v):
            f = shard_map(
                functools.partial(ring_attention, axis_name="model",
                                  n_shards=4, scale=scale),
                mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
            return jnp.sum(f(q, k, v) ** 2)

        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_full, g_ring):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=1e-5)

    def test_single_shard_degrades_to_full(self):
        q, k, v = qkv()
        scale = q.shape[-1] ** -0.5
        out = ring_attention(q, k, v, axis_name="model", n_shards=1,
                             scale=scale)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(full_attention(q, k, v, scale=scale)))


class TestAttnBlock:
    def test_identity_at_init(self):
        # gamma starts at 0 (SAGAN residual gate): the block is a no-op until
        # training moves it, so inserting it cannot perturb reference
        # dynamics at step 0.
        params = attn_init(jax.random.key(0), 32)
        x = jax.random.normal(jax.random.key(1), (2, 8, 8, 32))
        np.testing.assert_array_equal(np.asarray(attn_apply(params, x)),
                                      np.asarray(x))

    def test_sagan_channel_plan(self):
        params = attn_init(jax.random.key(0), 64)
        assert params["query"]["w"].shape == (64, 8)
        assert params["key"]["w"].shape == (64, 8)
        assert params["value"]["w"].shape == (64, 32)
        assert params["out"]["w"].shape == (32, 64)
        assert params["gamma"].shape == ()

    def test_rejects_narrow_channels(self):
        with pytest.raises(ValueError, match=">= 8 channels"):
            attn_init(jax.random.key(0), 4)

    def test_ring_path_matches_dense_path(self):
        params = attn_init(jax.random.key(0), 16)
        # gamma = 0 makes both paths trivially equal; test with it live
        params = dict(params, gamma=jnp.asarray(0.7))
        x = jax.random.normal(jax.random.key(1), (4, 8, 8, 16))
        dense = attn_apply(params, x)
        ringy = attn_apply(params, x, seq_mesh=ring_mesh(4))
        np.testing.assert_allclose(np.asarray(ringy), np.asarray(dense),
                                   atol=1e-5)

    def test_rejects_unshardable_sequence(self):
        params = attn_init(jax.random.key(0), 16)
        x = jax.random.normal(jax.random.key(1), (2, 3, 3, 16))
        with pytest.raises(ValueError, match="does not shard"):
            attn_apply(params, x, seq_mesh=ring_mesh(8))

    def test_multihead_all_paths_agree(self):
        """Heads fold into the batch dim, so dense / flash / ring must stay
        mutually exact with num_heads > 1 (same params — the head count is an
        apply-time split)."""
        params = attn_init(jax.random.key(0), 32)
        params = dict(params, gamma=jnp.asarray(0.6))
        x = jax.random.normal(jax.random.key(1), (4, 8, 8, 32))
        dense = attn_apply(params, x, num_heads=2)
        ringy = attn_apply(params, x, num_heads=2, seq_mesh=ring_mesh(4))
        fused = attn_apply(params, x, num_heads=2, use_pallas=True)
        np.testing.assert_allclose(np.asarray(ringy), np.asarray(dense),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                                   atol=1e-5)
        # heads=2 is a different function than heads=1
        single = attn_apply(params, x, num_heads=1)
        assert np.abs(np.asarray(dense) - np.asarray(single)).max() > 1e-4

    def test_multihead_rejects_indivisible(self):
        params = attn_init(jax.random.key(0), 16)  # qk dim 2, v dim 8
        x = jax.random.normal(jax.random.key(1), (2, 8, 8, 16))
        with pytest.raises(ValueError, match="does not divide"):
            attn_apply(params, x, num_heads=3)


class TestUlysses:
    """All-to-all sequence parallelism: the second SP strategy, exact vs the
    ring and the dense reference."""

    @pytest.mark.parametrize("n,heads", [(2, 2), (4, 4), (2, 4)])
    def test_matches_dense_and_ring(self, n, heads):
        params = attn_init(jax.random.key(0), 32)
        params = dict(params, gamma=jnp.asarray(0.8))
        x = jax.random.normal(jax.random.key(1), (4, 8, 8, 32))
        mesh = ring_mesh(n)
        dense = attn_apply(params, x, num_heads=heads)
        uly = attn_apply(params, x, num_heads=heads, seq_mesh=mesh,
                         seq_strategy="ulysses")
        ring = attn_apply(params, x, num_heads=heads, seq_mesh=mesh,
                          seq_strategy="ring")
        np.testing.assert_allclose(np.asarray(uly), np.asarray(dense),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(uly), np.asarray(ring),
                                   atol=1e-5)

    @pytest.mark.slow
    def test_gradients_match_dense(self):
        params = attn_init(jax.random.key(0), 32)
        params = dict(params, gamma=jnp.asarray(0.8))
        # batch must divide the mesh's data axis (8//2 = 4)
        x = jax.random.normal(jax.random.key(1), (4, 8, 8, 32))
        mesh = ring_mesh(2)

        def loss(fn_kwargs):
            def f(x):
                return jnp.sum(attn_apply(params, x, num_heads=2,
                                          **fn_kwargs) ** 2)
            return jax.grad(f)(x)

        g_dense = loss({})
        g_uly = loss({"seq_mesh": mesh, "seq_strategy": "ulysses"})
        np.testing.assert_allclose(np.asarray(g_uly), np.asarray(g_dense),
                                   atol=1e-4)

    def test_rejects_indivisible_heads(self):
        params = attn_init(jax.random.key(0), 32)
        x = jax.random.normal(jax.random.key(1), (4, 8, 8, 32))
        with pytest.raises(ValueError, match="divisible"):
            attn_apply(params, x, num_heads=1, seq_mesh=ring_mesh(2),
                       seq_strategy="ulysses")

    def test_unknown_strategy_rejected(self):
        params = attn_init(jax.random.key(0), 32)
        x = jax.random.normal(jax.random.key(1), (4, 8, 8, 32))
        with pytest.raises(ValueError, match="seq_strategy"):
            attn_apply(params, x, seq_mesh=ring_mesh(2),
                       seq_strategy="megatron")

    @pytest.mark.slow
    def test_sharded_train_step_ulysses(self):
        """Full train step under dp4 x sp2 with Ulysses attention matches the
        single-device step (same envelope as the ring test)."""
        # 16-ch attention site (gf=df=16) so the qk projection (ch/8 = 2)
        # splits into 2 heads; ATTN_TINY's 8-ch site gives qk dim 1
        cfg = TrainConfig(
            model=dataclasses.replace(ATTN_TINY, gf_dim=16, df_dim=16,
                                      attn_heads=2,
                                      attn_seq_strategy="ulysses"),
            batch_size=16, mesh=MeshConfig(data=4, model=2, spatial=True))
        xs = jnp.asarray(np.tanh(np.random.default_rng(0).normal(
            size=(16, 16, 16, 3))).astype(np.float32))
        key = jax.random.key(3)
        fns = make_train_step(cfg)
        s_ref, m_ref = jax.jit(fns.train_step)(
            fns.init(jax.random.key(0)), xs, key)
        pt = make_parallel_train(cfg)
        s_par, m_par = pt.step(pt.init(jax.random.key(0)), xs, key)
        np.testing.assert_allclose(float(m_par["d_loss"]),
                                   float(m_ref["d_loss"]), rtol=1e-4)
        np.testing.assert_allclose(float(m_par["g_loss"]),
                                   float(m_ref["g_loss"]), rtol=1e-4)
        assert max_abs_diff(jax.device_get(s_ref["params"]),
                            jax.device_get(s_par["params"])) \
            <= 2 * cfg.learning_rate + 1e-5


class TestModelWiring:
    def test_attn_res_validation(self):
        with pytest.raises(ValueError, match="not a feature-map resolution"):
            ModelConfig(output_size=64, attn_res=7)
        with pytest.raises(ValueError, match="not a feature-map resolution"):
            ModelConfig(output_size=64, attn_res=64)  # only intermediate maps
        ModelConfig(output_size=64, attn_res=4)       # base_size site is legal

    @pytest.mark.slow
    @pytest.mark.parametrize("attn_res", [4, 8])
    def test_generator_and_discriminator_run(self, attn_res):
        cfg = dataclasses.replace(ATTN_TINY, attn_res=attn_res)
        params, bn = gan_init(jax.random.key(0), cfg)
        assert "attn" in params["gen"] and "attn" in params["disc"]
        z = jax.random.uniform(jax.random.key(1), (4, cfg.z_dim),
                               minval=-1.0, maxval=1.0)
        img, _ = generator_apply(params["gen"], bn["gen"], z, cfg=cfg,
                                 train=True)
        assert img.shape == (4, 16, 16, 3)
        _, logit, _ = discriminator_apply(params["disc"], bn["disc"], img,
                                          cfg=cfg, train=True)
        assert logit.shape == (4, 1)

    @pytest.mark.slow
    def test_no_attn_params_without_attn_res(self):
        params, _ = gan_init(jax.random.key(0),
                             dataclasses.replace(ATTN_TINY, attn_res=0))
        assert "attn" not in params["gen"] and "attn" not in params["disc"]

    def test_gamma_learns(self):
        """One train step must move gamma off exactly 0 (gradient flows
        through the residual gate)."""
        cfg = TrainConfig(model=ATTN_TINY, batch_size=8,
                          mesh=MeshConfig(data=1))
        fns = make_train_step(cfg)
        state = fns.init(jax.random.key(0))
        xs = jnp.asarray(np.tanh(np.random.default_rng(0).normal(
            size=(8, 16, 16, 3))).astype(np.float32))
        state, metrics = jax.jit(fns.train_step)(state, xs, jax.random.key(1))
        assert float(state["params"]["disc"]["attn"]["gamma"]) != 0.0
        assert float(state["params"]["gen"]["attn"]["gamma"]) != 0.0
        for v in metrics.values():
            assert np.isfinite(float(v))


class TestShardedAttentionStep:
    @pytest.mark.slow
    def test_spatial_ring_step_matches_single_device(self):
        """dp4 x spatial2 with ring attention == the unsharded step (losses
        tight; params within the ±2·lr first-Adam-step sign-flip envelope —
        see test_parallel.py)."""
        cfg = TrainConfig(model=ATTN_TINY, batch_size=16,
                          mesh=MeshConfig(data=4, model=2, spatial=True))
        xs = jnp.asarray(np.tanh(np.random.default_rng(0).normal(
            size=(16, 16, 16, 3))).astype(np.float32))
        key = jax.random.key(3)

        fns = make_train_step(cfg)
        s_ref, m_ref = jax.jit(fns.train_step)(
            fns.init(jax.random.key(0)), xs, key)

        pt = make_parallel_train(cfg)
        s_par, m_par = pt.step(pt.init(jax.random.key(0)), xs, key)

        np.testing.assert_allclose(float(m_par["d_loss"]),
                                   float(m_ref["d_loss"]), rtol=1e-4)
        np.testing.assert_allclose(float(m_par["g_loss"]),
                                   float(m_ref["g_loss"]), rtol=1e-4)
        assert max_abs_diff(jax.device_get(s_ref["params"]),
                            jax.device_get(s_par["params"])) \
            <= 2 * cfg.learning_rate + 1e-5

    def test_dp_step_with_attention(self):
        """Pure DP (no spatial axis): attention stays dense and the batch
        shards; metrics finite across the mesh."""
        cfg = TrainConfig(model=ATTN_TINY, batch_size=16, mesh=MeshConfig())
        pt = make_parallel_train(cfg)
        xs = jnp.asarray(np.tanh(np.random.default_rng(0).normal(
            size=(16, 16, 16, 3))).astype(np.float32))
        state, metrics = pt.step(pt.init(jax.random.key(0)), xs,
                                 jax.random.key(1))
        assert int(state["step"]) == 1
        for v in metrics.values():
            assert np.isfinite(float(v))
