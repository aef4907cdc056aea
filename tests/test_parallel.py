"""Multi-device tests on the 8-virtual-CPU mesh: DP equivalence, TP sharding,
synced BN across shards (SURVEY.md §4, §7 phase 4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig
from dcgan_tpu.parallel import (
    batch_sharding,
    make_mesh,
    make_parallel_train,
    state_shardings,
)
from dcgan_tpu.train import make_train_step

TINY = ModelConfig(output_size=16, gf_dim=8, df_dim=8, compute_dtype="float32")


def real_batch(n=16, size=16):
    rng = np.random.default_rng(0)
    return jnp.asarray(
        np.tanh(rng.normal(size=(n, size, size, 3))).astype(np.float32))


def max_abs_diff(a, b):
    d = jax.tree_util.tree_map(lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)
    return max(jax.tree_util.tree_leaves(d))


def test_make_mesh_shapes():
    mesh = make_mesh(MeshConfig())
    assert mesh.devices.size == 8 and mesh.axis_names == ("data", "model")
    mesh2 = make_mesh(MeshConfig(model=2))
    assert mesh2.shape["data"] == 4 and mesh2.shape["model"] == 2


def test_sharding_rules():
    cfg = TrainConfig(model=TINY, batch_size=16, mesh=MeshConfig(model=2))
    mesh = make_mesh(cfg.mesh)
    fns = make_train_step(cfg)
    shapes = jax.eval_shape(fns.init, jax.random.key(0))
    sh = state_shardings(shapes, mesh)
    # conv kernels shard out-channels on "model"
    assert sh["params"]["disc"]["conv0"]["w"].spec == P(None, None, None, "model")
    # generator projection shards its wide output dim
    assert sh["params"]["gen"]["proj"]["w"].spec == P(None, "model")
    # head shards its wide input dim
    assert sh["params"]["disc"]["head"]["w"].spec == P("model", None)
    # BN params/stats and biases replicated
    assert sh["params"]["gen"]["bn0"]["scale"].spec == P()
    assert sh["bn"]["disc"]["bn1"]["mean"].spec == P()
    # Adam moments mirror the param rules (mu lives under the same leaf paths)
    opt_leaves = jax.tree_util.tree_leaves_with_path(sh["opt"]["gen"])
    conv_mu = [s for path, s in opt_leaves
               if any(getattr(p, "key", None) == "deconv1" for p in path)
               and any(getattr(p, "key", None) == "w" for p in path)]
    assert conv_mu and all(s.spec == P(None, None, None, "model")
                           for s in conv_mu)


def test_spatial_sharding_rules():
    """spatial=True: weights replicate; images shard over (batch, height) —
    the sequence-parallel analogue for conv data (SURVEY.md §2.5)."""
    cfg = TrainConfig(model=TINY, batch_size=16,
                      mesh=MeshConfig(model=2, spatial=True))
    mesh = make_mesh(cfg.mesh)
    fns = make_train_step(cfg)
    shapes = jax.eval_shape(fns.init, jax.random.key(0))
    sh = state_shardings(shapes, mesh, spatial=True)
    for s in jax.tree_util.tree_leaves(sh):
        assert s.spec == P()
    img_sh = batch_sharding(mesh, 4, spatial=True)
    assert img_sh.spec == P("data", "model", None, None)
    # non-image inputs never spatial-shard
    assert batch_sharding(mesh, 2, spatial=True).spec == P("data", None)


# dp8 is the one sharded-equivalence case kept in the smoke tier; the other
# partitionings are slow-tier (each is a fresh multi-device compile)
@pytest.mark.parametrize(
    "mesh_cfg,model,conditional",
    [pytest.param(MeshConfig(), TINY, False, id="dp8"),
     pytest.param(MeshConfig(model=2), TINY, False, id="dp4xtp2",
                  marks=pytest.mark.slow),
     pytest.param(MeshConfig(model=2, spatial=True), TINY, False,
                  id="dp4xsp2", marks=pytest.mark.slow),
     # spatial + attention + use_pallas: attention runs as ring x flash
     # (ops/pallas_attention.py::ring_flash_attention); the single-device
     # reference runs plain flash — both exact, so they must agree
     pytest.param(MeshConfig(model=2, spatial=True), "ring-flash", False,
                  id="dp4xsp2-ringflash", marks=pytest.mark.slow),
     # pure-DP gspmd + flash attention: the kernels run per data-shard
     # through attn_apply's pallas_mesh nested shard_map — the attention
     # presets' execution form; must match the single-device step exactly
     # like every other partitioning
     pytest.param(MeshConfig(), "dp-flash", False, id="dp8-flash",
                  marks=pytest.mark.slow),
     pytest.param(MeshConfig(shard_opt=True), TINY, False, id="dp8-zero1",
                  marks=pytest.mark.slow),
     pytest.param(MeshConfig(), "cbn", True, id="dp8-cbn",
                  marks=pytest.mark.slow)])
def test_sharded_step_matches_single_device(mesh_cfg, model, conditional):
    """The sharded SPMD step must be numerically equivalent to the unsharded
    step — data parallelism here is synchronous (one global batch, global BN
    moments, all-reduced grads), NOT the reference's async Hogwild
    (SURVEY.md §2.5). The cbn case additionally covers the conditional-BN
    per-example [K, C] table gather (labels batch-sharded, tables
    replicated)."""
    import dataclasses

    if model == "cbn":
        model = dataclasses.replace(TINY, num_classes=4, conditional_bn=True)
    elif model in ("ring-flash", "dp-flash"):
        model = dataclasses.replace(TINY, attn_res=8, use_pallas=True)
    cfg = TrainConfig(model=model, batch_size=16, mesh=mesh_cfg)
    xs, key = real_batch(), jax.random.key(3)
    labels = (jnp.asarray(np.arange(16) % model.num_classes),) \
        if conditional else ()

    fns = make_train_step(cfg)
    s_ref, m_ref = jax.jit(fns.train_step)(fns.init(jax.random.key(0)), xs,
                                           key, *labels)

    pt = make_parallel_train(cfg)
    s_par = pt.init(jax.random.key(0))
    s_par, m_par = pt.step(s_par, xs, key, *labels)

    # Losses agree tightly; params loosely — Adam's first step is
    # ~±lr·sign(grad), so f32 reduction-order noise between partitionings can
    # flip near-zero gradient signs, bounding the diff by ~2·lr = 4e-4.
    np.testing.assert_allclose(float(m_par["d_loss"]), float(m_ref["d_loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(m_par["g_loss"]), float(m_ref["g_loss"]),
                               rtol=1e-5)
    assert max_abs_diff(s_ref["params"], jax.device_get(s_par["params"])) \
        <= 2 * cfg.learning_rate + 1e-5


@pytest.mark.slow
def test_multi_step_matches_sequential_steps():
    """multi_step (K steps as one lax.scan program, one dispatch) must equal
    K individual step() calls fed the same keys and batches."""
    cfg = TrainConfig(model=TINY, batch_size=16)
    xs = real_batch()
    keys = jax.random.split(jax.random.key(7), 3)

    pt = make_parallel_train(cfg)
    s_seq = pt.init(jax.random.key(0))
    for i in range(3):
        s_seq, m_seq = pt.step(s_seq, xs, keys[i])

    s_scan = pt.init(jax.random.key(0))
    imgs_k = jnp.broadcast_to(xs, (3,) + xs.shape)
    s_scan, m_scan = pt.multi_step(s_scan, imgs_k, keys)

    assert int(s_scan["step"]) == 3
    np.testing.assert_allclose(float(m_scan["d_loss"]),
                               float(m_seq["d_loss"]), rtol=1e-4)
    # scanned and unrolled programs fuse differently; f32 reduction-order
    # noise can flip near-zero Adam update signs, ~±2*lr per step (same
    # bound as test_sharded_step_matches_single_device)
    assert max_abs_diff(jax.device_get(s_seq["params"]),
                        jax.device_get(s_scan["params"])) \
        <= 3 * 2 * cfg.learning_rate + 1e-5


@pytest.mark.slow
def test_sharded_state_placement():
    cfg = TrainConfig(model=TINY, batch_size=16, mesh=MeshConfig(model=2))
    pt = make_parallel_train(cfg)
    state = pt.init(jax.random.key(0))
    w = state["params"]["gen"]["proj"]["w"]
    # physically sharded over the model axis: each shard holds 1/2 the columns
    shard_shapes = {tuple(s.data.shape) for s in w.addressable_shards}
    assert shard_shapes == {(w.shape[0], w.shape[1] // 2)}
    step = state["step"]
    assert all(s.data.shape == () for s in step.addressable_shards)


@pytest.mark.slow
def test_sharded_sample_and_multiple_steps():
    cfg = TrainConfig(model=TINY, batch_size=16)
    pt = make_parallel_train(cfg)
    s = pt.init(jax.random.key(0))
    xs = real_batch()
    for i in range(3):
        s, m = pt.step(s, xs, jax.random.fold_in(jax.random.key(1), i))
    assert int(s["step"]) == 3
    z = jax.random.uniform(jax.random.key(2), (16, 100), minval=-1, maxval=1)
    img = pt.sample(s, z)
    assert img.shape == (16, 16, 16, 3)


@pytest.mark.slow
def test_conditional_sharded_step():
    cfg = TrainConfig(
        model=ModelConfig(output_size=16, gf_dim=8, df_dim=8, num_classes=4,
                          compute_dtype="float32"),
        batch_size=16)
    pt = make_parallel_train(cfg)
    s = pt.init(jax.random.key(0))
    y = jnp.arange(16) % 4
    s, m = pt.step(s, real_batch(), jax.random.key(1), y)
    assert np.isfinite(float(m["d_loss"]))


@pytest.mark.slow
def test_zero1_opt_state_sharding():
    """shard_opt=True (ZeRO-1, arXiv:2004.13336): Adam moments shard over
    the data axis; params/BN stay on their usual rules; the physical shards
    each hold 1/8 of the moment tensors."""
    cfg = TrainConfig(model=TINY, batch_size=16,
                      mesh=MeshConfig(shard_opt=True))
    mesh = make_mesh(cfg.mesh)
    fns = make_train_step(cfg)
    shapes = jax.eval_shape(fns.init, jax.random.key(0))
    sh = state_shardings(shapes, mesh, shard_opt=True)
    # conv-kernel moments [5,5,in,out]: data axis lands on the first dim it
    # divides; params themselves stay replicated (pure DP mesh)
    leaves = jax.tree_util.tree_leaves_with_path(sh["opt"]["disc"])
    kernel_specs = [s.spec for path, s in leaves
                    if any(getattr(p, "key", None) == "conv1" for p in path)
                    and any(getattr(p, "key", None) == "w" for p in path)]
    assert kernel_specs and all("data" in tuple(s) for s in kernel_specs)
    # params never pick up the data axis (ZeRO-1 shards only optimizer state)
    assert "data" not in tuple(sh["params"]["disc"]["conv1"]["w"].spec)

    pt = make_parallel_train(cfg, mesh)
    state = pt.init(jax.random.key(0))
    # [0] is the grad-clip slot (EmptyState), [1] the adam chain
    mu_w = state["opt"]["disc"][1][0].mu["conv1"]["w"]
    full = int(np.prod(mu_w.shape))
    shard_sizes = {int(np.prod(s.data.shape))
                   for s in mu_w.addressable_shards}
    assert shard_sizes == {full // 8}
    # and the params stayed fully replicated on every device
    w = state["params"]["disc"]["conv1"]["w"]
    assert all(s.data.shape == w.shape for s in w.addressable_shards)


def test_zero1_rejected_for_shard_map_backend():
    with pytest.raises(ValueError, match="shard_opt"):
        TrainConfig(model=TINY, backend="shard_map",
                    mesh=MeshConfig(shard_opt=True))


@pytest.mark.slow
def test_g_ema_sharded():
    """ema_gen mirrors the generator param paths, so the TP sharding rules
    hit it automatically; one sharded step keeps it consistent."""
    cfg = TrainConfig(model=TINY, batch_size=16, g_ema_decay=0.999,
                      mesh=MeshConfig(model=2))
    mesh = make_mesh(cfg.mesh)
    fns = make_train_step(cfg)
    shapes = jax.eval_shape(fns.init, jax.random.key(0))
    sh = state_shardings(shapes, mesh)
    assert sh["ema_gen"]["proj"]["w"].spec == P(None, "model")

    pt = make_parallel_train(cfg, mesh)
    s = pt.init(jax.random.key(0))
    s, m = pt.step(s, real_batch(), jax.random.key(1))
    assert np.isfinite(float(m["g_loss"]))
    z = jax.random.uniform(jax.random.key(2), (16, 100), minval=-1, maxval=1)
    assert pt.sample(s, z).shape == (16, 16, 16, 3)


@pytest.mark.slow
def test_wgan_gp_sharded():
    """Grad-of-grad through the GSPMD-sharded mesh (SURVEY.md §7 hard part c)."""
    cfg = TrainConfig(model=TINY, batch_size=16, loss="wgan-gp")
    pt = make_parallel_train(cfg)
    s = pt.init(jax.random.key(0))
    s, m = pt.step(s, real_batch(), jax.random.key(1))
    assert np.isfinite(float(m["gp"]))
