"""Unit tests for the op layer: shapes, init distributions, BN EMA semantics
(the reference had no tests at all — SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.ops import (
    batch_norm_apply,
    batch_norm_init,
    conv2d_apply,
    conv2d_init,
    deconv2d_apply,
    deconv2d_init,
    linear_apply,
    linear_init,
    lrelu,
)


def test_linear_shapes_and_init():
    p = linear_init(jax.random.key(0), 100, 8192)
    assert p["w"].shape == (100, 8192)
    assert p["b"].shape == (8192,)
    # W ~ N(0, 0.02) (reference init, distriubted_model.py:165-166)
    assert abs(float(jnp.std(p["w"])) - 0.02) < 0.002
    assert float(jnp.max(jnp.abs(p["b"]))) == 0.0
    y = linear_apply(p, jnp.ones((4, 100)))
    assert y.shape == (4, 8192)


def test_conv2d_downsamples_by_stride():
    p = conv2d_init(jax.random.key(1), 3, 64)
    assert p["w"].shape == (5, 5, 3, 64)
    # truncated normal: no sample beyond 2 sigma
    assert float(jnp.max(jnp.abs(p["w"]))) <= 2 * 0.02 + 1e-6
    x = jnp.ones((2, 64, 64, 3))
    y = conv2d_apply(p, x)
    assert y.shape == (2, 32, 32, 64)


def test_deconv2d_upsamples_by_stride():
    p = deconv2d_init(jax.random.key(2), 512, 256)
    x = jnp.ones((2, 4, 4, 512))
    y = deconv2d_apply(p, x)
    assert y.shape == (2, 8, 8, 256)


def test_conv_deconv_bf16_compute_keeps_shapes():
    p = conv2d_init(jax.random.key(3), 3, 8)
    y = conv2d_apply(p, jnp.ones((1, 16, 16, 3)), compute_dtype=jnp.bfloat16)
    assert y.dtype == jnp.bfloat16 and y.shape == (1, 8, 8, 8)


def test_lrelu():
    x = jnp.array([-1.0, 0.0, 2.0])
    np.testing.assert_allclose(lrelu(x), [-0.2, 0.0, 2.0], rtol=1e-6)


class TestBatchNorm:
    def test_train_normalizes_batch(self):
        p, s = batch_norm_init(jax.random.key(0), 8)
        x = 5.0 + 3.0 * jax.random.normal(jax.random.key(1), (32, 4, 4, 8))
        y, _ = batch_norm_apply(p, s, x, train=True)
        # output moments ~ (0,1) modulated by scale/bias (scale ~ N(1,0.02))
        m = jnp.mean(y, axis=(0, 1, 2))
        v = jnp.var(y, axis=(0, 1, 2))
        np.testing.assert_allclose(np.asarray(m), np.asarray(p["bias"]),
                                   atol=1e-3)
        np.testing.assert_allclose(np.asarray(v),
                                   np.asarray(p["scale"]) ** 2, rtol=0.05)

    def test_ema_update_rule(self):
        """EMA: new = 0.9*old + 0.1*batch (momentum 0.9, the reference's
        ExponentialMovingAverage decay, distriubted_model.py:23)."""
        p, s = batch_norm_init(jax.random.key(0), 4)
        x = 2.0 + jax.random.normal(jax.random.key(1), (64, 8, 8, 4))
        _, s1 = batch_norm_apply(p, s, x, train=True, momentum=0.9)
        batch_mean = jnp.mean(x, axis=(0, 1, 2))
        expect = 0.9 * s["mean"] + 0.1 * batch_mean
        np.testing.assert_allclose(np.asarray(s1["mean"]), np.asarray(expect),
                                   rtol=1e-5)

    def test_eval_uses_running_stats(self):
        p, s = batch_norm_init(jax.random.key(0), 4)
        s = {"mean": jnp.full((4,), 2.0), "var": jnp.full((4,), 4.0)}
        x = jnp.full((2, 3, 3, 4), 2.0)
        y, s_out = batch_norm_apply(p, s, x, train=False)
        # (2-2)/2 * scale + bias = bias
        np.testing.assert_allclose(
            np.asarray(y[0, 0, 0]), np.asarray(p["bias"]), atol=1e-5)
        assert s_out is s  # eval must not mutate state

    def test_2d_input(self):
        """The reference special-cases 2-D inputs (moments over [0,1],
        distriubted_model.py:38-39); here 'all but channel' covers it."""
        p, s = batch_norm_init(jax.random.key(0), 16)
        x = jax.random.normal(jax.random.key(1), (64, 16))
        y, _ = batch_norm_apply(p, s, x, train=True)
        assert y.shape == (64, 16)
        np.testing.assert_allclose(np.asarray(jnp.mean(y, axis=0)),
                                   np.asarray(p["bias"]), atol=1e-3)

    def test_conditional_bn_per_class_affine(self):
        """cBN: each example is scaled/shifted by its class row; moments stay
        shared (SAGAN/BigGAN conditional BN)."""
        p, s = batch_norm_init(jax.random.key(0), 8, num_classes=3)
        assert p["scale"].shape == (3, 8) and p["bias"].shape == (3, 8)
        assert s["mean"].shape == (8,)  # moments are unconditional
        x = jax.random.normal(jax.random.key(1), (6, 4, 4, 8))
        labels = jnp.asarray([0, 1, 2, 0, 1, 2])
        y, s1 = batch_norm_apply(p, s, x, train=True, labels=labels)
        assert y.shape == x.shape
        # same input row, different class -> different output
        x2 = jnp.broadcast_to(x[:1], x.shape)
        y2, _ = batch_norm_apply(p, s, x2, train=True, labels=labels)
        assert np.abs(np.asarray(y2[0] - y2[1])).max() > 1e-4
        # class affine recovery: normalized x2 rows are identical, so
        # y2[i] = xhat * scale[label_i] + bias[label_i]
        xhat = (y2[0] - p["bias"][0]) / p["scale"][0]
        recon = xhat * p["scale"][1] + p["bias"][1]
        np.testing.assert_allclose(np.asarray(y2[1]), np.asarray(recon),
                                   atol=1e-4)

    def test_conditional_bn_requires_labels(self):
        p, s = batch_norm_init(jax.random.key(0), 8, num_classes=3)
        x = jax.random.normal(jax.random.key(1), (4, 2, 2, 8))
        with pytest.raises(ValueError, match="labels"):
            batch_norm_apply(p, s, x, train=True)

    def test_synced_moments_pmean(self):
        """Cross-replica BN: pmean'd moments under pmap equal global moments."""
        n = jax.local_device_count()
        p, s = batch_norm_init(jax.random.key(0), 4)
        x = jax.random.normal(jax.random.key(1), (n, 8, 2, 2, 4)) * 3.0 + 1.0

        def f(xs):
            y, s1 = batch_norm_apply(p, s, xs, train=True, axis_name="d")
            return y, s1

        _, s_sync = jax.pmap(f, axis_name="d")(x)
        global_mean = jnp.mean(x.reshape(-1, 4)[:, :], axis=0)
        expect = 0.9 * s["mean"] + 0.1 * global_mean
        # every replica must hold identical, globally-synced stats
        for i in range(n):
            np.testing.assert_allclose(np.asarray(s_sync["mean"][i]),
                                       np.asarray(expect), rtol=1e-4)


def _bn_formula(p, s, x, *, train, act, labels, momentum=0.9, eps=1e-5,
                leak=0.2):
    """BatchNorm + activation written out in float32 `jax.numpy`: the plain
    formula `batch_norm_apply` is held to."""
    xf = x.astype(jnp.float32)
    if train:
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(xf, axes)
        var = jnp.mean((xf - mean) ** 2, axes)
        state = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
                 "var": momentum * s["var"] + (1 - momentum) * var}
    else:
        mean, var, state = s["mean"], s["var"], s
    scale, bias = p["scale"], p["bias"]
    if labels is not None:
        scale = scale[labels][:, None, None, :]
        bias = bias[labels][:, None, None, :]
    y = (xf - mean) / jnp.sqrt(var + eps) * scale + bias
    y = {"none": lambda u: u, "relu": jax.nn.relu, "tanh": jnp.tanh,
         "lrelu": lambda u: jnp.where(u > 0, u, leak * u)}[act](y)
    return y, state


@pytest.mark.parametrize("conditional", [False, True],
                         ids=["plain", "conditional"])
@pytest.mark.parametrize("train", [True, False], ids=["train", "eval"])
@pytest.mark.parametrize("act", ["none", "relu", "lrelu", "tanh"])
def test_batch_norm_apply_is_the_plain_formula(act, train, conditional):
    """The one BN path (XLA's since PR 29) against the formula: output,
    new state, and the gradients to input, scale and bias, in float32; then
    a bfloat16 input, whose moments must still be taken in float32."""
    k = jax.random.split(jax.random.key(7), 5)
    classes = 3 if conditional else 0
    p, s = batch_norm_init(k[0], 8, num_classes=classes)
    p = {"scale": p["scale"], "bias": 0.1 * jax.random.normal(
        k[1], p["bias"].shape)}
    s = {"mean": 0.3 * jax.random.normal(k[2], (8,)),
         "var": 1.0 + 0.5 * jax.random.uniform(k[3], (8,))}
    x = 2.0 * jax.random.normal(k[4], (6, 4, 4, 8)) + 0.5
    labels = jnp.arange(6) % 3 if conditional else None

    def loss(fn):
        def f(p, x):
            y, state = fn(p, s, x, train=train, act=act, labels=labels)
            w = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(
                y.shape)
            return jnp.sum(y.astype(jnp.float32) * w), (y, state)
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)

    (_, (y, state)), (gp, gx) = loss(batch_norm_apply)(p, x)
    (_, (y_ref, state_ref)), (gp_ref, gx_ref) = loss(_bn_formula)(p, x)
    assert y.dtype == jnp.float32
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    for name in ("mean", "var"):
        np.testing.assert_allclose(state[name], state_ref[name],
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gx, gx_ref, rtol=1e-4, atol=1e-5)
    for name in ("scale", "bias"):
        np.testing.assert_allclose(gp[name], gp_ref[name], rtol=1e-4,
                                   atol=1e-4)

    xb = x.astype(jnp.bfloat16)
    yb, state_b = batch_norm_apply(p, s, xb, train=train, act=act,
                                   labels=labels)
    yb_ref, state_b_ref = _bn_formula(p, s, xb, train=train, act=act,
                                      labels=labels)
    assert yb.dtype == jnp.bfloat16
    assert state_b["mean"].dtype == jnp.float32
    # the moments see the bfloat16 input's values and float32 arithmetic:
    # they match the formula far inside bfloat16's 3 digits
    for name in ("mean", "var"):
        np.testing.assert_allclose(state_b[name], state_b_ref[name],
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(yb.astype(jnp.float32), yb_ref,
                               rtol=0.05, atol=0.08)
