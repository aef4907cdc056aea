"""The selective-scan kernels (ops/pallas_scan.py: `ssm_scan_fwd`,
`ssm_scan_bwd`) in interpret mode against a plain `lax.scan` over time:
the output and all five gradients, at lengths that are and are not a
multiple of the chunk, one and several channel blocks, one and several
chunks; the chunk-boundary states; the names a recomputed block keeps; the
causal convolution against a convolution of XLA's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.ops import pallas_scan
from dcgan_tpu.ops.pallas_scan import causal_conv, selective_scan


def plain(u, dt, a, b, c, states=False):
    def step(s, xs):
        u_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, (s @ c_t, s)

    def row(u, dt, b, c):
        return jax.lax.scan(step, jnp.zeros(a.shape), (u, dt, b, c))[1]

    y, s = jax.vmap(row)(u, dt, b, c)
    return (y, s) if states else y


def draw(batch, length, d, n, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (batch, length, d)),
            jax.nn.softplus(jax.random.normal(ks[1], (batch, length, d)) - 1),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (d, n))),
            jax.random.normal(ks[3], (batch, length, n)),
            jax.random.normal(ks[4], (batch, length, n))), \
        jax.random.normal(ks[5], (batch, length, d))


# (batch, length, channels, states, chunk): two chunks and a ragged third
# over two channel blocks; one whole chunk; a length under one chunk and no
# multiple of eight; eight states (half a sublane tile pair); channels that
# are no multiple of a lane group
SHAPES = [(2, 40, 256, 16, 16), (1, 64, 128, 16, 64), (2, 21, 128, 16, 32),
          (1, 48, 128, 8, 16), (1, 24, 96, 16, 8)]


@pytest.mark.parametrize("batch,length,d,n,chunk", SHAPES)
def test_output_and_all_gradients_match_a_scan_over_time(batch, length, d, n,
                                                         chunk, monkeypatch):
    monkeypatch.setattr(pallas_scan, "SCAN_BLOCK_D", 128)
    args, w = draw(batch, length, d, n)
    np.testing.assert_allclose(
        np.asarray(selective_scan(*args, chunk)), np.asarray(plain(*args)),
        rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *x: jnp.sum(selective_scan(*x, chunk) * w),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *x: jnp.sum(plain(*x) * w),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip(("u", "dt", "a", "b", "c"), got, want):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=2e-6 * scale, rtol=0, err_msg=name)


def test_the_boundary_states_are_the_states_entering_each_chunk():
    args, _ = draw(1, 48, 128, 16, seed=3)
    _, s0 = pallas_scan._scan_fwd(*args, 16)          # [B, chunks, N, D]
    _, states = plain(*args, states=True)             # [B, L, D, N]
    assert s0.shape == (1, 3, 16, 128)
    np.testing.assert_array_equal(np.asarray(s0[:, 0]), 0.0)
    for c in (1, 2):
        np.testing.assert_allclose(
            np.asarray(s0[:, c]),
            np.asarray(jnp.swapaxes(states[:, 16 * c - 1], 1, 2)),
            rtol=1e-5, atol=1e-6)


def test_a_chunk_that_is_no_multiple_of_eight_is_refused():
    args, _ = draw(1, 32, 128, 16)
    with pytest.raises(ValueError, match="multiple of 8"):
        selective_scan(*args, 12)


def test_a_recomputed_block_that_keeps_the_names_holds_no_forward_scan():
    """Both of the forward's outputs carry a name: kept by policy, the
    recomputation runs `ssm_scan_bwd` alone; a bare checkpoint runs the
    forward twice."""
    args, w = draw(1, 32, 128, 16)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
                continue
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)

    def sites(policy):
        block = jax.checkpoint(
            lambda *x: jnp.sum(jnp.tanh(selective_scan(*x, 16)) * w),
            policy=policy)
        names = list(walk(jax.make_jaxpr(
            jax.grad(block, argnums=(0, 1)))(*args).jaxpr))
        return names.count("ssm_scan_fwd"), names.count("ssm_scan_bwd")

    keep = jax.checkpoint_policies.save_only_these_names(
        pallas_scan.SCAN_OUT_NAME, pallas_scan.SCAN_STATE_NAME)
    fwd_kept, bwd_kept = sites(keep)
    fwd_bare, bwd_bare = sites(None)
    assert (fwd_kept, bwd_kept) == (1, 1)
    assert (fwd_bare, bwd_bare) == (2, 1)


@pytest.mark.parametrize("taps", [1, 4])
def test_causal_conv_is_a_depthwise_convolution_of_the_past(taps):
    ks = jax.random.split(jax.random.key(taps), 3)
    u = jax.random.normal(ks[0], (2, 19, 24))
    w = jax.random.normal(ks[1], (taps, 24))
    b = jax.random.normal(ks[2], (24,))
    want = jax.lax.conv_general_dilated(
        u, w[:, None, :], window_strides=(1,), padding=[(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=24,
        precision=jax.lax.Precision.HIGHEST) + b
    np.testing.assert_allclose(np.asarray(causal_conv(u, w, b)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    # position t reads nothing after t
    bumped = causal_conv(u.at[:, 10].add(1.0), w, b)
    np.testing.assert_array_equal(np.asarray(bumped[:, :10]),
                                  np.asarray(causal_conv(u, w, b)[:, :10]))
