"""The windowed flash kernels (ops/pallas_attention.py: `flash_fwd_win`,
`flash_dq_dkv_win`; the static `window` argument) beside the causal ones:
forward and gradients against a dense masked softmax at q/k 64 with v 128,
at windows of one key, smaller than, equal to and larger than a tile; a
window as long as the sequence is the causal call bit for bit; and the call
without a window lowers to the text it lowered to before the argument
existed."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.ops import pallas_attention
from dcgan_tpu.ops.pallas_attention import flash_attention

S, D, DV, SCALE = 256, 64, 128, 0.125


def qkv(dtype=jnp.float32, batch=2):
    key = jax.random.key(11)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (batch, S, n),
                                   jnp.float32).astype(dtype)
                 for i, n in enumerate((D, D, DV)))


def dense(q, k, v, window):
    s = jnp.einsum("bqd,bkd->bqk", q, k) * SCALE
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    keep = (j <= i) & (j > i - window)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@pytest.fixture
def tiles(monkeypatch):
    def set_tiles(tq, tk):
        monkeypatch.setattr(pallas_attention, "WIN_BLOCK_Q", tq)
        monkeypatch.setattr(pallas_attention, "WIN_BLOCK_K", tk)
    return set_tiles


# window 1 (the position alone), under a tile, a tile, a tile and one, over a
# q-tile and a k-tile together (plain tiles inside the band), nearly all
WINDOWS = [1, 7, 32, 33, 100, 255]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("tq,tk", [(32, 32), (64, 32), (32, 64)])
def test_forward_and_gradients_match_a_dense_band(tiles, tq, tk, window):
    tiles(tq, tk)
    q, k, v = qkv()
    w = jax.random.normal(jax.random.key(5), (2, S, DV))
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, SCALE, True, window)),
        np.asarray(dense(q, k, v, window)), rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *x: jnp.sum(
        flash_attention(*x, SCALE, True, window) * w), argnums=(0, 1, 2))(
            q, k, v)
    want = jax.grad(lambda *x: jnp.sum(dense(*x, window) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5,
                                   rtol=0, err_msg=name)


def test_bfloat16_operands(tiles):
    tiles(64, 32)
    q, k, v = qkv(jnp.bfloat16)
    want = dense(*(t.astype(jnp.float32) for t in (q, k, v)), 50)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, SCALE, True, 50)),
        np.asarray(want), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("window", [S, S + 1, 10 * S])
def test_a_window_as_long_as_the_sequence_is_the_causal_call(window):
    """Bit for bit, outputs and gradients: the band hides nothing, and the
    call runs the causal kernels themselves."""
    q, k, v = qkv()
    run = lambda *extra: jax.value_and_grad(
        lambda *x: jnp.sum(jnp.sin(flash_attention(*x, SCALE, True, *extra))),
        argnums=(0, 1, 2))(q, k, v)
    (out, grads), (out0, grads0) = run(window), run()
    assert np.array_equal(out, out0)
    for g, g0 in zip(grads, grads0):
        assert np.array_equal(g, g0)


def test_a_window_is_the_causal_masks():
    q, k, v = qkv()
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, SCALE, False, 16)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(q, k, v, SCALE, True, 0)


def lowered(causal, d, dv, *window):
    sds = [jax.ShapeDtypeStruct((2, S, n), jnp.bfloat16) for n in (d, d, dv)]
    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, 0.3, causal,
                                                *window)),
        argnums=(0, 1, 2))).lower(*sds).as_text()


def test_the_windowed_call_names_its_own_kernels():
    """`flash_` stays in the names (the `flash_` readers of
    benchmark/layer_metrics count them) and `_win` tells them apart."""
    text = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, 0.3, True, 64)),
        argnums=(0, 1, 2))).lower(*qkv(jnp.bfloat16)).as_text(debug_info=True)
    names = sorted({re.findall(r"\((\w+)\)+/pallas_call", loc)[0]
                    for loc in re.findall(r'loc\("([^"]+)"', text)
                    if loc.endswith("/pallas_call")})
    assert names == ["flash_dq_dkv_win", "flash_fwd_win"]


# sha256 (first 16 hex digits) of the lowered text of forward + backward at
# [2, 256, d] bfloat16, scale 0.3, taken on the commit BEFORE the `window`
# argument existed (004c74c, this installation's jax 0.9.0)
BEFORE = {(False, 8, 32): "d707803e1477e797",      # sagan128's attention
          (True, 192, 128): "7162ff0590bbc190",    # the latent trunk's
          (True, 128, 128): "ce67c51da1986bc4"}    # the looped trunk's


@pytest.mark.parametrize("causal,d,dv", sorted(BEFORE))
def test_without_a_window_the_call_lowers_as_before(causal, d, dv):
    """`window=None` adds no instruction to the programs of the image and
    the two older token archs: the text is the parent commit's, and naming
    the default changes nothing."""
    text = lowered(causal, d, dv)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == BEFORE[causal, d, dv]
    assert lowered(causal, d, dv, None) == text
    assert "_win" not in text
