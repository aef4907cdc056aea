"""The fused flash backward (ops/pallas_attention.py::_bwd_core, one kernel
`flash_dq_dkv`): dQ, dK and dV against `jax.grad` of the dense reference.

Tier-1 on purpose (tests/test_pallas_attention.py is marked slow): small
shapes in interpret mode, with MORE THAN ONE tile on both axes, so the dQ
accumulator that lives across the sequential k-tile grid axis and the
dK^T / dV^T values carried across the q-tile loop are both exercised.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.ops import pallas_attention
from dcgan_tpu.ops.attention import full_attention
from dcgan_tpu.ops.pallas_attention import flash_attention

NAMES = ("dq", "dk", "dv")


def qkv(S, d, dv, dtype=jnp.float32, B=2):
    key = jax.random.key(S + d)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (B, S, n),
                                   jnp.float32).astype(dtype)
                 for i, n in enumerate((d, d, dv)))


def grads(attention, q, k, v):
    """Gradients of a loss whose cotangent differs from row to row."""
    w = jnp.linspace(0.5, 1.5, v.shape[-1])

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v).astype(jnp.float32) ** 2 * w)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.fixture
def tiles(monkeypatch):
    def set_tiles(tq, tk):
        monkeypatch.setenv("DCGAN_FLASH_TQ", str(tq))
        monkeypatch.setenv("DCGAN_FLASH_TK", str(tk))
    return set_tiles


# (S, TQ, TK): 4 x 8, 2 x 2 and 1 x 1 tiles; 192 has no 128-multiple tile
@pytest.mark.parametrize("S,tq,tk", [(256, 64, 32), (128, 64, 64),
                                     (192, 96, 48), (128, 1024, 1024)])
@pytest.mark.parametrize("d,dv", [(8, 32), (16, 16)])
def test_float32_gradients_match_dense(tiles, S, tq, tk, d, dv):
    tiles(tq, tk)
    q, k, v = qkv(S, d, dv)
    scale = d ** -0.5
    ref = grads(lambda q, k, v: full_attention(q, k, v, scale=scale), q, k, v)
    got = grads(lambda q, k, v: flash_attention(q, k, v, scale), q, k, v)
    for name, a, b in zip(NAMES, ref, got):
        assert b.dtype == jnp.float32 and b.shape == a.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("S,tq,tk", [(256, 64, 32), (128, 1024, 1024)])
def test_bfloat16_gradients_match_dense(tiles, S, tq, tk):
    tiles(tq, tk)
    q, k, v = qkv(S, 8, 32, jnp.bfloat16)
    scale = 8 ** -0.5
    # the reference sees the same bf16-rounded inputs, in float32
    ref = grads(lambda q, k, v: full_attention(q, k, v, scale=scale),
                *(t.astype(jnp.float32) for t in (q, k, v)))
    got = grads(lambda q, k, v: flash_attention(q, k, v, scale), q, k, v)
    for name, a, b in zip(NAMES, ref, got):
        assert b.dtype == jnp.bfloat16, name   # cotangents take the inputs'
        err = np.abs(np.asarray(b, np.float32) - np.asarray(a))
        assert err.max() <= 1e-2 * max(1.0, np.abs(np.asarray(a)).max()), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_dtype_float32_is_the_rings_contract(tiles, dtype):
    """`ring_flash_attention` sums per-hop gradients across hops and asks
    each hop for float32, whatever the operands are."""
    tiles(64, 32)
    S, d, dv = 256, 8, 32
    q, k, v = qkv(S, d, dv, dtype)
    scale = d ** -0.5
    out, lse = pallas_attention._fwd_impl(q, k, v, scale)
    g = jnp.ones_like(out)
    stats = pallas_attention._bwd_stats(q, out, lse, g)
    plain = pallas_attention._bwd_core(scale, k, v, *stats)
    wide = pallas_attention._bwd_core(scale, k, v, *stats,
                                      grad_dtype=jnp.float32)
    for name, a, b, x in zip(NAMES, plain, wide, (q, k, v)):
        assert a.dtype == dtype and b.dtype == jnp.float32, name
        assert a.shape == b.shape == x.shape, name
        # the same float32 accumulator, rounded once at the end or not at all
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b.astype(dtype)), name)


def test_one_backward_kernel_in_the_lowered_gradient():
    q, k, v = qkv(128, 8, 32)
    text = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, 0.3)),
        argnums=(0, 1, 2))).lower(q, k, v).as_text(debug_info=True)
    calls = {loc for loc in re.findall(r'loc\("([^"]+)"', text)
             if loc.endswith("/pallas_call")}
    assert {re.findall(r"flash_\w+", loc)[-1] for loc in calls} == {
        "flash_fwd", "flash_dq_dkv"}
    assert len(calls) == 2


def test_default_backward_tiles():
    """The backward's q-tile is its own constant; the k-tile is shared with
    the forward, and the DCGAN_FLASH_* overrides reach both."""
    assert pallas_attention._blocks(4096) == (256, 1024)
    assert pallas_attention._blocks(
        4096, pallas_attention.BWD_BLOCK_Q) == (1024, 1024)
    assert pallas_attention._blocks(
        512, pallas_attention.BWD_BLOCK_Q) == (512, 512)


# --- the causal path (a static argument; tiles above the diagonal skipped) ------

def dense_causal(q, k, v, scale):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    keep = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))


# (S, TQ, TK): q-tiles inside one k-tile (the shipped 256/1024 ratio), k-tiles
# inside one q-tile, square tiles (the backward's 1024/1024), one tile
@pytest.mark.parametrize("S,tq,tk", [(256, 32, 128), (256, 128, 32),
                                     (256, 64, 64), (128, 1024, 1024)])
@pytest.mark.parametrize("d,dv", [(192, 128), (8, 32)])
def test_causal_forward_and_gradients_match_dense_masked(tiles, S, tq, tk,
                                                         d, dv):
    """The latent-attention widths (q/k 192: more than one lane tile; v
    128) and the narrow ones, against dense masked attention: the forward's
    k-loop ends at the diagonal, the backward's q-loop starts at it, and
    only the tiles the diagonal crosses build a mask."""
    tiles(tq, tk)
    q, k, v = qkv(S, d, dv)
    scale = d ** -0.5
    out = flash_attention(q, k, v, scale, True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_causal(q, k, v, scale)),
                               atol=2e-5)
    # the first query sees the first key alone
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(v[:, 0]),
                               atol=1e-6)
    ref = grads(lambda q, k, v: dense_causal(q, k, v, scale), q, k, v)
    got = grads(lambda q, k, v: flash_attention(q, k, v, scale, True), q, k, v)
    for name, a, b in zip(NAMES, ref, got):
        assert b.dtype == jnp.float32 and b.shape == a.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-5,
                                   err_msg=name)


def test_causal_is_static_and_leaves_the_full_kernels_alone():
    """`causal` is a static argument: the non-causal call traces a kernel
    with no mask in it (no `iota`, no `select_n`, its loops from 0), as it
    did before the argument existed; the causal one builds its mask from
    `iota` in the diagonal tiles only."""
    q, k, v = qkv(128, 192, 128, jnp.bfloat16)

    def text(causal):
        f = (lambda q, k, v: flash_attention(q, k, v, 1.0, True)) if causal \
            else (lambda q, k, v: flash_attention(q, k, v, 1.0))
        return str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(f(q, k, v)), argnums=(0, 1, 2)))(q, k, v))

    full, causal = text(False), text(True)
    assert "iota" not in full and "select_n" not in full
    assert "iota" in causal and "select_n" in causal
    for t in (full, causal):
        assert len(re.findall(r"name=flash_fwd\b", t)) == 1
        assert len(re.findall(r"name=flash_dq_dkv\b", t)) == 1
