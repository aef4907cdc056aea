"""The flash forward in the transposed domain (ops/pallas_attention.py::
_fwd_core, kernel `flash_fwd`: score tile with keys on sublanes and queries
on lanes, softmax state in [1, TQ] rows, several folds per loop iteration,
o^T and lse out lane-dense) against dense attention, its hand-off to the
backward kernel against the row-major formulas it replaced, the ring
composition over it, and the one form every width takes, read from the
kernel's name in the lowered text.

Tier-1 like tests/test_flash_backward.py: small shapes in interpret mode,
more than one tile on both axes.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from dcgan_tpu.ops import pallas_attention
from dcgan_tpu.ops.attention import full_attention
from dcgan_tpu.ops.pallas_attention import (
    flash_attention,
    ring_flash_attention,
)
from dcgan_tpu.utils.backend import shard_map

# (S, TQ, TK): 4 x 8, 2 x 2 and 1 x 1 tiles, then a q-tile of 8 k-tiles (four
# folds per loop iteration, two iterations) and a k-tile of 4 q-tiles (one)
TILES = [(256, 64, 32), (128, 64, 64), (128, 1024, 1024), (256, 128, 16),
         (256, 32, 128)]
WIDTHS = [(8, 32), (16, 16)]


def qkv(S, d, dv, dtype=jnp.float32, B=2):
    key = jax.random.key(7 * S + d)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (B, S, n),
                                   jnp.float32).astype(dtype)
                 for i, n in enumerate((d, d, dv)))


def swap(t):
    return jnp.swapaxes(t, 1, 2)


def dense_scores(q, k, scale, causal):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[1:], bool)), s, -jnp.inf)
    return s


def dense(q, k, v, scale, causal):
    if not causal:
        return full_attention(q, k, v, scale=scale)
    p = jax.nn.softmax(dense_scores(q, k, scale, True), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))


@pytest.fixture
def tiles(monkeypatch):
    def set_tiles(tq, tk):
        monkeypatch.setenv("DCGAN_FLASH_TQ", str(tq))
        monkeypatch.setenv("DCGAN_FLASH_TK", str(tk))
    return set_tiles


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S,tq,tk", TILES)
@pytest.mark.parametrize("d,dv", WIDTHS)
def test_transposed_forward_and_lse_match_dense(tiles, S, tq, tk, d, dv,
                                                causal):
    tiles(tq, tk)
    q, k, v = qkv(S, d, dv)
    scale = d ** -0.5
    outT, lse = pallas_attention._fwd_core(swap(q), k, swap(v), scale,
                                             causal)
    assert outT.shape == (2, dv, S) and outT.dtype == jnp.float32
    assert lse.shape == (2, 1, S) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(swap(outT)),
                               np.asarray(dense(q, k, v, scale, causal)),
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(lse[:, 0]),
        np.asarray(jax.nn.logsumexp(dense_scores(q, k, scale, causal),
                                    axis=-1)), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S,tq,tk", TILES[::2])
def test_transposed_forward_bfloat16(tiles, S, tq, tk, causal):
    tiles(tq, tk)
    q, k, v = qkv(S, 8, 32, jnp.bfloat16)
    scale = 8 ** -0.5
    out = flash_attention(q, k, v, scale, causal)
    assert out.dtype == jnp.float32 and out.shape == v.shape
    # the reference sees the same bf16-rounded inputs, in float32
    ref = dense(*(t.astype(jnp.float32) for t in (q, k, v)), scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-2)


def test_extreme_logits_stay_finite():
    q, k, v = qkv(128, 8, 32)
    out = flash_attention(q * 100.0, k, v, 8 ** -0.5)
    assert np.all(np.isfinite(np.asarray(out)))


def grads(attention, q, k, v):
    """Gradients of a loss whose cotangent differs from row to row."""
    w = jnp.linspace(0.5, 1.5, v.shape[-1])

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v).astype(jnp.float32) ** 2 * w)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S,tq,tk", TILES)
@pytest.mark.parametrize("d,dv", WIDTHS)
def test_gradients_through_the_new_residuals_match_dense(tiles, S, tq, tk, d,
                                                         dv, causal):
    """The forward hands q^T, o^T and lse [B, 1, S] to the backward as they
    are; the gradients hold to the tolerance the row-major hand-off held."""
    tiles(tq, tk)
    q, k, v = qkv(S, d, dv)
    scale = d ** -0.5
    want = grads(lambda q, k, v: dense(q, k, v, scale, causal), q, k, v)
    got = grads(lambda q, k, v: flash_attention(q, k, v, scale, causal),
                q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), want, got):
        assert b.dtype == a.dtype and b.shape == a.shape, name
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-5 if causal else 2e-5,
                                   err_msg=name)


def test_bfloat16_gradients_through_the_new_residuals(tiles):
    tiles(64, 32)
    q, k, v = qkv(256, 8, 32, jnp.bfloat16)
    scale = 8 ** -0.5
    want = grads(lambda q, k, v: full_attention(q, k, v, scale=scale),
                 *(t.astype(jnp.float32) for t in (q, k, v)))
    got = grads(lambda q, k, v: flash_attention(q, k, v, scale), q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), want, got):
        assert b.dtype == jnp.bfloat16, name
        err = np.abs(np.asarray(b, np.float32) - np.asarray(a))
        assert err.max() <= 1e-2 * max(1.0, np.abs(np.asarray(a)).max()), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_backward_inputs_equal_the_row_major_formulas(tiles, dtype):
    """What the parent built in its backward pass from row-major residuals
    (q transposed, g cast once and transposed, lse relaid to [B, 1, S],
    delta = rowsum(g * out)), from the kernel's own residuals instead:
    q^T and lse are passed through, only g is transposed."""
    tiles(64, 32)
    q, k, v = qkv(256, 8, 32, dtype)
    qT = swap(q)
    outT, lse = pallas_attention._fwd_core(qT, k, swap(v), 0.3)
    g = jax.random.normal(jax.random.key(3), (2, 256, 32))
    got = pallas_attention._bwd_inputs(qT, outT, lse, g)
    assert got[0] is qT and got[2] is lse
    want = (swap(q), swap(g.astype(dtype)), lse,
            jnp.sum(g * swap(outT), axis=-1).reshape(2, 1, 256))
    for name, a, b in zip(("qT", "doT", "lse", "delta"), want, got):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32), atol=1e-5,
                                   err_msg=name)
    # and the row-major face builds the same from `_fwd_impl`'s outputs
    out, lse2 = pallas_attention._fwd_impl(q, k, v, 0.3)
    for a, b in zip(got, pallas_attention._bwd_stats(q, out, lse2, g)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# --- one form for every width, read from the kernel's name ------------------------

def kernel_names(d, dv, causal=False):
    q, k, v = qkv(128, d, dv, jnp.bfloat16)
    text = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, 0.3, causal)),
        argnums=(0, 1, 2))).lower(q, k, v).as_text(debug_info=True)
    return sorted(re.findall(r"\((\w+)\)+/pallas_call", loc)[0]
                  for loc in set(re.findall(r'loc\("([^"]+)"', text))
                  if loc.endswith("/pallas_call"))


@pytest.mark.parametrize("d,dv,causal", [
    (8, 32, False),             # sagan128's attention
    (64, 64, False),
    (192, 128, True)])          # the token trunk's
def test_every_width_takes_the_one_forward(d, dv, causal):
    """No shape rule is left to test: the chip sweep found the transposed
    forward faster at every width that has run (CHANGES.md, PR 28), so the
    row form went. The name is what `flash_fwd_ms`, `flash_fwd_calls` and
    the flash rooflines of benchmark/layer_metrics read."""
    assert kernel_names(d, dv, causal) == ["flash_dq_dkv", "flash_fwd"]
    src = open(pallas_attention.__file__).read()
    assert re.findall(r'name="(\w*flash_fwd\w*)"', src) == ["flash_fwd"]


@pytest.mark.parametrize("S,tq,tk,unroll", [
    (4096, None, None, 4), (1024, None, None, 4), (256, 128, 16, 4),
    (256, 64, 32, 2), (256, 32, 128, 1), (192, 96, 48, 2),
    (192, 96, 32, 1)])
def test_folds_per_loop_iteration_divide_every_loop_bound(
        monkeypatch, S, tq, tk, unroll):
    """`_fwd_unroll` is a divisor of TQ/TK (1 where TK does not divide TQ),
    so the whole sequence's tile count and the causal bounds of every
    q-tile, as `_fwd_kernel` computes them, are whole multiples of it."""
    for name, val in (("DCGAN_FLASH_TQ", tq), ("DCGAN_FLASH_TK", tk)):
        if val is not None:
            monkeypatch.setenv(name, str(val))
    tq, tk = pallas_attention._blocks(S, pallas_attention.FWD_BLOCK_Q,
                                      pallas_attention.FWD_BLOCK_K)
    assert pallas_attention._fwd_unroll(tq, tk) == unroll
    assert (S // tk) % unroll == 0
    for q0 in range(0, S, tq):
        assert ((q0 + 1) // tk) % unroll == 0
        assert ((q0 + tq + tk - 1) // tk) % unroll == 0


def test_default_tiles():
    """The forward has its own tile constants (its q-tile rides the lane
    axis); the backward's are as they were."""
    assert pallas_attention._blocks(
        4096, pallas_attention.FWD_BLOCK_Q,
        pallas_attention.FWD_BLOCK_K) == (2048, 256)
    assert pallas_attention.FWD_UNROLL == 4
    assert pallas_attention._blocks(
        4096, pallas_attention.BWD_BLOCK_Q) == (1024, 1024)
    assert pallas_attention.ATTN_GEN == 5


# --- ring x flash over the transposed fold -----------------------------------------

def ring(n, scale):
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(1, n),
                ("data", "model"))
    spec = P("data", "model", None)
    # check=False: pallas_call outputs carry no vma annotations
    return shard_map(
        functools.partial(ring_flash_attention, scale=scale,
                          axis_name="model", n_shards=n),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check=False)


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("d,dv", [(8, 32), (16, 160)])
def test_ring_flash_is_exact(n, d, dv):
    """Partials merge in the kernel's layout ([B, dv, S] with lse
    [B, 1, S]) and swap back once; gradients go home with their blocks as
    before."""
    q, k, v = qkv(128, d, dv)
    scale = d ** -0.5
    rf = ring(n, scale)
    np.testing.assert_allclose(
        np.asarray(rf(q, k, v)),
        np.asarray(full_attention(q, k, v, scale=scale)), atol=2e-5)
    want = grads(lambda q, k, v: full_attention(q, k, v, scale=scale),
                 q, k, v)
    got = grads(rf, q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-5,
                                   err_msg=name)
