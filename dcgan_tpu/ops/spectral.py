"""Spectral normalization (Miyato et al. 2018, arXiv:1802.05957) as explicit
functional state.

The reference has no Lipschitz control at all — its discriminator is the raw
DCGAN stack (distriubted_model.py:114-128). Spectral norm is the modern
stabilizer the SAGAN family (arXiv:1805.08318) is defined with: every weight
is divided by its largest singular value, estimated by one power-iteration
step per training step. Torch/TF keep the power-iteration vector `u` as
hidden mutable module state; here it is an explicit state leaf threaded
through apply exactly like BatchNorm's running moments (ops/norm.py) — no
hidden side effects, checkpointed with everything else, replicated under the
mesh (it is a tiny per-layer vector).

Gradient convention (matching the paper and the torch implementation): the
power-iteration vectors are stop-gradiented, but sigma = v^T W u keeps W
live, so d(W/sigma)/dW includes the -W·(dsigma/dW)/sigma^2 term.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _l2n(x: jax.Array, eps: float) -> jax.Array:
    return x / (jnp.linalg.norm(x) + eps)


def spectral_u_init(key, out_dim: int, *, dtype=jnp.float32) -> jax.Array:
    """Unit-norm power-iteration start vector, one per normalized weight."""
    return _l2n(jax.random.normal(key, (out_dim,), jnp.float32),
                1e-12).astype(dtype)


@jax.named_scope("sn")
def spectral_normalize(w: jax.Array, u: jax.Array, *, train: bool,
                       n_iter: int = 1, eps: float = 1e-12
                       ) -> Tuple[jax.Array, jax.Array]:
    """Returns (w / sigma_max_estimate, u_new).

    `w` is any-rank weight; its last axis is the output dim ([in, out]
    linear, [h, w, in, out] conv — both reshape to [N, out] for the power
    iteration, torch's convention transposed). Both modes run `n_iter`
    power-iteration steps from the stored u to estimate sigma; train=True
    persists the advanced u into the returned state, train=False returns
    the stored u unchanged (the BN train/eval contract — repeated eval
    applies are idempotent).
    """
    out_dim = w.shape[-1]
    w2d = w.astype(jnp.float32).reshape(-1, out_dim)     # [N, out]
    w_sg = lax.stop_gradient(w2d)
    u_c = lax.stop_gradient(u.astype(jnp.float32))

    def one_iter(u_i, _):
        v_i = _l2n(w_sg @ u_i, eps)          # [N]
        u_i = _l2n(w_sg.T @ v_i, eps)        # [out]
        return u_i, None

    u_new, _ = lax.scan(one_iter, u_c, None, length=n_iter)
    v = _l2n(w_sg @ u_new, eps)
    u_new = lax.stop_gradient(u_new)
    v = lax.stop_gradient(v)
    # sigma through the LIVE weight: the normalization's own gradient term
    sigma = v @ (w2d @ u_new)
    w_sn = (w2d / sigma).reshape(w.shape).astype(w.dtype)
    return w_sn, (u_new if train else
                  lax.stop_gradient(u.astype(jnp.float32))).astype(u.dtype)
