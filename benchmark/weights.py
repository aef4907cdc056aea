"""Weights and initial model state, made by the benchmark from the seed.

The program and the plain reference both start from what `make_model_state`
draws, so neither takes weights the other made. The program's own
initializer leaves the attention gate `gamma` at 0 (the block is then the
identity and its kernels' results never reach the loss); here `gamma` is
drawn from [0.5, 1), as in a trained SAGAN, so that the comparison that
decides `correct` sees the flash kernels' forward and backward results.

A leaf's draw depends only on the seed, its path and its shape, so the same
values come out on one device or sharded over four.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Tree = Dict[str, Any]


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's pass 2**31)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _leaf(path: str, like, key):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    name = path.rsplit("/", 1)[-1]
    shape = like.shape
    if "/sn_" in path:                      # power-iteration start vector
        u = jax.random.normal(k, shape, jnp.float32)
        val = u / (jnp.linalg.norm(u) + 1e-12)
    elif name == "mean":
        val = jnp.zeros(shape, jnp.float32)
    elif name == "var":
        val = jnp.ones(shape, jnp.float32)
    elif name == "gamma":
        val = jax.random.uniform(k, shape, jnp.float32, 0.5, 1.0)
    elif name == "scale":
        val = 1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
    elif name in ("w", "b", "bias"):
        val = 0.02 * jax.random.normal(k, shape, jnp.float32)
    else:
        raise ValueError(f"no rule to draw leaf {path!r}")
    return val.astype(like.dtype)


def _draw(tree, prefix, key):
    return {name: (_draw(sub, f"{prefix}/{name}", key)
                   if isinstance(sub, dict)
                   else _leaf(f"{prefix}/{name}", sub, key))
            for name, sub in tree.items()}


def make_model_state(shapes: Tree, key: jax.Array) -> Tree:
    """{"params": ..., "bn": ...} drawn for the shape tree of the program's
    state (`jax.eval_shape` of its init): names, shapes and dtypes only."""
    return {"params": _draw(shapes["params"], "params", key),
            "bn": _draw(shapes["bn"], "bn", key)}
