"""Weights and initial model state, made by the benchmark from the seed.

The program and the plain reference both start from what `draw_tree` draws,
so neither takes weights the other made. What every model family shares is
here: the key of a seed, and the walk over a tree of shapes in which a
leaf's draw depends only on the seed, its path and its shape, so the same
values come out on one device or sharded over four. What a leaf of a given
name is drawn from is the family's rule (`draw_leaf` of
`families/<family>.py`), which the caller hands in.
"""

from __future__ import annotations

import zlib
from typing import Any, Callable, Dict

import jax
import numpy as np

Tree = Dict[str, Any]
# what each stream of a run's seed is for: `seed_key(seed, WEIGHTS)` ...
WEIGHTS, BATCHES, STEP_KEYS, PROGRAM_INIT = 0, 1, 2, 9


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's pass 2**31)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def draw_tree(shapes: Tree, key: jax.Array, rule: Callable,
              dtype=None, prefix: str = "") -> Tree:
    """The tree of `shapes` (names, shapes and dtypes only: `jax.eval_shape`
    of the program's init) with every leaf drawn by `rule(path, shape, key)`
    from the leaf's own key, in the leaf's type (or in `dtype`: the
    reference's float32). A path is the leaf's names joined by `/`."""
    out = {}
    for name, sub in shapes.items():
        path = f"{prefix}/{name}" if prefix else name
        if isinstance(sub, dict):
            out[name] = draw_tree(sub, key, rule, dtype, path)
        else:
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            out[name] = rule(path, sub.shape, k).astype(dtype or sub.dtype)
    return out
