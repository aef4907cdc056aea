"""Cross-mesh resharding restore: per-array placement onto the CURRENT
topology.

Two paths, chosen by what changed (DESIGN.md §6h's decision tree):

- **Device path** (same process count, different mesh): the Orbax/
  tensorstore read is simply DIRECTED at the new placement — the
  abstract tree's ShapeDtypeStructs carry the current NamedShardings
  (resolved by the rule engine against the current mesh), and each
  process reads exactly the bytes its new shards need. One pass, no
  staging copy.

- **Host path** (process count changed): the checkpoint's OCDBT layout
  was committed by a different process set, and a sharded device read
  under a different process census would have each process depend on
  chunk files a missing writer may never have made visible to it
  identically; instead every process restores the FULL arrays host-side
  (numpy — no device memory for the staging copy), then
  `jax.make_array_from_callback` uploads only each device's addressable
  shard of the target NamedSharding. Collective-free: every process
  performs the same local reads and puts, so the dispatch-thread
  contract is untouched.

Both paths return trees with exactly the target state's shardings, so
everything downstream of restore (warmup plan lowering, rollback
snapshots) sees the same tree it would after a same-topology restore.
"""

from __future__ import annotations

from typing import Any

Pytree = Any


def host_abstract(target_state: Pytree) -> Pytree:
    """Numpy-template abstract tree: StandardRestore hands back plain
    np.ndarrays (full arrays, host memory) for these leaves — the host
    path's staging form."""
    import jax
    import numpy as np

    return jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape, x.dtype)
        if hasattr(x, "shape") else x, target_state)


def device_abstract(target_state: Pytree) -> Pytree:
    """Sharded ShapeDtypeStruct abstract tree carrying the CURRENT
    shardings — the device path's read direction (also the same-topology
    restore's abstract; one derivation for both keeps them in lockstep)."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=getattr(x, "sharding",
                                                        None))
        if hasattr(x, "shape") else x,
        target_state)


def put_host_tree(host_tree: Pytree, target_state: Pytree) -> Pytree:
    """Host-staged full arrays -> device arrays with the target tree's
    shardings. Each device uploads only its shard (the callback slices
    the host array per addressable index), so peak device memory is the
    final footprint, not a replicated copy."""
    import jax
    import numpy as np

    def put(host, like):
        sharding = getattr(like, "sharding", None)
        arr = np.asarray(host)
        if sharding is None:
            return jax.device_put(arr)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx, _a=arr: _a[idx])
    return jax.tree_util.tree_map(put, host_tree, target_state)
