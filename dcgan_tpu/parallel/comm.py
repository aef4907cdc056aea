"""Collective overlap plane (ISSUE 20, DESIGN §6n).

PR 13's ZeRO hooks made the collective stream explicit but naive: one
small reduce-scatter / all-gather per LEAF (shard_map@zero2 censuses
16 + 16 per step), and stage 3's `gather_params` materializes the whole
tree before the first conv — exactly the latency-bound regime ParaGAN
(arXiv:2411.03999) identifies. This module restructures the wire plan
without touching the math:

- **Bucketed collectives** (`bucketed_reduce` / `bucketed_gather`,
  `--comm_overlap bucket`): the per-leaf trees are packed into
  dtype-grouped, size-capped flat buffers (`elastic/rules.py::
  zero_bucket_plan` derives the plan from the SAME rule table that
  placed the shards, so layout and wire can never disagree) and each
  bucket rides ONE dim-0 tiled collective. The packing is shard-major —
  leaf `g` with scatter dim `d` contributes `moveaxis(g, d, 0)
  .reshape(n_shards, -1)` rows, buckets concatenate along the row axis —
  so a single `psum_scatter(..., scatter_dimension=0, tiled=True)`
  hands every shard exactly the rows its per-leaf collective would
  have. Sum / divide are elementwise and data movement is bijective,
  so the result is BIT-exact vs the per-leaf plan (pinned by
  tests/test_comm_overlap.py), while the census shrinks from one op
  per leaf to one op per bucket (pinned by the `@overlap` manifest
  rows).
- **Layer-ahead gather prefetch** (`staged_gather`,
  `--comm_overlap prefetch`, ZeRO-3 only): instead of one up-front
  full-tree gather, params are gathered per top-level layer with a
  one-stage-ahead `lax.optimization_barrier` chain — releasing layer
  i's params to compute is tied to layer i+1's gather being issued, so
  XLA's latency-hiding scheduler overlaps gather i+1 with compute i.
  The barrier is the identity on values: bit-exact, same all-gather
  census as `off`.
- **Backward-overlapped reduce-scatter** falls out of bucketing: each
  bucket's psum_scatter depends only on ITS leaves' cotangents, so the
  scheduler issues it as soon as that slice of the backward completes
  rather than after the full walk. On gspmd the partitioner owns
  collective placement; `maybe_apply_xla_overlap_flags` arms libtpu's
  async-collective scheduler flags so its inserted collectives overlap
  too.

Module-level imports stay jax-free: the CLI applies the XLA flags
before jax's backend initializes, and the analyzer imports this module
on lint passes.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Any, Callable, Dict, List, Sequence, Tuple

Pytree = Any

#: Async-collective scheduler flags for the gspmd backend's half of the
#: backward-overlap story (DESIGN §6n): let XLA fuse collectives into
#: async start/done pairs and float compute between them. They are
#: libtpu's own flags and travel in ITS variable: jaxlib's XLA_FLAGS parser
#: does not know them and aborts the process on an unknown flag — on the
#: chip too (seen on a v5e: "Unknown flags in XLA_FLAGS" at client init),
#: while libtpu takes all four from LIBTPU_INIT_ARGS.
LIBTPU_FLAGS_VAR = "LIBTPU_INIT_ARGS"
XLA_OVERLAP_FLAGS: Tuple[str, ...] = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_reduce=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def maybe_apply_xla_overlap_flags(env=None, *, platform: str = "",
                                  force: bool = False) -> Tuple[str, ...]:
    """Append XLA_OVERLAP_FLAGS to env["LIBTPU_INIT_ARGS"] when the run
    will actually land on TPU, skipping flags whose key the user already
    set. Two gates, BOTH required, so that "armed" is only ever said of
    a run libtpu will serve: the requested platform (the explicit
    `platform` arg, else env["JAX_PLATFORMS"]; "" = auto) must not name
    a non-TPU backend, and libtpu must be importable. Returns the tuple
    of flags actually added. `force=True` bypasses both probes for tests
    driving a fake env dict. Must run before jax initializes its
    backend."""
    env = os.environ if env is None else env
    if not force:
        requested = (platform or env.get("JAX_PLATFORMS", "")).lower()
        if requested and "tpu" not in requested:
            return ()
        if importlib.util.find_spec("libtpu") is None:
            return ()
    existing = env.get(LIBTPU_FLAGS_VAR, "")
    added = tuple(f for f in XLA_OVERLAP_FLAGS
                  if f.split("=", 1)[0] not in existing)
    if added:
        joined = " ".join(added)
        env[LIBTPU_FLAGS_VAR] = f"{existing} {joined}".strip()
    return added


# -- pack / unpack -----------------------------------------------------------
#
# Shard-major layout. For a leaf of shape S with scatter dim d over an
# n-way axis (S[d] % n == 0, guaranteed by rules.zero_insert's
# divisibility guard), define moved = moveaxis(leaf, d, 0):
#
#   scatter packing: moved.reshape(n, -1) — row k is the flat of the
#     block the per-leaf psum_scatter would hand shard k. Buckets
#     concatenate rows along axis 1, flatten C-order, and ONE
#     psum_scatter(scatter_dimension=0, tiled=True) returns each shard
#     its own (seg_total,) row.
#   gather packing: the local shard's moved block flattens to one
#     segment; ONE all_gather(axis=0, tiled=True) stacks every shard's
#     segment, and reshape(n, seg_total) recovers the per-shard rows.
#
# Both directions are pure reshapes/transposes — bijective data
# movement, no arithmetic — so round-trip equality is exact by
# construction (unit-tested leaf-for-leaf in test_comm_overlap.py).

def pack_scatter(leaves: Sequence, dims: Sequence[int],
                 idxs: Sequence[int], n_shards: int):
    """Pack full (unreduced) leaves of one bucket into the shard-major
    flat buffer. Returns (buf, segs) where segs rows are
    (leaf_index, row_width, moved_shape) for `unpack_scatter`."""
    import jax.numpy as jnp

    rows, segs = [], []
    for i in idxs:
        moved = jnp.moveaxis(leaves[i], dims[i], 0)
        r = moved.reshape(n_shards, -1)
        segs.append((i, int(r.shape[1]), tuple(moved.shape)))
        rows.append(r)
    return jnp.concatenate(rows, axis=1).reshape(-1), segs


def unpack_scatter(seg_buf, segs, n_shards: int, dims: Sequence[int],
                   out: List) -> None:
    """Split this shard's reduced (seg_total,) row back into the
    per-leaf LOCAL blocks (shape = leaf shape with dim d divided by
    n_shards), writing them into `out` at each leaf's index."""
    import jax.numpy as jnp

    o = 0
    for i, width, moved_shape in segs:
        local = seg_buf[o:o + width]
        o += width
        local_moved = local.reshape(
            (moved_shape[0] // n_shards,) + tuple(moved_shape[1:]))
        out[i] = jnp.moveaxis(local_moved, 0, dims[i])


def pack_gather(leaves: Sequence, dims: Sequence[int],
                idxs: Sequence[int]):
    """Pack the LOCAL shard blocks of one bucket into a flat segment.
    Returns (seg, segs) with segs rows (leaf_index, width,
    local_moved_shape) for `unpack_gather`."""
    import jax.numpy as jnp

    flats, segs = [], []
    for i in idxs:
        moved = jnp.moveaxis(leaves[i], dims[i], 0)
        flat = moved.reshape(-1)
        segs.append((i, int(flat.shape[0]), tuple(moved.shape)))
        flats.append(flat)
    return jnp.concatenate(flats), segs


def unpack_gather(gathered, segs, n_shards: int, dims: Sequence[int],
                  out: List) -> None:
    """Split the all-gathered (n_shards * seg_total,) buffer back into
    FULL per-leaf arrays, writing them into `out` at each leaf's
    index."""
    import jax.numpy as jnp

    total = sum(w for _, w, _ in segs)
    view = gathered.reshape(n_shards, total)
    o = 0
    for i, width, moved_shape in segs:
        cols = view[:, o:o + width]
        o += width
        full = cols.reshape(
            (n_shards * moved_shape[0],) + tuple(moved_shape[1:]))
        out[i] = jnp.moveaxis(full, 0, dims[i])


# -- bucketed hook bodies ----------------------------------------------------

def bucketed_reduce(grads: Pytree, dims: Pytree,
                    plan: Sequence[Sequence[int]], *, axis_name: str,
                    n_shards: int) -> Pytree:
    """Drop-in body for ZeroHooks.reduce_grads: one psum_scatter per
    BUCKET (replicated leaves, dim == -1, keep their per-leaf pmean —
    they are outside every bucket by plan construction). Bit-exact vs
    the per-leaf plan: the packed psum_scatter sums the same operands
    elementwise and the /n_shards is the same elementwise divide."""
    import jax
    from jax import lax

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    dleaves = jax.tree_util.tree_leaves(dims)
    out = list(leaves)
    in_bucket = {i for b in plan for i in b}
    for i, (g, d) in enumerate(zip(leaves, dleaves)):
        if i in in_bucket:
            continue
        out[i] = (lax.pmean(g, axis_name) if d < 0 else
                  lax.psum_scatter(g, axis_name, scatter_dimension=d,
                                   tiled=True) / n_shards)
    for b in plan:
        buf, segs = pack_scatter(leaves, dleaves, b, n_shards)
        red = lax.psum_scatter(buf, axis_name, scatter_dimension=0,
                               tiled=True) / n_shards
        unpack_scatter(red, segs, n_shards, dleaves, out)
    return jax.tree_util.tree_unflatten(treedef, out)


def bucketed_gather(tree: Pytree, dims: Pytree,
                    plan: Sequence[Sequence[int]], *, axis_name: str,
                    n_shards: int) -> Pytree:
    """Drop-in body for ZeroHooks.gather_updates: one all_gather per
    BUCKET (replicated leaves pass through untouched). Pure data
    movement — bit-exact by construction."""
    import jax
    from jax import lax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    dleaves = jax.tree_util.tree_leaves(dims)
    out = list(leaves)
    in_bucket = {i for b in plan for i in b}
    for i, (x, d) in enumerate(zip(leaves, dleaves)):
        if i in in_bucket:
            continue
        out[i] = x if d < 0 else lax.all_gather(x, axis_name, axis=d,
                                                tiled=True)
    for b in plan:
        seg, segs = pack_gather(leaves, dleaves, b)
        g = lax.all_gather(seg, axis_name, axis=0, tiled=True)
        unpack_gather(g, segs, n_shards, dleaves, out)
    return jax.tree_util.tree_unflatten(treedef, out)


def staged_gather(params: Pytree,
                  gather_stage: Callable[[str], Pytree]) -> Pytree:
    """ZeRO-3 layer-ahead gather prefetch: walk the net's top-level
    layer dict in insertion order (== the model's stage walk), gather
    each layer with `gather_stage(name)`, and chain stages with a
    one-ahead `lax.optimization_barrier` — layer i's gathered params
    are released to compute only once layer i+1's gather is in flight,
    which is the dependence XLA's latency-hiding scheduler needs to
    overlap gather i+1 with compute i. optimization_barrier is the
    identity on values, so the result is bit-exact vs the up-front
    full-tree gather. Non-dict or single-layer trees degrade to the
    plain per-stage gather (nothing to prefetch ahead of)."""
    from jax import lax

    if not isinstance(params, dict) or len(params) < 2:
        if isinstance(params, dict):
            return {n: gather_stage(n) for n in params}
        return gather_stage(None)
    names = list(params)
    out: Dict[str, Pytree] = {}
    cur = gather_stage(names[0])
    for i, name in enumerate(names):
        nxt = gather_stage(names[i + 1]) if i + 1 < len(names) else None
        if nxt is not None:
            cur, nxt = lax.optimization_barrier((cur, nxt))
        out[name] = cur
        cur = nxt
    return out
