"""The one `jax.shard_map` call site.

Every explicit-collective layer (the shard_map backend, ring attention,
per-shard Pallas BN, the fused stage blocks) routes through `shard_map`
here, and hygiene rule DCG003 (analysis/hygiene.py) keeps it that way: the
replication check is switched in one place, under one keyword.
"""

from __future__ import annotations


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = True):
    """`jax.shard_map` with its replication check (`check_vma`) named
    `check`."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
