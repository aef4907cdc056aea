"""The eval job: stream real-data and generator features once, score FID
(BASELINE.json north star: FID-50k parity) and optionally KID from the same
pass.

Layout mirrors the training driver: the sampler is the mesh-sharded
`ParallelTrain.sample` (generation fans out over the data axis), features are
extracted on device batch-by-batch, and only [D] / [D, D] moment statistics —
plus a bounded KID reservoir when enabled — live on host. 50k samples at
batch 256 is ~200 device round trips of [B, D] floats — negligible next to
generation itself.
"""

from __future__ import annotations

from typing import Callable, Iterable, Optional

import jax
import numpy as np

from dcgan_tpu.evals.features import FeatureFn, make_random_feature_fn
from dcgan_tpu.evals.fid import StreamingStats, frechet_distance
from dcgan_tpu.evals.kid import FeaturePool, kid_score


def stats_from_batches(feature_fn: FeatureFn, batches: Iterable,
                       num_examples: int, feature_dim: int,
                       pool: Optional[FeaturePool] = None) -> StreamingStats:
    """Fold image batches ([B,H,W,C] in [-1,1]) into feature statistics until
    `num_examples` have been consumed; the last batch is trimmed to land
    exactly on the target count. `pool`, if given, reservoir-samples the same
    features for KID."""
    stats = StreamingStats(feature_dim)
    for batch in batches:
        take = min(int(batch.shape[0]), num_examples - stats.n)
        feats = jax.device_get(feature_fn(batch[:take]))
        stats.update(feats)
        if pool is not None:
            pool.update(feats)
        if stats.n >= num_examples:
            break
    if stats.n < num_examples:
        raise ValueError(
            f"data stream exhausted at {stats.n}/{num_examples} examples")
    return stats


def generator_stats(sample_fn: Callable, feature_fn: FeatureFn,
                    feature_dim: int, *, num_samples: int, batch_size: int,
                    z_dim: int, seed: int = 0, num_classes: int = 0,
                    pool: Optional[FeaturePool] = None) -> StreamingStats:
    """Stream `num_samples` generated images into feature statistics.

    `sample_fn(z[, labels]) -> images` is the EMA-stat sampler path
    (ParallelTrain.sample / sampler_apply). z is drawn U(-1,1) like training
    (image_train.py:151); labels cycle through the classes when conditional.
    """
    stats = StreamingStats(feature_dim)
    base = jax.random.key(seed)
    i = 0
    while stats.n < num_samples:
        z = jax.random.uniform(jax.random.fold_in(base, i),
                               (batch_size, z_dim), minval=-1.0, maxval=1.0)
        if num_classes:
            labels = (np.arange(i * batch_size, (i + 1) * batch_size)
                      % num_classes)
            images = sample_fn(z, jax.numpy.asarray(labels))
        else:
            images = sample_fn(z)
        take = min(batch_size, num_samples - stats.n)
        feats = jax.device_get(feature_fn(images[:take]))
        stats.update(feats)
        if pool is not None:
            pool.update(feats)
        i += 1
    return stats


def _allgather_f64(x: np.ndarray) -> np.ndarray:
    """process_allgather that PRESERVES float64: device_put canonicalizes
    f64 -> f32 without jax_enable_x64, which would silently corrupt the
    moment accumulators (finalize()'s covariance is a cancellation-prone
    subtraction that needs the full 52-bit mantissa at 50k samples). The
    array crosses the wire as its uint32 bit pattern instead."""
    from jax.experimental import multihost_utils as mh

    bits = np.ascontiguousarray(np.asarray(x, np.float64)).view(np.uint32)
    return np.ascontiguousarray(
        np.asarray(mh.process_allgather(bits))).view(np.float64)


def _norm_npz(path: str) -> str:
    """np.savez APPENDS '.npz' to extensionless paths; normalize up front so
    the save path and the existence check can never disagree."""
    return path if path.endswith(".npz") else path + ".npz"


def real_side_to_npz(path: str, stats: StreamingStats,
                     pool: Optional[FeaturePool] = None) -> None:
    """Persist real-side statistics (raw accumulators, not finalized
    moments, so merging/extending later stays exact; plus the KID reservoir
    when present). The standard precomputed-real-statistics pattern of FID
    tooling: the real pass over 50k images is paid once per dataset, not
    once per checkpoint."""
    path = _norm_npz(path)
    arrays = {"n": np.asarray(stats.n, np.int64), "sum": stats._sum,
              "outer": stats._outer}
    if pool is not None:
        arrays["pool_features"] = pool.features()
        arrays["pool_n_seen"] = np.asarray(pool.n_seen, np.int64)
        arrays["pool_capacity"] = np.asarray(pool.capacity, np.int64)
    np.savez(path, **arrays)


def real_side_from_npz(path: str, *, need_pool: bool
                       ) -> tuple:
    """Load (StreamingStats, FeaturePool | None) written by
    real_side_to_npz. Raises if KID is requested but the file carries no
    reservoir (it was written without kid)."""
    raw = np.load(_norm_npz(path))
    dim = int(raw["sum"].shape[0])
    stats = StreamingStats(dim)
    stats.n = int(raw["n"])
    stats._sum = np.asarray(raw["sum"], np.float64)
    stats._outer = np.asarray(raw["outer"], np.float64)
    pool = None
    if "pool_features" in raw:
        pool = pool_from_features(
            np.asarray(raw["pool_features"], np.float32),
            int(raw["pool_n_seen"]), int(raw["pool_capacity"]))
    if need_pool and pool is None:
        raise ValueError(
            f"{path} has no feature reservoir (it was written without "
            "--kid/--prdc); recompute the real statistics with the "
            "reservoir-needing flag set")
    return stats, pool


def allgather_merge_stats(stats: StreamingStats) -> StreamingStats:
    """Cross-process reduction of per-process feature statistics: every
    process contributes its (n, Σx, Σxxᵀ) accumulators and every process
    gets the identical global StreamingStats back. No-op single-process."""
    if jax.process_count() == 1:
        return stats
    from jax.experimental import multihost_utils as mh

    merged = StreamingStats(stats.dim)
    # n fits int32 comfortably (sample budgets are ~1e5), so the default
    # canonicalization is harmless here
    merged.n = int(np.sum(mh.process_allgather(np.asarray(stats.n))))
    merged._sum = np.sum(_allgather_f64(stats._sum), axis=0)
    merged._outer = np.sum(_allgather_f64(stats._outer), axis=0)
    return merged


def pool_from_features(feats: np.ndarray, n_seen: int, capacity: int, *,
                       seed: int = 0) -> FeaturePool:
    """Rebuild a FeaturePool around an existing uniform sample (used to
    reconstruct remote processes' pools after an allgather)."""
    pool = FeaturePool(feats.shape[1], capacity, seed=seed)
    pool._buf[:len(feats)] = feats
    pool.n_seen = int(n_seen)
    return pool


def allgather_merge_pool(pool: FeaturePool) -> FeaturePool:
    """Cross-process weighted reservoir merge: gather every process's pool
    and fold them with FeaturePool.merge. Deterministic given the pool's
    rng state, so all processes converge on the same merged sample.

    Requires every process to have streamed the same number of examples
    (the distributed compute_fid splits num_samples evenly), so the
    gathered buffers have equal shapes.
    """
    if jax.process_count() == 1:
        return pool
    from jax.experimental import multihost_utils as mh

    feats = mh.process_allgather(pool.features())           # [P, S, D]
    counts = mh.process_allgather(np.asarray(pool.n_seen))  # [P]
    counts = counts.reshape(-1)
    # EVERY process folds in the same order (0, then 1..P-1) with the same
    # fixed rng — starting from each process's own buffer would swap
    # mine/theirs in the weighted draws and give per-process results
    merged = pool_from_features(np.asarray(feats[0]), counts[0],
                                pool.capacity, seed=0)
    merged._rng = np.random.default_rng(12345)
    for p in range(1, feats.shape[0]):
        merged.merge(pool_from_features(np.asarray(feats[p]), counts[p],
                                        pool.capacity))
    return merged


def compute_fid(sample_fn: Callable, data_batches: Iterable, *,
                image_size: int, c_dim: int = 3, z_dim: int = 100,
                num_samples: int = 50_000, batch_size: int = 256,
                num_classes: int = 0, seed: int = 0,
                feature_fn: Optional[FeatureFn] = None,
                feature_dim: Optional[int] = None,
                kid: bool = False, kid_subset_size: int = 1000,
                kid_subsets: int = 100,
                kid_pool_size: int = 10_000,
                prdc: bool = False, prdc_k: int = 5,
                distributed: bool = False,
                real_side: Optional[tuple] = None,
                real_cache_path: Optional[str] = None) -> dict:
    """End-to-end scoring: returns {"fid", "num_samples", "feature_dim"} and,
    with kid=True, {"kid", "kid_std"} from the SAME feature pass (a bounded
    reservoir of features feeds the subset-averaged unbiased-MMD estimator —
    evals/kid.py). prdc=True adds {"precision", "recall", "density",
    "coverage"} (evals/prdc.py) computed on the same reservoirs — fidelity
    and diversity separated, where FID/KID compress them into one number.

    With feature_fn=None the fixed-seed random embedder is used — scores are
    then comparable across runs/processes but are surrogate scores, not
    Inception ones (see evals/features.py).

    distributed=True under a jax.distributed job splits num_samples evenly
    over the processes — each streams its own real-data shard and generates
    with a process-distinct z stream — then all-gathers the moment
    accumulators (and KID reservoirs) so every process returns the same
    global score. There is no multi-eval counterpart in the reference (its
    only eval was the chief eyeballing sample grids, SURVEY.md §4).

    real_side, if given, is a (StreamingStats, FeaturePool | None) pair of
    PRECOMPUTED real statistics — the data stream is not touched. Repeated
    scoring of a fixed real set (the in-training probe) computes it once
    and amortizes it; the pair must have been built with the same
    feature_fn and sample budget.

    real_cache_path names an on-disk cache for the real side (the CLI's
    --real_stats): loaded when the file exists (with n / feature-dim /
    reservoir-capacity validation), else the real side is computed here as
    usual and written there. Keeping this inside compute_fid means the
    cached and uncached paths share one copy of the real-pass construction
    (same pool seeding, same trimming). Exclusive with real_side and with
    distributed (the distributed real pass is a per-process split).
    """
    if feature_fn is None:
        feature_fn, feature_dim = make_random_feature_fn(image_size, c_dim)
    elif feature_dim is None:
        raise ValueError("feature_dim required with a custom feature_fn")

    n_proc = jax.process_count() if distributed else 1
    local_samples = num_samples // n_proc
    if distributed and num_samples % n_proc:
        raise ValueError(
            f"num_samples ({num_samples}) must divide evenly over "
            f"{n_proc} processes")
    # process-distinct generator stream; real-data sharding is the
    # pipeline's job (per-host shard ownership / per-process seeds)
    gen_seed = seed + 7919 * (jax.process_index() if distributed else 0)

    if real_cache_path:
        import os

        if real_side is not None:
            raise ValueError("pass real_side OR real_cache_path, not both")
        if distributed:
            raise ValueError(
                "real_cache_path does not compose with distributed scoring "
                "(the distributed real pass is a per-process split)")
        if os.path.exists(_norm_npz(real_cache_path)):
            real_side = real_side_from_npz(real_cache_path,
                                           need_pool=kid or prdc)
            cached, cached_pool = real_side
            if cached.n != num_samples:
                raise ValueError(
                    f"{real_cache_path} holds statistics over {cached.n} "
                    f"examples but num_samples is {num_samples}; FID sides "
                    "must match — recompute or adjust num_samples")
            if cached.dim != feature_dim:
                raise ValueError(
                    f"{real_cache_path} has feature dim {cached.dim}, the "
                    f"current extractor yields {feature_dim} — it was "
                    "written under a different feature config")
            if (kid or prdc) and cached_pool.capacity != kid_pool_size:
                raise ValueError(
                    f"{real_cache_path} reservoir capacity "
                    f"{cached_pool.capacity} != kid_pool_size "
                    f"{kid_pool_size}; kid/prdc sides must draw from "
                    "same-sized reservoirs — recompute or adjust kid_pool")

    need_pools = kid or prdc
    fake_pool = FeaturePool(feature_dim, kid_pool_size, seed=seed + 1) \
        if need_pools else None
    if real_side is not None:
        real, real_pool = real_side
        if need_pools and real_pool is None:
            raise ValueError(
                "kid/prdc need a FeaturePool in real_side")
    else:
        real_pool = FeaturePool(feature_dim, kid_pool_size, seed=seed) \
            if need_pools else None
        real = stats_from_batches(feature_fn, data_batches, local_samples,
                                  feature_dim, pool=real_pool)
        if real_cache_path:
            real_side_to_npz(real_cache_path, real, real_pool)
    fake = generator_stats(sample_fn, feature_fn, feature_dim,
                           num_samples=local_samples, batch_size=batch_size,
                           z_dim=z_dim, seed=gen_seed,
                           num_classes=num_classes,
                           pool=fake_pool)
    if distributed:
        # a caller-provided real_side is taken as already global — merging
        # it again would double-count
        if real_side is None:
            real = allgather_merge_stats(real)
            if need_pools:
                real_pool = allgather_merge_pool(real_pool)
        fake = allgather_merge_stats(fake)
        if need_pools:
            fake_pool = allgather_merge_pool(fake_pool)
    fid = frechet_distance(*real.finalize(), *fake.finalize())
    out = {"fid": fid, "num_samples": num_samples,
           "feature_dim": feature_dim}
    if kid:
        mean, std = kid_score(real_pool.features(), fake_pool.features(),
                              subset_size=kid_subset_size,
                              num_subsets=kid_subsets, seed=seed)
        out["kid"] = mean
        out["kid_std"] = std
        # the score is computed on at most this many reservoir-sampled
        # features per side — recorded so KID numbers are comparable
        out["kid_pool"] = min(kid_pool_size, num_samples)
    if prdc:
        from dcgan_tpu.evals.prdc import prdc as prdc_fn

        out.update(prdc_fn(real_pool.features(), fake_pool.features(),
                           k=prdc_k))
        # comparability keys, like kid_pool above: P&R values only compare
        # across runs at a fixed (pool, k)
        out["prdc_pool"] = min(kid_pool_size, num_samples)
        out["prdc_k"] = prdc_k
    return out
