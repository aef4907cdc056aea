"""Synthetic data + TFRecord dataset writer (tests, smoke runs, tools).

The writer produces shards in the reference's on-disk schema — one bytes
feature `image_raw` holding raw [H,W,C] pixels, float64 by default
(image_input.py:42-51) — so the loader path is exercised against the real
format without needing CelebA on disk.
"""

from __future__ import annotations

import os
from typing import Iterator, List

import numpy as np

from dcgan_tpu.data.example_proto import serialize_example
from dcgan_tpu.data.tfrecord import write_tfrecords


def write_image_tfrecords(out_dir: str, *, num_examples: int,
                          image_size: int = 64, channels: int = 3,
                          num_shards: int = 2, record_dtype: str = "float64",
                          seed: int = 0,
                          feature_name: str = "image_raw",
                          num_classes: int = 0,
                          label_feature: str = "label") -> List[str]:
    """Write `num_examples` random images (pixel scale [0,255]) across shards.

    num_classes > 0 also writes an int64 `label_feature` per example (the
    schema the reference's pipeline comments out, image_input.py:44), for
    conditional-model runs. Returns the shard paths.
    """
    rng = np.random.default_rng(seed)
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    per_shard = (num_examples + num_shards - 1) // num_shards
    written = 0
    for s in range(num_shards):
        n = min(per_shard, num_examples - written)
        if n <= 0:
            break

        def records() -> Iterator[bytes]:
            for _ in range(n):
                img = rng.uniform(0, 255,
                                  size=(image_size, image_size, channels))
                raw = img.astype(record_dtype).tobytes()
                feats = {feature_name: [raw]}
                if num_classes:
                    feats[label_feature] = [int(rng.integers(num_classes))]
                yield serialize_example(feats)

        path = os.path.join(out_dir, f"shard-{s:05d}.tfrecord")
        write_tfrecords(path, records())
        paths.append(path)
        written += n
    return paths


def synthetic_batches(batch_size: int, image_size: int = 64, channels: int = 3,
                      seed: int = 0, num_classes: int = 0,
                      pool: int = 64) -> Iterator:
    """Endless stream of [-1,1] float32 batches (no disk involved).

    num_classes > 0 yields (images, int32 labels) pairs instead.

    The first `pool` batches are freshly drawn, then the stream cycles them
    (pool=0 disables; every batch fresh — REQUIRED when the stream feeds
    statistics, e.g. the evals CLI's synthetic real side, where duplicated
    samples would bias FID/KID). Synthetic data exists to exercise the
    training machinery, not to be learned from — and drawing batch*H*W*C
    gaussians per step in numpy can be slower than the training step it
    feeds on a small host (measured: a 1-core host generates ~3 MB batches
    at well under the ~65 MB/s a v5e chip consumes at DCGAN-64 throughput).
    Cycling keeps smoke runs device-bound while every batch within an
    epoch-of-`pool` stays distinct. The cache is additionally capped at
    ~256 MB whatever the batch geometry.
    """
    if pool < 0:
        raise ValueError(f"pool must be >= 0, got {pool}")
    rng = np.random.default_rng(seed)
    if pool:
        # 0 when one batch alone exceeds the budget: fall back to fresh
        # batches rather than silently repeating a single giant one
        batch_bytes = 4 * batch_size * image_size * image_size * channels
        pool = min(pool, (256 << 20) // max(1, batch_bytes))
    cache = []
    while True:
        if pool and len(cache) >= pool:
            for item in cache:
                yield item
            continue
        imgs = np.tanh(rng.normal(
            size=(batch_size, image_size, image_size, channels))
        ).astype(np.float32)
        if num_classes:
            item = (imgs, rng.integers(num_classes, size=(batch_size,),
                                       dtype=np.int32))
        else:
            item = imgs
        if pool:
            cache.append(item)
        yield item


def synthetic_id_batches(batch_size: int, seq_len: int, vocab_size: int,
                         seed: int = 0, pool: int = 64) -> Iterator:
    """Endless stream of int32 token-id batches [batch, seq_len], uniform
    over `vocab_size` ids (one document per row, no structure to learn:
    like `synthetic_batches`, it exists to exercise the training machinery).
    The first `pool` batches are drawn, then cycled (pool=0: every batch
    fresh)."""
    if pool < 0:
        raise ValueError(f"pool must be >= 0, got {pool}")
    rng = np.random.default_rng(seed)
    cache = []
    while True:
        if pool and len(cache) >= pool:
            yield from cache
            continue
        ids = rng.integers(vocab_size, size=(batch_size, seq_len),
                           dtype=np.int32)
        if pool:
            cache.append(ids)
        yield ids
