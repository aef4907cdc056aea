"""The one general generator of training traffic: every mix is a data file.

A training mix states `feed` (`resident` | `records`), `per_chip_batch`,
`chips`, `mesh`, `backend`, `in_flight` (steps kept queued on the device),
for a resident feed `resident_batches`, and for records their `count`,
`shards`, `dtype` and `seed`; none of them has a default in code. From those and the run's `--seed` this module makes

- resident batches: `resident_batches` batches drawn on the device in one
  jitted call, every row different, each by the model family's draw
  (float32 images in the tanh range for `gan`);
- records: a uint8 TFRecord data set in the layout and with the manifest
  that the program's `data.prepare` writes (one `tf.train.Example` per
  image with the bytes feature `image_raw`; `dataset.json` beside the
  shards), written once into the benchmark's cache directory in the
  checkout and reused. The pixels come from the mix's own `seed`, so every
  run of the cell reads the same data set and `--seed` orders it (the
  loader's shuffle seed). Each image carries its index in its first two
  bytes, so that a delivered row can be held against the record it claims
  to be.

The container and protobuf framing are written here from the public
formats (TFRecord: length, masked CRC32C of the length, payload, masked
CRC32C of the payload), with the CRCs of all records computed together in
numpy: the program's pure-Python writer needs minutes for 192 MiB.
"""

from __future__ import annotations

import json
import os
import shutil
import struct
from typing import Dict, List, Tuple

import numpy as np

FEATURE = "image_raw"


# --- records ----------------------------------------------------------------

def _crc_table() -> np.ndarray:
    table = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        table = np.where(table & 1, (table >> 1) ^ np.uint32(0x82F63B78),
                         table >> 1).astype(np.uint32)
    return table


def masked_crc32c_rows(rows: np.ndarray) -> np.ndarray:
    """Masked CRC32C of every row of a [N, L] uint8 matrix, as uint32 [N]."""
    table = _crc_table()
    cols = np.ascontiguousarray(rows.T)
    crc = np.full(rows.shape[0], 0xFFFFFFFF, np.uint32)
    for col in cols:
        crc = table[(crc ^ col) & np.uint32(0xFF)] ^ (crc >> np.uint32(8))
    crc = ~crc
    return (((crc >> np.uint32(15)) | (crc << np.uint32(17)))
            + np.uint32(0xA282EAD8)).astype(np.uint32)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _example_prefix(n_bytes: int) -> bytes:
    """Everything of a serialized Example{features{feature{image_raw:
    bytes_list{value}}}} that precedes the pixel bytes."""
    h_value = b"\x0a" + _varint(n_bytes)            # BytesList.value = 1
    len_list = len(h_value) + n_bytes
    h_list = b"\x0a" + _varint(len_list)            # Feature.bytes_list = 1
    len_feature = len(h_list) + len_list
    h_entry = (b"\x0a" + _varint(len(FEATURE)) + FEATURE.encode()   # key = 1
               + b"\x12" + _varint(len_feature))                     # value = 2
    len_entry = len(h_entry) + len_feature
    h_features = b"\x0a" + _varint(len_entry)       # Features.feature = 1
    len_features = len(h_features) + len_entry
    h_example = b"\x0a" + _varint(len_features)     # Example.features = 1
    return h_example + h_features + h_entry + h_list + h_value


def record_images(spec: dict, image_size: int, channels: int) -> np.ndarray:
    """The data set's pixels, [count, S, S, C] uint8, from the mix's seed;
    image i holds i in its first two bytes."""
    n = int(spec["count"])
    if n > 65536:
        raise ValueError("records.count above 65,536 needs a wider index")
    rng = np.random.default_rng(int(spec["seed"]))
    imgs = rng.integers(0, 256, (n, image_size, image_size, channels),
                        dtype=np.uint8)
    flat = imgs.reshape(n, -1)
    idx = np.arange(n)
    flat[:, 0] = idx >> 8
    flat[:, 1] = idx & 0xFF
    return imgs


def record_ids(rows: np.ndarray) -> np.ndarray:
    """Index of the record each delivered (normalized) row claims to be."""
    flat = rows.reshape(rows.shape[0], -1)[:, :2].astype(np.float64)
    b = np.rint((flat + 1.0) * 127.5).astype(np.int64)
    return b[:, 0] * 256 + b[:, 1]


def normalize(images_u8: np.ndarray) -> np.ndarray:
    """uint8 pixels to the tanh range, as a float32 reader would."""
    return images_u8.astype(np.float32) / np.float32(127.5) - np.float32(1.0)


def records_dir(cache_root: str, spec: dict, image_size: int,
                channels: int) -> str:
    name = (f"{spec['dtype']}_{image_size}x{channels}_n{spec['count']}"
            f"_s{spec['shards']}_seed{spec['seed']}")
    return os.path.join(cache_root, "records", name)


def ensure_records(cache_root: str, spec: dict, image_size: int,
                   channels: int) -> str:
    """Write the data set unless this checkout already holds it; returns
    its directory. The manifest is written last and marks it complete."""
    if spec["dtype"] != "uint8":
        raise ValueError("the generator writes uint8 records only")
    out = records_dir(cache_root, spec, image_size, channels)
    manifest = os.path.join(out, "dataset.json")
    if os.path.isfile(manifest):
        return out
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    imgs = record_images(spec, image_size, channels)
    n, n_bytes = imgs.shape[0], imgs[0].size
    prefix = np.frombuffer(_example_prefix(n_bytes), np.uint8)
    rows = np.empty((n, prefix.size + n_bytes), np.uint8)
    rows[:, :prefix.size] = prefix
    rows[:, prefix.size:] = imgs.reshape(n, -1)
    length = struct.pack("<Q", rows.shape[1])
    length_crc = masked_crc32c_rows(np.frombuffer(length, np.uint8)[None])[0]
    framed = np.empty((n, 12 + rows.shape[1] + 4), np.uint8)
    framed[:, :8] = np.frombuffer(length, np.uint8)
    framed[:, 8:12] = np.frombuffer(struct.pack("<I", int(length_crc)),
                                    np.uint8)
    framed[:, 12:-4] = rows
    framed[:, -4:] = masked_crc32c_rows(rows).astype("<u4").view(
        np.uint8).reshape(n, 4)
    shards = max(1, min(int(spec["shards"]), n))
    bounds = np.linspace(0, n, shards + 1, dtype=int)
    for s in range(shards):
        with open(os.path.join(out, f"shard-{s:05d}.tfrecord"), "wb") as f:
            f.write(framed[bounds[s]:bounds[s + 1]].tobytes())
    with open(manifest, "w") as f:
        json.dump({"num_examples": n, "image_size": image_size,
                   "crop_size": 0, "channels": channels,
                   "record_dtype": "uint8", "classes": [],
                   "feature_name": FEATURE, "label_feature": "",
                   "num_shards": shards}, f, indent=2)
    return out


# --- resident batches -------------------------------------------------------

def uniform_images(key, shape: Tuple[int, ...]):
    """One float32 batch in [-1, 1), the tanh range, every row different."""
    import jax
    import jax.numpy as jnp

    return jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)


def resident_batches(key, count: int, shape: Tuple[int, ...], sharding,
                     draw=uniform_images) -> List:
    """`count` batches, each `draw(key_i, shape)` (the model family's draw
    of one batch; images where none is given), drawn on the device in one
    jitted call from the key and laid out with the step's batch sharding."""
    import jax

    def draw_all(k):
        return [draw(jax.random.fold_in(k, i), shape) for i in range(count)]

    return jax.jit(draw_all, out_shardings=[sharding] * count)(key)


def check_mix(traffic: Dict) -> None:
    """Refuse a training mix that leaves a needed parameter out."""
    need = ["kind", "feed", "per_chip_batch", "chips", "mesh", "backend",
            "in_flight"]
    if traffic.get("feed") == "resident":
        need.append("resident_batches")
    missing = [k for k in need if k not in traffic]
    if traffic.get("feed") == "records":
        missing += [k for k in ("count", "shards", "dtype", "seed")
                    if k not in traffic.get("records", {})]
    elif traffic.get("feed") != "resident":
        missing.append("feed=resident|records")
    if missing:
        raise ValueError(f"traffic mix lacks {missing}")
