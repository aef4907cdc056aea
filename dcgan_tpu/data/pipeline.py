"""High-level input pipeline: shards -> shuffled batches -> sharded device
arrays with prefetch.

The reference's `distorted_inputs(data_dir, batch_size)` (image_input.py:98)
returned a dequeue op whose batches the trainer then pulled to host and fed
back per step (image_train.py:153-158 — the device round-trip defect,
SURVEY.md §2.4 #10). `make_dataset` instead yields jax.Arrays already laid
out with the training step's batch sharding, one batch ahead (double
buffering), so the step consumes device-resident data.

Per-host file sharding replaces the reference's "every worker reads every
file" (image_input.py:107): process i owns shards i, i+P, i+2P, ...
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import queue
import random
import struct
import threading
from typing import Iterator, List, Optional, Sequence

import numpy as np

from dcgan_tpu.data.example_proto import parse_example
from dcgan_tpu.data.tfrecord import read_tfrecords


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Input knobs (reference: image_input.py:11-16,75-84 and trainer flags)."""
    data_dir: str = "train"
    image_size: int = 64
    channels: int = 3
    batch_size: int = 64            # per-process batch
    record_dtype: str = "float64"   # on-disk pixel dtype (image_input.py:48)
    min_after_dequeue: int = 10_776  # 10% of epoch (image_input.py:134-136)
    n_threads: int = 16             # (image_input.py:77)
    prefetch_batches: int = 8       # measured best on a 1-core host (+3-15%
                                    # vs 4 — smooths bursty consumers like
                                    # the scanned multi-step dispatch)
    prefetch_device_batches: int = 2  # depth of the DEVICE-side feed queue:
                                    # a background thread assembles host
                                    # batches and starts their H2D transfer,
                                    # keeping up to N already-sharded device
                                    # batches ready ahead of the consumer —
                                    # host batch assembly + transfer overlap
                                    # device compute instead of alternating
                                    # with it. 0 = the legacy single-slot
                                    # double buffer on the consumer thread
    seed: int = 0
    normalize: bool = True          # [-1,1]; False = strict reference parity
    feature_name: str = "image_raw"
    label_feature: str = ""         # non-empty: also read an int64 label per
                                    # example (the feature the reference
                                    # comments out, image_input.py:44) and
                                    # yield (images, labels) batches
    num_classes: int = 0            # >0: validate every label < num_classes
                                    # host-side before transfer. On device an
                                    # out-of-range label fails SILENTLY two
                                    # different ways (one_hot -> zeros; the
                                    # cBN table gather -> clamped index), so
                                    # the pipeline is where it must be caught
    max_corrupt_records: int = 0    # >0: CRC/parse failures QUARANTINE the
                                    # record (skip + log file/offset + count,
                                    # data/quarantine.py) up to this many
                                    # before hard-failing; 0 = any corrupt
                                    # record is fatal (seed behavior). The
                                    # pure-Python loader verifies CRCs only
                                    # when quarantine is on (detection needs
                                    # verification; the native loader always
                                    # verifies, in hardware)
    use_native: bool = True         # C++ loader; False = pure-Python fallback
    loop: bool = True


# Sidecar manifest prepare.py writes next to its shards; the one filename
# list_shards exempts from "every file is a shard".
MANIFEST_NAME = "dataset.json"


def list_shards(data_dir: str) -> List[str]:
    """Every regular file in data_dir is a shard, as the reference assumes
    (image_input.py:107) — except the dataset.json manifest prepare.py
    writes next to its shards."""
    paths = sorted(p for p in glob.glob(os.path.join(data_dir, "*"))
                   if os.path.isfile(p)
                   and os.path.basename(p) != MANIFEST_NAME)
    if not paths:
        raise FileNotFoundError(f"no TFRecord shards in {data_dir}")
    return paths


def read_manifest(data_dir: str) -> dict:
    """The dataset.json sidecar prepare.py writes, or {} when absent —
    lets read-only consumers (evals, trajectory tools) adopt the recorded
    wire format instead of requiring the user to re-specify it."""
    path = os.path.join(data_dir, MANIFEST_NAME)
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)


def check_manifest(data_dir: str, cfg: "DataConfig") -> None:
    """Validate DataConfig against the dataset.json manifest, if present.

    prepare.py records the knobs the records were written with; a mismatched
    DataConfig otherwise fails deep in the loader ("example has N values,
    expected M") or, for byte-coincidental sizes, silently misreads pixels.
    """
    manifest = read_manifest(data_dir)
    if not manifest:
        return
    checks = [
        ("image_size", cfg.image_size),
        ("channels", cfg.channels),
        ("record_dtype", cfg.record_dtype),
        ("feature_name", cfg.feature_name),
    ]
    problems = [
        f"{key}: dataset was prepared with {manifest[key]!r}, "
        f"config says {got!r}"
        for key, got in checks
        if key in manifest and manifest[key] != got
    ]
    if cfg.label_feature and manifest.get("label_feature", "") and \
            manifest["label_feature"] != cfg.label_feature:
        problems.append(
            f"label_feature: dataset has {manifest['label_feature']!r}, "
            f"config says {cfg.label_feature!r}")
    if cfg.label_feature and "label_feature" in manifest and \
            not manifest["label_feature"]:
        problems.append(
            "config requests labels but the dataset was prepared unlabeled")
    if problems:
        raise ValueError(
            f"DataConfig disagrees with "
            f"{os.path.join(data_dir, MANIFEST_NAME)}:\n  "
            + "\n  ".join(problems))


def shard_for_process(paths: Sequence[str], process_index: int,
                      process_count: int) -> List[str]:
    mine = [p for i, p in enumerate(paths)
            if i % process_count == process_index]
    # fewer shards than processes: everyone reads everything, seeds differ
    return mine or list(paths)


def process_local_box(sharding, global_shape, *, devices=None):
    """Bounding box (tuple of index slices) of THIS process's addressable
    shards of a global array under `sharding`.

    `devices`: override the "addressable" set (default: devices whose
    process_index is this process's) — lets single-process tests exercise
    the multi-process geometry.

    `make_array_from_process_local_data` requires local data shaped like the
    process-local portion of the global array — which is "batch/process_count
    x everything else" ONLY when each process's devices cover whole rows of
    every non-batch sharded axis. Under a spatial mesh whose "model" axis
    spans processes (e.g. 4 processes x 2 devices with a 4-way height axis),
    a process owns a batch-slice x height-slice BLOCK instead; feeding it
    the naive per-process batch silently mis-assembles the global array
    (observed as a doubled height dim at trace time). This helper computes
    the true block from the sharding itself, so data sources can produce
    exactly the addressable portion for ANY (data, model) layout.
    """
    import jax

    idx_map = sharding.devices_indices_map(tuple(global_shape))
    if devices is not None:
        owned = set(devices)
        mine = [idx for d, idx in idx_map.items() if d in owned]
    else:
        mine = [idx for d, idx in idx_map.items()
                if d.process_index == jax.process_index()]
    if not mine:  # no addressable shard (shouldn't happen in practice)
        raise ValueError("sharding has no addressable shards here")
    ndim = len(global_shape)
    lo = [min(s.indices(global_shape[a])[0] for s in (idx[a] for idx in mine))
          for a in range(ndim)]
    hi = [max(s.indices(global_shape[a])[1] for s in (idx[a] for idx in mine))
          for a in range(ndim)]
    # the union of this process's shards must tile the bounding box exactly
    # (true for any mesh-aligned NamedSharding; guards pathological cases)
    distinct = {tuple((s.indices(global_shape[a])[:2])
                      for a, s in enumerate(idx)) for idx in mine}
    box_vol = 1
    for a in range(ndim):
        box_vol *= hi[a] - lo[a]
    tiled = sum(
        int(np.prod([e - b for b, e in idx])) for idx in distinct)
    if tiled != box_vol:
        raise ValueError(
            f"process-local shards do not tile a box: {sorted(distinct)}")
    return tuple(slice(lo[a], hi[a]) for a in range(ndim))


# ---------------------------------------------------------------------------
# Pure-Python loader (fallback / reference implementation for tests)
# ---------------------------------------------------------------------------

class PythonLoader:
    """Same contract as native.NativeLoader, implemented with Python threads.

    Reader threads parse shards into a shuffle pool; a batcher assembles
    batches into a bounded queue.
    """

    def __init__(self, paths: Sequence[str], *, batch: int,
                 example_shape: Sequence[int], record_dtype: str = "float64",
                 min_after_dequeue: int = 1024, n_threads: int = 4,
                 prefetch_batches: int = 4, seed: int = 0,
                 normalize: bool = True, loop: bool = True,
                 feature_name: str = "image_raw", label_feature: str = "",
                 verify_crc: bool = False, max_corrupt_records: int = 0):
        self.batch = batch
        self.example_shape = tuple(example_shape)
        self.labeled = bool(label_feature)
        self._paths = list(paths)
        self._dtype = np.dtype(record_dtype)
        self._mad = min_after_dequeue
        # same capacity bound as the native loader (and the reference's queue,
        # image_input.py:75-76): readers block when the pool is full
        self._capacity = min_after_dequeue + 3 * batch
        self._normalize = normalize
        self._loop = loop
        self._feature = feature_name
        self._label_feature = label_feature
        self._rng = random.Random(seed)
        self._verify_crc = verify_crc
        self._max_corrupt = max_corrupt_records
        self._corrupt = 0            # DISTINCT records quarantined
        self._quarantined: set = set()   # (path, offset) already counted
        self._pool: List[np.ndarray] = []
        self._pool_lock = threading.Condition()
        self._batches: "queue.Queue" = queue.Queue(maxsize=prefetch_batches)
        self._stop = False
        self._error: Optional[str] = None
        self._readers_done = 0
        n = max(1, min(n_threads, len(self._paths)))
        self._n_readers = n
        self._threads = [
            threading.Thread(target=self._read_loop, args=(t, n), daemon=True)
            for t in range(n)]
        self._threads.append(
            threading.Thread(target=self._batch_loop, daemon=True))
        for t in self._threads:
            t.start()

    def _decode(self, payload: bytes) -> np.ndarray:
        n = int(np.prod(self.example_shape))
        arr = np.frombuffer(payload, dtype=self._dtype)
        if arr.size != n:
            raise ValueError(
                f"example has {arr.size} values, expected {n}")
        x = arr.astype(np.float32).reshape(self.example_shape)
        if self._normalize:
            x = x / 127.5 - 1.0
        return x

    @property
    def corrupt_records(self) -> int:
        """Records this loader has quarantined so far."""
        return self._corrupt

    def _quarantine(self, path: str, offset: int, reason: str) -> None:
        """Count one skipped record; raises CorruptRecordError past the
        budget (data/quarantine.py owns the log line and the process-wide
        tally the trainer surfaces as data/corrupt_records). A looping
        dataset re-encounters the same bad record every epoch — repeats are
        skipped silently, so the budget bounds DISTINCT corrupt records,
        not epochs survived."""
        from dcgan_tpu.data import quarantine

        with self._pool_lock:
            if (path, offset) in self._quarantined:
                return
            self._quarantined.add((path, offset))
            self._corrupt += 1
            seen = self._corrupt
        quarantine.record(path, offset, reason,
                          budget=self._max_corrupt, seen=seen)

    def _read_loop(self, tid: int, n_threads: int) -> None:
        quarantining = self._max_corrupt > 0
        try:
            while not self._stop:
                read_any = False
                for i in range(tid, len(self._paths), n_threads):
                    path = self._paths[i]
                    on_corrupt = (
                        (lambda off, why, p=path: self._quarantine(p, off,
                                                                   why))
                        if quarantining else None)
                    for off, rec in read_tfrecords(
                            path, verify_crc=self._verify_crc,
                            on_corrupt=on_corrupt, with_offsets=True):
                        try:
                            feats = parse_example(rec)
                            if self._feature not in feats:
                                raise ValueError(
                                    "record missing feature "
                                    f"{self._feature!r}")
                            x = self._decode(feats[self._feature][0])
                            if self.labeled:
                                lab = feats.get(self._label_feature)
                                if not lab:
                                    raise ValueError(
                                        "record missing int64 feature "
                                        f"{self._label_feature!r}")
                                # same bound as the native loader: reject
                                # rather than silently wrap/round class ids
                                if not 0 <= int(lab[0]) <= (1 << 24):
                                    raise ValueError(
                                        f"label {int(lab[0])} out of range "
                                        "[0, 2^24]")
                                x = (x, np.int32(lab[0]))
                        except (ValueError, IndexError, KeyError,
                                struct.error) as e:
                            # parse-layer corruption: quarantine the record
                            # like a CRC failure, or fail-fast when off.
                            # parse_example surfaces malformed proto bytes
                            # as struct.error/IndexError, not just
                            # ValueError — all of them are data faults here
                            if not quarantining:
                                raise
                            self._quarantine(path, off,
                                             f"{type(e).__name__}: {e}")
                            continue
                        read_any = True
                        with self._pool_lock:
                            self._pool_lock.wait_for(
                                lambda: len(self._pool) < self._capacity
                                or self._stop)
                            if self._stop:
                                return
                            self._pool.append(x)
                            self._pool_lock.notify_all()
                if not self._loop or not read_any:
                    break
        except Exception as e:  # surface errors to the consumer
            self._error = str(e)
        finally:
            with self._pool_lock:
                self._readers_done += 1
                self._pool_lock.notify_all()

    def _batch_loop(self) -> None:
        while not self._stop:
            with self._pool_lock:
                def ready():
                    done = self._readers_done == self._n_readers
                    return (self._stop or self._error or
                            len(self._pool) >= self._mad + self.batch or
                            (done and len(self._pool) >= self.batch) or
                            (done and not self._loop))
                self._pool_lock.wait_for(ready)
                if self._stop or self._error:
                    self._batches.put(None)
                    return
                if len(self._pool) < self.batch:
                    self._batches.put(None)  # end of data
                    return
                picked = []
                for _ in range(self.batch):
                    j = self._rng.randrange(len(self._pool))
                    self._pool[j], self._pool[-1] = (self._pool[-1],
                                                     self._pool[j])
                    picked.append(self._pool.pop())
                self._pool_lock.notify_all()  # wake readers waiting for space
            if self.labeled:
                self._batches.put((np.stack([p[0] for p in picked]),
                                   np.asarray([p[1] for p in picked],
                                              dtype=np.int32)))
            else:
                self._batches.put(np.stack(picked))

    def next(self):
        """Next [B, ...] batch — an (images, int32 labels) pair when labeled —
        or None at end-of-data."""
        b = self._batches.get()
        if b is None and self._error:
            raise RuntimeError(self._error)
        return b

    def __iter__(self):
        while True:
            b = self.next()
            if b is None:
                return
            yield b

    def close(self):
        self._stop = True
        with self._pool_lock:
            self._pool_lock.notify_all()
        try:
            while True:
                self._batches.get_nowait()
        except queue.Empty:
            pass


# ---------------------------------------------------------------------------
# Device pipeline
# ---------------------------------------------------------------------------

def _make_loader(cfg: DataConfig, paths: Sequence[str], seed: int):
    shape = (cfg.image_size, cfg.image_size, cfg.channels)
    kwargs = dict(batch=cfg.batch_size, example_shape=shape,
                  record_dtype=cfg.record_dtype,
                  min_after_dequeue=cfg.min_after_dequeue,
                  n_threads=cfg.n_threads,
                  prefetch_batches=cfg.prefetch_batches, seed=seed,
                  normalize=cfg.normalize, loop=cfg.loop,
                  feature_name=cfg.feature_name,
                  label_feature=cfg.label_feature,
                  max_corrupt_records=cfg.max_corrupt_records)
    if cfg.use_native:
        from dcgan_tpu.data.native import NativeLoader, NativeLoaderError
        try:
            return NativeLoader(paths, **kwargs)
        except NativeLoaderError as e:
            # no toolchain (the build failed): a development machine carries
            # on in Python. A path that is measured must not — chip_smoke.py
            # turns this warning into an error.
            import warnings
            warnings.warn(f"native loader unavailable ({e}); "
                          "using pure-Python loader")
    # the pure-Python CRC pass is a per-byte Python loop — too slow to run
    # unconditionally on the fallback path, but quarantine without
    # verification cannot DETECT a payload flip, so opting in turns it on
    return PythonLoader(paths, verify_crc=cfg.max_corrupt_records > 0,
                        **kwargs)


def to_global(batch, sharding, label_sharding=None):
    """Host batch — or an (images, labels) pair — to global sharded arrays."""
    import jax

    if isinstance(batch, tuple):
        imgs, labels = batch
        if label_sharding is None:
            raise ValueError("labeled dataset needs label_sharding")
        return (jax.make_array_from_process_local_data(sharding, imgs),
                jax.make_array_from_process_local_data(label_sharding, labels))
    return jax.make_array_from_process_local_data(sharding, batch)


def _check_labels(batch, num_classes: int):
    """Host-side label-range gate (see DataConfig.num_classes) — shared by
    the inline and prefetch-thread feed paths."""
    labels = batch[1]
    bad = int(labels.max(initial=0))
    if bad >= num_classes or int(labels.min(initial=0)) < 0:
        raise ValueError(
            f"label {bad} out of range for num_classes="
            f"{num_classes} (dataset/config mismatch; on device "
            "this would silently one-hot to zeros or clamp the cBN "
            "table gather)")


class DevicePrefetcher:
    """Background device-feed thread: host batches -> a bounded queue of
    already-sharded global device arrays.

    The single-slot double buffer this replaces still ran batch assembly
    and the H2D transfer start on the CONSUMER's thread — the trainer's
    dispatch thread alternated between feeding and dispatching (the stall
    class ParaGAN's congestion-aware pipeline attacks, PAPERS.md
    arxiv 2411.03999). Here one producer thread pulls `host_iter`,
    validates labels, and calls `to_global` (which starts the transfer),
    so up to `depth` device batches sit ready while the device computes.

    Order is the host iterator's order (single producer, FIFO queue).
    Producer exceptions re-raise on the consumer thread at the next
    `__next__`. `close()` is idempotent, safe mid-epoch, unblocks a
    producer stuck on a full queue, and closes `owner` (the underlying
    loader) when given.

    Spans (utils/profiling.py::span, always on). Consumer thread:
    `feed/wait` around the blocking get of `__next__`, one per delivered
    batch, its count the queue depth seen on entry (0: the step waits for
    the loader). Producer thread: `feed/load` around `next(host_iter)`
    (records read, decoded, assembled), `feed/h2d` around `to_global` (the
    transfer's start), and `feed/full` around a put that found the queue
    full (the producer's slack).
    """

    _SENTINEL = object()

    def __init__(self, host_iter: Iterator, sharding, label_sharding=None, *,
                 depth: int = 2, num_classes: int = 0, owner=None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._host_iter = host_iter
        self._sharding = sharding
        self._label_sharding = label_sharding
        self._num_classes = num_classes
        self._owner = owner
        self._queue: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        # imported here, not at the top: the loaders import without jax
        from dcgan_tpu.utils.profiling import span

        self._span = span
        self._thread = threading.Thread(
            target=self._produce, name="dcgan-device-feed", daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Queue-put that stays interruptible by close()."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self) -> None:
        span, host_iter = self._span, iter(self._host_iter)
        try:
            while True:
                try:
                    with span("feed/load"):
                        batch = next(host_iter)
                except StopIteration:
                    return
                if self._stop.is_set():
                    return
                if self._num_classes and isinstance(batch, tuple):
                    _check_labels(batch, self._num_classes)
                with span("feed/h2d"):
                    arr = to_global(batch, self._sharding,
                                    self._label_sharding)
                if self._queue.full():
                    with span("feed/full"):
                        ok = self._put(arr)
                else:
                    ok = self._put(arr)
                if not ok:
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised on consumer
            self._error = e
        finally:
            self._put(self._SENTINEL)

    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self):
        with self._span("feed/wait", count=self._queue.qsize()):
            return self._get()

    def _get(self):
        """The blocking get; StopIteration at the end of the feed (which
        leaves no `feed/wait` record: nothing was delivered)."""
        while True:
            if self._stop.is_set():
                raise StopIteration
            try:
                item = self._queue.get(timeout=0.5)
            except queue.Empty:
                # producer still filling (or wedged on a slow loader) —
                # keep waiting unless it died with an error
                if self._error is not None and not self._thread.is_alive():
                    self._raise()
                continue
            if item is self._SENTINEL:
                if self._error is not None:
                    self._raise()
                raise StopIteration
            return item

    def _raise(self):
        err = self._error
        self._error = None
        self.close()
        # re-raise the producer's exception with its original type and
        # traceback — consumers match on the loader's own error classes
        raise err

    def close(self) -> None:
        """Stop the producer and release the loader. Mid-epoch safe: any
        queued device batches are discarded. A producer parked in the
        loader's untimed batch get() must be unblocked by the loader
        itself, not our stop flag — but RELEASING the loader while the
        producer is still inside it is a use-after-free (the native
        loader's destroy tears the handle down under a thread parked in
        `dcgan_loader_next`; segfault chased on prefetcher close). So the
        order is: non-destructive owner `stop()` (unblocks the producer),
        join, THEN destroy. Owners without a `stop()` (the pure-Python
        loaders) keep the old unblock path — their `close()` is the
        sentinel put and frees no native state."""
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        owner, self._owner = self._owner, None
        stop = getattr(owner, "stop", None)
        if callable(stop):
            stop()
        elif owner is not None and hasattr(owner, "close"):
            owner.close()
            owner = None
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        if owner is not None and hasattr(owner, "close"):
            owner.close()

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def make_dataset(cfg: DataConfig, sharding=None,
                 label_sharding=None) -> Iterator:
    """Endless (or one-epoch, cfg.loop=False) iterator of device batches.

    With `sharding` (a NamedSharding over the mesh's data axis), each yielded
    array is a global array assembled from this process's local batch —
    cfg.batch_size is the PER-PROCESS batch, and the global batch is
    batch_size * process_count. Without `sharding`, yields host numpy.

    With cfg.label_feature set, yields (images, labels) pairs; labels use
    `label_sharding` (required alongside `sharding` for labeled configs).

    With cfg.prefetch_device_batches > 0 (the default) the returned
    iterator is a DevicePrefetcher — a background thread keeps that many
    sharded device batches queued ahead of the consumer; call `.close()`
    (or exhaust it) to release the feed thread and the loader. 0 keeps the
    legacy consumer-thread double buffer.
    """
    import jax

    check_manifest(cfg.data_dir, cfg)
    if cfg.record_dtype == "float64" and any(
            d.platform not in ("cpu",) for d in jax.devices()):
        # The parity wire format moves and decodes 8 bytes for every value
        # that carries 8 bits — the format most likely to leave an
        # accelerator waiting for input (rates not measured on the current
        # machine). Warn, don't fail: short runs and parity experiments
        # are legitimate.
        import warnings

        warnings.warn(
            "float64 TFRecords feeding an accelerator: 8 bytes decoded per "
            "8-bit value makes this the format most likely to starve the "
            "chip — re-prepare with --record_dtype uint8 (the default) "
            "unless byte-exact reference parity is the goal",
            RuntimeWarning, stacklevel=2)
    paths = shard_for_process(list_shards(cfg.data_dir),
                              jax.process_index(), jax.process_count())
    loader = _make_loader(cfg, paths, cfg.seed + jax.process_index())
    labeled = bool(cfg.label_feature)

    if sharding is None:
        return iter(loader)
    if labeled and label_sharding is None:
        raise ValueError("labeled dataset needs label_sharding")
    if cfg.prefetch_device_batches > 0:
        return DevicePrefetcher(
            iter(loader), sharding, label_sharding,
            depth=cfg.prefetch_device_batches,
            num_classes=cfg.num_classes if labeled else 0,
            owner=loader)
    return _double_buffer(cfg, loader, sharding, label_sharding,
                          labeled=labeled)


def _double_buffer(cfg: DataConfig, loader, sharding, label_sharding, *,
                   labeled: bool) -> Iterator:
    """Legacy consumer-thread feed (prefetch_device_batches=0): keep one
    device transfer in flight ahead of the consumer."""
    pending = None
    for batch in iter(loader):
        if labeled and cfg.num_classes:
            _check_labels(batch, cfg.num_classes)
        nxt = to_global(batch, sharding, label_sharding)
        if pending is not None:
            yield pending
        pending = nxt
    if pending is not None:
        yield pending
