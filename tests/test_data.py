"""Input-pipeline tests: TFRecord framing + CRC, Example codec, native C++
loader vs pure-Python loader, sharded device delivery
(reference behavior: image_input.py; SURVEY.md §2.2)."""

import os

import jax
import numpy as np
import pytest

from dcgan_tpu.data import tfrecord
from dcgan_tpu.data.example_proto import parse_example, serialize_example
from dcgan_tpu.data.pipeline import (
    DataConfig,
    DevicePrefetcher,
    PythonLoader,
    list_shards,
    make_dataset,
    shard_for_process,
)
from dcgan_tpu.data.synthetic import synthetic_batches, write_image_tfrecords


class TestTFRecord:
    def test_crc32c_known_vectors(self):
        # public CRC32C test vectors
        assert tfrecord.crc32c(b"") == 0
        assert tfrecord.crc32c(b"123456789") == 0xE3069283
        assert tfrecord.crc32c(b"\x00" * 32) == 0x8A9136AA

    def test_roundtrip_with_crc(self, tmp_path):
        path = str(tmp_path / "x.tfrecord")
        recs = [b"alpha", b"", b"\x00\xff" * 100]
        assert tfrecord.write_tfrecords(path, recs) == 3
        out = list(tfrecord.read_tfrecords(path, verify_crc=True))
        assert out == recs

    def test_corruption_detected(self, tmp_path):
        path = str(tmp_path / "x.tfrecord")
        tfrecord.write_tfrecords(path, [b"payload-payload"])
        raw = bytearray(open(path, "rb").read())
        raw[14] ^= 0xFF  # flip a data byte
        open(path, "wb").write(bytes(raw))
        with pytest.raises(IOError):
            list(tfrecord.read_tfrecords(path, verify_crc=True))

    def test_missing_file_raises(self):
        with pytest.raises(FileNotFoundError):
            list(tfrecord.read_tfrecords("/nonexistent/shard"))


class TestExampleProto:
    def test_bytes_roundtrip(self):
        msg = serialize_example({"image_raw": [b"\x01\x02\x03"]})
        assert parse_example(msg) == {"image_raw": [b"\x01\x02\x03"]}

    def test_mixed_features(self):
        msg = serialize_example({
            "image_raw": [b"pixels"],
            "label": [3],
            "scores": [0.5, -1.5],
        })
        out = parse_example(msg)
        assert out["image_raw"] == [b"pixels"]
        assert out["label"] == [3]
        np.testing.assert_allclose(out["scores"], [0.5, -1.5])

    @pytest.mark.slow
    def test_cross_check_against_tensorflow(self):
        """Our codec must interoperate with the real tf.train.Example."""
        tf = pytest.importorskip("tensorflow")
        feats = {"image_raw": [b"\x00" * 16], "label": [7]}
        ours = serialize_example(feats)
        theirs = tf.train.Example()
        theirs.ParseFromString(ours)
        assert theirs.features.feature["image_raw"].bytes_list.value[0] \
            == b"\x00" * 16
        assert theirs.features.feature["label"].int64_list.value[0] == 7
        # and the reverse: parse TF's serialization with our parser
        assert parse_example(theirs.SerializeToString()) == feats


def _write_dataset(tmp_path, n=48, size=8, dtype="float64", shards=3):
    return write_image_tfrecords(
        str(tmp_path / "data"), num_examples=n, image_size=size,
        channels=3, num_shards=shards, record_dtype=dtype)


LOADER_KW = dict(batch=16, example_shape=(8, 8, 3), min_after_dequeue=8,
                 n_threads=3, seed=0, normalize=True, loop=True)


class TestLoaders:
    @pytest.mark.parametrize("dtype", ["float64", "float32", "uint8"])
    def test_native_loader_batches(self, tmp_path, dtype):
        native = pytest.importorskip("dcgan_tpu.data.native")
        paths = _write_dataset(tmp_path, dtype=dtype)
        with native.NativeLoader(paths, record_dtype=dtype,
                                 **LOADER_KW) as ld:
            for _ in range(5):
                b = ld.next()
                assert b.shape == (16, 8, 8, 3) and b.dtype == np.float32
                assert -1.0 <= b.min() and b.max() <= 1.0
                assert b.std() > 0.1  # actually data, not zeros

    def test_stop_terminates_looping_consumer_thread(self, tmp_path):
        """`stop()` must end a loop=True stream (which never reaches EOF on
        its own) from another thread WITHOUT freeing the handle, whether the
        consumer is parked inside `next()` or between calls — the
        destroy-safety contract DevicePrefetcher relies on
        (owner.stop -> join -> owner.close)."""
        import threading

        native = pytest.importorskip("dcgan_tpu.data.native")
        paths = _write_dataset(tmp_path)
        ld = native.NativeLoader(paths, **LOADER_KW)
        first = threading.Event()
        consumed = []

        def consume():
            while True:
                b = ld.next()
                first.set()
                if b is None:
                    return
                consumed.append(b.shape)

        t = threading.Thread(target=consume)
        t.start()
        assert first.wait(timeout=10.0)  # stream is live before the stop
        ld.stop()
        t.join(timeout=5.0)
        assert not t.is_alive()  # next() drained to None — no park, no hang
        assert all(s == (16, 8, 8, 3) for s in consumed)
        ld.close()  # safe: the consumer thread is out of the native call

    def test_native_large_record_crc_roundtrip(self, tmp_path):
        """64px float64 records (98 KB payloads) exercise the 3-way
        interleaved hardware-CRC path (blocks >= 12 KB) against CRCs written
        by the independent Python implementation — the tiny records every
        other test uses only ever hit the serial tail loop. Values must also
        round-trip exactly (decode is a cast, normalize=False)."""
        native = pytest.importorskip("dcgan_tpu.data.native")
        rng = np.random.default_rng(7)
        img = rng.uniform(0.0, 255.0, size=(64, 64, 3)).astype(np.float64)
        path = str(tmp_path / "big.tfrecord")
        tfrecord.write_tfrecords(
            path, [serialize_example({"image_raw": [img.tobytes()]})] * 4)
        kw = dict(batch=4, example_shape=(64, 64, 3), min_after_dequeue=4,
                  n_threads=1, seed=0, normalize=False, loop=True,
                  record_dtype="float64")
        with native.NativeLoader([path], **kw) as ld:
            b = ld.next()
        np.testing.assert_array_equal(b[0], img.astype(np.float32))

    def test_native_large_record_crc_detects_corruption(self, tmp_path):
        """A bit flip deep inside a >=12 KB payload must still be caught —
        pins the interleaved-CRC combine, not just the tail path."""
        native = pytest.importorskip("dcgan_tpu.data.native")
        img = np.zeros((64, 64, 3), np.float64)
        path = str(tmp_path / "big.tfrecord")
        tfrecord.write_tfrecords(
            path, [serialize_example({"image_raw": [img.tobytes()]})])
        raw = bytearray(open(path, "rb").read())
        raw[20_000] ^= 0x01  # inside the first 4 KB-chunk triple
        open(path, "wb").write(bytes(raw))
        kw = dict(batch=1, example_shape=(64, 64, 3), min_after_dequeue=1,
                  n_threads=1, seed=0, normalize=False, loop=True,
                  record_dtype="float64")
        with native.NativeLoader([path], **kw) as ld:
            with pytest.raises(native.NativeLoaderError, match="CRC"):
                ld.next()

    def test_native_missing_feature_error(self, tmp_path):
        native = pytest.importorskip("dcgan_tpu.data.native")
        path = str(tmp_path / "bad.tfrecord")
        tfrecord.write_tfrecords(
            path, [serialize_example({"other": [b"\x00" * 8]})])
        with native.NativeLoader([path], **LOADER_KW) as ld:
            with pytest.raises(native.NativeLoaderError,
                               match="image_raw"):
                ld.next()

    def test_native_crc_error(self, tmp_path):
        native = pytest.importorskip("dcgan_tpu.data.native")
        paths = _write_dataset(tmp_path, n=4, shards=1)
        raw = bytearray(open(paths[0], "rb").read())
        raw[40] ^= 0xFF
        open(paths[0], "wb").write(bytes(raw))
        with native.NativeLoader(paths, **LOADER_KW) as ld:
            with pytest.raises(native.NativeLoaderError, match="CRC"):
                ld.next()

    def test_python_loader_matches_semantics(self, tmp_path):
        paths = _write_dataset(tmp_path)
        ld = PythonLoader(paths, record_dtype="float64", **LOADER_KW)
        b = ld.next()
        assert b.shape == (16, 8, 8, 3)
        assert -1.0 <= b.min() and b.max() <= 1.0
        ld.close()

    def test_no_normalize_keeps_raw_scale(self, tmp_path):
        """normalize=False reproduces the reference's raw-pixel feed
        (SURVEY.md §2.4 #1)."""
        paths = _write_dataset(tmp_path)
        kw = dict(LOADER_KW, normalize=False)
        ld = PythonLoader(paths, record_dtype="float64", **kw)
        b = ld.next()
        assert b.max() > 10.0  # raw [0,255] scale
        ld.close()

    def test_one_epoch_mode(self, tmp_path):
        paths = _write_dataset(tmp_path, n=40)
        kw = dict(LOADER_KW, loop=False)
        ld = PythonLoader(paths, record_dtype="float64", **kw)
        batches = list(ld)
        assert len(batches) == 2  # 40 examples -> 2 full batches of 16
        ld.close()

    @pytest.mark.parametrize("loader_kind", ["native", "python"])
    def test_labeled_batches(self, tmp_path, loader_kind):
        """label_feature yields (images, int32 labels) pairs — the int64
        feature the reference comments out (image_input.py:44)."""
        paths = write_image_tfrecords(
            str(tmp_path / "data"), num_examples=48, image_size=8,
            channels=3, num_shards=3, num_classes=10)
        kw = dict(LOADER_KW, label_feature="label")
        if loader_kind == "native":
            native = pytest.importorskip("dcgan_tpu.data.native")
            ld = native.NativeLoader(paths, record_dtype="float64", **kw)
        else:
            ld = PythonLoader(paths, record_dtype="float64", **kw)
        try:
            for _ in range(3):
                imgs, labels = ld.next()
                assert imgs.shape == (16, 8, 8, 3)
                assert imgs.dtype == np.float32
                assert -1.0 <= imgs.min() and imgs.max() <= 1.0
                assert labels.shape == (16,) and labels.dtype == np.int32
                assert (0 <= labels).all() and (labels < 10).all()
        finally:
            ld.close()

    def test_tiny_dataset_no_deadlock(self, tmp_path):
        """Regression: exactly batch-many examples, one shard, loop=False.

        The reader-completion check used to compare readers_done_ against
        readers_.size(), which the spawned thread can read stale (emplace_back
        publishes the vector size unsynchronized with the thread it starts) —
        a reader finishing a tiny shard before the constructor returned would
        never set done_ and Next() hung forever. 30 fresh loaders catch the
        race reliably; each must yield its single batch then EOF."""
        native = pytest.importorskip("dcgan_tpu.data.native")
        paths = write_image_tfrecords(
            str(tmp_path / "tiny"), num_examples=6, image_size=8,
            num_shards=1, num_classes=2)
        for trial in range(30):
            ld = native.NativeLoader(
                paths, batch=6, example_shape=(8, 8, 3),
                min_after_dequeue=2, n_threads=1, seed=trial,
                normalize=False, loop=False, label_feature="label")
            try:
                first = ld.next()
                assert first is not None, f"trial {trial}: lost final batch"
                imgs, labels = first
                assert imgs.shape == (6, 8, 8, 3)
                assert sorted(labels.tolist()).count(0) + \
                    sorted(labels.tolist()).count(1) == 6
                assert ld.next() is None  # clean EOF after the only batch
            finally:
                ld.close()

    def test_empty_feature_name_skips_non_bytes_entries(self, tmp_path):
        """feature_name='' means 'first bytes feature' — an int64 entry that
        happens to precede the image in map order must be skipped, not fail."""
        native = pytest.importorskip("dcgan_tpu.data.native")
        img = np.full((8, 8, 3), 128.0).astype("float64").tobytes()
        path = str(tmp_path / "mixed.tfrecord")
        # label entry serialized before the image entry
        tfrecord.write_tfrecords(path, [serialize_example(
            {"label": [3], "image_raw": [img]}) for _ in range(16)])
        kw = dict(LOADER_KW, feature_name="", min_after_dequeue=4)
        with native.NativeLoader([path], record_dtype="float64", **kw) as ld:
            b = ld.next()
            assert b.shape == (16, 8, 8, 3)
            np.testing.assert_allclose(b, 128.0 / 127.5 - 1.0, atol=1e-6)

    def test_native_label_out_of_range_errors(self, tmp_path):
        """Labels ride a float32 slot; ids beyond 2^24 must hard-error rather
        than silently round."""
        native = pytest.importorskip("dcgan_tpu.data.native")
        img = np.zeros((8, 8, 3)).astype("float64").tobytes()
        path = str(tmp_path / "big.tfrecord")
        tfrecord.write_tfrecords(path, [serialize_example(
            {"image_raw": [img], "label": [(1 << 24) + 1]})])
        kw = dict(LOADER_KW, label_feature="label")
        with native.NativeLoader([path], record_dtype="float64", **kw) as ld:
            with pytest.raises(native.NativeLoaderError, match="out of range"):
                ld.next()

    def test_labeled_missing_label_feature_errors(self, tmp_path):
        # unlabeled shards + label_feature set -> hard error, not zeros
        paths = _write_dataset(tmp_path, n=8, shards=1)
        kw = dict(LOADER_KW, label_feature="label")
        native = pytest.importorskip("dcgan_tpu.data.native")
        with native.NativeLoader(paths, record_dtype="float64", **kw) as ld:
            with pytest.raises(native.NativeLoaderError, match="label"):
                ld.next()

    def test_python_label_out_of_range_errors(self, tmp_path):
        # the fallback loader enforces the same bound as the native one
        img = np.zeros((8, 8, 3)).astype("float64").tobytes()
        path = str(tmp_path / "big.tfrecord")
        tfrecord.write_tfrecords(path, [serialize_example(
            {"image_raw": [img], "label": [-1]})])
        ld = PythonLoader([path], record_dtype="float64",
                          **dict(LOADER_KW, label_feature="label"))
        try:
            with pytest.raises(RuntimeError, match="out of range"):
                ld.next()
        finally:
            ld.close()


class TestPipeline:
    def test_shard_for_process(self):
        paths = [f"s{i}" for i in range(5)]
        assert shard_for_process(paths, 0, 2) == ["s0", "s2", "s4"]
        assert shard_for_process(paths, 1, 2) == ["s1", "s3"]
        # fewer shards than processes: everyone reads everything
        assert shard_for_process(["s0"], 3, 8) == ["s0"]

    def test_list_shards_empty_dir(self, tmp_path):
        os.makedirs(tmp_path / "empty", exist_ok=True)
        with pytest.raises(FileNotFoundError):
            list_shards(str(tmp_path / "empty"))

    def test_process_local_box_spatial_block(self):
        """The geometry behind the 4-process ring test (VERDICT r4 #3b):
        with a (data=2, model=4) spatial mesh split across 4 hypothetical
        2-device processes, a process owns a batch-slice x height-slice
        BLOCK, not batch/nproc x full height — the assumption that
        silently mis-assembled global arrays before process_local_box."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dcgan_tpu.data.pipeline import process_local_box
        from dcgan_tpu.parallel import make_mesh
        from dcgan_tpu.config import MeshConfig

        mesh = make_mesh(MeshConfig(model=4, spatial=True))  # (2, 4)
        sh = NamedSharding(mesh, P("data", "model", None, None))
        shape = (16, 16, 16, 3)
        dev = mesh.devices  # [2, 4] grid
        # "process 0" = first half of data-row 0: batch 0:8, height 0:8
        box = process_local_box(sh, shape, devices=dev[0, :2])
        assert box == (slice(0, 8), slice(0, 8), slice(0, 16), slice(0, 3))
        # "process 3" = second half of data-row 1: batch 8:16, height 8:16
        box = process_local_box(sh, shape, devices=dev[1, 2:])
        assert box == (slice(8, 16), slice(8, 16), slice(0, 16),
                       slice(0, 3))
        # a full mesh row (the 2-process-x-4-device layout): full height
        box = process_local_box(sh, shape, devices=dev[0, :])
        assert box == (slice(0, 8), slice(0, 16), slice(0, 16),
                       slice(0, 3))
        # labels replicate over "model": same batch slice whichever half
        # of the row the process owns
        lsh = NamedSharding(mesh, P("data"))
        assert process_local_box(lsh, (16,), devices=dev[0, :2]) == \
            process_local_box(lsh, (16,), devices=dev[0, 2:]) == \
            (slice(0, 8),)
        # a diagonal (non-box) device set is rejected, not mis-assembled
        with pytest.raises(ValueError, match="tile a box"):
            process_local_box(sh, shape,
                              devices=[dev[0, 0], dev[1, 1]])

    def test_make_dataset_sharded_delivery(self, tmp_path):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from dcgan_tpu.parallel import make_mesh
        _write_dataset(tmp_path)
        cfg = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                         batch_size=16, min_after_dequeue=8, n_threads=2,
                         use_native=True)
        mesh = make_mesh()
        sh = NamedSharding(mesh, P("data", None, None, None))
        it = make_dataset(cfg, sh)
        b = next(it)
        assert b.shape == (16, 8, 8, 3)
        assert b.sharding == sh
        # each of the 8 data-axis shards holds 2 examples
        assert {s.data.shape for s in b.addressable_shards} == {(2, 8, 8, 3)}
        b2 = next(it)
        assert b2.shape == (16, 8, 8, 3)

    def test_float64_on_accelerator_warns(self, tmp_path, monkeypatch):
        """The parity wire format is the one most likely to starve a chip;
        the pipeline must say so when a float64 corpus meets a non-CPU
        consumer (VERDICT r3 #6) — and stay quiet for uint8."""
        import types
        import warnings

        import jax

        _write_dataset(tmp_path)
        fake_tpu = [types.SimpleNamespace(platform="tpu")]
        monkeypatch.setattr(jax, "devices", lambda *a, **k: fake_tpu)
        cfg = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                         batch_size=16, min_after_dequeue=8, n_threads=2)
        with pytest.warns(RuntimeWarning, match="float64"):
            next(make_dataset(cfg))
        # uint8 records: no warning
        write_image_tfrecords(
            str(tmp_path / "data8"), num_examples=48, image_size=8,
            channels=3, num_shards=3, record_dtype="uint8")
        cfg8 = DataConfig(data_dir=str(tmp_path / "data8"), image_size=8,
                          batch_size=16, min_after_dequeue=8, n_threads=2,
                          record_dtype="uint8")
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            next(make_dataset(cfg8))

    def test_make_dataset_labeled_delivery(self, tmp_path):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from dcgan_tpu.parallel import make_mesh
        write_image_tfrecords(
            str(tmp_path / "data"), num_examples=48, image_size=8,
            channels=3, num_shards=3, num_classes=4)
        cfg = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                         batch_size=16, min_after_dequeue=8, n_threads=2,
                         label_feature="label")
        mesh = make_mesh()
        sh = NamedSharding(mesh, P("data", None, None, None))
        lsh = NamedSharding(mesh, P("data"))
        imgs, labels = next(make_dataset(cfg, sh, lsh))
        assert imgs.shape == (16, 8, 8, 3) and imgs.sharding == sh
        assert labels.shape == (16,) and labels.sharding == lsh
        assert (np.asarray(labels) < 4).all()

    def test_make_dataset_label_range_guard(self, tmp_path):
        """num_classes mismatch (e.g. a 10-class dataset fed to a 4-class
        model) must fail host-side: on device an out-of-range label silently
        one-hots to zeros or clamps the cBN table gather."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from dcgan_tpu.parallel import make_mesh
        write_image_tfrecords(
            str(tmp_path / "data"), num_examples=48, image_size=8,
            channels=3, num_shards=3, num_classes=10)
        cfg = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                         batch_size=16, min_after_dequeue=8, n_threads=2,
                         label_feature="label", num_classes=4)
        mesh = make_mesh()
        sh = NamedSharding(mesh, P("data", None, None, None))
        lsh = NamedSharding(mesh, P("data"))
        with pytest.raises(ValueError, match="out of range for num_classes"):
            next(make_dataset(cfg, sh, lsh))
        # matching num_classes passes
        ok = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                        batch_size=16, min_after_dequeue=8, n_threads=2,
                        label_feature="label", num_classes=10)
        imgs, labels = next(make_dataset(ok, sh, lsh))
        assert (np.asarray(labels) < 10).all()

    def test_make_dataset_labeled_requires_label_sharding(self, tmp_path):
        write_image_tfrecords(
            str(tmp_path / "data"), num_examples=8, image_size=8,
            channels=3, num_shards=1, num_classes=4)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from dcgan_tpu.parallel import make_mesh
        cfg = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                         batch_size=8, min_after_dequeue=4,
                         label_feature="label")
        sh = NamedSharding(make_mesh(), P("data", None, None, None))
        with pytest.raises(ValueError, match="label_sharding"):
            next(make_dataset(cfg, sh))

    def test_synthetic_batches(self):
        it = synthetic_batches(4, image_size=8)
        b = next(it)
        assert b.shape == (4, 8, 8, 3) and b.dtype == np.float32
        assert -1.0 <= b.min() and b.max() <= 1.0

    def test_synthetic_batches_pool_cycles(self):
        """After `pool` fresh batches the stream cycles them (host-RNG cost
        bounded); pool=0 keeps every batch fresh."""
        it = synthetic_batches(2, image_size=8, pool=3)
        first = [next(it) for _ in range(3)]
        second = [next(it) for _ in range(3)]
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a, b)
        # distinct batches within the pool
        assert np.abs(first[0] - first[1]).max() > 0
        fresh = synthetic_batches(2, image_size=8, pool=0)
        a = [next(fresh) for _ in range(4)]
        assert np.abs(a[0] - a[3]).max() > 0
        with pytest.raises(ValueError, match="pool"):
            next(synthetic_batches(2, image_size=8, pool=-1))

    def test_synthetic_labeled_batches(self):
        imgs, labels = next(synthetic_batches(4, image_size=8, num_classes=5))
        assert imgs.shape == (4, 8, 8, 3)
        assert labels.shape == (4,) and labels.dtype == np.int32
        assert (labels < 5).all()


class TestManifestAdoption:
    """read_manifest (pipeline.py): read-only consumers (evals,
    fid_trajectory) adopt the dataset's recorded wire format instead of
    requiring a --record_dtype flag they don't have."""

    def test_read_manifest_absent_then_present(self, tmp_path):
        from dcgan_tpu.data.pipeline import read_manifest

        assert read_manifest(str(tmp_path)) == {}
        (tmp_path / "dataset.json").write_text(
            '{"record_dtype": "uint8", "feature_name": "image_raw"}')
        assert read_manifest(str(tmp_path))["record_dtype"] == "uint8"

    def test_uint8_dataset_loads_via_manifest(self, tmp_path):
        """The evals-side construction: DataConfig derived from dataset.json
        must load a uint8-record dataset that the float64 default would
        reject at the manifest check."""
        import json

        from dcgan_tpu.data.pipeline import read_manifest

        d = str(tmp_path)
        write_image_tfrecords(d, num_examples=8, image_size=8,
                              record_dtype="uint8", num_shards=1)
        (tmp_path / "dataset.json").write_text(json.dumps(
            {"record_dtype": "uint8", "image_size": 8, "channels": 3,
             "feature_name": "image_raw"}))
        m = read_manifest(d)
        cfg = DataConfig(data_dir=d, image_size=8, batch_size=4,
                         min_after_dequeue=4,
                         record_dtype=m.get("record_dtype", "float64"),
                         feature_name=m.get("feature_name", "image_raw"))
        batch = next(iter(make_dataset(cfg)))
        assert batch.shape == (4, 8, 8, 3)
        # float64 default would have been rejected by check_manifest
        bad = DataConfig(data_dir=d, image_size=8, batch_size=4,
                         min_after_dequeue=4)
        with pytest.raises(ValueError, match="record_dtype"):
            next(iter(make_dataset(bad)))


class TestDevicePrefetcher:
    """The background device-feed queue (ISSUE 2 tentpole): depth bound,
    ordering, mid-epoch shutdown, and producer-error propagation."""

    @staticmethod
    def _sharding():
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dcgan_tpu.parallel import make_mesh
        return NamedSharding(make_mesh(), P("data", None, None, None))

    @staticmethod
    def _host_batches(n, size=8):
        rng = np.random.default_rng(0)
        return [rng.uniform(-1, 1, (16, size, size, 3)).astype(np.float32)
                for _ in range(n)]

    def test_ordering_and_delivery(self):
        sh = self._sharding()
        batches = self._host_batches(6)
        pf = DevicePrefetcher(iter(batches), sh, depth=2)
        out = list(pf)
        assert len(out) == 6
        for host, dev in zip(batches, out):
            assert dev.sharding == sh
            np.testing.assert_array_equal(np.asarray(dev), host)
        pf.close()

    def test_depth_bounds_producer_runahead(self):
        """With a stalled consumer the producer parks at depth batches
        queued (+1 in flight) — the queue is bounded, not a hoard of
        device memory."""
        import itertools
        import time as _time

        sh = self._sharding()
        produced = itertools.count()
        count = {"n": 0}

        def host_iter():
            for b in self._host_batches(50):
                count["n"] = next(produced) + 1
                yield b

        pf = DevicePrefetcher(host_iter(), sh, depth=3)
        deadline = _time.time() + 5.0
        while count["n"] < 4 and _time.time() < deadline:
            _time.sleep(0.01)
        _time.sleep(0.3)  # give an unbounded producer time to run away
        assert count["n"] <= 3 + 2  # depth queued + one assembling + slack
        first = next(pf)
        assert first.shape == (16, 8, 8, 3)
        pf.close()

    def test_mid_epoch_close_stops_producer(self):
        closed = {"owner": False}

        class Owner:
            def close(self):
                closed["owner"] = True

        sh = self._sharding()
        pf = DevicePrefetcher(iter(self._host_batches(50)), sh, depth=2,
                              owner=Owner())
        next(pf)  # mid-epoch
        pf.close()
        assert closed["owner"]
        assert not pf._thread.is_alive()
        with pytest.raises(StopIteration):
            next(pf)
        pf.close()  # idempotent

    def test_producer_error_propagates_with_type(self):
        sh = self._sharding()

        def bad_iter():
            yield self._host_batches(1)[0]
            raise ValueError("decode exploded")

        pf = DevicePrefetcher(bad_iter(), sh, depth=2)
        next(pf)
        with pytest.raises(ValueError, match="decode exploded"):
            while True:
                next(pf)

    def test_label_gate_runs_on_producer_thread(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dcgan_tpu.parallel import make_mesh

        mesh = make_mesh()
        sh = NamedSharding(mesh, P("data", None, None, None))
        lsh = NamedSharding(mesh, P("data"))
        imgs = self._host_batches(1)[0]
        labels = np.asarray([7] * 16, dtype=np.int32)  # >= num_classes
        pf = DevicePrefetcher(iter([(imgs, labels)]), sh, lsh, depth=2,
                              num_classes=4)
        with pytest.raises(ValueError, match="out of range"):
            next(pf)

    def test_make_dataset_returns_prefetcher_and_legacy_path(self, tmp_path):
        _write_dataset(tmp_path)
        sh = self._sharding()
        cfg = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                         batch_size=16, min_after_dequeue=8, n_threads=2,
                         prefetch_device_batches=2)
        it = make_dataset(cfg, sh)
        assert isinstance(it, DevicePrefetcher)
        b = next(it)
        assert b.shape == (16, 8, 8, 3) and b.sharding == sh
        it.close()
        legacy = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                            batch_size=16, min_after_dequeue=8, n_threads=2,
                            prefetch_device_batches=0)
        it2 = make_dataset(legacy, sh)
        assert not isinstance(it2, DevicePrefetcher)
        b2 = next(it2)
        assert b2.shape == (16, 8, 8, 3) and b2.sharding == sh

    def test_close_joins_producer_before_release(self, tmp_path):
        """Regression: close() used to destroy the native loader while the
        producer thread could still be inside `dcgan_loader_next` — a
        use-after-free that segfaulted the whole test process
        intermittently. The fixed order (owner.stop -> join -> owner.close)
        must leave the producer joined on every close."""
        _write_dataset(tmp_path)
        sh = self._sharding()
        cfg = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                         batch_size=16, min_after_dequeue=8, n_threads=2,
                         prefetch_device_batches=2)
        for _ in range(10):
            it = make_dataset(cfg, sh)
            next(it)
            it.close()
            assert not it._thread.is_alive()
            assert it._owner is None

    def test_one_epoch_drains_to_stop_iteration(self, tmp_path):
        _write_dataset(tmp_path, n=32, shards=2)
        sh = self._sharding()
        cfg = DataConfig(data_dir=str(tmp_path / "data"), image_size=8,
                         batch_size=16, min_after_dequeue=8, n_threads=2,
                         loop=False, prefetch_device_batches=2)
        out = list(make_dataset(cfg, sh))
        assert len(out) == 2  # 32 examples / 16 per batch, no repeat
