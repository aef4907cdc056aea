"""Serving plane (ISSUE 9): bucket ladder, continuous batcher, lifecycle,
and the zero-recompile-after-warmup guarantee.

Batcher semantics are pinned against a fake source (no device work, so
the units are milliseconds): bucket snap + padding, deadline flush,
drop-oldest backpressure, FIFO drain-on-stop, oversized-request
chunking. The end-to-end tier serves a real tiny checkpoint through the
framework sampler and pins (a) request/response parity with generate.py
for the same latent rows and (b) zero compile requests after the AOT
bucket warmup, measured through CompileCacheMonitor under a live
persistent cache — every served batch hits a precompiled bucket.
"""

import os
import threading
import time

import numpy as np
import pytest

from dcgan_tpu.serve.buckets import (
    BucketLadder,
    build_ladder,
    parse_buckets,
    sampler_plan,
)
from dcgan_tpu.serve.server import (
    SamplerServer,
    ServeError,
    ServeOverloadError,
)


class FakeSource:
    """No-device source: images encode their latent's first coordinate so
    tests can assert per-request routing through shared batches."""

    def __init__(self, granule=1, z_dim=4, num_classes=0, block=None):
        self.granule = granule
        self.z_dim = z_dim
        self.num_classes = num_classes
        self.block = block            # optional Event: stall dispatches
        self.calls = []               # (bucket, z.shape[0]) per dispatch
        self.label_rows = []

    def prepare(self):
        return {"source": "fake", "step": 0, "weights": "live"}

    def bucket_plan(self, ladder):
        return []

    def bind(self, compiled):
        pass

    def sample(self, bucket, z, labels=None):
        if self.block is not None:
            self.block.wait()
        self.calls.append((bucket, z.shape[0]))
        if labels is not None:
            self.label_rows.append(np.asarray(labels))
        img = np.zeros((bucket, 2, 2, 1), np.float32)
        img[:, 0, 0, 0] = z[:, 0]
        return img


_LIVE_SERVERS = []


def make_server(source=None, **kw):
    kw.setdefault("ladder", BucketLadder((4, 8), 1))
    kw.setdefault("max_wait_ms", 5.0)
    s = SamplerServer(source if source is not None else FakeSource(), **kw)
    _LIVE_SERVERS.append(s)
    return s


@pytest.fixture(autouse=True)
def _reap_servers():
    """A failing test must never leave a blocked worker alive holding the
    global tripwire dispatch scope — unblock and stop every server this
    test created."""
    yield
    while _LIVE_SERVERS:
        s = _LIVE_SERVERS.pop()
        block = getattr(s.source, "block", None)
        if block is not None:
            block.set()
        try:
            s.stop(drain=False, timeout=10.0)
        except Exception:
            pass


class TestBucketLadder:
    def test_build_and_snap(self):
        lad = build_ladder(64, 8)
        assert lad.buckets == (8, 16, 32, 64)
        assert lad.snap(1) == 8 and lad.snap(9) == 16 and lad.snap(64) == 64
        # past the top rung the caller chunks: snap returns the top
        assert lad.snap(65) == 64

    def test_granule_alignment_and_validation(self):
        assert build_ladder(20, 8).buckets == (8, 16, 24)  # top rounded up
        with pytest.raises(ValueError, match="granule"):
            BucketLadder((4, 10), granule=4)
        with pytest.raises(ValueError, match="ascending"):
            BucketLadder((8, 8, 16), granule=1)
        with pytest.raises(ValueError, match="empty"):
            BucketLadder(())
        with pytest.raises(ValueError):
            build_ladder(0)

    def test_parse_buckets(self):
        assert parse_buckets("16,8,32").buckets == (8, 16, 32)
        with pytest.raises(ValueError, match="comma-separated"):
            parse_buckets("8;16")

    def test_sampler_plan_rows(self):
        fn = object()
        rows = sampler_plan(fn, BucketLadder((2, 4), 1), z_dim=7)
        assert [name for name, _, _ in rows] == ["sampler@b2", "sampler@b4"]
        for (_, f, args), b in zip(rows, (2, 4)):
            assert f is fn and args[0].shape == (b, 7)
        rows = sampler_plan(fn, BucketLadder((2,), 1), z_dim=7,
                            state={"s": 1}, num_classes=3)
        _, _, args = rows[0]
        assert args[0] == {"s": 1} and args[1].shape == (2, 7) \
            and args[2].shape == (2,)


class TestBatcher:
    def test_coalesce_snap_and_padding(self):
        """Requests coalesce into one bucket-snapped batch; every request
        gets exactly its own rows back."""
        src = FakeSource()
        s = make_server(src, max_wait_ms=20.0)
        s.start(timeout=10)
        r1 = s.submit(z=np.full((3, 4), 0.5, np.float32))
        r2 = s.submit(z=np.full((2, 4), -0.25, np.float32))
        a, b = r1.result(5), r2.result(5)
        s.stop()
        assert src.calls == [(8, 8)]        # 5 rows -> bucket 8, one batch
        assert a.shape == (3, 2, 2, 1) and b.shape == (2, 2, 2, 1)
        assert np.all(a[:, 0, 0, 0] == 0.5)
        assert np.all(b[:, 0, 0, 0] == -0.25)
        assert r1.meta["buckets"] == [8] and r1.meta["total_ms"] > 0
        rep = s.report()
        assert rep["serve/batches"] == 1 and rep["serve/pad_frac"] == 3 / 8

    def test_deadline_flush_bounds_latency(self):
        """A lone small request must not wait for batchmates past
        max_wait_ms."""
        s = make_server(FakeSource(), max_wait_ms=30.0)
        s.start(timeout=10)
        t0 = time.monotonic()
        r = s.submit(num_images=1)
        r.result(5)
        waited = (time.monotonic() - t0) * 1e3
        s.stop()
        assert 20.0 <= waited < 2000.0      # flushed by deadline, not full
        assert r.meta["buckets"] == [4]     # snapped to the SMALL rung

    def test_full_top_bucket_flushes_immediately(self):
        """Work filling the largest bucket dispatches without waiting for
        the deadline."""
        s = make_server(FakeSource(), max_wait_ms=10_000.0)
        s.start(timeout=10)
        r = s.submit(num_images=8)
        r.result(timeout=5)                 # way under the 10 s deadline
        s.stop()
        assert r.meta["buckets"] == [8]

    def test_oversized_request_chunks_fifo(self):
        """A request past the top rung chunks across dispatches; a later
        arrival never overtakes the earlier request's chunks."""
        block = threading.Event()
        src = FakeSource(block=block)
        s = make_server(src, max_wait_ms=1.0)
        s.start(timeout=10)
        big = s.submit(z=np.full((19, 4), 0.75, np.float32))
        small = s.submit(z=np.full((2, 4), -0.5, np.float32))
        block.set()
        b, sm = big.result(5), small.result(5)
        s.stop()
        assert b.shape[0] == 19 and np.all(b[:, 0, 0, 0] == 0.75)
        assert sm.shape[0] == 2 and np.all(sm[:, 0, 0, 0] == -0.5)
        assert big.meta["buckets"][:2] == [8, 8]  # chunked at the top rung
        # FIFO: the big request's final chunk rides no later than the
        # small request's rows
        assert src.calls[0] == (8, 8) and src.calls[1] == (8, 8)

    def test_drop_oldest_backpressure(self):
        """Queue full -> the OLDEST pending request is shed with
        ServeOverloadError; newest work keeps its place."""
        block = threading.Event()
        src = FakeSource(block=block)
        s = make_server(src, max_queue=2, max_wait_ms=1.0)
        s.start(timeout=10)
        # stall the worker on a first batch so later submits pile up
        first = s.submit(num_images=1)
        time.sleep(0.1)                     # worker is now blocked in sample
        r1 = s.submit(num_images=1)
        r2 = s.submit(num_images=1)
        r3 = s.submit(num_images=1)         # displaces r1
        block.set()
        with pytest.raises(ServeOverloadError):
            r1.result(5)
        assert r2.result(5).shape[0] == 1
        assert r3.result(5).shape[0] == 1
        first.result(5)
        s.stop()
        assert s.dropped == 1
        assert s.counters().serve_dropped == 1

    def test_drain_on_stop_completes_fifo(self):
        """stop(drain=True) finishes every queued request in submit
        order, then the worker exits; post-stop submits are rejected."""
        block = threading.Event()
        src = FakeSource(block=block)
        s = make_server(src, max_wait_ms=10_000.0, max_queue=64)
        s.start(timeout=10)
        resps = [s.submit(z=np.full((2, 4), i / 10, np.float32))
                 for i in range(5)]
        stopper = threading.Thread(target=lambda: s.stop(drain=True))
        stopper.start()
        time.sleep(0.05)
        block.set()
        stopper.join(timeout=10)
        assert not stopper.is_alive()
        order = []
        for i, r in enumerate(resps):
            imgs = r.result(1)
            order.append(float(imgs[0, 0, 0, 0]))
            assert imgs.shape[0] == 2
        assert order == pytest.approx([i / 10 for i in range(5)],
                                      abs=1e-6)     # FIFO held
        late = s.submit(num_images=1)
        with pytest.raises(ServeError, match="stopped"):
            late.result(1)
        assert s.counters().serve_completed == 5

    def test_worker_failure_poisons_server(self):
        class ExplodingSource(FakeSource):
            def sample(self, bucket, z, labels=None):
                raise RuntimeError("device on fire")

        s = make_server(ExplodingSource(), max_wait_ms=1.0)
        s.start(timeout=10)
        r = s.submit(num_images=1)
        with pytest.raises(RuntimeError, match="device on fire"):
            r.result(5)
        with pytest.raises(ServeError, match="failed"):
            s.submit(num_images=1).result(1)
        with pytest.raises(ServeError):
            s.stop()

    def test_conditional_labels_concatenate_and_pad(self):
        src = FakeSource(num_classes=3)
        s = make_server(src, max_wait_ms=20.0)
        s.start(timeout=10)
        r1 = s.submit(num_images=2, labels=np.array([1, 2]))
        r2 = s.submit(num_images=1)          # unlabeled -> class 0
        r1.result(5), r2.result(5)
        s.stop()
        (lbl,) = src.label_rows
        # 3 rows snap to bucket 4: one zero pad row after the coalesced
        # per-request labels (unlabeled requests default to class 0)
        assert lbl.tolist() == [1, 2, 0, 0]

    def test_submit_validation(self):
        s = make_server(FakeSource())
        with pytest.raises(ValueError, match="num_images"):
            s.submit(num_images=0)
        with pytest.raises(ValueError, match="z must be"):
            s.submit(z=np.zeros((4,), np.float32))
        with pytest.raises(ValueError, match="z width"):
            s.submit(z=np.zeros((2, 7), np.float32))   # z_dim is 4
        with pytest.raises(ValueError, match="labels length"):
            s.submit(num_images=3, labels=np.array([1, 2]))
        with pytest.raises(ValueError, match="max_queue"):
            make_server(FakeSource(), max_queue=0)

    def test_bad_width_cold_start_submit_fails_only_itself(self):
        """A wrong-width z submitted during the cold-start window (before
        the source has resolved z_dim) fails ITS response at assembly —
        it must never poison the server for other clients."""
        class ColdSource(FakeSource):
            def __init__(self):
                super().__init__()
                self.z_dim = 0            # unknown until prepare()

            def prepare(self):
                self.z_dim = 4
                return super().prepare()

        s = make_server(ColdSource(), max_wait_ms=5.0)
        bad = s.submit(z=np.zeros((2, 7), np.float32))  # width check skipped
        good = s.submit(num_images=1)
        s.start(timeout=10)
        with pytest.raises(ValueError, match="z width"):
            bad.result(5)
        assert good.result(5).shape[0] == 1   # server still serving
        later = s.submit(num_images=1)
        assert later.result(5).shape[0] == 1
        s.stop()

    def test_drop_oldest_spares_partially_dispatched_request(self):
        """Backpressure must not shed a request whose earlier chunks the
        device already computed — the oldest NEVER-dispatched request is
        the victim; with nothing undispatched, the NEW request is
        rejected instead."""
        class BlockNth(FakeSource):
            """Blocks only the n-th dispatch (the base class's `block`
            stalls EVERY dispatch, which would stop chunk 1 too)."""

            def __init__(self, n):
                super().__init__()
                self.block = threading.Event()
                self.n = n
                self.entered = threading.Event()

            def sample(self, bucket, z, labels=None):
                if len(self.calls) + 1 == self.n:
                    self.entered.set()
                    self.block.wait()
                self.calls.append((bucket, z.shape[0]))
                img = np.zeros((bucket, 2, 2, 1), np.float32)
                img[:, 0, 0, 0] = z[:, 0]
                return img

        src = BlockNth(2)                  # block the SECOND dispatch
        s = make_server(src, max_queue=2, max_wait_ms=1.0)
        s.start(timeout=10)
        big = s.submit(z=np.full((19, 4), 0.5, np.float32))  # chunks 8,8,3
        assert src.entered.wait(5)         # chunk 1 done, chunk 2 in flight
        r2 = s.submit(num_images=1)
        r3 = s.submit(num_images=1)        # queue full: sheds r2, NOT big
        src.block.set()
        assert big.result(5).shape[0] == 19
        assert np.all(big.result(0)[:, 0, 0, 0] == 0.5)
        with pytest.raises(ServeOverloadError):
            r2.result(5)
        assert r3.result(5).shape[0] == 1
        s.stop()
        assert s.dropped == 1

    def test_stop_timeout_raises_instead_of_claiming_clean_drain(self):
        """A drain that outlives the join timeout must raise, never
        return success over a still-running worker."""
        block = threading.Event()
        s = make_server(FakeSource(block=block), max_wait_ms=1.0)
        s.start(timeout=10)
        r = s.submit(num_images=1)
        time.sleep(0.05)                   # worker now blocked in sample
        with pytest.raises(TimeoutError, match="drain did not finish"):
            s.stop(drain=True, timeout=0.2)
        block.set()                        # now the drain can finish
        s.stop(drain=True, timeout=10.0)
        assert r.result(1).shape[0] == 1


@pytest.fixture(scope="module")
def trained_ckpt(tmp_path_factory):
    from dcgan_tpu.config import ModelConfig, TrainConfig
    from dcgan_tpu.train.trainer import train

    root = tmp_path_factory.mktemp("serve")
    cfg = TrainConfig(
        model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                          compute_dtype="float32"),
        batch_size=8,
        checkpoint_dir=str(root / "ckpt"),
        sample_dir=str(root / "samples"),
        sample_every_steps=0, save_summaries_secs=1e9, save_model_secs=1e9,
        log_every_steps=0, tensorboard=False)
    train(cfg, synthetic_data=True, max_steps=1)
    return str(root / "ckpt")


OVERRIDES = {"output_size": 16, "gf_dim": 8, "df_dim": 8}


class TestServeEndToEnd:
    def test_zero_recompiles_after_bucket_warmup(self, trained_ckpt,
                                                 tmp_path):
        """The acceptance pin: under a live persistent compile cache,
        NO compile request fires after the AOT bucket warmup — every
        served batch (odd sizes included) rides a precompiled bucket
        executable, input-transfer programs primed at cold start."""
        from dcgan_tpu.serve import CheckpointSource

        src = CheckpointSource(trained_ckpt, overrides=OVERRIDES)
        s = SamplerServer(src, max_batch=16,
                          cache_dir=str(tmp_path / "cc"), max_wait_ms=2.0)
        s.start(timeout=300)
        assert s.ladder.buckets == (8, 16)   # granule 8: the test mesh
        for n in (3, 11, 5, 16, 2, 8):
            imgs = s.submit(num_images=n, seed=n).result(timeout=60)
            assert imgs.shape == (n, 16, 16, 3)
        rep = s.report()
        s.stop()
        assert rep["serve/recompiles_after_warmup"] == 0
        assert rep["perf/compile_cache_requests"] > 0  # warmup was real
        assert rep["serve/completed"] == 6
        assert rep["serve/p99_ms"] >= rep["serve/p50_ms"] > 0
        assert set(s.compile_ms) == {"sampler@b8", "sampler@b16"}

    def test_request_response_parity_with_generate(self, trained_ckpt,
                                                   tmp_path):
        """Submitting the exact latent rows generate.py draws for a seed
        returns byte-identical images — serving is the same program, not
        a lookalike."""
        import jax

        from dcgan_tpu.generate import build_parser, generate
        from dcgan_tpu.serve import CheckpointSource

        z = np.asarray(jax.random.uniform(
            jax.random.fold_in(jax.random.key(0), 0), (8, 100),
            minval=-1.0, maxval=1.0))
        s = SamplerServer(CheckpointSource(trained_ckpt,
                                           overrides=OVERRIDES),
                          max_batch=8, max_wait_ms=2.0)
        s.start(timeout=300)
        served = s.submit(z=z).result(timeout=60)
        s.stop()

        args = build_parser().parse_args(
            ["--checkpoint_dir", trained_ckpt,
             "--out_dir", str(tmp_path / "out"),
             "--num_images", "8", "--batch_size", "8", "--grid", "0",
             "--npz", str(tmp_path / "g.npz"), "--seed", "0",
             "--output_size", "16", "--gf_dim", "8", "--df_dim", "8"])
        generate(args)
        gen = np.load(tmp_path / "g.npz")["images"]
        assert served.shape == gen.shape == (8, 16, 16, 3)
        np.testing.assert_array_equal(served, gen)

    def test_requests_accepted_during_cold_start(self, trained_ckpt):
        """Lifecycle: submits queued while the plane is still cold serve
        as soon as it turns warm."""
        from dcgan_tpu.serve import CheckpointSource

        from dcgan_tpu.serve.worker import ServeWorker

        s = SamplerServer(CheckpointSource(trained_ckpt,
                                           overrides=OVERRIDES),
                          max_batch=8, max_wait_ms=2.0)
        # start the worker without blocking on readiness (what start()
        # does minus the wait), then submit while the plane is cold
        s._started = True
        s._worker = ServeWorker(s)
        s._worker.start()
        r = s.submit(num_images=2, seed=1)
        assert s._ready.wait(300)
        imgs = r.result(timeout=60)
        s.stop()
        assert imgs.shape == (2, 16, 16, 3)

    def test_missing_checkpoint_fails_start_loudly(self, tmp_path):
        from dcgan_tpu.serve import CheckpointSource

        s = SamplerServer(CheckpointSource(str(tmp_path / "nope"),
                                           overrides=OVERRIDES),
                          max_batch=8)
        with pytest.raises(ServeError, match="no checkpoint"):
            s.start(timeout=300)
        # queued-in-the-dark submits are rejected, not stranded
        with pytest.raises(ServeError):
            s.submit(num_images=1).result(1)


@pytest.mark.slow
class TestArtifactServing:
    def test_artifact_source_serves_without_checkpoint(self, trained_ckpt,
                                                       tmp_path):
        """Cold start from a .jaxexport artifact + sidecar alone: the
        sidecar's serving block supplies z_dim and the bucket-ladder
        hint, and the served images match the artifact's own call."""
        import jax

        from dcgan_tpu.export import export_sampler, load_sampler
        from dcgan_tpu.serve import ArtifactSource

        out = str(tmp_path / "sampler.jaxexport")
        meta = export_sampler(trained_ckpt, out, overrides=OVERRIDES,
                              platforms=("cpu",), max_serve_batch=8)
        assert meta["serving"]["bucket_ladder"] == [1, 2, 4, 8]
        assert meta["serving"]["source"] == "live"

        src = ArtifactSource(out)
        assert src.ladder_hint() == [1, 2, 4, 8]
        s = SamplerServer(src, max_wait_ms=2.0)
        s.start(timeout=300)
        assert s.ladder.buckets == (1, 2, 4, 8)  # the sidecar hint won
        z = np.random.default_rng(3).uniform(
            -1, 1, (5, 100)).astype(np.float32)
        served = s.submit(z=z).result(timeout=60)
        s.stop()
        direct = np.asarray(load_sampler(out).call(z))
        np.testing.assert_allclose(served, direct, atol=1e-6)
        assert served.shape == (5, 16, 16, 3)

    def test_pinned_batch_artifact_ladder_is_one_rung(self, trained_ckpt,
                                                      tmp_path):
        from dcgan_tpu.export import export_sampler

        out = str(tmp_path / "pinned.jaxexport")
        meta = export_sampler(trained_ckpt, out, overrides=OVERRIDES,
                              platforms=("cpu",), batch_size=4)
        assert meta["serving"]["bucket_ladder"] == [4]


@pytest.mark.slow
class TestGenerateTailBuckets:
    def test_tail_snaps_to_ladder_bucket(self, trained_ckpt, tmp_path):
        """generate.py satellite: --num_images not divisible by
        --batch_size pads the tail to a smaller compiled ladder bucket
        (16 + 8 here), not a second full batch and not a one-off
        shape."""
        from dcgan_tpu.generate import build_parser, generate

        args = build_parser().parse_args(
            ["--checkpoint_dir", trained_ckpt,
             "--out_dir", str(tmp_path / "out"),
             "--num_images", "20", "--batch_size", "16", "--grid", "0",
             "--npz", str(tmp_path / "t.npz"),
             "--output_size", "16", "--gf_dim", "8", "--df_dim", "8"])
        result = generate(args)
        assert result["num_images"] == 20
        imgs = np.load(tmp_path / "t.npz")["images"]
        assert imgs.shape == (20, 16, 16, 3)
