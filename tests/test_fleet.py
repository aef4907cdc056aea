"""Serving fleet (ISSUE 19): router policy, heartbeat health, failover,
and zero-downtime weight promotion.

The router units run against fake replicas (no threads, no device) so
the policy decisions — least-queue-depth tie-break, unhealthy exclusion,
sticky clients, hedge-once failover — are pinned deterministically. The
fleet tier runs real SamplerServers over fake sources: a poisoned
replica's requests fail over with zero failed client requests, a wedged
replica is drained by the heartbeat monitor and its backlog rescued, and
a promotion control op drains behind the in-flight batch. The end-to-end
tier serves a real checkpoint and pins the acceptance contract: a
mid-serve promotion to a newly finalized step swaps weights with ZERO
compile-cache requests (the prime() trick re-links the swapped state
through every cached executable) and zero dropped requests.
"""

import os
import shutil
import threading
import time

import numpy as np
import pytest

from dcgan_tpu.serve.fleet import PROMOTION_SEQUENCE, ServeFleet
from dcgan_tpu.serve.router import (
    MAX_ATTEMPTS,
    Router,
    RouterError,
    promotion_targets,
)
from dcgan_tpu.serve.server import (
    Response,
    SamplerServer,
    ServeError,
    ServeOverloadError,
)


class FakeSource:
    """No-device source: images encode their latent's first coordinate
    (the test_serve convention) plus reload() so promotions work."""

    def __init__(self, granule=1, z_dim=4, num_classes=0, block=None,
                 explode_at=0):
        self.granule = granule
        self.z_dim = z_dim
        self.num_classes = num_classes
        self.block = block            # optional Event: stall dispatches
        self.explode_at = explode_at  # raise on the n-th sample (1-based)
        self.calls = []
        self.events = []              # interleaving probe: sample/reload
        self.step = 0

    def prepare(self):
        return {"source": "fake", "step": self.step, "weights": "live"}

    def bucket_plan(self, ladder):
        return []

    def bind(self, compiled):
        pass

    def reload(self):
        self.step += 1
        self.events.append("reload")
        return {"source": "fake", "step": self.step, "weights": "live"}

    def sample(self, bucket, z, labels=None):
        if self.block is not None:
            self.block.wait()
        if self.explode_at and len(self.calls) + 1 >= self.explode_at:
            raise RuntimeError("replica device on fire")
        self.calls.append((bucket, z.shape[0]))
        self.events.append("sample")
        img = np.zeros((bucket, 2, 2, 1), np.float32)
        img[:, 0, 0, 0] = z[:, 0]
        return img


class FakeReplica:
    """The replica surface the router sees, with scripted behavior."""

    def __init__(self, depth=0, fail_with=None):
        self.depth = depth
        self.beats = 0
        self.is_poisoned = False
        self.fail_with = fail_with    # exception failing every submit
        self.responses = []           # unsettled Responses handed out
        self.evictions = 0
        self.failover_drops = 0

    def queue_depth(self):
        return self.depth

    def poisoned(self):
        return self.is_poisoned

    def submit(self, num_images=1, **kw):
        r = Response()
        self.responses.append(r)
        if self.fail_with is not None:
            r._fail(self.fail_with)
        return r

    def evict_pending(self):
        self.evictions += 1
        return 0

    def record_failover_drop(self, n=1):
        self.failover_drops += n


_LIVE_FLEETS = []


def make_fleet(sources, **kw):
    kw.setdefault("buckets", (4, 8))
    kw.setdefault("max_wait_ms", 5.0)
    f = ServeFleet(sources, **kw)
    _LIVE_FLEETS.append(f)
    return f


@pytest.fixture(autouse=True)
def _reap_fleets():
    """A failing test must never leave blocked workers alive holding
    dispatch scopes — unblock and stop every fleet this test created."""
    yield
    while _LIVE_FLEETS:
        f = _LIVE_FLEETS.pop()
        for s in f.servers:
            block = getattr(s.source, "block", None)
            if block is not None:
                block.set()
        try:
            f.stop(drain=False, timeout=10.0)
        except Exception:
            pass


class TestPromotionTargets:
    def test_targets_are_sorted_healthy_indices(self):
        assert promotion_targets({0: True, 1: True, 2: True}) == (0, 1, 2)
        assert promotion_targets({2: True, 0: True, 1: False}) == (0, 2)
        assert promotion_targets({0: False, 1: False}) == ()

    def test_sequence_is_the_committed_lattice(self):
        # the protocol tier's virtual fleet replays this exact tuple; a
        # rename or reorder must drift the committed lock deliberately
        assert PROMOTION_SEQUENCE == ("drain", "swap", "prime", "resume")


class TestRouterPolicy:
    def test_least_queue_depth_lowest_index_tie_break(self):
        r = Router([FakeReplica(depth=2), FakeReplica(depth=1),
                    FakeReplica(depth=1)])
        assert r.pick() == 1          # min depth, lowest index wins ties
        r._replicas[1].depth = 5
        assert r.pick() == 2

    def test_unhealthy_and_poisoned_replicas_excluded(self):
        r = Router([FakeReplica(), FakeReplica(depth=9), FakeReplica()])
        r.mark_unhealthy(0, "test")
        assert r.pick() == 2          # depth 9 still beats unhealthy 0
        assert r._replicas[0].evictions == 1   # drain rescued its queue
        r._replicas[2].is_poisoned = True
        assert r.pick() == 1          # poisoned excluded without marking
        r.mark_unhealthy(1, "test")
        with pytest.raises(RouterError, match="no healthy"):
            r.pick()

    def test_sticky_client_survives_depth_changes(self):
        r = Router([FakeReplica(), FakeReplica(depth=1)])
        assert r.pick(client_id="c") == 0
        r._replicas[0].depth = 50     # 1 is now far cheaper
        assert r.pick(client_id="c") == 0      # sticky: FIFO preserved
        assert r.pick(client_id="new") == 1    # new clients go by depth
        r.mark_unhealthy(0, "test")
        assert r.pick(client_id="c") == 1      # re-picked out of rotation

    def test_mark_healthy_readmits_but_never_poisoned(self):
        r = Router([FakeReplica(), FakeReplica()])
        r.mark_unhealthy(0, "test")
        r.mark_healthy(0)
        assert r.health()[0] is True
        r._replicas[1].is_poisoned = True
        r.mark_unhealthy(1, "poisoned")
        r.mark_healthy(1)
        assert r.health()[1] is False  # poisoning is permanent

    def test_poll_health_miss_beats_then_readmission(self):
        r = Router([FakeReplica(), FakeReplica()], miss_beats=3)
        r._replicas[1].beats = 5
        r.poll_health()                # baseline tick records beats
        for _ in range(2):
            r.poll_health()            # 2 silent polls: still in rotation
        assert r.health() == {0: True, 1: True}
        r.poll_health()                # 3rd silent poll: drained
        assert r.health() == {0: False, 1: False}
        r._replicas[0].beats += 1      # heartbeat resumes
        r.poll_health()
        assert r.health() == {0: True, 1: False}
        assert (0, "missed 3 heartbeats") in r.unhealthy_events

    def test_hedge_once_failover_rescues_request(self):
        dead = FakeReplica(fail_with=ServeError("worker died"))
        peer = FakeReplica(depth=1)
        r = Router([dead, peer])
        resp = r.submit(num_images=2, client_id="c")
        assert not resp.done()         # hedged onto the peer, in flight
        assert len(peer.responses) == 1
        img = np.zeros((2, 2, 2, 1), np.float32)
        peer.responses[0]._resolve(img, {"buckets": [4]})
        assert resp.result(1).shape == (2, 2, 2, 1)
        assert r.failovers == 1 and r.failover_drops == 0
        # the sticky mapping followed the failover
        assert r.pick(client_id="c") == 1

    def test_hedge_budget_is_one_retry(self):
        both_dead = [FakeReplica(fail_with=ServeError("worker died")),
                     FakeReplica(fail_with=ServeError("worker died"))]
        r = Router(both_dead)
        resp = r.submit(num_images=1)
        with pytest.raises(ServeError, match="worker died"):
            resp.result(1)
        assert MAX_ATTEMPTS == 2
        assert sum(len(x.responses) for x in both_dead) == 2
        assert r.failovers == 1 and r.failover_drops == 1
        assert sum(x.failover_drops for x in both_dead) == 1

    def test_overload_and_bad_requests_are_not_hedged(self):
        shed = FakeReplica(fail_with=ServeOverloadError(
            "queue full", queue_depth=7, oldest_wait_ms=12.5))
        idle = FakeReplica()
        r = Router([shed, idle])
        resp = r.submit(num_images=1)
        with pytest.raises(ServeOverloadError) as ei:
            resp.result(1)
        # the overload error carries live pressure telemetry (ISSUE 19
        # satellite): clients can back off proportionally
        assert ei.value.queue_depth == 7
        assert ei.value.oldest_wait_ms == 12.5
        assert idle.responses == []    # deliberate shedding: no hedge
        assert r.failovers == 0 and r.failover_drops == 0


class TestFleetOverFakeSources:
    def test_replica_death_fails_over_zero_failed_requests(self):
        """Kill one replica's device mid-trace: every client request
        still completes, the death is logged, the drop split shows NO
        failover drops (every orphan was rescued)."""
        fleet = make_fleet([FakeSource(explode_at=1), FakeSource(),
                            FakeSource()])
        fleet.start(timeout=30)
        fleet.router.stop_monitor()    # poll manually: deterministic
        # all depths 0: the tie-break routes request 1 to replica 0,
        # whose first dispatch explodes — the request must fail over
        resps = [fleet.submit(2, client_id=f"c{i}") for i in range(6)]
        out = [r.result(30) for r in resps]
        fleet.router.poll_health()     # notice the poisoned worker
        fleet.stop(drain=True)
        assert all(o.shape == (2, 2, 2, 1) for o in out)
        rep = fleet.report()
        assert rep["serve/completed"] == 6.0
        assert rep["serve/dropped_failover"] == 0.0
        assert rep["serve/fleet_unhealthy"] == 1.0
        assert rep["serve/fleet_failovers"] >= 1.0
        assert (0, "poisoned") in fleet.router.unhealthy_events
        # the dead replica's stop error was collected, not raised
        assert [i for i, _ in fleet.stop_errors] == [0]

    def test_wedged_replica_backlog_rescued_by_heartbeat(self):
        """A replica blocked in dispatch stops beating; the monitor
        drains it and its NEVER-dispatched backlog fails over to the
        peer. The in-flight request still completes when the wedge
        clears, and the resumed heartbeat re-admits the replica."""
        block = threading.Event()
        wedged = FakeSource(block=block)
        fleet = make_fleet([wedged, FakeSource()], miss_beats=2)
        fleet.start(timeout=30)
        fleet.router.stop_monitor()
        block.clear()                  # wedge AFTER warmup dispatches
        first = fleet.submit(1, client_id="c")   # sticks to replica 0
        time.sleep(0.1)                # worker now blocked in sample
        parked = fleet.submit(1, client_id="c")  # queued behind the wedge
        # poll slower than the idle beat cadence (~0.1s), like the real
        # monitor: an IDLE healthy peer must never accumulate misses
        deadline = time.monotonic() + 10.0
        while fleet.router.health()[0] and time.monotonic() < deadline:
            fleet.router.poll_health()
            time.sleep(0.15)
        assert fleet.router.health() == {0: False, 1: True}
        assert parked.result(10).shape == (1, 2, 2, 1)   # rescued
        assert fleet.router.failovers == 1
        block.set()                    # wedge clears: in-flight finishes
        assert first.result(10).shape == (1, 2, 2, 1)
        deadline = time.monotonic() + 10.0
        while not fleet.router.health()[0] \
                and time.monotonic() < deadline:
            fleet.router.poll_health()
            time.sleep(0.15)
        assert fleet.router.health()[0] is True   # re-admitted
        fleet.stop(drain=True)

    def test_promotion_drains_behind_inflight_batch(self):
        """The control op pops only between batches and ahead of queued
        requests: sample(in-flight) -> reload -> sample(queued) — the
        drain barrier is the sequential dispatch thread itself."""
        block = threading.Event()
        block.set()
        src = FakeSource(block=block)
        fleet = make_fleet([src], max_wait_ms=1.0)
        fleet.start(timeout=30)
        block.clear()
        inflight = fleet.submit(1)
        time.sleep(0.1)                # worker blocked inside sample 1
        ticket = fleet.servers[0].request_promote()
        queued = fleet.submit(1)
        time.sleep(0.05)
        assert not ticket.done()       # promotion waits on the drain
        block.set()
        info = ticket.result(10)
        assert inflight.result(10) is not None
        assert queued.result(10) is not None
        fleet.stop(drain=True)
        assert src.events == ["sample", "reload", "sample"]
        assert info["replica"] == 0 and info["step"] == 1
        assert info["compile_requests_delta"] is None   # no cache wired
        rep = fleet.report()
        assert rep["serve/promotions"] == 1.0
        assert rep["serve/promote_swap_ms"] >= 0.0

    def test_promote_targets_only_healthy_replicas(self):
        fleet = make_fleet([FakeSource(explode_at=1), FakeSource(),
                            FakeSource()])
        fleet.start(timeout=30)
        fleet.router.stop_monitor()
        fleet.submit(1).result(30)     # first pick poisons replica 0,
        fleet.router.poll_health()     # the request fails over
        results = fleet.promote()
        fleet.stop(drain=True)
        assert sorted(r["replica"] for r in results) == [1, 2]
        assert all("error" not in r for r in results)
        assert all(r["step"] == 1 for r in results)

    def test_overload_split_and_telemetry_on_fleet_report(self):
        block = threading.Event()
        src = FakeSource(block=block)
        fleet = make_fleet([src], max_queue=2, max_wait_ms=1.0)
        fleet.start(timeout=30)
        block.clear()
        first = fleet.submit(1)
        time.sleep(0.1)                # worker blocked: submits pile up
        shed = fleet.submit(1)
        fleet.submit(1)
        overflow = fleet.submit(1)     # displaces `shed` (drop-oldest)
        block.set()
        with pytest.raises(ServeOverloadError) as ei:
            shed.result(10)
        assert ei.value.queue_depth >= 1
        assert ei.value.oldest_wait_ms >= 0.0
        first.result(10), overflow.result(10)
        fleet.stop(drain=True)
        rep = fleet.report()
        assert rep["serve/dropped"] == 1.0
        assert rep["serve/dropped_overload"] == 1.0
        assert rep["serve/dropped_failover"] == 0.0
        assert fleet.servers[0].counters().serve_dropped_overload == 1

    def test_single_replica_fleet_matches_bare_server(self):
        """The router layer adds no transformation: the same latent rows
        through a 1-replica fleet and a bare server produce byte-
        identical images."""
        z = np.random.default_rng(7).uniform(
            -1, 1, (5, 4)).astype(np.float32)
        bare = SamplerServer(FakeSource(), buckets=(4, 8),
                             max_wait_ms=5.0)
        bare.start(timeout=30)
        want = bare.submit(z=z).result(10)
        bare.stop()
        fleet = make_fleet([FakeSource()])
        fleet.start(timeout=30)
        got = fleet.submit(z=z).result(10)
        fleet.stop(drain=True)
        np.testing.assert_array_equal(got, want)


@pytest.fixture(scope="module")
def promotable_ckpt(tmp_path_factory):
    """Two checkpoint dirs from one training lineage: `serve` holds only
    step 1 (what the fleet cold-starts on); `donor` holds step 2 (the
    newly finalized step a test injects mid-serve)."""
    from dcgan_tpu.config import ModelConfig, TrainConfig
    from dcgan_tpu.train.trainer import train

    root = tmp_path_factory.mktemp("fleet")
    serve_dir = str(root / "serve")

    def cfg(ckpt_dir):
        return TrainConfig(
            model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                              compute_dtype="float32"),
            batch_size=8,
            checkpoint_dir=ckpt_dir,
            sample_dir=str(root / "samples"),
            sample_every_steps=0, save_summaries_secs=1e9,
            save_model_secs=1e9, log_every_steps=0, tensorboard=False)

    train(cfg(serve_dir), synthetic_data=True, max_steps=1)
    donor_dir = str(root / "donor")
    shutil.copytree(serve_dir, donor_dir)
    train(cfg(donor_dir), synthetic_data=True, max_steps=2)  # resumes @1
    assert os.path.isdir(os.path.join(donor_dir, "2"))
    return serve_dir, donor_dir


OVERRIDES = {"output_size": 16, "gf_dim": 8, "df_dim": 8}


def inject_step(donor_dir, serve_dir, step):
    """Deliver `step` into `serve_dir` the way a trainer would: integrity
    sidecars first, then the step dir copied under a tmp name and RENAMED
    in — a digit-named dir is finalized by the Orbax contract, so the
    watcher/promotion can never see a half-copied step."""
    integ = os.path.join(donor_dir, "integrity")
    if os.path.isdir(integ):
        dst = os.path.join(serve_dir, "integrity")
        os.makedirs(dst, exist_ok=True)
        for name in os.listdir(integ):
            if name.startswith(f"{step}."):
                shutil.copy2(os.path.join(integ, name),
                             os.path.join(dst, name))
    tmp = os.path.join(serve_dir, f"tmp.promote.{step}")
    shutil.copytree(os.path.join(donor_dir, str(step)), tmp)
    os.rename(tmp, os.path.join(serve_dir, str(step)))


class TestPromotionEndToEnd:
    def test_zero_recompile_promotion_serves_new_weights(
            self, promotable_ckpt, tmp_path):
        """The acceptance pin: a newly finalized step injected mid-serve
        promotes with compile_requests_delta == 0 (measured by the live
        CompileCacheMonitor across the swap + re-prime) and the swapped
        weights actually serve — same latents, different images."""
        from dcgan_tpu.serve import CheckpointSource

        serve_dir, donor_dir = promotable_ckpt
        fleet = make_fleet(
            [CheckpointSource(serve_dir, overrides=OVERRIDES)],
            buckets=None, max_batch=16, max_wait_ms=2.0,
            cache_dir=str(tmp_path / "cc"))
        metas = fleet.start(timeout=300)
        assert metas[0]["step"] == 1
        z = np.random.default_rng(11).uniform(
            -1, 1, (6, 100)).astype(np.float32)
        before = fleet.submit(z=z).result(60)

        inject_step(donor_dir, serve_dir, 2)
        results = fleet.promote()
        assert results == [{"replica": 0, "step": 2,
                            "swap_ms": results[0]["swap_ms"],
                            "compile_requests_delta": 0}]
        assert results[0]["swap_ms"] > 0

        after = fleet.submit(z=z).result(60)
        rep = fleet.report()
        fleet.stop(drain=True)
        assert rep["serve/recompiles_after_warmup"] == 0.0
        assert rep["serve/dropped"] == 0.0
        assert rep["serve/completed"] == 2.0
        assert rep["serve/promotions"] == 1.0
        assert before.shape == after.shape == (6, 16, 16, 3)
        # one optimizer step moved the generator: the swap was real
        assert not np.array_equal(before, after)

    def test_watcher_promotes_newly_finalized_step(
            self, promotable_ckpt, tmp_path):
        """The watch loop notices the renamed-in step and hot-swaps
        without an explicit promote() call."""
        from dcgan_tpu.serve import CheckpointSource, latest_finalized_step

        serve_dir, donor_dir = promotable_ckpt
        work = str(tmp_path / "watch")
        shutil.copytree(serve_dir, work)
        # the previous test may have already injected step 2 into the
        # module-scoped serve dir; the watcher needs a fresh copy at 1
        if os.path.isdir(os.path.join(work, "2")):
            shutil.rmtree(os.path.join(work, "2"))
        assert latest_finalized_step(work) == 1
        fleet = make_fleet(
            [CheckpointSource(work, overrides=OVERRIDES)],
            buckets=None, max_batch=16, max_wait_ms=2.0,
            watch_promotions=True, watch_interval_secs=0.05)
        fleet.start(timeout=300)
        inject_step(donor_dir, work, 2)
        deadline = time.monotonic() + 60.0
        while not fleet.promotion_results \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        fleet.stop(drain=True)
        assert fleet.promotion_results, "watcher never promoted"
        (result,) = fleet.promotion_results[0]
        assert result["step"] == 2 and "error" not in result
