"""Multi-host coordination units (ISSUE 4): anomaly consensus, coordinated
stop, hung-collective watchdog, device-resident rollback snapshots — all
with fake process-count/allgather shims, no subprocesses. The end-to-end
2-process proofs live in tools/chaos_drill.py --multihost (smoke-pinned in
test_tools_chaos.py) and the parity A/B in test_multihost.py."""

import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.train import coordination
from dcgan_tpu.train.rollback import RollbackManager

pytestmark = pytest.mark.chaos


def _fake_allgather(values):
    """An allgather shim returning a fixed per-process verdict vector."""
    return lambda local: np.asarray(values, np.int32)


class TestAnomalyConsensus:
    def test_single_process_passthrough(self):
        assert coordination.anomaly_consensus(False) == (False, [])
        assert coordination.anomaly_consensus(True) == (True, [0])

    def test_any_tripped_process_trips_all(self, monkeypatch):
        monkeypatch.setattr(jax, "process_count", lambda: 3)
        monkeypatch.setattr(coordination, "_allgather_i32",
                            _fake_allgather([0, 1, 0]))
        bad, trippers = coordination.anomaly_consensus(False)
        assert bad and trippers == [1]

    def test_no_trip_anywhere_passes(self, monkeypatch):
        monkeypatch.setattr(jax, "process_count", lambda: 3)
        monkeypatch.setattr(coordination, "_allgather_i32",
                            _fake_allgather([0, 0, 0]))
        assert coordination.anomaly_consensus(False) == (False, [])

    def test_local_verdict_reaches_the_wire(self, monkeypatch):
        """The shim must SEE the local verdict — the transport carries it
        to the peers."""
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        sent = []

        def capture(local):
            sent.append(local)
            return np.asarray([local, 0], np.int32)

        monkeypatch.setattr(coordination, "_allgather_i32", capture)
        bad, trippers = coordination.anomaly_consensus(True)
        assert sent == [1] and bad and trippers == [0]


class TestCoordinatedStop:
    def test_single_process_signal_flag_roundtrip(self):
        stop = coordination.CoordinatedStop()
        stop.install()
        try:
            assert stop.poll() == (None, [])
            signal.raise_signal(signal.SIGTERM)
            assert stop.local_signal == signal.SIGTERM
            assert stop.poll() == (signal.SIGTERM, [0])
        finally:
            stop.restore()

    def test_handler_is_one_shot(self):
        """First delivery restores the original handlers, so a second
        signal can still kill a hung final save."""
        stop = coordination.CoordinatedStop()
        seen = []
        orig = signal.signal(signal.SIGTERM, lambda *a: seen.append(a[0]))
        try:
            stop.install()
            signal.raise_signal(signal.SIGTERM)   # flag only
            assert seen == []
            signal.raise_signal(signal.SIGTERM)   # restored handler fires
            assert seen == [signal.SIGTERM]
        finally:
            stop.restore()
            signal.signal(signal.SIGTERM, orig)

    def test_multihost_consensus_any_host_stops_all(self, monkeypatch):
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(coordination, "_allgather_i32",
                            _fake_allgather([0, signal.SIGTERM]))
        stop = coordination.CoordinatedStop()  # local flag NOT set
        sig, origins = stop.poll()
        assert sig == signal.SIGTERM and origins == [1]

    def test_multihost_no_signal_anywhere(self, monkeypatch):
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(coordination, "_allgather_i32",
                            _fake_allgather([0, 0]))
        assert coordination.CoordinatedStop().poll() == (None, [])

    def test_multihost_sigterm_beats_sigint(self, monkeypatch):
        """Mixed signals resolve to one deterministic representative so
        every process logs and acts identically."""
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(
            coordination, "_allgather_i32",
            _fake_allgather([signal.SIGINT, signal.SIGTERM]))
        sig, origins = coordination.CoordinatedStop().poll()
        assert sig == max(signal.SIGTERM, signal.SIGINT)
        assert origins == [0, 1]


class TestCollectiveWatchdog:
    def _make(self, timeout=0.15, **kw):
        trips = []
        wd = coordination.CollectiveWatchdog(
            timeout, poll_interval=0.02,
            on_trip=lambda phase, step: trips.append((phase, step)), **kw)
        return wd, trips

    def test_expired_deadline_trips_with_context(self):
        wd, trips = self._make()
        try:
            wd.arm("collective-save", 7)
            time.sleep(0.5)
            assert trips and trips[0] == ("collective-save", 7)
        finally:
            wd.close()

    def test_disarm_prevents_trip(self):
        wd, trips = self._make()
        try:
            wd.arm("step-dispatch", 3)
            wd.disarm()
            time.sleep(0.4)
            assert trips == []
        finally:
            wd.close()

    def test_guard_context_disarms_on_exit(self):
        wd, trips = self._make()
        try:
            with wd.guard("stop-consensus", 1):
                pass
            time.sleep(0.4)
            assert trips == []
        finally:
            wd.close()

    def test_nested_guard_restores_outer_deadline(self):
        """The NaN-consensus guard nests inside the step-dispatch window;
        its exit must hand the (still ticking) outer deadline back, not
        silently disarm the outer section."""
        wd, trips = self._make()
        try:
            wd.arm("step-dispatch", 5)
            with wd.guard("nan-consensus", 5):
                pass  # quick inner collective
            time.sleep(0.5)  # outer section hangs past its deadline
            assert trips and trips[0] == ("step-dispatch", 5)
        finally:
            wd.close()

    def test_trips_while_main_thread_sleeps(self):
        """The enforcement thread is independent of the armed thread — a
        process hung in a sleep (or a GIL-releasing collective) still
        trips on schedule."""
        wd, trips = self._make()
        try:
            wd.arm("step-dispatch", 2)
            t0 = time.monotonic()
            while not trips and time.monotonic() - t0 < 2.0:
                time.sleep(0.05)
            assert trips == [("step-dispatch", 2)]
        finally:
            wd.close()

    def test_rearm_refreshes_deadline(self):
        wd, trips = self._make(timeout=0.2)
        try:
            for _ in range(4):
                wd.arm("step-dispatch", 1)
                time.sleep(0.08)  # always re-armed before expiry
            assert trips == []
        finally:
            wd.close()

    def test_zero_timeout_is_null_watchdog(self):
        wd = coordination.make_watchdog(0.0)
        wd.arm("x", 1)
        with wd.guard("y", 2):
            pass
        wd.disarm()
        wd.close()  # all free no-ops

    def test_rejects_nonpositive_timeout(self):
        with pytest.raises(ValueError, match="timeout_secs"):
            coordination.CollectiveWatchdog(0.0)

    def test_close_stops_thread(self):
        wd, _ = self._make()
        wd.close()
        assert not wd._thread.is_alive()
        assert threading.active_count() >= 1  # no stray state


class TestDeviceResidentRollback:
    """The multi-host snapshot mode: device-resident jitted copies, no host
    gather — restore survives buffer donation and serves repeat rollbacks."""

    def _state(self, value):
        return {"w": jnp.full((4, 4), value, jnp.float32),
                "step": jnp.asarray(int(value), jnp.int32)}

    def test_snapshot_restore_roundtrip(self):
        mgr = RollbackManager(every=2, max_rollbacks=3,
                              device_resident=True)
        mgr.snapshot(4, self._state(4.0))
        restored, step = mgr.restore(FloatingPointError("nan at 5"))
        assert step == 4
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((4, 4), 4.0, np.float32))

    def test_restore_returns_fresh_buffers(self):
        """The returned arrays must not alias the snapshot: the next step
        donates them, and the snapshot has to survive to serve a second
        rollback."""
        mgr = RollbackManager(every=2, max_rollbacks=3,
                              device_resident=True)
        mgr.snapshot(2, self._state(2.0))
        first, _ = mgr.restore(FloatingPointError("trip 1"))
        # simulate donation: delete the restored buffers entirely
        for leaf in jax.tree_util.tree_leaves(first):
            leaf.delete()
        second, step = mgr.restore(FloatingPointError("trip 2"))
        assert step == 2
        np.testing.assert_array_equal(np.asarray(second["w"]),
                                      np.full((4, 4), 2.0, np.float32))

    def test_snapshot_is_a_copy_not_a_reference(self):
        """Donating the ORIGINAL state after snapshotting must not corrupt
        the restore point — the jitted identity copy owns its buffers."""
        mgr = RollbackManager(every=1, max_rollbacks=3,
                              device_resident=True)
        state = self._state(7.0)
        mgr.snapshot(1, state)
        for leaf in jax.tree_util.tree_leaves(state):
            leaf.delete()
        restored, _ = mgr.restore(FloatingPointError("trip"))
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((4, 4), 7.0, np.float32))

    def test_budget_still_enforced(self):
        from dcgan_tpu.train.rollback import RollbackExhausted

        mgr = RollbackManager(every=1, max_rollbacks=1,
                              device_resident=True)
        mgr.snapshot(1, self._state(1.0))
        mgr.restore(FloatingPointError("one"))
        with pytest.raises(RollbackExhausted, match="max_rollbacks"):
            mgr.restore(FloatingPointError("two"))


class TestFleetHealth:
    """ISSUE 6 fleet health plane: the allgathered per-host vector and the
    fleet/* derivation, with the same fake-transport shims as the rest of
    this file (the end-to-end single-process path is
    tests/test_flight_recorder.py::TestFleetHealthEndToEnd)."""

    def _vec(self, step, step_ms, host_ms=1.0, queue=0, dropped=0,
             rollbacks=0, corrupt=0, phase=0):
        return np.asarray([step, step_ms, host_ms, queue, dropped,
                           rollbacks, corrupt, phase], np.float32)

    def test_single_process_gather_is_local_table(self):
        table = coordination.fleet_health_gather(self._vec(4, 12.5))
        assert table.shape == (1, len(coordination.HEALTH_FIELDS))
        assert table[0, 1] == pytest.approx(12.5)

    def test_multihost_gather_uses_the_f32_transport(self, monkeypatch):
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        sent = []

        def capture(vec):
            sent.append(np.asarray(vec))
            return np.stack([np.asarray(vec), np.asarray(vec) * 2])

        monkeypatch.setattr(coordination, "_allgather_f32", capture)
        n = len(coordination.HEALTH_FIELDS)
        table = coordination.fleet_health_gather(self._vec(4, 10.0))
        assert sent and sent[0].shape == (n,)   # local vector on the wire
        assert table.shape == (2, n)
        assert table[1, 1] == pytest.approx(20.0)

    def test_fleet_metrics_skew_and_slowest_host(self):
        table = np.stack([self._vec(6, 10.0, host_ms=2.0, dropped=1),
                          self._vec(6, 35.0, host_ms=3.0, queue=4),
                          self._vec(6, 12.0, rollbacks=1, corrupt=2)])
        row, note = coordination.fleet_metrics(table)
        assert row["fleet/step_ms_max"] == pytest.approx(35.0)
        assert row["fleet/step_ms_min"] == pytest.approx(10.0)
        assert row["fleet/step_ms_skew"] == pytest.approx(25.0)
        assert row["fleet/slowest_host"] == 1.0
        assert row["fleet/queue_depth_max"] == 4.0
        assert row["fleet/dropped_total"] == 1.0
        assert row["fleet/rollbacks_total"] == 1.0
        assert row["fleet/corrupt_total"] == 2.0
        assert "process 1" in note and "35.0" in note

    def test_trip_header_names_the_slowest_host(self, capfd, monkeypatch):
        """The watchdog note (set at each health gather) must surface in
        the trip header — the operator's first straggler attribution.
        capfd, not capsys: faulthandler writes to the real fd."""
        import os as os_mod

        wd = coordination.CollectiveWatchdog(0.1, poll_interval=0.02,
                                             on_trip=lambda *a: None)
        try:
            wd.set_note("slowest host: process 3 (step_ms_mean 99.0 vs "
                        "fleet min 10.0)")
            monkeypatch.setattr(os_mod, "_exit", lambda code: None)
            wd._dump_and_exit("step-dispatch", 7)
            err = capfd.readouterr().err
            assert "slowest host: process 3" in err
            assert "step-dispatch" in err
        finally:
            wd.close()


class TestNewKnobs:
    def test_config_validation(self):
        from dcgan_tpu.config import TrainConfig

        assert TrainConfig().coord_stop is True
        assert TrainConfig().collective_timeout_secs == 0.0
        with pytest.raises(ValueError, match="collective_timeout_secs"):
            TrainConfig(collective_timeout_secs=-1.0)

    def test_observability_knob_validation(self):
        from dcgan_tpu.config import TrainConfig

        cfg = TrainConfig()
        assert cfg.fleet_health_steps == 0      # off: parity default
        assert cfg.flight_recorder_steps == 64  # on, crash-path-only IO
        assert cfg.profile_trigger == ""
        with pytest.raises(ValueError, match="fleet_health_steps"):
            TrainConfig(fleet_health_steps=-1)
        with pytest.raises(ValueError, match="flight_recorder_steps"):
            TrainConfig(flight_recorder_steps=-2)
        # the health gather is a collective: it joins the steps_per_call
        # cadence-alignment rule
        with pytest.raises(ValueError, match="fleet_health_steps"):
            TrainConfig(steps_per_call=4, sample_every_steps=4,
                        nan_check_steps=4, activation_summary_steps=4,
                        save_model_steps=4, log_every_steps=4,
                        fleet_health_steps=3)

    def test_observability_flags_reach_config(self):
        from dcgan_tpu.train.cli import build_parser, config_from_args

        cfg = config_from_args(build_parser().parse_args(
            ["--profile_trigger", "/tmp/t", "--flight_recorder_steps",
             "32", "--fleet_health_steps", "50"]))
        assert cfg.profile_trigger == "/tmp/t"
        assert cfg.flight_recorder_steps == 32
        assert cfg.fleet_health_steps == 50

    def test_flags_reach_config(self):
        from dcgan_tpu.train.cli import build_parser, config_from_args

        cfg = config_from_args(build_parser().parse_args(
            ["--coord_stop", "false", "--collective_timeout_secs", "45"]))
        assert cfg.coord_stop is False
        assert cfg.collective_timeout_secs == 45.0

    def test_multihost_rollback_no_longer_rejected(self):
        """PR 3 hard-errored nan_policy='rollback' under multi-host; the
        consensus + device-resident snapshot layer makes it legal, so the
        trainer constructs a device-resident manager instead of raising."""
        import inspect

        from dcgan_tpu.train import trainer

        # _train_run is the run body (PR 5 split _train into a setup
        # wrapper owning the compile-cache monitor's lifetime + this)
        src = inspect.getsource(trainer._train_run)
        assert "single-process only" not in src
        assert "device_resident=jax.process_count() > 1" in src
