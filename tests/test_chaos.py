"""Fail-operational layer (ISSUE 3): fault plans, quarantine, retry,
checkpoint integrity fallback, NaN rollback — the fast in-process half of
the proof (tools/chaos_drill.py is the subprocess end-to-end half; its
--smoke subset is pinned in tests/test_tools_chaos.py).

Everything here is `chaos`-marked and stays in the tier-1 (not-slow) suite
except the full-trainer rollback/parity runs at the bottom."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.data import quarantine
from dcgan_tpu.data.tfrecord import read_tfrecords, write_tfrecords
from dcgan_tpu.testing import chaos
from dcgan_tpu.utils.retry import retry_io

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_chaos_state():
    """No armed plan or quarantine tally may leak between tests (both are
    process-global by design)."""
    chaos.reset()
    quarantine.reset()
    yield
    chaos.reset()
    quarantine.reset()


class TestFaultPlan:
    def test_env_parse_and_unknown_key(self):
        plan = chaos.plan_from_env({chaos.ENV_VAR: json.dumps(
            {"nan_at_step": 7, "io_error_once": "services"})})
        assert plan.nan_at_step == 7 and plan.io_error_once == "services"
        assert chaos.plan_from_env({}) is None
        with pytest.raises(ValueError, match="unknown"):
            chaos.plan_from_env({chaos.ENV_VAR: '{"nope": 1}'})

    def test_nan_injection_is_one_shot(self):
        chaos.set_plan(chaos.FaultPlan(nan_at_step=3))
        assert not chaos.should_inject_nan(2)
        assert chaos.should_inject_nan(3)
        assert not chaos.should_inject_nan(3)  # replayed step after rollback
        chaos.set_plan(None)
        assert not chaos.should_inject_nan(3)

    def test_io_error_fires_only_on_matching_tag_and_once(self):
        chaos.set_plan(chaos.FaultPlan(io_error_once="ckpt-manifest"))
        chaos.maybe_io_error("services")  # wrong site: no-op
        with pytest.raises(OSError, match="chaos"):
            chaos.maybe_io_error("ckpt-manifest")
        chaos.maybe_io_error("ckpt-manifest")  # consumed

    def test_disk_helpers(self, tmp_path):
        path = str(tmp_path / "t.tfrecord")
        write_tfrecords(path, [b"a" * 40, b"b" * 40, b"c" * 40])
        chaos.corrupt_tfrecord_payload(path, record_index=1)
        got = list(read_tfrecords(path))                # no verify: 3 records
        assert len(got) == 3 and got[1] != b"b" * 40    # payload flipped
        with pytest.raises(IOError, match="data CRC"):
            list(read_tfrecords(path, verify_crc=True))
        size = os.path.getsize(path)
        assert chaos.truncate_file(path, 10) == size - 10


class TestRetryIO:
    def test_retries_then_succeeds(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert retry_io(flaky, tag="t", sleep=lambda s: None) == "ok"
        assert len(calls) == 3

    def test_exhausts_and_reraises(self):
        def always():
            raise OSError("persistent")

        with pytest.raises(OSError, match="persistent"):
            retry_io(always, tag="t", attempts=2, sleep=lambda s: None)

    def test_non_retryable_errors_propagate_immediately(self):
        calls = []

        def bad():
            calls.append(1)
            raise ValueError("logic bug")

        with pytest.raises(ValueError):
            retry_io(bad, tag="t", sleep=lambda s: None)
        assert len(calls) == 1

    def test_absorbs_injected_fault(self):
        chaos.set_plan(chaos.FaultPlan(io_error_once="site"))
        assert retry_io(lambda: "ok", tag="site",
                        sleep=lambda s: None) == "ok"


class TestTFRecordQuarantine:
    def _shard(self, tmp_path, n=4):
        path = str(tmp_path / "s.tfrecord")
        write_tfrecords(path, [bytes([i]) * 32 for i in range(n)])
        return path

    def test_data_crc_skips_exactly_that_record(self, tmp_path):
        path = self._shard(tmp_path)
        chaos.corrupt_tfrecord_payload(path, record_index=1)
        seen = []
        got = list(read_tfrecords(path, verify_crc=True,
                                  on_corrupt=lambda off, why:
                                  seen.append((off, why))))
        assert got == [bytes([i]) * 32 for i in (0, 2, 3)]
        assert len(seen) == 1 and "data CRC" in seen[0][1]
        assert seen[0][0] > 0  # offset of record 1, not the file head

    def test_truncated_tail_abandons_file_after_callback(self, tmp_path):
        path = self._shard(tmp_path)
        chaos.truncate_file(path, 10)
        seen = []
        got = list(read_tfrecords(path, verify_crc=True,
                                  on_corrupt=lambda off, why:
                                  seen.append(why)))
        assert len(got) == 3 and len(seen) == 1
        assert "truncated" in seen[0]

    def test_without_callback_still_raises(self, tmp_path):
        path = self._shard(tmp_path)
        chaos.corrupt_tfrecord_payload(path, 0)
        with pytest.raises(IOError, match="data CRC"):
            list(read_tfrecords(path, verify_crc=True))

    def test_budget_enforced_via_quarantine_record(self):
        quarantine.record("p", 0, "r", budget=2, seen=1)
        quarantine.record("p", 9, "r", budget=2, seen=2)
        with pytest.raises(quarantine.CorruptRecordError, match="budget"):
            quarantine.record("p", 18, "r", budget=2, seen=3)
        assert quarantine.count() == 3


def _labeled_shards(tmp_path, corrupt_index=None):
    from dcgan_tpu.data.synthetic import write_image_tfrecords

    data_dir = str(tmp_path / "data")
    paths = write_image_tfrecords(data_dir, num_examples=32, image_size=8,
                                  num_shards=1)
    if corrupt_index is not None:
        chaos.corrupt_tfrecord_payload(paths[0], corrupt_index)
    return paths


class TestLoaderQuarantine:
    KW = dict(batch=4, example_shape=(8, 8, 3), min_after_dequeue=4,
              n_threads=1, seed=0, loop=False)

    def test_python_loader_skips_and_counts(self, tmp_path):
        from dcgan_tpu.data.pipeline import PythonLoader

        paths = _labeled_shards(tmp_path, corrupt_index=3)
        loader = PythonLoader(paths, verify_crc=True, max_corrupt_records=8,
                              **self.KW)
        batches = list(loader)
        assert sum(b.shape[0] for b in batches) == 28  # 31 good, 7 batches
        assert loader.corrupt_records == 1
        assert quarantine.count() == 1

    def test_python_loader_counts_distinct_records_not_epochs(self,
                                                              tmp_path):
        """A looping dataset re-reads the same bad record every epoch; the
        budget must bound DISTINCT corrupt records, or one flipped bit
        still kills the run after budget-many epochs — the exact failure
        quarantine exists to prevent."""
        from dcgan_tpu.data.pipeline import PythonLoader

        paths = _labeled_shards(tmp_path, corrupt_index=3)
        kw = dict(self.KW, loop=True)
        loader = PythonLoader(paths, verify_crc=True, max_corrupt_records=1,
                              **kw)
        try:
            for _ in range(20):   # ~2.5 epochs of 31 good examples
                assert loader.next() is not None
            assert loader.corrupt_records == 1  # one distinct record
            assert quarantine.count() == 1
        finally:
            loader.close()

    def test_native_loader_counts_distinct_records_not_epochs(self,
                                                              tmp_path):
        from dcgan_tpu.data.native import NativeLoader

        paths = _labeled_shards(tmp_path, corrupt_index=3)
        kw = dict(self.KW, loop=True)
        loader = NativeLoader(paths, max_corrupt_records=1, **kw)
        try:
            for _ in range(20):
                assert loader.next() is not None
            assert loader.corrupt_records == 1
        finally:
            loader.close()

    def test_python_loader_fail_fast_without_budget(self, tmp_path):
        from dcgan_tpu.data.pipeline import PythonLoader

        paths = _labeled_shards(tmp_path, corrupt_index=3)
        loader = PythonLoader(paths, verify_crc=True, **self.KW)
        with pytest.raises(RuntimeError, match="data CRC"):
            list(loader)

    def test_python_loader_budget_exhaustion_fails(self, tmp_path):
        from dcgan_tpu.data.pipeline import PythonLoader

        paths = _labeled_shards(tmp_path, corrupt_index=1)
        chaos.corrupt_tfrecord_payload(paths[0], 5)
        loader = PythonLoader(paths, verify_crc=True, max_corrupt_records=1,
                              **self.KW)
        with pytest.raises(RuntimeError, match="budget"):
            list(loader)

    def test_native_loader_skips_and_counts(self, tmp_path):
        from dcgan_tpu.data.native import NativeLoader

        paths = _labeled_shards(tmp_path, corrupt_index=3)
        loader = NativeLoader(paths, max_corrupt_records=8, **self.KW)
        try:
            batches = list(loader)
            assert sum(b.shape[0] for b in batches) == 28
            assert loader.corrupt_records == 1
            assert quarantine.count() == 1  # bridge mirrors the native count
        finally:
            loader.close()

    def test_native_loader_budget_exhaustion_fails(self, tmp_path):
        from dcgan_tpu.data.native import NativeLoader, NativeLoaderError

        paths = _labeled_shards(tmp_path, corrupt_index=1)
        chaos.corrupt_tfrecord_payload(paths[0], 5)
        loader = NativeLoader(paths, max_corrupt_records=1, **self.KW)
        try:
            with pytest.raises(NativeLoaderError, match="budget"):
                list(loader)
        finally:
            loader.close()


def _tiny_state(value: float):
    return {"w": jnp.full((4, 4), value, jnp.float32),
            "step": jnp.asarray(int(value), jnp.int32)}


class TestCheckpointIntegrity:
    def _ckpt(self, tmp_path):
        from dcgan_tpu.utils.checkpoint import Checkpointer

        return Checkpointer(str(tmp_path / "ck"), async_save=False)

    def test_manifest_written_and_verifies(self, tmp_path):
        ck = self._ckpt(tmp_path)
        ck.save(1, _tiny_state(1.0), force=True)
        ck.save(2, _tiny_state(2.0), force=True)
        ck.wait()
        man = json.load(open(os.path.join(ck.directory, "integrity",
                                          "2.json")))
        assert man["step"] == 2 and man["files"]
        for rec in man["files"].values():
            assert rec["size"] > 0
        assert ck._verify_step(2) == (True, "verified")

    def test_truncated_latest_falls_back_to_previous(self, tmp_path, capsys):
        ck = self._ckpt(tmp_path)
        ck.save(1, _tiny_state(1.0), force=True)
        ck.save(2, _tiny_state(2.0), force=True)
        ck.wait()
        files = []
        for root, _, names in os.walk(os.path.join(ck.directory, "2")):
            files += [os.path.join(root, n) for n in names]
        chaos.truncate_file(max(files, key=os.path.getsize), 16)

        restored = ck.restore_latest(_tiny_state(0.0))
        assert int(restored["step"]) == 1
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((4, 4), 1.0, np.float32))
        assert os.path.isdir(os.path.join(ck.directory, "2.corrupt"))
        assert "failed integrity check" in capsys.readouterr().out
        # the manager's view is consistent after the quarantine rename
        assert ck.latest_step() == 1

    def test_all_corrupt_restores_none(self, tmp_path):
        ck = self._ckpt(tmp_path)
        ck.save(1, _tiny_state(1.0), force=True)
        ck.wait()
        for root, _, names in os.walk(os.path.join(ck.directory, "1")):
            for n in names:
                chaos.truncate_file(os.path.join(root, n), 8)
        assert ck.restore_latest(_tiny_state(0.0)) is None

    def test_legacy_step_without_manifest_still_restores(self, tmp_path):
        import shutil

        ck = self._ckpt(tmp_path)
        ck.save(3, _tiny_state(3.0), force=True)
        ck.wait()
        shutil.rmtree(os.path.join(ck.directory, "integrity"))
        restored = ck.restore_latest(_tiny_state(0.0))
        assert int(restored["step"]) == 3

    def test_delete_steps_after(self, tmp_path):
        ck = self._ckpt(tmp_path)
        for s in (1, 2, 3):
            ck.save(s, _tiny_state(float(s)), force=True)
        ck.wait()
        assert ck.delete_steps_after(1) == [3, 2]
        assert ck.latest_step() == 1
        # the dropped steps' manifests die with them — a REPLAYED save at
        # the same step number (the rollback scenario) writes different
        # bytes and must be manifested fresh, not judged against the stale
        # checksums and falsely quarantined
        assert not os.path.exists(os.path.join(ck.directory, "integrity",
                                               "2.json"))
        ck.save(2, _tiny_state(9.0), force=True)
        ck.wait()
        assert ck._verify_step(2) == (True, "verified")
        restored = ck.restore_latest(_tiny_state(0.0))
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((4, 4), 9.0, np.float32))
        # stale manifests of deleted-and-not-replayed steps are pruned on
        # the next manifest pass
        ck.save(4, _tiny_state(4.0), force=True)
        ck.wait()
        names = sorted(os.listdir(os.path.join(ck.directory, "integrity")))
        assert names == ["1.json", "2.json", "4.json"]

    def test_manifest_write_retries_injected_io_error(self, tmp_path,
                                                      capsys):
        chaos.set_plan(chaos.FaultPlan(io_error_once="ckpt-manifest"))
        ck = self._ckpt(tmp_path)
        ck.save(1, _tiny_state(1.0), force=True)
        ck.wait()
        assert "retrying" in capsys.readouterr().out
        assert ck._verify_step(1) == (True, "verified")

    def test_transient_verify_io_error_does_not_condemn(self, tmp_path,
                                                        capsys):
        """ISSUE 4 satellite: a transient OSError during verification gets
        its bounded retries — an INTACT checkpoint must restore, not be
        renamed `<step>.corrupt` (permanent condemnation) over one IO
        blip."""
        ck = self._ckpt(tmp_path)
        ck.save(1, _tiny_state(1.0), force=True)
        ck.wait()
        chaos.set_plan(chaos.FaultPlan(io_error_once="ckpt-verify"))
        restored = ck.restore_latest(_tiny_state(0.0))
        assert int(restored["step"]) == 1
        out = capsys.readouterr().out
        assert "retrying" in out
        assert not os.path.isdir(os.path.join(ck.directory, "1.corrupt"))
        assert "failed integrity check" not in out


class TestServicesFaults:
    def test_worker_crash_surfaces_on_dispatch_thread(self):
        from dcgan_tpu.train.services import HostServices, ServiceError

        chaos.set_plan(chaos.FaultPlan(services_worker_crash=1))
        svc = HostServices()
        try:
            svc.submit(lambda: None, tag="scalars")
            with pytest.raises(ServiceError, match="chaos"):
                svc.drain()
        finally:
            chaos.set_plan(None)
            try:
                svc.close()
            except ServiceError:
                pass

    def test_transient_os_error_in_task_is_retried(self):
        from dcgan_tpu.train.services import HostServices

        svc = HostServices()
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 2:
                raise OSError("transient")

        try:
            svc.submit(flaky, tag="scalars")
            svc.drain()  # would raise if the worker had failed
            assert len(calls) == 2 and svc.completed == 1
        finally:
            svc.close()


class TestRollbackManager:
    def test_snapshot_restore_roundtrip_and_exhaustion(self):
        from dcgan_tpu.train.rollback import (
            RollbackExhausted,
            RollbackManager,
        )

        mgr = RollbackManager(every=2, max_rollbacks=1, lr_backoff=0.5)
        state = {"w": jnp.arange(4.0), "step": jnp.asarray(4)}
        mgr.snapshot(4, state)
        trip = FloatingPointError("nan at step 5")
        restored, step = mgr.restore(trip)
        assert step == 4 and mgr.rollbacks == 1
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(4.0))
        assert restored["w"].sharding == state["w"].sharding
        assert mgr.lr_scale() == 0.5
        with pytest.raises(RollbackExhausted, match="max_rollbacks"):
            mgr.restore(trip)

    def test_no_snapshot_reraises(self):
        from dcgan_tpu.train.rollback import RollbackManager

        mgr = RollbackManager(every=2, max_rollbacks=3)
        with pytest.raises(FloatingPointError, match="boom"):
            mgr.restore(FloatingPointError("boom"))


class TestConfigAndCLI:
    def test_validation(self):
        from dcgan_tpu.config import TrainConfig

        with pytest.raises(ValueError, match="nan_policy"):
            TrainConfig(nan_policy="retry")
        with pytest.raises(ValueError, match="nan_check_steps"):
            TrainConfig(nan_policy="rollback", nan_check_steps=0)
        with pytest.raises(ValueError, match="rollback_lr_backoff"):
            TrainConfig(rollback_lr_backoff=0.0)
        with pytest.raises(ValueError, match="max_corrupt_records"):
            TrainConfig(max_corrupt_records=-1)
        with pytest.raises(ValueError, match="max_rollbacks"):
            TrainConfig(max_rollbacks=0)

    def test_flags_reach_config(self):
        from dcgan_tpu.train.cli import build_parser, config_from_args

        cfg = config_from_args(build_parser().parse_args(
            ["--nan_policy", "rollback", "--rollback_snapshot_steps", "50",
             "--max_rollbacks", "7", "--rollback_lr_backoff", "0.5",
             "--max_corrupt_records", "100"]))
        assert cfg.nan_policy == "rollback"
        assert cfg.rollback_snapshot_steps == 50
        assert cfg.max_rollbacks == 7
        assert cfg.rollback_lr_backoff == 0.5
        assert cfg.max_corrupt_records == 100

    def test_snapshot_cadence_constrains_scanned_dispatch_only_when_armed(
            self):
        """The snapshot cadence joins the steps_per_call alignment rule
        ONLY under nan_policy='rollback' — its default (100) must not
        reject steps_per_call=3 runs that never arm rollback."""
        from dcgan_tpu.config import TrainConfig

        TrainConfig(steps_per_call=3, sample_every_steps=3,
                    activation_summary_steps=3, nan_check_steps=3,
                    save_model_steps=3, log_every_steps=3)  # fine: inert
        with pytest.raises(ValueError, match="rollback_snapshot_steps"):
            TrainConfig(steps_per_call=3, sample_every_steps=3,
                        activation_summary_steps=3, nan_check_steps=3,
                        save_model_steps=3, log_every_steps=3,
                        nan_policy="rollback", rollback_snapshot_steps=100)

    def test_defaults_are_parity(self):
        from dcgan_tpu.config import TrainConfig

        cfg = TrainConfig()
        assert cfg.nan_policy == "abort"
        assert cfg.max_corrupt_records == 0

    def test_per_process_chaos_plan_selected_by_mh_pid(self):
        """ISSUE 4: an all-digit-keyed DCGAN_CHAOS object is a per-process
        map — the MH_PID process gets its entry, everyone else gets no
        plan, so one env value arms a fault on exactly one host."""
        env = {chaos.ENV_VAR: json.dumps({"1": {"nan_at_step": 3}}),
               "MH_PID": "1"}
        plan = chaos.plan_from_env(env)
        assert plan is not None and plan.nan_at_step == 3
        assert chaos.plan_from_env(dict(env, MH_PID="0")) is None
        assert chaos.plan_from_env(  # no MH_PID -> pid 0 -> no entry
            {chaos.ENV_VAR: json.dumps({"1": {"nan_at_step": 3}})}) is None
        with pytest.raises(ValueError, match="per-process"):
            chaos.plan_from_env({chaos.ENV_VAR: json.dumps({"1": 5}),
                                 "MH_PID": "1"})
        with pytest.raises(ValueError, match="unknown"):
            chaos.plan_from_env({chaos.ENV_VAR: json.dumps(
                {"1": {"nope": 1}}), "MH_PID": "1"})

    def test_new_fault_hooks_are_one_shot(self, monkeypatch):
        recorded = []
        monkeypatch.setattr(os, "kill",
                            lambda pid, sig: recorded.append((pid, sig)))
        chaos.set_plan(chaos.FaultPlan(sigterm_at_step=2))
        chaos.maybe_self_signal(1)
        assert recorded == []
        chaos.maybe_self_signal(2)
        chaos.maybe_self_signal(2)  # one-shot
        assert len(recorded) == 1

        slept = []
        import time as time_mod
        monkeypatch.setattr(time_mod, "sleep",
                            lambda s: slept.append(s))
        chaos.set_plan(chaos.FaultPlan(hang_at_step=3, hang_secs=5.0))
        chaos.maybe_hang(2)
        chaos.maybe_hang(3)
        chaos.maybe_hang(3)  # one-shot
        assert slept == [5.0]


def _tiny_cfg(tmp_path, **kw):
    from dcgan_tpu.config import ModelConfig, TrainConfig

    base = dict(
        model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                          compute_dtype="float32"),
        batch_size=16,
        checkpoint_dir=str(tmp_path / "ckpt"),
        sample_dir=str(tmp_path / "samples"),
        sample_every_steps=0, save_summaries_secs=0.0, save_model_secs=1e9,
        log_every_steps=0, tensorboard=False)
    base.update(kw)
    return TrainConfig(**base)


@pytest.mark.slow
class TestTrainerRollbackEndToEnd:
    def test_injected_nan_rolls_back_and_completes(self, tmp_path, capsys):
        from dcgan_tpu.train.trainer import train

        chaos.set_plan(chaos.FaultPlan(nan_at_step=3))
        cfg = _tiny_cfg(tmp_path, nan_policy="rollback", nan_check_steps=1,
                        rollback_snapshot_steps=2, max_rollbacks=2)
        state = train(cfg, synthetic_data=True, max_steps=6)
        assert int(jax.device_get(state["step"])) == 6
        out = capsys.readouterr().out
        assert "rolling back to last-good snapshot at step 2" in out
        events = [json.loads(l) for l in
                  open(tmp_path / "ckpt" / "events.jsonl")]
        rb = [e["values"]["anomaly/rollbacks"] for e in events
              if e["kind"] == "scalars"
              and "anomaly/rollbacks" in e["values"]]
        assert rb and max(rb) == 1

    def test_exhausted_rollbacks_abort(self, tmp_path):
        from dcgan_tpu.train.rollback import RollbackExhausted
        from dcgan_tpu.train.trainer import train

        # a genuinely divergent run (NaN learning rate poisons the params):
        # every restore re-trips, so the budget must end in a loud abort.
        # Summaries are off — the NaN params would crash the histogram
        # writer first, which is the abort path, not the one under test.
        cfg = _tiny_cfg(tmp_path, nan_policy="rollback", nan_check_steps=1,
                        rollback_snapshot_steps=2, max_rollbacks=2,
                        learning_rate=float("nan"), save_summaries_secs=1e9)
        with pytest.raises(RollbackExhausted, match="max_rollbacks"):
            train(cfg, synthetic_data=True, max_steps=8)

    def test_no_fault_parity_with_rollback_armed(self, tmp_path):
        """The A/B half of the acceptance parity criterion: arming the
        rollback machinery (snapshots, forced gate at boundaries) without
        any fault must leave every JSONL metric VALUE identical to the
        default-policy run — the snapshot path reads state, never touches
        it."""
        from dcgan_tpu.train.trainer import train

        def run(name, **kw):
            root = tmp_path / name
            cfg = _tiny_cfg(root, nan_check_steps=1, **kw)
            train(cfg, synthetic_data=True, max_steps=5)
            rows = {}
            for line in open(root / "ckpt" / "events.jsonl"):
                e = json.loads(line)
                if e["kind"] == "scalars":
                    rows[e["step"]] = {k: v for k, v in e["values"].items()
                                       if not k.startswith("perf/")}
            return rows

        a = run("abort")
        b = run("rollback", nan_policy="rollback",
                rollback_snapshot_steps=2, max_rollbacks=2,
                rollback_lr_backoff=0.5)
        assert a == b


@pytest.mark.slow
class TestQuarantineBaselineAcrossRuns:
    def test_second_train_call_does_not_inherit_counts(self, tmp_path):
        """ISSUE 4 satellite: the quarantine tally is process-global, so
        the trainer baselines it (`corrupt_base`) at startup — run 2's
        `data/corrupt_records` stream must report run 2's OWN corruption
        (zero here), not run 1's leftovers."""
        import dcgan_tpu.data.synthetic as synthetic
        from dcgan_tpu.train.trainer import train

        def events(root):
            out = []
            for line in open(root / "ckpt" / "events.jsonl"):
                e = json.loads(line)
                if e["kind"] == "scalars":
                    out.append(e["values"])
            return out

        # run 1: one corrupt record on disk, quarantined within budget
        data_dir = tmp_path / "data"
        paths = synthetic.write_image_tfrecords(
            str(data_dir), num_examples=48, image_size=16, num_shards=1)
        chaos.corrupt_tfrecord_payload(paths[0], record_index=2)
        root1 = tmp_path / "run1"
        cfg = _tiny_cfg(root1, data_dir=str(data_dir),
                        max_corrupt_records=10, shuffle_buffer=16,
                        num_loader_threads=1, save_summaries_secs=0.0)
        train(cfg, synthetic_data=False, max_steps=4)
        run1_counts = [v["data/corrupt_records"] for v in events(root1)
                       if "data/corrupt_records" in v]
        assert run1_counts and max(run1_counts) == 1

        # run 2, same process, clean synthetic data: the parity contract
        # says the counter key must be ABSENT (it only appears nonzero),
        # which is exactly what leaks from run 1 would violate
        root2 = tmp_path / "run2"
        cfg2 = _tiny_cfg(root2, max_corrupt_records=10,
                         save_summaries_secs=0.0)
        train(cfg2, synthetic_data=True, max_steps=4)
        assert all("data/corrupt_records" not in v for v in events(root2))
