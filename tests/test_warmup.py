"""Warm-start subsystem (ISSUE 5): persistent compile cache wiring, AOT
warmup shape set, single-pass verified restore, and warm-vs-cold parity.

What must hold: a second run against a primed cache dir records hits where
the cold dir recorded misses; the warmup plan covers every future call
shape (k=1 tail, steps_per_call scan, sampler/probe, the LR-backoff rebuild
variant) so a rollback drill triggers no recompile; default-flags event
streams stay byte-identical to warm-start-enabled ones (the parity
contract); and the fused restore reads each verified byte once, still
quarantining same-size corruption. The cross-process half of the story is
tools/bench_startup.py, pinned in test_tools.py."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.testing import chaos
from dcgan_tpu.train import warmup

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _pristine_chaos_plan():
    """The armed chaos plan is process-global and must not leak into later
    tests (the cache placement is restored by tests/conftest.py)."""
    chaos.reset()
    yield
    chaos.reset()


def _tiny_cfg(root, **kw):
    from dcgan_tpu.config import ModelConfig, TrainConfig

    base = dict(
        model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                          compute_dtype="float32"),
        batch_size=8,
        checkpoint_dir=os.path.join(str(root), "ckpt"),
        sample_dir=os.path.join(str(root), "samples"),
        sample_every_steps=0, save_summaries_secs=0.0, save_model_secs=1e9,
        log_every_steps=0, tensorboard=False, activation_summary_steps=0)
    base.update(kw)
    return TrainConfig(**base)


def _scalar_events(root):
    out = []
    with open(os.path.join(str(root), "ckpt", "events.jsonl")) as f:
        for line in f:
            e = json.loads(line)
            if e["kind"] == "scalars":
                out.append((e["step"], e["values"]))
    return out


def _startup_values(root):
    for _, vals in _scalar_events(root):
        if "perf/startup/total_ms" in vals:
            return vals
    return None


class TestCacheConfig:
    @pytest.mark.parametrize("flag,env,entry_point,want", [
        # --compile_cache_dir overrides both the variable and the default
        ("/a/b", {"JAX_COMPILATION_CACHE_DIR": "/c"}, True, "/a/b"),
        ("/a/b", {}, False, "/a/b"),
        # the variable set: that directory, for library and entry point
        ("", {"JAX_COMPILATION_CACHE_DIR": "/c"}, False, "/c"),
        ("", {"JAX_COMPILATION_CACHE_DIR": "/c"}, True, "/c"),
        # unset: the fixed in-checkout path at an entry point, and "leave
        # the process alone" for a library caller
        ("", {}, True, warmup.CHECKOUT_CACHE_DIR),
        ("", {}, False, ""),
    ])
    def test_resolve_flag_then_env_then_checkout(self, flag, env,
                                                 entry_point, want):
        assert warmup.resolve_cache_dir(
            flag, entry_point=entry_point, env=env) == want

    def test_checkout_dir_is_fixed_and_git_ignored(self):
        """The path is part of the cache key: it must not move between
        runs (no tempfile/pid/time component), and what lands in it must
        never be committed."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert warmup.CHECKOUT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_train_leaves_an_outside_placement_alone(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR lands in jax's config at import; a
        train() with no --compile_cache_dir must keep the cache there —
        entries written there, the setting untouched afterwards."""
        from dcgan_tpu.train.trainer import train

        outside = str(tmp_path / "outside")
        jax.config.update("jax_compilation_cache_dir", outside)
        train(_tiny_cfg(tmp_path), synthetic_data=True, max_steps=1)
        assert jax.config.jax_compilation_cache_dir == outside
        assert os.listdir(outside)
        # and the run knew its cache was on: the adoption counters landed
        assert _startup_values(tmp_path)["perf/compile_cache_misses"] > 0

    def test_configure_points_jax_at_dir(self, tmp_path):
        d = str(tmp_path / "cc")
        assert warmup.configure_compile_cache("") is None
        assert warmup.configure_compile_cache(d) == d
        assert os.path.isdir(d)
        assert jax.config.jax_compilation_cache_dir == d
        # every program in this trainer is worth caching (DESIGN.md §6d)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_configure_empty_leaves_the_process_setting_alone(self,
                                                              tmp_path):
        """An empty dir is "no opinion", not "off": the directory in force
        stays in force and is what the caller is told, so the trainer's
        cache-keyed behaviour (monitor, warm proof) sees the cache that
        is really active."""
        d = str(tmp_path / "cc")
        warmup.configure_compile_cache(d)
        assert warmup.configure_compile_cache("") == d
        assert jax.config.jax_compilation_cache_dir == d

    def test_per_process_dirs_do_not_claim_fleet_warmth(self):
        """JAX writes cache entries from the chief only, so
        per-process multi-host stores never fill on non-chief processes —
        warm proof (the watchdog arming shortcut) must not ride on them.
        Single-process is always servable."""
        assert warmup.cache_serves_all_processes(False)
        assert warmup.cache_serves_all_processes(True)  # 1 process

    def test_monitor_counts_and_unregisters(self, tmp_path):
        warmup.configure_compile_cache(str(tmp_path / "cc"))
        mon = warmup.CompileCacheMonitor()
        f = jax.jit(lambda x: x * 2 + 1)
        f(jnp.ones((8, 8))).block_until_ready()
        live = mon.counters()
        assert live["requests"] >= 1 and live["misses"] >= 1
        mon.close()
        baseline = mon.counters()
        g = jax.jit(lambda x: x * 3 - 1)
        g(jnp.ones((8, 8))).block_until_ready()
        assert mon.counters() == baseline  # closed monitors stop counting

    def test_backoff_config_matches_trainer_construction(self):
        from dcgan_tpu.config import TrainConfig

        cfg = TrainConfig(learning_rate=2e-4, d_learning_rate=1e-4)
        bk = warmup.backoff_config(cfg, 0.5)
        assert bk.learning_rate == pytest.approx(1e-4)
        assert bk.d_learning_rate == pytest.approx(5e-5)
        assert bk.g_learning_rate is None  # None stays None (lr fallback)


class TestWarmupPlan:
    def _pt_state(self, cfg):
        from dcgan_tpu.parallel import make_mesh, make_parallel_train

        mesh = make_mesh(cfg.mesh)
        pt = make_parallel_train(cfg, mesh)
        return mesh, pt, pt.init(jax.random.key(0))

    def test_plan_covers_known_future_call_shapes(self, tmp_path):
        """The full shape set: k=1 tail + steps_per_call scan + sampler +
        probe + summarize + the LR-backoff step variants, with a pre-built
        backoff ParallelTrain returned for the trainer to stash."""
        from dcgan_tpu.parallel import make_parallel_train

        cfg = _tiny_cfg(tmp_path, steps_per_call=2, sample_every_steps=2,
                        activation_summary_steps=2, nan_check_steps=2,
                        log_every_steps=2, nan_policy="rollback",
                        rollback_snapshot_steps=2, rollback_lr_backoff=0.5)
        mesh, pt, state = self._pt_state(cfg)
        z = jax.random.uniform(jax.random.key(1), (8, cfg.model.z_dim))
        plan, pt_backoff = warmup.build_warmup_plan(
            cfg, pt, state, sample_z=z, eval_z=z,
            make_backoff_pt=lambda c: make_parallel_train(c, mesh))
        names = [n for n, _, _ in plan]
        assert names == ["train_step", "state_copy", "multi_step@k2",
                         "sampler", "eval_losses", "summarize",
                         "train_step@lr_backoff",
                         "multi_step@k2@lr_backoff"]
        assert pt_backoff is not None
        assert pt_backoff.cfg.learning_rate == \
            pytest.approx(cfg.learning_rate * 0.5)

    def test_plan_minimal_when_probes_off(self, tmp_path):
        cfg = _tiny_cfg(tmp_path)
        _, pt, state = self._pt_state(cfg)
        plan, pt_backoff = warmup.build_warmup_plan(cfg, pt, state)
        assert [n for n, _, _ in plan] == ["train_step", "state_copy"]
        assert pt_backoff is None

    def test_aot_compile_times_every_program(self, tmp_path):
        cfg = _tiny_cfg(tmp_path)
        _, pt, state = self._pt_state(cfg)
        plan, _ = warmup.build_warmup_plan(cfg, pt, state)
        timings = warmup.aot_compile(plan)
        assert set(timings) == {"train_step", "state_copy"}
        assert all(ms > 0 for ms in timings.values())


@pytest.mark.slow
class TestCacheWiringEndToEnd:
    def test_cold_dir_misses_then_primed_dir_hits(self, tmp_path):
        """The tentpole's cache contract: a run against a cold cache dir
        records misses; a SECOND run (fresh jit objects, same programs,
        same dir) records hits and zero misses — the restart path
        deserializes instead of compiling."""
        from dcgan_tpu.train.trainer import train

        cache = str(tmp_path / "cache")
        cfg1 = _tiny_cfg(tmp_path / "r1", compile_cache_dir=cache,
                         aot_warmup=True)
        train(cfg1, synthetic_data=True, max_steps=3)
        cold = _startup_values(tmp_path / "r1")
        assert cold is not None
        assert cold["perf/compile_cache_misses"] > 0
        assert cold["perf/compile_ms/train_step"] > 0

        cfg2 = _tiny_cfg(tmp_path / "r2", compile_cache_dir=cache,
                         aot_warmup=True)
        train(cfg2, synthetic_data=True, max_steps=3)
        warm = _startup_values(tmp_path / "r2")
        assert warm is not None
        assert warm["perf/compile_cache_hits"] > 0
        assert warm["perf/compile_cache_misses"] == 0
        assert warm["perf/startup/warmup_ms"] > 0

    def test_rollback_drill_recompiles_nothing_warm(self, tmp_path, capsys):
        """The watchdog-adjacent warmup claim: with the backoff variant
        pre-compiled and the cache primed, a live NaN rollback with LR
        backoff swaps in the pre-warmed surface and the WHOLE drill —
        restore, replay, backoff dispatch — records zero cache misses."""
        from dcgan_tpu.train.trainer import train

        cache = str(tmp_path / "cache")
        kw = dict(compile_cache_dir=cache, aot_warmup=True,
                  nan_policy="rollback", nan_check_steps=1,
                  rollback_snapshot_steps=2, max_rollbacks=2,
                  rollback_lr_backoff=0.5)
        train(_tiny_cfg(tmp_path / "prime", **kw), synthetic_data=True,
              max_steps=3)  # no fault: primes every program incl. backoff

        mon = warmup.CompileCacheMonitor()
        before = mon.counters()
        chaos.set_plan(chaos.FaultPlan(nan_at_step=3))
        state = train(_tiny_cfg(tmp_path / "drill", **kw),
                      synthetic_data=True, max_steps=6)
        delta = mon.delta(mon.counters(), before)
        mon.close()
        assert int(jax.device_get(state["step"])) == 6
        out = capsys.readouterr().out
        assert "rolling back to last-good snapshot" in out
        assert "pre-warmed surface swapped in" in out
        assert delta["hits"] > 0
        assert delta["misses"] == 0, delta

    def test_warm_vs_cold_jsonl_value_parity(self, tmp_path):
        """The acceptance parity criterion: warm-start knobs change WHEN
        programs compile, never what they compute — scalar values per step
        identical modulo the perf/ channel, and the default run carries no
        warm-start keys at all."""
        from dcgan_tpu.train.trainer import train

        def run(root, **kw):
            train(_tiny_cfg(root, nan_check_steps=1, **kw),
                  synthetic_data=True, max_steps=5)
            rows = {}
            for step, vals in _scalar_events(root):
                rows[step] = {k: v for k, v in vals.items()
                              if not k.startswith("perf/")}
            return rows

        cold = run(tmp_path / "default")
        warm = run(tmp_path / "warm",
                   compile_cache_dir=str(tmp_path / "cache"),
                   aot_warmup=True)
        assert cold == warm
        # the default stream must not even carry the startup/cache keys
        for _, vals in _scalar_events(tmp_path / "default"):
            assert not any(k.startswith(("perf/startup/", "perf/compile"))
                           for k in vals)


class TestFusedRestore:
    def _ckpt(self, tmp_path):
        from dcgan_tpu.utils.checkpoint import Checkpointer

        return Checkpointer(str(tmp_path / "ck"), async_save=False)

    def _state(self, value):
        return {"w": jnp.full((64, 64), value, jnp.float32),
                "step": jnp.asarray(int(value), jnp.int32)}

    def test_same_size_corruption_quarantined(self, tmp_path, capsys):
        """Bit rot that preserves file SIZE sails past the stat pre-check
        and must be caught by the checksum pass running CONCURRENTLY with
        the Orbax read — the restored-from-bad-bytes tree is discarded and
        the previous step restores instead."""
        ck = self._ckpt(tmp_path)
        ck.save(1, self._state(1.0), force=True)
        ck.save(2, self._state(2.0), force=True)
        ck.wait()
        # flip one payload byte, size unchanged
        files = []
        for root, _, names in os.walk(os.path.join(ck.directory, "2")):
            files += [os.path.join(root, n) for n in names]
        target = max(files, key=os.path.getsize)
        with open(target, "r+b") as f:
            f.seek(os.path.getsize(target) // 2)
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
        from dcgan_tpu.utils import checkpoint as ckpt_mod

        ckpt_mod._CRC_CACHE.clear()  # the flip is invisible to stat

        restored = ck.restore_latest(self._state(0.0))
        assert int(restored["step"]) == 1
        np.testing.assert_array_equal(
            np.asarray(restored["w"]), np.full((64, 64), 1.0, np.float32))
        assert os.path.isdir(os.path.join(ck.directory, "2.corrupt"))
        assert "crc32 mismatch" in capsys.readouterr().out

    def test_restore_stats_read_once_and_hash_sharing(self, tmp_path):
        """Single-pass accounting: the verify layer reads each manifest
        byte at most once, and hashes computed at SAVE time (the manifest
        write) serve a same-process restore from the fingerprint cache
        without re-reading."""
        ck = self._ckpt(tmp_path)
        ck.save(1, self._state(1.0), force=True)
        ck.wait()  # manifest written -> hashes in the fingerprint cache
        with open(os.path.join(ck.directory, "integrity", "1.json")) as f:
            manifest_bytes = sum(rec["size"] for rec
                                 in json.load(f)["files"].values())

        restored = ck.restore_latest(self._state(0.0))
        assert int(restored["step"]) == 1
        stats = ck.last_restore_stats
        assert stats is not None
        assert stats["files"] > 0
        assert stats["bytes_read"] + stats["bytes_cached"] == manifest_bytes
        # same process, same bytes: the save-time hashes did the work
        assert stats["bytes_cached"] == manifest_bytes
        assert stats["restore_ms"] > 0

    def test_fused_large_file_path_verifies_and_quarantines(self, tmp_path,
                                                            monkeypatch):
        """With the structural-first threshold forced to 0 every file takes
        the FUSED path (background CRC concurrent with the Orbax read):
        a clean step restores with correct read-once stats, and same-size
        corruption still discards the concurrently-restored tree and falls
        back."""
        from dcgan_tpu.utils import checkpoint as ckpt_mod

        monkeypatch.setattr(ckpt_mod, "_PREPARSE_VERIFY_MAX_BYTES", 0)
        ck = self._ckpt(tmp_path)
        ck.save(1, self._state(1.0), force=True)
        ck.save(2, self._state(2.0), force=True)
        ck.wait()
        with open(os.path.join(ck.directory, "integrity", "2.json")) as f:
            manifest_bytes = sum(rec["size"] for rec
                                 in json.load(f)["files"].values())
        restored = ck.restore_latest(self._state(0.0))
        assert int(restored["step"]) == 2
        stats = ck.last_restore_stats
        assert stats["bytes_read"] + stats["bytes_cached"] == manifest_bytes

        # now corrupt step 2 in place (same size) — the fused path must
        # discard the concurrent restore and fall back to step 1
        files = []
        for root, _, names in os.walk(os.path.join(ck.directory, "2")):
            files += [os.path.join(root, n) for n in names]
        target = max(files, key=os.path.getsize)
        with open(target, "r+b") as f:
            f.seek(os.path.getsize(target) // 2)
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
        ckpt_mod._CRC_CACHE.clear()
        restored = ck.restore_latest(self._state(0.0))
        assert int(restored["step"]) == 1
        assert os.path.isdir(os.path.join(ck.directory, "2.corrupt"))

    def test_transient_stat_error_does_not_condemn(self, tmp_path,
                                                   monkeypatch):
        """PR 4's retry contract extended to the new stat pre-screen: one
        transient EIO on a stat must get its bounded retries instead of
        permanently quarantining an intact checkpoint."""
        ck = self._ckpt(tmp_path)
        ck.save(1, self._state(1.0), force=True)
        ck.wait()
        real_stat = os.stat
        tripped = {}

        def flaky_stat(path, *a, **kw):
            p = os.fspath(path)
            if "integrity" not in p and str(ck.directory) in p \
                    and p.endswith("_METADATA") and "once" not in tripped:
                tripped["once"] = True
                raise OSError(5, "Input/output error", p)
            return real_stat(path, *a, **kw)

        monkeypatch.setattr(os, "stat", flaky_stat)
        assert ck._verify_step(1) == (True, "verified")
        assert tripped  # the fault actually fired
        assert not os.path.isdir(os.path.join(ck.directory, "1.corrupt"))

    def test_verify_step_contract_unchanged(self, tmp_path):
        ck = self._ckpt(tmp_path)
        ck.save(3, self._state(3.0), force=True)
        ck.wait()
        assert ck._verify_step(3) == (True, "verified")

    def test_restored_state_donates_into_a_deserialized_executable(
            self, tmp_path):
        """What the donation guards this repo used to carry (a rebase of
        every restored tree, an owned copy of every host snapshot) stood
        against under an older jaxlib: an executable read back from the
        persistent cache donating buffers XLA does not own, or donating
        over a live host view. Under the installed jaxlib a
        tensorstore-backed restore and a device_put tree both donate
        cleanly into such an executable, and a host view taken before the
        donation keeps its values — so the guards are gone, and this is
        the test that says when they would be needed again."""
        warmup.configure_compile_cache(str(tmp_path / "cc"))

        def make_step():
            # a fresh function object per call: the in-memory jit cache
            # misses, the persistent one (keyed on the program) does not
            def step(t):
                return jax.tree_util.tree_map(lambda a: a * 2 + 1, t)
            return jax.jit(step, donate_argnums=0)

        make_step()(self._state(1.0))  # compiles, writes the entry
        mon = warmup.CompileCacheMonitor()
        deserialized = make_step()

        ck = self._ckpt(tmp_path)
        ck.save(1, self._state(5.0), force=True)
        ck.wait()
        restored = ck.restore_latest(self._state(0.0))
        put = jax.device_put(jax.device_get(self._state(3.0)))
        for tree, start in ((restored, 5.0), (put, 3.0)):
            view = jax.device_get(tree)
            out, want = tree, start
            for _ in range(8):
                out, want = deserialized(out), want * 2 + 1
            np.testing.assert_array_equal(
                np.asarray(out["w"]), np.full((64, 64), want, np.float32))
            np.testing.assert_array_equal(
                view["w"], np.full((64, 64), start, np.float32))
        assert mon.counters()["hits"] >= 1  # it WAS read back, not compiled
        mon.close()


class TestStartupProfile:
    def test_phases_accumulate_and_first_step_wins_once(self):
        from dcgan_tpu.utils.profiling import StartupProfile

        sp = StartupProfile()
        with sp.phase("init"):
            pass
        with sp.phase("init"):
            pass
        assert not sp.done
        sp.first_step()
        total = sp.summary()["perf/startup/total_ms"]
        sp.first_step()  # idempotent
        assert sp.summary()["perf/startup/total_ms"] == total
        assert sp.summary()["perf/startup/init_ms"] >= 0
