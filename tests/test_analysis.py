"""Invariant analyzer (ISSUE 8): per-checker fixture suites, the
suppression/baseline machinery, the runtime tripwire, and the
full-package clean pin.

Each checker gets synthetic bad-code snippets that must produce exactly
their seeded finding, plus clean twins that must produce none — the
fixtures are the spec for what the AST heuristics resolve. Paths are
chosen to land inside (or outside) each checker's scope."""

import json
import threading

import numpy as np
import pytest

from dcgan_tpu.analysis import core, tripwire
from dcgan_tpu.analysis.parity import key_in_inventory


def run(snippets, checks=None, inventory=None, **cfg_kw):
    """snippets: {relpath: source} -> findings (suppressions applied)."""
    sources = [core.SourceFile.from_source(src, path)
               for path, src in snippets.items()]
    cfg = core.Config(inventory=inventory if inventory is not None else {},
                      **cfg_kw)
    return core.run_checks(sources, cfg, checks=checks)


# -- DCG001: collectives off the dispatch thread -----------------------------

class TestCollectiveThreads:
    BAD_THREAD = '''
import threading
from jax.experimental import multihost_utils

def worker():
    multihost_utils.process_allgather(1)

def start():
    threading.Thread(target=worker, daemon=True).start()
'''

    def test_thread_target_reaching_collective_flagged(self):
        fs = run({"dcgan_tpu/x.py": self.BAD_THREAD}, checks=["DCG001"])
        assert [f.check for f in fs] == ["DCG001"]
        assert fs[0].key == "worker->process_allgather"
        assert "dispatch thread" in fs[0].message

    def test_multi_hop_and_submit_root(self):
        src = '''
from jax import lax

def helper(x):
    return lax.psum(x, "data")

def task(x):
    return helper(x)

def main(svc, x):
    svc.submit(task)
'''
        fs = run({"dcgan_tpu/x.py": src}, checks=["DCG001"])
        assert [f.key for f in fs] == ["task->psum"]

    def test_cross_module_resolution(self):
        coord = '''
def anomaly_consensus(bad):
    return bad
'''
        user = '''
import threading
from dcgan_tpu.train.coordination import anomaly_consensus

def poller():
    anomaly_consensus(False)

def go():
    threading.Thread(target=poller).start()
'''
        fs = run({"dcgan_tpu/train/coordination.py": coord,
                  "dcgan_tpu/train/x.py": user}, checks=["DCG001"])
        assert [f.key for f in fs] == ["poller->anomaly_consensus"]

    def test_receiver_gating_save(self):
        # img.save is PIL, ckpt.save is a collective: only the checkpoint
        # receiver trips the generic method name
        src = '''
def grid_task(img, path):
    img.save(path)

def save_task(ckpt, step, state):
    ckpt.save(step, state)

def go(svc):
    svc.submit(grid_task)
    svc.submit(save_task)
'''
        fs = run({"dcgan_tpu/x.py": src}, checks=["DCG001"])
        assert [f.key for f in fs] == ["save_task->ckpt.save"]

    def test_positional_thread_target_slot(self):
        # Thread(group, target): the positional target is args[1]
        src = '''
import threading
from jax import lax

def worker():
    lax.psum(1, "data")

def go():
    threading.Thread(None, worker).start()
'''
        fs = run({"dcgan_tpu/x.py": src}, checks=["DCG001"])
        assert [f.key for f in fs] == ["worker->psum"]

    def test_pt_gating_is_whole_segment(self):
        # `opt.step` is an optimizer, `script.init` a helper — neither may
        # trip the pt-dispatch heuristic (substring matching once did)
        src = '''
def task(opt, script, grads):
    opt.step(grads)
    script.init()

def go(svc):
    svc.submit(task)
'''
        assert run({"dcgan_tpu/x.py": src}, checks=["DCG001"]) == []

    def test_clean_twin_host_local_tail(self):
        src = '''
import threading, json

def worker(rows, path):
    with open(path, "a") as f:
        for r in rows:
            f.write(json.dumps(r) + "\\n")

def start(rows, path):
    threading.Thread(target=worker, args=(rows, path)).start()
'''
        assert run({"dcgan_tpu/x.py": src}, checks=["DCG001"]) == []

    DISPATCH_OWNER = '''
import threading

class ServeWorker:
    def _run(self):
        self._ckpt.restore_latest(self._state)
        self._pt.sample(self._state, None)

    def start(self):
        threading.Thread(target=self._run).start()
'''

    def test_declared_dispatch_thread_target_exempt(self):
        """ISSUE 9: a thread target declared in
        Config.dispatch_thread_targets IS a dispatch thread by design
        (the serving plane's single worker owns every collective) — no
        finding; the same code undeclared still trips on the
        `restore_latest` terminal-name sink."""
        path = "dcgan_tpu/serve/w.py"
        flagged = run({path: self.DISPATCH_OWNER}, checks=["DCG001"])
        assert [f.key for f in flagged] == [
            "self._run->restore_latest"]
        clean = run({path: self.DISPATCH_OWNER}, checks=["DCG001"],
                    dispatch_thread_targets=(
                        f"{path}::ServeWorker._run",))
        assert clean == []

    def test_dispatch_owner_declaration_is_exact(self):
        """The allowlist matches path::QualName exactly — a different
        class or file with the same method name keeps tripping."""
        path = "dcgan_tpu/serve/w.py"
        fs = run({path: self.DISPATCH_OWNER}, checks=["DCG001"],
                 dispatch_thread_targets=(
                     "dcgan_tpu/serve/other.py::ServeWorker._run",
                     f"{path}::OtherWorker._run"))
        assert [f.check for f in fs] == ["DCG001"]

    def test_real_services_and_coordination_are_clean(self):
        sources = core.collect_sources(
            [core.default_root() + "/dcgan_tpu"], core.default_root())
        fs = core.run_checks(sources, core.Config(inventory={}),
                             checks=["DCG001"])
        assert fs == []


# -- DCG003: raw shard_map ---------------------------------------------------

class TestRawShardMap:
    def test_import_and_attribute_flagged(self):
        src = '''
from jax.experimental.shard_map import shard_map
import jax

def use(f, mesh):
    return jax.shard_map(f, mesh=mesh)
'''
        fs = run({"dcgan_tpu/parallel/x.py": src}, checks=["DCG003"])
        assert {f.key for f in fs} == {"jax.experimental.shard_map",
                                       "jax.shard_map"}

    def test_plain_import_form_flagged(self):
        src = '''
import jax.experimental.shard_map as shmap

def use(f, mesh):
    return shmap.shard_map(f, mesh=mesh)
'''
        fs = run({"dcgan_tpu/parallel/x.py": src}, checks=["DCG003"])
        assert "jax.experimental.shard_map" in {f.key for f in fs}

    def test_docstring_claim_flagged(self):
        src = '"""This backend drives jax.shard_map by hand."""\n'
        fs = run({"dcgan_tpu/parallel/x.py": src}, checks=["DCG003"])
        assert [f.key for f in fs] == ["docstring:jax.shard_map"]

    def test_backend_shim_exempt_and_shim_users_clean(self):
        shim = '''
"""The jax.shard_map compat shim."""
from jax.experimental.shard_map import shard_map as _shard_map
'''
        user = '''
from dcgan_tpu.utils.backend import shard_map

def build(f, mesh, specs):
    return shard_map(f, mesh=mesh, in_specs=specs, out_specs=specs)
'''
        fs = run({"dcgan_tpu/utils/backend.py": shim,
                  "dcgan_tpu/parallel/x.py": user}, checks=["DCG003"])
        assert fs == []

    def test_corrected_shard_map_backend_is_negative_fixture(self):
        # the satellite fix: the real shard_map_backend.py no longer
        # claims the modern API anywhere (docstring included)
        sources = core.collect_sources(
            [core.default_root() + "/dcgan_tpu/parallel"],
            core.default_root())
        fs = core.run_checks(sources, core.Config(inventory={}),
                             checks=["DCG003"])
        assert fs == []


# -- DCG004: parity key inventory --------------------------------------------

class TestKeyInventory:
    TRAINER = "dcgan_tpu/train/trainer.py"  # inside the parity scope

    def test_ungated_key_flagged(self):
        src = 'row = {"perf/new_thing_ms": 1.0}\n'
        fs = run({self.TRAINER: src}, checks=["DCG004"], inventory={})
        assert [f.key for f in fs] == ["perf/new_thing_ms"]
        assert "event-key inventory" in fs[0].message

    def test_declared_and_wildcard_keys_clean(self):
        src = ('row = {"perf/new_thing_ms": 1.0}\n'
               'row2 = {f"sample/{k}": v for k, v in vals.items()}\n')
        inv = {"perf/new_thing_ms": "always", "sample/*": "probe"}
        assert run({self.TRAINER: src}, checks=["DCG004"],
                   inventory=inv) == []

    def test_fstring_prefix_needs_wildcard_entry(self):
        src = 'row[f"perf/compile_ms/{name}"] = ms\n'
        fs = run({self.TRAINER: src}, checks=["DCG004"], inventory={})
        assert [f.key for f in fs] == ["perf/compile_ms/*"]
        assert run({self.TRAINER: src}, checks=["DCG004"],
                   inventory={"perf/compile_ms/*": "aot_warmup"}) == []

    def test_out_of_scope_module_ignored(self):
        src = 'row = {"perf/whatever": 1.0}\n'
        assert run({"dcgan_tpu/evals/x.py": src}, checks=["DCG004"],
                   inventory={}) == []

    def test_serve_namespace_linted_in_serve_modules(self):
        """ISSUE 9: the serving plane's server/__main__ modules are in the
        parity scope and the `serve/` namespace marks key literals — an
        undeclared serve key fails the lint like a trainer key would."""
        src = 'row = {"serve/new_counter": 1.0}\n'
        path = "dcgan_tpu/serve/server.py"
        fs = run({path: src}, checks=["DCG004"], inventory={})
        assert [f.key for f in fs] == ["serve/new_counter"]
        assert run({path: src}, checks=["DCG004"],
                   inventory={"serve/new_counter": "serve entrypoint"}) \
            == []
        # serve literals outside the declared parity modules stay out of
        # scope, same as every other namespace
        assert run({"dcgan_tpu/serve/buckets.py": src}, checks=["DCG004"],
                   inventory={}) == []

    def test_runtime_steptimer_keys_covered(self):
        """The inventory-completeness half the static pass cannot see:
        the keys StepTimer actually produces are all declared."""
        from dcgan_tpu.train.event_keys import EVENT_KEYS
        from dcgan_tpu.utils.profiling import StepTimer

        t = StepTimer(window=4, images_per_step=8)
        t.tick(now=0.0)
        t.note_host(0.001)
        t.tick(now=0.01)
        for key in t.summary():
            assert key_in_inventory(key, EVENT_KEYS), key

    def test_runtime_startup_and_fleet_keys_covered(self):
        from dcgan_tpu.train.coordination import HEALTH_FIELDS, fleet_metrics
        from dcgan_tpu.train.event_keys import EVENT_KEYS
        from dcgan_tpu.utils.profiling import StartupProfile

        sp = StartupProfile()
        with sp.phase("init"):
            pass
        sp.first_step()
        for key in sp.summary():
            assert key_in_inventory(key, EVENT_KEYS), key
        row, _ = fleet_metrics(np.ones((2, len(HEALTH_FIELDS))))
        for key in row:
            assert key_in_inventory(key, EVENT_KEYS), key

    def test_inventory_has_no_stale_trainer_literals(self):
        """Round-trip tightness: every non-wildcard inventory entry that
        names a literal the static pass CAN see is actually still emitted
        somewhere in the scanned modules — a renamed key must retire its
        inventory row, not leave it lying."""
        from dcgan_tpu.analysis.parity import _extract_keys
        from dcgan_tpu.train.event_keys import EVENT_KEYS

        cfg = core.Config()
        sources = core.collect_sources(
            [core.default_root() + "/dcgan_tpu/train",
             core.default_root() + "/dcgan_tpu/serve",
             core.default_root() + "/dcgan_tpu/progressive"],
            core.default_root())
        found = set()
        for sf in sources:
            if sf.path in cfg.parity_modules:
                found.update(k for k, _ in _extract_keys(sf))
        # keys produced through prefix parameters in OTHER modules are
        # pinned by the runtime tests above instead
        runtime_built = {k for k in EVENT_KEYS
                         if k.startswith(("perf/step_ms", "perf/steps_per",
                                          "perf/images_per", "perf/host_ms",
                                          "perf/dispatch_occupancy",
                                          "perf/startup/"))}
        stale = [k for k in EVENT_KEYS
                 if k not in found and k not in runtime_built]
        assert stale == [], f"inventory entries no longer emitted: {stale}"


# -- DCG005: traced-body hygiene ---------------------------------------------

class TestTracedBodyHygiene:
    def test_decorated_jit_with_wall_clock_flagged(self):
        src = '''
import jax, time

@jax.jit
def f(x):
    return x * time.time()
'''
        fs = run({"dcgan_tpu/x.py": src}, checks=["DCG005"])
        assert [f.key for f in fs] == ["f:time.time"]

    def test_passed_by_name_and_lambda_forms(self):
        src = '''
import jax
import numpy as np

def body(x):
    return x + np.random.rand()

g = jax.jit(body)
h = jax.jit(lambda x: x * np.random.rand())
'''
        fs = run({"dcgan_tpu/x.py": src}, checks=["DCG005"])
        assert sorted(f.key for f in fs) == ["<lambda>:np.random.rand",
                                             "body:np.random.rand"]

    def test_shard_map_body_with_host_rng_flagged(self):
        src = '''
import random
from dcgan_tpu.utils.backend import shard_map

def step_body(state, images):
    noise = random.random()
    return state

def build(mesh, specs):
    return shard_map(step_body, mesh=mesh, in_specs=specs,
                     out_specs=specs)
'''
        fs = run({"dcgan_tpu/x.py": src}, checks=["DCG005"])
        assert [f.key for f in fs] == ["step_body:random.random"]

    def test_from_import_form_still_flagged(self):
        src = '''
import jax
from time import time as _t

@jax.jit
def f(x):
    return x * _t()
'''
        fs = run({"dcgan_tpu/x.py": src}, checks=["DCG005"])
        assert [f.key for f in fs] == ["f:time.time"]

    def test_clean_twin_jax_prng_and_untraced_clock(self):
        src = '''
import jax, time

def step_body(state, key):
    z = jax.random.uniform(key, (4,))
    return state, z

g = jax.jit(step_body)

def host_loop():
    return time.time()  # untraced: fine
'''
        assert run({"dcgan_tpu/x.py": src}, checks=["DCG005"]) == []


# -- DCG006: bare filesystem IO ----------------------------------------------

class TestBareIO:
    CKPT = "dcgan_tpu/utils/checkpoint.py"  # inside the IO scope

    def test_bare_replace_flagged(self):
        src = '''
import os

def mark(src, dst):
    os.replace(src, dst)
'''
        fs = run({self.CKPT: src}, checks=["DCG006"])
        assert [f.key for f in fs] == ["os.replace"]

    def test_retry_wrapped_and_fenced_twins_clean(self):
        src = '''
import os
from dcgan_tpu.utils.retry import retry_io

def write(path, payload):
    def _write():
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(payload)
        os.replace(tmp, path)
    retry_io(_write, tag="x")

def lam(path):
    retry_io(lambda: os.remove(path), tag="y")

def best_effort(path):
    try:
        os.remove(path)
    except OSError:
        pass
'''
        assert run({self.CKPT: src}, checks=["DCG006"]) == []

    def test_from_import_mutator_still_flagged(self):
        src = '''
from os import replace

def mark(a, b):
    replace(a, b)
'''
        fs = run({self.CKPT: src}, checks=["DCG006"])
        assert [f.key for f in fs] == ["os.replace"]

    def test_reads_exempt_and_scope_respected(self):
        read = '''
def checksum(path):
    with open(path, "rb") as f:
        return len(f.read())
'''
        outside = '''
import os

def anywhere(a, b):
    os.replace(a, b)
'''
        assert run({self.CKPT: read, "dcgan_tpu/evals/x.py": outside},
                   checks=["DCG006"]) == []


# -- suppression + baseline round-trip ---------------------------------------

class TestSuppressionAndBaseline:
    BAD = '''
import jax

def use(f, mesh):
    return jax.shard_map(f, mesh=mesh)
'''

    def test_line_suppression(self):
        suppressed = self.BAD.replace(
            "jax.shard_map(f, mesh=mesh)",
            "jax.shard_map(f, mesh=mesh)  # dcg: disable=DCG003")
        assert run({"dcgan_tpu/x.py": suppressed}, checks=["DCG003"]) == []
        # the wrong ID does not suppress
        wrong = self.BAD.replace(
            "jax.shard_map(f, mesh=mesh)",
            "jax.shard_map(f, mesh=mesh)  # dcg: disable=DCG001")
        assert len(run({"dcgan_tpu/x.py": wrong}, checks=["DCG003"])) == 1

    def test_baseline_round_trip(self, tmp_path):
        fs = run({"dcgan_tpu/x.py": self.BAD}, checks=["DCG003"])
        assert len(fs) == 1
        path = tmp_path / "baseline.jsonl"
        path.write_text("# comment line\n" + "".join(
            json.dumps(f.baseline_entry(why="known legacy")) + "\n"
            for f in fs))
        baseline = core.load_baseline(str(path))
        new, old = core.split_baselined(fs, baseline)
        assert new == [] and len(old) == 1
        # a NEW finding is not absorbed by the old baseline
        two = self.BAD + "\n\ndef more(g, mesh):\n" \
                         "    return jax.shard_map(g, mesh=mesh)\n"
        fs2 = run({"dcgan_tpu/x.py": two}, checks=["DCG003"])
        new2, old2 = core.split_baselined(fs2, baseline)
        assert len(old2) == 1 and len(new2) == 1
        assert new2[0].symbol == "more"

    def test_baseline_requires_why(self, tmp_path):
        path = tmp_path / "b.jsonl"
        path.write_text(json.dumps({"check": "DCG003", "path": "x",
                                    "symbol": "s", "key": "k"}) + "\n")
        with pytest.raises(ValueError, match="why"):
            core.load_baseline(str(path))
        # the --write-baseline draft placeholder is not a justification
        path.write_text(json.dumps({"check": "DCG003", "path": "x",
                                    "symbol": "s", "key": "k",
                                    "why": "TODO: justify"}) + "\n")
        with pytest.raises(ValueError, match="TODO"):
            core.load_baseline(str(path))

    def test_baseline_matching_is_multiset(self):
        """One reviewed entry absorbs one finding: a SECOND violation with
        the same fingerprint (another bare write in the same function)
        still fails the run."""
        src = '''
import os

def mark(a, b, c):
    os.replace(a, b)
    os.replace(b, c)
'''
        fs = run({"dcgan_tpu/utils/checkpoint.py": src}, checks=["DCG006"])
        assert len(fs) == 2 and fs[0].fingerprint() == fs[1].fingerprint()
        entry = fs[0].baseline_entry(why="reviewed once")
        new, old = core.split_baselined(fs, [entry])
        assert len(old) == 1 and len(new) == 1

    def test_unknown_check_id_rejected(self):
        with pytest.raises(ValueError, match="DCG999"):
            run({"dcgan_tpu/x.py": "x = 1\n"}, checks=["DCG999"])


# -- the full-package pin ----------------------------------------------------

class TestPackageClean:
    def test_package_run_is_clean_under_committed_baseline(self):
        root = core.default_root()
        sources = core.collect_sources([root + "/dcgan_tpu"], root)
        findings = core.run_checks(sources, core.Config())
        baseline = core.load_baseline(core.default_baseline_path())
        new, _ = core.split_baselined(findings, baseline)
        assert new == [], "\n".join(
            f"{f.path}:{f.line}: {f.check} {f.message}" for f in new)

    def test_cli_exit_codes(self, tmp_path, capsys):
        from dcgan_tpu.analysis.__main__ import main

        assert main([]) == 0
        capsys.readouterr()
        # with the baseline ignored, the committed exemption resurfaces
        assert main(["--baseline", ""]) == 1
        out = capsys.readouterr().out
        assert "DCG006" in out and "MetricWriter._emit" in out


# -- runtime tripwire --------------------------------------------------------

class TestTripwire:
    def test_offthread_collective_trips_and_dispatch_thread_passes(
            self, monkeypatch):
        monkeypatch.setenv(tripwire.ENV_VAR, "1")
        assert tripwire.maybe_install()
        from dcgan_tpu.train import coordination

        with tripwire.dispatch_scope():
            # dispatch thread: the wrapped entry point passes through
            table = coordination.fleet_health_gather(
                np.zeros(len(coordination.HEALTH_FIELDS), np.float32))
            assert table.shape[0] == 1
            # any other thread: trips
            err = []

            def offthread():
                try:
                    coordination.fleet_health_gather(
                        np.zeros(len(coordination.HEALTH_FIELDS),
                                 np.float32))
                except tripwire.ThreadDisciplineError as e:
                    err.append(e)

            t = threading.Thread(target=offthread)
            t.start()
            t.join()
            assert len(err) == 1
            assert "dispatch thread" in str(err[0])

    def test_silent_outside_dispatch_scope(self):
        """Tools/tests that own their single thread are never tripped:
        without an active scope the wrappers are pass-through from any
        thread."""
        from dcgan_tpu.train import coordination

        results = []

        def offthread():
            results.append(coordination.fleet_health_gather(
                np.zeros(len(coordination.HEALTH_FIELDS), np.float32)))

        t = threading.Thread(target=offthread)
        t.start()
        t.join()
        assert len(results) == 1

    def test_scope_restores_previous_owner(self):
        me = threading.current_thread()
        with tripwire.dispatch_scope():
            assert me in tripwire.dispatch_owners()
            with tripwire.dispatch_scope():
                # re-entrant: still exactly one membership for this thread
                assert me in tripwire.dispatch_owners()
            # the inner exit must not evict the outer scope's ownership
            assert me in tripwire.dispatch_owners()
        # conftest installs but no scope is active between tests
        assert me not in tripwire.dispatch_owners()

    def test_concurrent_replica_scopes_are_independent_owners(self):
        """The serving-fleet shape (ISSUE 19): N dispatch threads each
        inside their own dispatch_scope must all pass the check
        concurrently — one replica entering its scope must never evict
        another's ownership — while an unscoped bystander thread still
        trips."""
        from dcgan_tpu.train import coordination

        n = 3
        entered = threading.Barrier(n + 1)
        release = threading.Event()
        errs, oks = [], []

        def replica(i):
            with tripwire.dispatch_scope():
                entered.wait(timeout=10)
                release.wait(timeout=10)
                try:
                    coordination.fleet_health_gather(
                        np.zeros(len(coordination.HEALTH_FIELDS),
                                 np.float32))
                    oks.append(i)
                except tripwire.ThreadDisciplineError as e:
                    errs.append(e)

        threads = [threading.Thread(target=replica, args=(i,),
                                    name=f"replica-{i}")
                   for i in range(n)]
        for t in threads:
            t.start()
        entered.wait(timeout=10)   # all three scopes active at once
        assert len(tripwire.dispatch_owners()) >= n

        def bystander():
            try:
                coordination.fleet_health_gather(
                    np.zeros(len(coordination.HEALTH_FIELDS), np.float32))
                oks.append("bystander")
            except tripwire.ThreadDisciplineError as e:
                errs.append(e)

        b = threading.Thread(target=bystander, name="bystander")
        b.start()
        b.join(timeout=10)
        release.set()
        for t in threads:
            t.join(timeout=10)
        assert sorted(i for i in oks if i != "bystander") == list(range(n))
        assert "bystander" not in oks
        assert len(errs) == 1 and "dispatch thread" in str(errs[0])
        assert not tripwire.dispatch_owners()

    def test_wrapped_programs_keep_lower(self, monkeypatch):
        """The AOT warmup contract: wrapping pt.* must not hide .lower()."""
        monkeypatch.setenv(tripwire.ENV_VAR, "1")
        tripwire.maybe_install()
        import jax

        from dcgan_tpu.analysis.tripwire import _GuardedFn

        fn = _GuardedFn(jax.jit(lambda x: x + 1), "pt.test")
        assert fn(1) == 2
        lowered = fn.lower(jax.ShapeDtypeStruct((), "int32"))
        assert lowered is not None

    def test_trainer_smoke_zero_trips(self, tmp_path, monkeypatch):
        """A tiny in-process train() under the armed tripwire: the
        default dispatch path records zero trips (the tier-1-wide claim,
        in miniature and in-process)."""
        monkeypatch.setenv(tripwire.ENV_VAR, "1")
        from dcgan_tpu.config import ModelConfig, TrainConfig
        from dcgan_tpu.train.trainer import train

        cfg = TrainConfig(
            model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                              compute_dtype="float32"),
            batch_size=8, tensorboard=False, sample_every_steps=0,
            save_summaries_secs=0.0, log_every_steps=0,
            save_model_secs=1e9,
            checkpoint_dir=str(tmp_path / "ck"),
            sample_dir=str(tmp_path / "sm"))
        state = train(cfg, synthetic_data=True, max_steps=2)
        assert int(np.asarray(state["step"])) == 2


# -- semantic tier (ISSUE 11) ------------------------------------------------
# Fixtures are synthetic jitted programs audited through
# semantic.audit_callable — the spec for what the lowered-program checkers
# resolve, each with a clean twin. The real enumeration runs as the
# tier-1 subprocess pin (tests/test_tools.py), not in-process.

import dataclasses as _dc
import os as _os

import jax as _jax
import jax.numpy as _jnp

from dcgan_tpu.analysis import manifest as mlib
from dcgan_tpu.analysis import semantic


def _audit(fn, args, name="fx::prog", expect_donation=False):
    return semantic.audit_callable(name, fn, args, path="dcgan_tpu/fx.py",
                                   expect_donation=expect_donation)


class TestDonationAliasing:
    """DCG007: donation realized as aliasing, both directions."""

    def test_donated_but_unaliased_flagged(self):
        # the donated dict arg is USED (so it is a live executable input)
        # but no output matches its shape — XLA cannot alias it and every
        # dispatch silently copies
        fn = _jax.jit(lambda s, x: s["a"].sum() + x,
                      donate_argnums=(0,))
        a = _audit(fn, ({"a": _jnp.zeros((4,))}, _jnp.zeros(())),
                   expect_donation=True)
        assert a.donation is not None
        assert a.donation["donated"] == 1 and a.donation["aliased"] == 0
        fs = semantic.check_donation([a])
        assert [f.check for f in fs] == ["DCG007"]
        assert fs[0].key.startswith("unaliased:fx::prog:")
        assert "'a'" in fs[0].key
        assert "input_output_aliases" in fs[0].message

    def test_realized_donation_clean(self):
        fn = _jax.jit(lambda s, x: ({"a": s["a"] + x}, x.sum()),
                      donate_argnums=(0,))
        a = _audit(fn, ({"a": _jnp.zeros((4,))}, _jnp.ones((4,))),
                   expect_donation=True)
        assert a.donation == {"donated": 1, "aliased": 1, "pruned": 0,
                              "unaliased": []}
        assert semantic.check_donation([a]) == []

    def test_pruned_donation_is_not_a_copy_hazard(self):
        # an UNUSED donated arg is pruned from the executable entirely —
        # no input buffer, no copy; classified, not flagged
        fn = _jax.jit(lambda s, x: x * 2.0, donate_argnums=(0,))
        a = _audit(fn, ({"a": _jnp.zeros((4,))}, _jnp.ones((4,))),
                   expect_donation=True)
        assert a.donation["pruned"] == 1 and a.donation["unaliased"] == []
        assert semantic.check_donation([a]) == []

    def test_declared_donor_that_stopped_donating_flagged(self):
        fn = _jax.jit(lambda s: {"a": s["a"] * 2})
        a = _audit(fn, ({"a": _jnp.zeros((4,))},), expect_donation=True)
        assert a.donation is None
        fs = semantic.check_donation([a])
        assert [f.key for f in fs] == ["undonated:fx::prog"]

    def test_undeclared_donor_flagged(self):
        fn = _jax.jit(lambda s: {"a": s["a"] * 2}, donate_argnums=(0,))
        a = _audit(fn, ({"a": _jnp.zeros((4,))},), expect_donation=False)
        fs = semantic.check_donation([a])
        assert [f.key for f in fs] == ["undeclared-donor:fx::prog"]

    def test_non_donor_clean(self):
        a = _audit(_jax.jit(lambda x: x + 1), (_jnp.ones((2,)),))
        assert a.donation is None
        assert semantic.check_donation([a]) == []


class TestProgramManifest:
    """DCG008: manifest round-trip, deliberate-drift detection, the
    transport registry, and the generated DESIGN §6c.1 table."""

    REC = mlib.ProgramRecord(
        name="fx::prog", kind="program", path="dcgan_tpu/fx.py",
        args=("f32[2]",), fingerprint="abcd1234abcd1234",
        collectives={"psum": 2}, donation={"donated": 1, "aliased": 1,
                                           "pruned": 0, "unaliased": []},
        cadence="every step")

    def test_write_read_round_trip(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with open(path, "w") as f:
            f.write(mlib.dumps([self.REC]))
        assert mlib.load_path(path) == [self.REC]
        # serialization is deterministic: a second dump is byte-identical
        assert mlib.dumps([self.REC]) == mlib.dumps([self.REC])

    def test_census_drift_detected(self):
        committed = [_dc.replace(self.REC, collectives={"psum": 3})]
        fs = mlib.diff([self.REC], committed)
        assert [f.check for f in fs] == ["DCG008"]
        assert fs[0].key == "census:fx::prog"
        assert "psum ×2" in fs[0].message and "psum ×3" in fs[0].message

    def test_fingerprint_and_donation_drift_detected(self):
        committed = [_dc.replace(
            self.REC, fingerprint="ffff0000ffff0000",
            donation={"donated": 1, "aliased": 0, "pruned": 0,
                      "unaliased": ["[0]"]})]
        keys = {f.key for f in mlib.diff([self.REC], committed)}
        assert keys == {"fingerprint:fx::prog", "donation:fx::prog"}

    def test_vanished_and_uncommitted_programs_detected(self):
        other = _dc.replace(self.REC, name="fx::other")
        assert {f.key for f in mlib.diff([self.REC], [other])} == \
            {"missing:fx::other", "uncommitted:fx::prog"}

    def test_identical_records_clean(self):
        assert mlib.diff([self.REC], [_dc.replace(self.REC)]) == []

    def test_missing_manifest_is_a_finding(self, tmp_path):
        fs = semantic.check_manifest([self.REC],
                                     str(tmp_path / "nope.jsonl"))
        assert [f.key for f in fs] == ["manifest-missing"]

    def test_transport_registry_live_and_wrapped(self, monkeypatch):
        assert semantic.check_transports() == []
        from dcgan_tpu.train import coordination

        monkeypatch.setattr(
            coordination, "TRANSPORT_CENSUS",
            {"ghost": ("_allgather_i64", {"all_gather": 1}, "never")})
        keys = {f.key for f in semantic.check_transports()}
        assert keys == {"transport:ghost", "transport-unwrapped:ghost"}

    def test_committed_manifest_carries_the_consensus_transports(self):
        recs = mlib.load_path(mlib.default_manifest_path())
        transports = {r.name for r in recs if r.kind == "transport"}
        # the two PR 4 consensus allgathers, by name — the §6c.1 stream
        assert {"coordination::stop_consensus",
                "coordination::anomaly_consensus"} <= transports
        # and the dispatch surface itself: both backends + serve rungs
        names = {r.name for r in recs}
        assert "gspmd::train_step" in names
        assert "shard_map::train_step" in names
        assert any(n.startswith("serve::sampler@b") for n in names)

    def test_design_stream_table_matches_committed_manifest(self):
        """The §6c.1 dispatch-stream table is GENERATED — the doc block
        between the markers must equal the render from the committed
        manifest, so the doc cannot drift from the programs."""
        recs = mlib.load_path(mlib.default_manifest_path())
        design_path = _os.path.join(core.default_root(), "docs",
                                    "DESIGN.md")
        with open(design_path, encoding="utf-8") as f:
            design = f.read()
        i = design.find(mlib.STREAM_TABLE_BEGIN)
        j = design.find(mlib.STREAM_TABLE_END)
        assert 0 <= i < j, "stream-table markers missing from DESIGN §6c.1"
        block = design[i + len(mlib.STREAM_TABLE_BEGIN):j].strip()
        assert block == mlib.render_stream_table(recs), (
            "DESIGN §6c.1 stream table drifted from the committed "
            "manifest — regenerate with `python -m dcgan_tpu.analysis "
            "--semantic --stream-table` and paste between the markers")


def test_fingerprint_ignores_frozenset_print_order():
    """A shard_map equation prints `manual_axes=frozenset({...})` in the
    order the process's string-hash seed gives it; the committed
    fingerprints must not depend on that."""
    a = "shard_map[manual_axes=frozenset({'data', 'model'}) x=0x7f00] a"
    b = "shard_map[manual_axes=frozenset({'model', 'data'}) x=0x5a1c] a"
    assert semantic._sanitized(a) == semantic._sanitized(b)
    assert "frozenset({'data', 'model'})" in semantic._sanitized(b)


class TestRetraceHazards:
    """DCG009: baked-in consts, weak-typed leaks, warmup coverage."""

    def test_closure_captured_array_flagged(self):
        big = _jnp.arange(100.0)
        a = _audit(_jax.jit(lambda x: x + big.sum()), (_jnp.zeros(()),))
        fs = semantic.check_retrace([a])
        assert len(fs) == 1 and fs[0].check == "DCG009"
        assert fs[0].key.startswith("const:fx::prog:")
        assert "100 elements" in fs[0].message

    def test_argument_passed_array_clean(self):
        a = _audit(_jax.jit(lambda x, big: x + big.sum()),
                   (_jnp.zeros(()), _jnp.arange(100.0)))
        assert semantic.check_retrace([a]) == []

    def test_weak_typed_const_flagged(self):
        w = _jnp.asarray(3.0)  # python float -> weak-typed scalar
        assert w.aval.weak_type
        a = _audit(_jax.jit(lambda x: x * w), (_jnp.ones((2,)),))
        fs = semantic.check_retrace([a])
        assert [f.check for f in fs] == ["DCG009"]
        assert fs[0].key.startswith("weak-const:")

    def test_strong_typed_const_clean(self):
        w = _jnp.float32(3.0)
        a = _audit(_jax.jit(lambda x: x * w), (_jnp.ones((2,)),))
        assert semantic.check_retrace([a]) == []

    def test_warmup_coverage_gap_flagged(self):
        row = semantic.CoverageRow(
            variant="fx", path="dcgan_tpu/fx.py",
            programs=frozenset({"train_step", "sampler"}),
            plan=("train_step",),
            must_cover=frozenset({"train_step", "sampler"}))
        keys = {f.key for f in semantic.check_warmup_coverage([row])}
        assert keys == {"warmup-gap:fx:sampler",
                        "warmup-unplanned:fx:sampler"}

    def test_warmup_full_coverage_clean(self):
        row = semantic.CoverageRow(
            variant="fx", path="dcgan_tpu/fx.py",
            programs=frozenset({"train_step", "sampler", "init"}),
            plan=("train_step", "sampler"),
            must_cover=frozenset({"train_step", "sampler"}))
        assert semantic.check_warmup_coverage([row]) == []

    def test_shape_variant_covers_base_program(self):
        # multi_step planned as "multi_step@k2" still covers the
        # programs-dict entry "multi_step" (base-name match)
        row = semantic.CoverageRow(
            variant="fx", path="dcgan_tpu/fx.py",
            programs=frozenset({"multi_step"}),
            plan=("multi_step@k2",),
            must_cover=frozenset({"multi_step@k2"}))
        assert semantic.check_warmup_coverage([row]) == []


class TestTracedBodySemanticHygiene:
    """DCG010: callbacks, f64 promotion, embedded transfers."""

    def test_host_callback_flagged(self):
        def body(x):
            _jax.debug.print("x = {}", x)
            return x + 1

        a = _audit(_jax.jit(body), (_jnp.ones((2,)),))
        fs = semantic.check_hygiene([a])
        assert len(fs) == 1 and fs[0].check == "DCG010"
        assert fs[0].key.startswith("callback:")

    def test_embedded_device_put_flagged(self):
        a = _audit(_jax.jit(lambda x: _jax.device_put(x) * 2),
                   (_jnp.ones((2,)),))
        fs = semantic.check_hygiene([a])
        assert [f.key for f in fs] == \
            ["transfer:fx::prog:device_put"]

    def test_f64_promotion_flagged(self):
        with _jax.enable_x64(True):
            a = _audit(_jax.jit(lambda x: x.astype(_jnp.float64) * 2),
                       (_jnp.ones((2,), _jnp.float32),))
        fs = semantic.check_hygiene([a])
        assert fs and all(f.key.startswith("f64:") for f in fs)

    def test_plain_program_clean(self):
        a = _audit(_jax.jit(lambda x: x * 2 + 1), (_jnp.ones((2,)),))
        assert semantic.check_hygiene([a]) == []


class TestSemanticBaselineAndChecks:
    """The shared suppression machinery extended to DCG007-010."""

    def test_semantic_finding_round_trips_through_baseline(self):
        fn = _jax.jit(lambda s, x: s["a"].sum() + x, donate_argnums=(0,))
        a = _audit(fn, ({"a": _jnp.zeros((4,))}, _jnp.zeros(())),
                   expect_donation=True)
        fs = semantic.check_donation([a])
        assert len(fs) == 1
        entry = fs[0].baseline_entry(why="fixture: reviewed copy is fine")
        new, old = core.split_baselined(fs, [entry])
        assert new == [] and len(old) == 1
        # multiset semantics: a SECOND identical finding still fails
        new2, old2 = core.split_baselined(fs + fs, [entry])
        assert len(new2) == 1 and len(old2) == 1

    def test_semantic_ids_rejected_by_ast_driver_with_redirect(self):
        with pytest.raises(ValueError, match="--semantic"):
            run({"dcgan_tpu/x.py": "x = 1\n"}, checks=["DCG007"])

    def test_unknown_semantic_id_rejected(self):
        with pytest.raises(ValueError, match="DCG999"):
            semantic.run_semantic(checks=["DCG999"])

    def test_records_from_audits_match_manifest_shape(self):
        a = _audit(_jax.jit(lambda x: x + 1), (_jnp.ones((2,)),))
        recs = semantic.records_from([a])
        by_name = {r.name: r for r in recs}
        assert by_name["fx::prog"].kind == "program"
        assert by_name["fx::prog"].fingerprint == a.fingerprint
        # the declared transports always join the record set
        assert "coordination::stop_consensus" in by_name
        text = mlib.dumps(recs)
        assert mlib.loads(text) == sorted(recs, key=lambda r: r.name)


class TestSpecCoverage:
    """DCG011 (ISSUE 12): every model family's full train state must
    match exactly one sharding-rule row — unmatched and multiply-matched
    paths are findings. The clean case doubles as the committed table's
    coverage proof (tests/test_elastic.py pins the engine semantics)."""

    def test_committed_table_is_clean(self):
        assert semantic.check_spec_coverage() == []

    def test_removed_rule_reports_unmatched(self, monkeypatch):
        from dcgan_tpu.elastic import rules as rmod

        pruned = tuple(r for r in rmod.PARTITION_RULES
                       if r[0] != r"(^|/)proj/w$")
        monkeypatch.setattr(rmod, "PARTITION_RULES", pruned)
        fs = semantic.check_spec_coverage()
        assert fs and all(f.check == "DCG011" for f in fs)
        assert any("spec-unmatched" in f.key and "proj/w" in f.key
                   for f in fs)
        # params, BOTH Adam moments, and the EMA mirror all lose coverage
        keys = "\n".join(f.key for f in fs)
        for stem in ("params/gen/proj/w", "opt/gen/1/0/mu/proj/w",
                     "opt/gen/1/0/nu/proj/w"):
            assert stem in keys

    def test_overlapping_rule_reports_ambiguous(self, monkeypatch):
        from dcgan_tpu.elastic import rules as rmod

        widened = rmod.PARTITION_RULES + (
            (r"(^|/)proj/w$", (None, None)),)
        monkeypatch.setattr(rmod, "PARTITION_RULES", widened)
        fs = semantic.check_spec_coverage()
        assert any(f.check == "DCG011" and "spec-ambiguous" in f.key
                   and "proj/w" in f.key for f in fs)

    def test_prefix_keyed_rule_reports_grad_spec_drift(self, monkeypatch):
        """ISSUE 13: a rule row that keys on the mu/ prefix makes the
        moment resolve differently from the bare-tail GRADIENT spec —
        the reduce-scattered gradient and the shard-local Adam state
        would disagree on layout under zero_stage >= 2, which the
        grad-spec derivation audit must surface."""
        from dcgan_tpu.elastic import rules as rmod

        keyed = ((r"(^|/)mu/proj/w$", rmod.REPLICATED),) \
            + rmod.PARTITION_RULES
        monkeypatch.setattr(rmod, "PARTITION_RULES", keyed)
        fs = semantic.check_spec_coverage()
        assert any(f.check == "DCG011" and "grad-spec-drift" in f.key
                   and "proj/w" in f.key for f in fs)

    def test_dcg011_redirected_from_ast_driver(self):
        with pytest.raises(ValueError, match="--semantic"):
            run({"dcgan_tpu/x.py": "x = 1\n"}, checks=["DCG011"])
