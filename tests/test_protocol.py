"""Protocol tier units (ISSUE 14): the lockstep simulator engine, the
virtual-trainer scenarios, the committed protocol lock, the DCG013
divergence lint, and the DCG014/015 stale-exemption audits — all
in-process (the simulator needs no subprocesses by design). The live
2-process replay proof is tools/chaos_drill.py mh-sigterm-stop (pinned
via test_tools_chaos.py), which compares a real trainer's logged collective
sequence against the committed simulator schedule."""

import json
import os

import pytest

from dcgan_tpu.analysis import core, protocol, simulate

pytestmark = pytest.mark.chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMITTED_LOCK = os.path.join(REPO, "dcgan_tpu", "analysis",
                              "protocol.lock.jsonl")


@pytest.fixture(scope="module")
def lattice():
    """One shared exploration — deterministic by construction, so every
    test reads the same result set (~2 s once per module)."""
    return simulate.run_lattice()


@pytest.fixture(scope="module")
def lock_rows(lattice):
    return protocol.rows_from_results(lattice)


def _scenario(lattice, config, fault):
    for r in lattice:
        if r.knobs.name == config and r.fault.name == fault:
            return r
    raise AssertionError(f"lattice has no {config}/{fault} scenario")


# -- three-way transport registry ---------------------------------------------

class TestTransportRegistry:
    """A transport added to any one of {simulator shims, runtime
    tripwire, census declarations} must fail loudly in the other two."""

    def test_three_way_set_equality(self):
        from dcgan_tpu.analysis import tripwire
        from dcgan_tpu.train import coordination

        sim = set(simulate.SIM_TRANSPORTS)
        wrapped = set(tripwire.WRAPPED_TRANSPORTS)
        census = {row[0] for row in
                  coordination.TRANSPORT_CENSUS.values()}
        assert sim == wrapped
        assert census <= sim
        # every simulated transport is a real coordination callable
        for name in sim:
            assert callable(getattr(coordination, name))

    def test_verify_passes_on_the_real_registries(self):
        simulate.verify_transport_registry()

    def test_new_sim_transport_fails(self, monkeypatch):
        monkeypatch.setattr(simulate, "SIM_TRANSPORTS",
                            simulate.SIM_TRANSPORTS + ("_allgather_i64",))
        with pytest.raises(simulate.SimProtocolError, match="diverged"):
            simulate.verify_transport_registry()

    def test_new_wrapped_transport_fails(self, monkeypatch):
        from dcgan_tpu.analysis import tripwire

        monkeypatch.setattr(
            tripwire, "WRAPPED_TRANSPORTS",
            tripwire.WRAPPED_TRANSPORTS + ("_allgather_i64",))
        with pytest.raises(simulate.SimProtocolError, match="diverged"):
            simulate.verify_transport_registry()

    def test_new_census_transport_fails(self, monkeypatch):
        from dcgan_tpu.train import coordination

        census = dict(coordination.TRANSPORT_CENSUS)
        census["new_thing"] = ("_allgather_i64", {"all_gather": 1}, "x")
        monkeypatch.setattr(coordination, "TRANSPORT_CENSUS", census)
        with pytest.raises(simulate.SimProtocolError,
                           match="does not drive"):
            simulate.verify_transport_registry()

    def test_every_census_op_appears_in_the_lock(self, lock_rows):
        """Coverage, not just registration: the explored lattice must
        actually EXERCISE every declared logical transport (plus the
        warmup barrier) somewhere."""
        from dcgan_tpu.train import coordination

        entries = set()
        for row in lock_rows:
            if row["kind"] == "scenario":
                for e in row["schedule"]:
                    entries.add(e.split(":", 1)[-1].split("@")[0])
        for op in coordination.TRANSPORT_CENSUS:
            assert op in entries, f"lattice never exercises {op}"
        assert "warmup_barrier" in entries


# -- the rendezvous engine ----------------------------------------------------

def _knobs(**kw):
    kw.setdefault("name", "fixture")
    return simulate.Knobs(**kw)


class TestEngine:
    def test_consensus_values_cross_processes(self):
        """The real anomaly_consensus over the rendezvous transport: a
        verdict local to process 1 reaches process 0's branch."""
        def program(mesh, pid, knobs, plan):
            from dcgan_tpu.train import coordination

            with mesh.phase("anomaly_consensus@1"):
                bad, who = coordination.anomaly_consensus(pid == 1)
            return f"verdict:{bad}:{who}"

        r = simulate.run_scenario(_knobs(), simulate.Fault.make("clean"),
                                  program=program)
        assert r.statuses == ["done", "done"]
        assert r.outcomes == ["verdict:True:[1]"] * 2
        assert r.schedules[0] == r.schedules[1] \
            == ["ag:anomaly_consensus@1"]

    def test_asymmetric_branch_is_a_dcg012_deadlock(self):
        """The canonical single-branch asymmetry: one process runs an
        extra consensus its peer never enters — caught, attributed."""
        def program(mesh, pid, knobs, plan):
            from dcgan_tpu.train import coordination

            if pid == 0:
                with mesh.phase("anomaly_consensus@1"):
                    coordination.anomaly_consensus(False)
            mesh.collective("save", "final_save@1")
            return "completed@1"

        r = simulate.run_scenario(_knobs(), simulate.Fault.make("clean"),
                                  program=program)
        assert r.failure is not None
        assert not r.terminated
        findings = protocol.audit_results([r])
        assert [f.check for f in findings] == ["DCG012"]
        assert findings[0].key == "deadlock"
        assert "anomaly_consensus" in findings[0].message

    def test_early_exit_leaves_peer_blocked(self):
        """A process that returns while its peer enters a collective is
        the other deadlock shape (the PR 3-era one-host-save bug)."""
        def program(mesh, pid, knobs, plan):
            if pid == 1:
                return "completed@0"  # exits without the final save
            mesh.collective("save", "final_save@0")
            return "completed@0"

        r = simulate.run_scenario(_knobs(), simulate.Fault.make("clean"),
                                  program=program)
        assert r.failure is not None and r.failure["absent"] == [1]
        findings = protocol.audit_results([r])
        assert findings and findings[0].key == "deadlock"

    def test_hang_with_watchdog_resolves_as_trip(self):
        def program(mesh, pid, knobs, plan):
            mesh.collective("prog", "train_step@0")
            if pid == 1:
                mesh.hang("hang@1")
            mesh.collective("prog", "train_step@1")
            return "completed@2"

        r = simulate.run_scenario(_knobs(collective_timeout_secs=8.0),
                                  simulate.Fault.make("clean"),
                                  program=program)
        assert r.terminated
        assert r.statuses == ["trip", "hung"]
        assert r.outcomes[0] == "watchdog-trip:train_step@1"
        assert protocol.audit_results([r]) == []

    def test_hang_without_watchdog_is_a_finding(self):
        def program(mesh, pid, knobs, plan):
            if pid == 1:
                mesh.hang("hang@0")
            mesh.collective("prog", "train_step@0")
            return "completed@1"

        r = simulate.run_scenario(_knobs(), simulate.Fault.make("clean"),
                                  program=program)
        assert not r.terminated
        findings = protocol.audit_results([r])
        assert findings and findings[0].key == "deadlock"

    def test_single_process_collectives_complete_immediately(self):
        def program(mesh, pid, knobs, plan):
            from dcgan_tpu.train import coordination

            with mesh.phase("anomaly_consensus@1"):
                bad, _ = coordination.anomaly_consensus(False)
            mesh.collective("save", "final_save@1")
            return f"completed:{bad}"

        r = simulate.run_scenario(_knobs(n_proc=1),
                                  simulate.Fault.make("clean"),
                                  program=program)
        assert r.statuses == ["done"]
        # single-process consensus takes the local branch: no collective
        # entry for it, exactly the real transport's contract
        assert r.schedules[0] == ["save:final_save@1"]

    def test_repeated_tags_rendezvous_by_occurrence(self):
        """A replayed window re-enters the same (op, tag) — occurrence
        counting must pair the n-th entries, not wedge."""
        def program(mesh, pid, knobs, plan):
            for _ in range(2):
                mesh.collective("prog", "train_step@2")
            return "completed@2"

        r = simulate.run_scenario(_knobs(), simulate.Fault.make("clean"),
                                  program=program)
        assert r.statuses == ["done", "done"]
        assert r.schedules[0] == ["prog:train_step@2"] * 2


# -- virtual-trainer scenarios ------------------------------------------------

class TestVirtualTrainer:
    def test_drill_scenario_lockstep_stop(self, lattice):
        r = _scenario(lattice, *protocol.DRILL_REPLAY_SCENARIO)
        assert r.statuses == ["done", "done"]
        assert r.outcomes == ["stopped@3", "stopped@3"]
        assert r.schedules[0] == r.schedules[1]
        assert protocol.coord_ops(r.schedules[0]) == \
            ["stop_consensus"] * 4

    def test_nan_on_one_host_aborts_both(self, lattice):
        r = _scenario(lattice, "consensus-abort", "nan@p1@2")
        assert r.outcomes == ["aborted@2", "aborted@2"]
        assert "ag:anomaly_consensus@2" in r.schedules[0]
        # abort exits never reach the final collective save
        assert not any(e.startswith("save:") for e in r.schedules[0])

    def test_rollback_delete_protocol_in_schedule(self, lattice):
        r = _scenario(lattice, "rollback", "nan@p0@2")
        assert r.outcomes == ["completed@6", "completed@6"]
        sched = r.schedules[0]
        # the real delete_steps_after's verdict allgather, at the
        # consensus-agreed rollback point
        assert any(e.startswith("ag:rollback_delete@") for e in sched)

    def test_transient_io_fault_is_protocol_invisible(self, lattice):
        """retry_io absorbs the injected ckpt-delete OSError: the
        schedule must be IDENTICAL to the same fault without the IO
        error — transient host IO never perturbs the collective
        stream."""
        plain = _scenario(lattice, "rollback", "nan@p0@2")
        with_io = _scenario(lattice, "rollback",
                            "nan@p0@2+io-ckpt-delete")
        assert with_io.schedules == plain.schedules
        assert with_io.outcomes == plain.outcomes

    def test_pipeline_drain_precedes_rollback_delete(self, lattice):
        """ISSUE 7's ordering contract, audited: the pipelined-stack
        drain (parked on RollbackManager.on_restore) runs before the
        delete barrier."""
        r = _scenario(lattice, "pipelined-zero2", "nan@p0@2")
        sched = r.schedules[0]
        drain = sched.index("local:pipeline-drain:rollback")
        delete = next(i for i, e in enumerate(sched)
                      if e.startswith("ag:rollback_delete@"))
        assert drain < delete
        # pipelined dispatch refills after the drain: gen_fakes again
        assert sum(1 for e in sched
                   if e.startswith("prog:gen_fakes")) >= 2

    def test_zero_stage_names_the_program_stream(self, lattice):
        r = _scenario(lattice, "pipelined-zero2", "clean")
        assert any(e.startswith("prog:d_update@zero2@")
                   for e in r.schedules[0])
        r3 = _scenario(lattice, "zero3-fleet", "clean")
        assert any(e.startswith("prog:train_step@zero3@")
                   for e in r3.schedules[0])

    @pytest.mark.parametrize("config,decision", [
        ("rollback", "direct"), ("zero3-fleet", "device"),
        ("elastic-host-restore", "host")])
    def test_elastic_restore_decision_variants(self, lattice, config,
                                               decision):
        r = _scenario(lattice, config, "clean")
        assert r.schedules[0][0] == f"local:restore:{decision}"
        assert r.schedules[0] == r.schedules[-1]

    def test_warmup_barrier_in_armed_configs(self, lattice):
        r = _scenario(lattice, "rollback", "clean")
        assert "bar:warmup_barrier@start" in r.schedules[0]
        r2 = _scenario(lattice, "drill-defaults", "clean")
        assert not any(e.startswith("bar:") for e in r2.schedules[0])

    def test_fleet_health_cadence(self, lattice):
        r = _scenario(lattice, "zero3-fleet", "clean")
        health = [e for e in r.schedules[0]
                  if e.startswith("ag:fleet_health@")]
        assert health == [f"ag:fleet_health@{s}" for s in (2, 4, 6)]

    def test_local_stop_config_has_no_stop_consensus(self, lattice):
        r = _scenario(lattice, "local-stop", "clean")
        assert not any("stop_consensus" in e for e in r.schedules[0])

    def test_hang_fault_watchdog_prefix_rule(self, lattice):
        r = _scenario(lattice, "watchdog", "hang@p0@1")
        assert r.terminated
        assert r.statuses[0] == "hung" and r.statuses[1] == "trip"
        hung = r.schedules[0][:-1]  # strip the hang marker
        assert r.schedules[1][:len(hung)] == hung
        assert protocol.audit_results([r]) == []

    def test_rollback_budget_exhaustion_aborts_symmetrically(self):
        k = _knobs(name="exhaust", nan_policy="rollback",
                   nan_check_steps=1, max_rollbacks=1,
                   rollback_snapshot_steps=2, total_steps=6)
        f = simulate.Fault.make("nan-twice", {0: {"nan_at_step": 2},
                                              1: {"nan_at_step": 4}})
        r = simulate.run_scenario(k, f)
        assert r.statuses == ["done", "done"]
        assert r.outcomes[0] == r.outcomes[1]
        assert r.outcomes[0].startswith("aborted@")
        assert protocol.audit_results([r]) == []


# -- the lattice + lock -------------------------------------------------------

class TestLatticeAndLock:
    def test_acceptance_coverage(self, lattice):
        """ISSUE 14 acceptance: >= 4 knob configs x >= 6 fault
        interleavings each, every interleaving terminating, zero audit
        findings."""
        per = {}
        for r in lattice:
            per[r.knobs.name] = per.get(r.knobs.name, 0) + 1
            assert r.terminated, f"{r.knobs.name}/{r.fault.name}"
        assert len([c for c, n in per.items() if n >= 6]) >= 4
        assert protocol.audit_results(lattice) == []

    def test_committed_lock_matches_a_fresh_exploration(self, lock_rows):
        """Byte-reproducibility AND drift, at full strength: a fresh
        exploration serialized must equal the committed lock exactly."""
        with open(COMMITTED_LOCK, encoding="utf-8") as f:
            committed = f.read()
        assert protocol.dumps(lock_rows) == committed, (
            "protocol.lock.jsonl drifted — the coordination protocol's "
            "collective schedule moved; regenerate deliberately with "
            "`python -m dcgan_tpu.analysis --protocol --write-lock` and "
            "review the diff")

    def test_lock_round_trip(self, lock_rows):
        assert protocol.loads(protocol.dumps(lock_rows)) == \
            sorted(lock_rows, key=protocol._row_key)

    def test_deliberate_drift_is_a_named_finding(self, lock_rows):
        committed = protocol.load_path(COMMITTED_LOCK)
        live = [dict(r) for r in lock_rows]
        row = next(r for r in live if r["kind"] == "scenario")
        row["schedule"] = list(row["schedule"]) + ["ag:extra@9"]
        findings = protocol.lock_diff(live, committed)
        assert any(f.key == "schedule-drift" and "--write-lock"
                   in f.message for f in findings)

    def test_missing_and_uncommitted_rows(self, lock_rows):
        committed = protocol.load_path(COMMITTED_LOCK)
        live = [r for r in lock_rows
                if not (r["kind"] == "scenario"
                        and r["fault"] == "clean")]
        findings = protocol.lock_diff(live, committed)
        assert any(f.key == "missing-row" for f in findings)
        findings = protocol.lock_diff(
            committed + [{"kind": "scenario", "config": "x", "fault": "y",
                          "n_proc": 2, "status": "completed",
                          "outcomes": [], "schedule": []}], committed)
        assert any(f.key == "uncommitted-row" for f in findings)

    def test_missing_lock_file_is_a_finding(self, tmp_path):
        findings, _rows, _stats = protocol.run_protocol(
            lock_path=str(tmp_path / "nope.jsonl"))
        assert any(f.key == "missing-lock" for f in findings)

    def test_drill_replay_ops_from_committed_lock(self):
        assert protocol.drill_replay_ops() == ["stop_consensus"] * 4


# -- DCG013: static divergence lint -------------------------------------------

def _lint(src, path="dcgan_tpu/train/x.py", **cfg):
    sf = core.SourceFile.from_source(src, path)
    return core.run_checks([sf], core.Config(inventory={}, **cfg),
                           checks=["DCG013"])


class TestDivergenceLint:
    def test_wall_clock_branch_into_program_dispatch(self):
        src = ("import time\n"
               "def f(pt, state, z):\n"
               "    t0 = time.monotonic()\n"
               "    while True:\n"
               "        if time.monotonic() - t0 > 30.0:\n"
               "            pt.sample(state, z)\n")
        fs = _lint(src)
        assert [f.check for f in fs] == ["DCG013"]
        assert fs[0].key == "pt.sample"
        assert "host-local" in fs[0].message

    def test_tainted_name_chain(self):
        src = ("import time\n"
               "def f(ckpt, step, state):\n"
               "    t0 = time.time()\n"
               "    waited = t0 - step\n"
               "    if waited > 5:\n"
               "        ckpt.save(step, state)\n")
        fs = _lint(src)
        assert [f.key for f in fs] == ["ckpt.save"]

    def test_process_index_branch(self):
        src = ("import jax\n"
               "def f(ckpt, step, state):\n"
               "    chief = jax.process_index() == 0\n"
               "    if chief:\n"
               "        ckpt.save(step, state)\n")
        assert [f.key for f in _lint(src)] == ["ckpt.save"]

    def test_exception_handler_collective(self):
        src = ("from dcgan_tpu.train.coordination import "
               "anomaly_consensus\n"
               "def f():\n"
               "    try:\n"
               "        risky()\n"
               "    except OSError:\n"
               "        anomaly_consensus(True)\n")
        fs = _lint(src)
        assert [f.key for f in fs] == ["anomaly_consensus"]
        assert "exception handler" in fs[0].message

    def test_handler_counter_branch(self):
        src = ("def f(ckpt, step, state):\n"
               "    fails = 0\n"
               "    try:\n"
               "        risky()\n"
               "    except OSError:\n"
               "        fails += 1\n"
               "    if fails:\n"
               "        ckpt.save(step, state)\n")
        assert [f.key for f in _lint(src)] == ["ckpt.save"]

    def test_consensus_sanitizes_the_branch(self):
        """The blessed shape: gather first, branch on the mesh-uniform
        verdict — the exact structure of the trainer's gate."""
        src = ("from dcgan_tpu.train.coordination import "
               "anomaly_consensus\n"
               "def f(ckpt, step, local_bad):\n"
               "    bad, who = anomaly_consensus(local_bad)\n"
               "    if bad:\n"
               "        ckpt.delete_steps_after(step)\n")
        assert _lint(src) == []

    def test_stop_poll_sanitizes(self):
        src = ("def f(ckpt, step, state, stop):\n"
               "    sig, origins = stop.poll()\n"
               "    if sig is not None:\n"
               "        ckpt.save(step, state)\n")
        assert _lint(src) == []

    def test_argument_position_does_not_taint(self):
        """A function's RESULT is not host-local because an exception
        rode in as an argument (the trainer's rollback.restore(e))."""
        src = ("def f(pt, rollback, state, images, key):\n"
               "    try:\n"
               "        risky()\n"
               "    except FloatingPointError as e:\n"
               "        state, step = rollback.restore(e)\n"
               "    while step < 5:\n"
               "        state, m = pt.step(state, images, key)\n"
               "        step = step + 1\n")
        assert _lint(src) == []

    def test_nested_callback_definition_is_not_a_sink(self):
        """A callback merely DEFINED inside a tainted region runs
        elsewhere (the trainer parks drain lambdas on rollback hooks
        from handler context) — the whole nested def/lambda subtree is
        pruned, not just its root node."""
        src = ("from dcgan_tpu.train.coordination import "
               "warmup_barrier\n"
               "def f(rollback):\n"
               "    try:\n"
               "        risky()\n"
               "    except OSError:\n"
               "        rollback.on_restore = lambda: warmup_barrier()\n"
               "        def _later():\n"
               "            return warmup_barrier()\n"
               "        rollback.late = _later\n")
        assert _lint(src) == []

    def test_sanitizer_reassignment_kills_taint(self):
        """The blessed shape reusing the pre-gather NAME: assignment
        from a consensus call strong-updates the target back to
        mesh-uniform."""
        src = ("import time\n"
               "from dcgan_tpu.train.coordination import "
               "anomaly_consensus, warmup_barrier\n"
               "def f(deadline):\n"
               "    bad = time.monotonic() > deadline\n"
               "    bad, trippers = anomaly_consensus(bad)\n"
               "    if bad:\n"
               "        warmup_barrier()\n")
        assert _lint(src) == []

    def test_plain_reassignment_kills_taint(self):
        src = ("import time\n"
               "def f(pt, state, z):\n"
               "    t = time.monotonic()\n"
               "    t = 0.0\n"
               "    if t > 5:\n"
               "        pt.sample(state, z)\n")
        assert _lint(src) == []

    def test_out_of_scope_module_is_skipped(self):
        src = ("import time\n"
               "def f(pt, state, z):\n"
               "    if time.monotonic() > 5:\n"
               "        pt.sample(state, z)\n")
        assert _lint(src, path="dcgan_tpu/serve/x.py") == []

    def test_suppression_comment(self):
        src = ("import time\n"
               "def f(pt, state, z):\n"
               "    if time.monotonic() > 5:\n"
               "        pt.sample(state, z)  # dcg: disable=DCG013\n")
        assert _lint(src) == []

    def test_routing_error_names_the_protocol_driver(self):
        with pytest.raises(ValueError, match="--protocol"):
            core.run_checks([], core.Config(inventory={}),
                            checks=["DCG012"])


# -- DCG014/015: stale-exemption audits ---------------------------------------

class TestStaleAudits:
    def test_docstring_mention_is_not_a_suppression(self):
        """Suppressions come from real comment tokens only — prose like
        this line must neither suppress nor be audited:
        `# dcg: disable=DCG005` in a docstring is just text."""
        src = ('"""docs say `# dcg: disable=DCG005` here."""\n'
               "x = 1  # dcg: disable=DCG006\n")
        sf = core.SourceFile.from_source(src, "dcgan_tpu/x.py")
        assert list(sf.suppressed) == [2]
        assert sf.suppressed[2] == {"DCG006"}

    def test_dead_suppression_is_flagged(self):
        src = ("import time\n"
               "def f():\n"
               "    return 1  # dcg: disable=DCG005\n")
        sf = core.SourceFile.from_source(src, "dcgan_tpu/x.py")
        suppressed = []
        core.run_checks([sf], core.Config(inventory={}),
                        suppressed_out=suppressed)
        fs = core.audit_stale_suppressions([sf], suppressed)
        assert [(f.check, f.key, f.line) for f in fs] == \
            [("DCG014", "DCG005", 3)]

    def test_working_suppression_is_not_flagged(self):
        src = ("import jax, time\n"
               "def f(x):\n"
               "    k = jax.jit(lambda a: a + time.time())"
               "  # dcg: disable=DCG005\n"
               "    return k(x)\n")
        sf = core.SourceFile.from_source(src, "dcgan_tpu/x.py")
        suppressed = []
        findings = core.run_checks([sf], core.Config(inventory={}),
                                   suppressed_out=suppressed)
        assert not any(f.check == "DCG005" for f in findings)
        assert any(f.check == "DCG005" for f in suppressed)
        assert core.audit_stale_suppressions([sf], suppressed) == []

    def test_stale_baseline_row_scoped_to_ran_checks(self):
        entries = [
            {"check": "DCG006", "path": "p.py", "symbol": "f",
             "key": "open(w)", "why": "x", "_line": 4},
            {"check": "DCG007", "path": "q.py", "symbol": "g",
             "key": "donate", "why": "x", "_line": 5},
        ]
        fs, stale = core.audit_stale_baseline(
            entries, consumed=[], ran_checks=("DCG006",),
            baseline_rel_path="dcgan_tpu/analysis/baseline.jsonl")
        # the DCG007 row's tier did not run — it must NOT be called dead
        assert [f.check for f in fs] == ["DCG015"]
        assert [e["check"] for e in stale] == ["DCG006"]
        assert fs[0].line == 4

    def test_consumed_row_is_not_stale(self):
        f = core.Finding(check="DCG006", path="p.py", line=9, symbol="f",
                         key="open(w)", message="m")
        entries = [{"check": "DCG006", "path": "p.py", "symbol": "f",
                    "key": "open(w)", "why": "x", "_line": 4}]
        fs, stale = core.audit_stale_baseline(
            entries, consumed=[f], ran_checks=("DCG006",),
            baseline_rel_path="b.jsonl")
        assert fs == [] and stale == []

    def test_prune_rewrites_minus_dead_rows(self, tmp_path):
        path = tmp_path / "baseline.jsonl"
        rows = [
            {"check": "DCG006", "path": "p.py", "symbol": "f",
             "key": "a", "why": "keep"},
            {"check": "DCG006", "path": "p.py", "symbol": "g",
             "key": "b", "why": "dead"},
        ]
        path.write_text("# header comment\n"
                        + "\n".join(json.dumps(r) for r in rows) + "\n")
        entries = core.load_baseline(str(path))
        dropped = core.prune_baseline_file(str(path), [entries[1]])
        assert dropped == 1
        text = path.read_text()
        assert text.startswith("# header comment\n")
        assert "keep" in text and "dead" not in text

    def test_path_scoped_run_never_calls_unscanned_rows_dead(
            self, tmp_path):
        """A run over a path subset must neither flag nor prune baseline
        rows anchored on files outside the scan — the committed DCG006
        exemption lives in utils/metrics.py, which a train/-only scan
        never sees."""
        from dcgan_tpu.analysis.__main__ import main

        committed = os.path.join(REPO, "dcgan_tpu", "analysis",
                                 "baseline.jsonl")
        with open(committed, encoding="utf-8") as f:
            original = f.read()
        work = tmp_path / "baseline.jsonl"
        work.write_text(original)
        scoped = os.path.join(REPO, "dcgan_tpu", "train")
        assert main([scoped, "--baseline", str(work)]) == 0
        assert main([scoped, "--baseline", str(work),
                     "--prune-baseline"]) == 0
        assert work.read_text() == original

    def test_lowercase_checks_still_audit_stale_rows(self, tmp_path):
        """--checks IDs are case-normalized everywhere: a lowercase
        `--checks dcg006` must scope the DCG015 audit exactly like the
        uppercase form."""
        from dcgan_tpu.analysis.__main__ import main

        committed = os.path.join(REPO, "dcgan_tpu", "analysis",
                                 "baseline.jsonl")
        with open(committed, encoding="utf-8") as f:
            original = f.read()
        dead = {"check": "DCG006", "path": "dcgan_tpu/gone.py",
                "symbol": "f", "key": "open(w)", "why": "obsolete"}
        work = tmp_path / "baseline.jsonl"
        work.write_text(original + json.dumps(dead) + "\n")
        assert main(["--checks", "dcg006", "--baseline", str(work)]) == 1

    def test_cli_stale_row_fails_then_prunes(self, tmp_path):
        """End-to-end through the AST driver: a dead baseline row is a
        DCG015 exit-1; --prune-baseline resolves it by rewriting the
        file back to the committed content."""
        from dcgan_tpu.analysis.__main__ import main

        committed = os.path.join(REPO, "dcgan_tpu", "analysis",
                                 "baseline.jsonl")
        with open(committed, encoding="utf-8") as f:
            original = f.read()
        work = tmp_path / "baseline.jsonl"
        dead = {"check": "DCG001", "path": "dcgan_tpu/gone.py",
                "symbol": "f", "key": "x->psum", "why": "obsolete"}
        work.write_text(original + json.dumps(dead) + "\n")
        assert main(["--baseline", str(work)]) == 1
        assert main(["--baseline", str(work), "--prune-baseline"]) == 0
        assert work.read_text() == original
        assert main(["--baseline", str(work)]) == 0


# -- driver flag plumbing -----------------------------------------------------

class TestDriverFlags:
    def test_protocol_flags_require_protocol(self, capsys):
        from dcgan_tpu.analysis.__main__ import main

        with pytest.raises(SystemExit):
            main(["--write-lock"])
        assert "--protocol or --all" in capsys.readouterr().err

    def test_all_excludes_per_tier_modes(self, capsys):
        from dcgan_tpu.analysis.__main__ import main

        with pytest.raises(SystemExit):
            main(["--all", "--semantic"])
        assert "mutually exclusive" in capsys.readouterr().err

    def test_protocol_rejects_ast_check_ids(self):
        with pytest.raises(ValueError, match="AST-tier"):
            protocol.run_protocol(checks=["DCG013"])
