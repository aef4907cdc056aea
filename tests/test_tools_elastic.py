"""tools/chaos_drill.py's state-movement scenarios as subprocess smokes
(the chaos-marker pattern of pytest.ini): progressive phase switches,
ZeRO-3 sharded rollback, the cross-process-census resume and the live
mesh shrink, each through real trainer subprocesses. The ZeRO rollback
and the live shrink run in tier-1, the other two in the slow tier. The
full matrix runs standalone:
`JAX_PLATFORMS=cpu python tools/chaos_drill.py`."""

import pytest

from tool_smoke import json_rows, run_tool


@pytest.mark.slow
@pytest.mark.chaos
class TestProgressiveSwitchSmoke:
    """The progressive schedule's pin (chaos-marker pattern): a NaN at the
    first
    step after a progressive phase switch must roll back to the
    POST-switch snapshot (the new phase's tree), complete, replay
    STATE_SUM bit-exactly, and keep the pre-switch phase's losses
    bit-exact against an unfaulted control — through real trainer
    subprocesses. Slow tier: tier-1 holds the switch in-process
    (tests/test_progressive.py::TestWarmup::
    test_headline_ladder_switches_with_zero_compile_requests,
    ::TestCarry::test_carried_leaves_bit_exact) and the snapshot
    restore (tests/test_chaos.py::TestRollbackManager)."""

    def test_progressive_switch_within_budget(self):
        # three trainer launches (faulted pair + control, each compiling
        # two phase surfaces): 111.1 s alone, 123.2 s under six workers
        res = run_tool(["tools/chaos_drill.py", "--only",
                        "progressive-switch"], timeout=300)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"progressive-switch"}
        row = scenarios["progressive-switch"]
        assert row["rollbacks"] >= 1
        assert row["replay_bit_exact"] is True
        assert row["preswitch_losses_bit_exact"] is True


@pytest.mark.chaos
class TestZeroRollbackSmoke:
    """The ZeRO stages' tier-1 pin (chaos-marker pattern): a NaN fault
    under
    --zero_stage 3 must restore the data-SHARDED state from the rollback
    snapshot, complete, and replay losses + STATE_SUM bit-exactly against
    a --zero_stage 1 control — through real trainer subprocesses."""

    def test_zero_rollback_within_budget(self):
        # two 2-device trainer launches: 40.6 s alone, 36.8-44.1 s under six
        # workers
        res = run_tool(["tools/chaos_drill.py", "--only", "zero-rollback"],
                       timeout=120)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"zero-rollback"}
        assert scenarios["zero-rollback"]["rollbacks"] >= 1
        # to 1e-5, not to the bit: see the scenario's docstring for what
        # the installed XLA:CPU does to the shard-local update
        assert scenarios["zero-rollback"]["replay_rtol"] == 1e-5


@pytest.mark.slow
@pytest.mark.chaos
class TestElasticShrinkSmoke:
    """The elastic resume's pin (chaos-marker pattern): a checkpoint saved
    by 2 processes must resume on 1 process (2 virtual devices — same
    2-way data mesh, different process census) through the sharding
    sidecar's host-staged reshard, with post-resume losses and final
    STATE_SUM replaying against a same-topology control resume to within
    ulp-scale reduction-order tolerances (the cross-process collective
    may sum partials in a different order than the intra-process one —
    the drill documents the bound; see chaos_drill._elastic_scenario) —
    through real trainer subprocesses. The grow direction (and the rest
    of the matrix) runs standalone:
    `JAX_PLATFORMS=cpu python tools/chaos_drill.py`. Slow tier: tier-1
    holds the reshard in-process (tests/test_elastic.py::
    TestCheckpointerReshard::test_host_path_reshard,
    ::TestSameTopologyParity)."""

    def test_elastic_shrink_within_budget(self):
        # five trainer launches (a 2-process save pair, a 1-process cross
        # resume, a 2-process control pair): 72.5 s alone, 87.0 s under
        # six workers
        res = run_tool(["tools/chaos_drill.py", "--only", "elastic-shrink"],
                       timeout=180)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"elastic-shrink"}
        row = scenarios["elastic-shrink"]
        assert row["direction"] == "2proc->1proc"
        assert row["replay_within_tolerance"] is True
        assert row["state_sum_rel"] <= 5e-4
        assert row["final_step"] == 6
        assert row["reshard_ms"] > 0


@pytest.mark.chaos
class TestLiveNoticeShrinkSmoke:
    """The live elasticity's tier-1 pin: a chaos preemption notice at step
    3 drives
    a LIVE t2x1 -> t1x1 mesh switch in one uninterrupted trainer process
    (no restart), the run completes to step 6, the switch line reports
    compile_requests_delta=0 (both topologies AOT-warmed+primed up
    front), pre-notice losses replay bit-exactly against an
    armed-but-unnotified control, and elastic/live_* event keys appear
    only in the notified run. The grow-back direction runs standalone:
    `JAX_PLATFORMS=cpu python tools/chaos_drill.py --only grow-back`."""

    def test_live_notice_shrink_within_budget(self):
        # two 2-device trainer launches (control + notified): 48.8 s
        # alone, 43.4-64.6 s under six workers
        res = run_tool(["tools/chaos_drill.py", "--only", "notice-shrink"],
                       timeout=180)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        row = scenarios["notice-shrink"]
        assert row["compile_requests_delta"] == 0
        assert row["final_step"] == 6
        assert row["switch_ms"] > 0
        assert row["state_sum_rel"] <= 5e-4
