"""tools/chaos_drill.py's trainer scenarios as subprocess smokes (the
chaos-marker pattern of pytest.ini): each test drives real trainer
subprocesses through one slice of the drill's matrix and reads the JSON
rows it prints; all but the slow-marked observability trio run in
tier-1. The full matrix runs standalone:
`JAX_PLATFORMS=cpu python tools/chaos_drill.py`."""

import pytest

from tool_smoke import json_rows, run_tool


@pytest.mark.chaos
class TestChaosDrillSmoke:
    """tools/chaos_drill.py --smoke pinned into tier-1 (not slow, per the
    chaos-marker contract in pytest.ini): the cheap scenario subset —
    corrupt-record quarantine, transient-IO retry, services-crash
    surfacing — must keep passing end to end through real trainer
    subprocesses. The full 9-scenario matrix (rollback + checkpoint
    fallback + the observability trio included) runs standalone:
    `python tools/chaos_drill.py`."""

    def test_smoke_matrix_passes(self):
        # three trainer launches: 56.3-58.6 s alone, 69.7-103.5 s under six
        # workers
        res = run_tool(["tools/chaos_drill.py", "--smoke"], timeout=240)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["label"] == "chaos-drill"
        assert summary["scenarios"] == 3 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"corrupt-record", "io-error-once",
                                  "services-crash"}
        assert scenarios["corrupt-record"]["corrupt_records"] >= 1

    def test_multihost_smoke_passes_within_budget(self):
        """tools/chaos_drill.py --multihost --smoke pinned into tier-1:
        the cheapest coordinated-recovery scenario — SIGTERM on
        one host of a real 2-process localhost-gRPC job becomes a
        collective stop + bit-exact resume. The full 3-scenario matrix
        (coordinated rollback + watchdog trip included) runs standalone:
        `python tools/chaos_drill.py --multihost`."""
        # two 2-process launches: 42.8 s alone, 58.7-77.2 s under six workers
        res = run_tool(["tools/chaos_drill.py", "--multihost", "--smoke"],
                       timeout=180)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["label"] == "chaos-drill-multihost"
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"mh-sigterm-stop"}
        assert scenarios["mh-sigterm-stop"]["resumed"] is True


@pytest.mark.slow
@pytest.mark.chaos
class TestObservabilitySmoke:
    """The observability pin (chaos-marker pattern): the
    trigger-file capture -> in-process digest loop and the flight-recorder
    dump triggers must keep working end to end through real trainer
    subprocesses. Slow tier: tier-1 holds the parts in-process
    (tests/test_profiling.py::TestTraceCapture::
    test_trigger_file_starts_and_stops_capture, tests/test_flight_recorder.py
    ::TestRing::test_dump_roundtrip and ::TestWatchdogDumpHook)."""

    def test_trace_trigger_and_flight_recorder_within_budget(self):
        # three trainer launches (19, 23 and 31 s alone, start-up and AOT
        # warmup bound): 85.6 s alone, 91.2 s under six workers
        res = run_tool(["tools/chaos_drill.py", "--only", "flight-recorder",
                        "watchdog-dump", "trace-trigger"], timeout=240)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 3 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"flight-recorder", "watchdog-dump",
                                  "trace-trigger"}
        assert scenarios["flight-recorder"]["failing_step"] == 3
        assert scenarios["watchdog-dump"]["phase"] == "step-dispatch"
        assert scenarios["trace-trigger"]["device_compute_ms"] > 0


@pytest.mark.chaos
class TestPipelineRollbackSmoke:
    """The pipelined G/D step's tier-1 pin (chaos-marker pattern): a NaN
    fault under
    --pipeline_gd must drain the in-flight fake stack at the rollback,
    refill from the restored state, complete, and replay bit-exactly —
    through real trainer subprocesses."""

    def test_pipeline_rollback_within_budget(self):
        # two trainer launches (the replay pair): 46.0 s alone, 54.9-66.1 s
        # under six workers
        res = run_tool(["tools/chaos_drill.py", "--only",
                        "pipeline-rollback"], timeout=180)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"pipeline-rollback"}
        assert scenarios["pipeline-rollback"]["rollbacks"] >= 1
        assert scenarios["pipeline-rollback"]["replay_bit_exact"] is True
