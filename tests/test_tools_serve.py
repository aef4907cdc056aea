"""The start-up and serving tools as subprocess smokes (the chaos-marker
pattern of pytest.ini): tools/bench_startup.py's and tools/bench_serve.py's
--smoke A/Bs and tools/chaos_drill.py's serve-drain scenario in tier-1,
its fleet-replica-kill scenario in the slow tier, each through real
trainer and serve subprocesses."""

import json

import pytest

from tool_smoke import json_rows, run_tool


@pytest.mark.chaos
class TestBenchStartupSmoke:
    """tools/bench_startup.py --smoke pinned into tier-1 (mirroring the
    chaos_drill pattern): the cold-vs-warm trainer A/B must
    keep proving the warm-start invariants end to end through real trainer
    subprocesses — warm compile strictly lower with a primed cache, zero
    warm cache misses, and the fused verified restore reading each
    manifest byte exactly once. The full-size run is standalone:
    `JAX_PLATFORMS=cpu python tools/bench_startup.py`."""

    def test_cold_warm_ab_passes_within_budget(self):
        # three trainer launches: 50.8 s alone, 51.9-83.4 s under six workers
        res = run_tool(["tools/bench_startup.py", "--smoke"], timeout=180)
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        row = json.loads(res.stdout.strip().splitlines()[-1])
        assert row["label"] == "bench-startup" and row["ok"] is True
        assert row["checks"]["warm_compile_strictly_lower"]
        assert row["checks"]["warm_zero_misses"]
        assert row["checks"]["restore_bytes_read_once"]
        assert row["warm"]["cache"]["hits"] > 0
        # the cross-topology arm: save@2-dev -> restore@1-dev
        # must take the sidecar reshard path, and the same-topology warm
        # arm must NOT
        assert row["checks"]["cross_resharded"]
        assert row["checks"]["warm_no_reshard"]
        assert row["cross"]["reshard_ms"] > 0


@pytest.mark.chaos
class TestBenchServeSmoke:
    """tools/bench_serve.py --smoke pinned into tier-1 (the chaos-marker
    pattern): the cold-vs-warm serving A/B over a bursty
    Poisson trace must keep proving the serving-plane invariants end to
    end through real subprocesses — zero sampler recompiles after the
    AOT bucket warmup on BOTH arms (every served batch hits a
    precompiled bucket), warm cache hits with zero misses, and the
    finite-trace drain losing nothing. The full-size run is standalone:
    `JAX_PLATFORMS=cpu python tools/bench_serve.py`."""

    def test_cold_warm_serve_ab_passes_within_budget(self):
        # one trainer + two serve launches: 44.7 s alone, 42.0-54.1 s under
        # six workers
        res = run_tool(["tools/bench_serve.py", "--smoke"], timeout=120)
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        row = json.loads(res.stdout.strip().splitlines()[-1])
        assert row["label"] == "bench-serve" and row["ok"] is True
        assert row["checks"]["cold_zero_recompiles_after_warmup"]
        assert row["checks"]["warm_zero_recompiles_after_warmup"]
        assert row["checks"]["warm_has_hits"]
        assert row["checks"]["warm_zero_misses"]
        assert row["cold"]["p99_ms"] >= row["cold"]["p50_ms"] > 0
        assert row["warm"]["completed"] == row["trace"]["requests"]

    def test_serve_drain_scenario_within_budget(self):
        """chaos_drill serve-drain pinned alongside: SIGTERM mid-load ->
        in-flight requests complete, queue drains, clean exit (the
        serving plane's first chaos consumer)."""
        # one trainer + one serve launch: 34.5 s alone, 36.8-46.8 s under
        # six workers
        res = run_tool(["tools/chaos_drill.py", "--only", "serve-drain"],
                       timeout=120)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenario = next(p for p in lines if p.get("scenario") == "serve-drain")
        assert scenario["clean_exit"] is True
        assert scenario["completed"] == scenario["submitted"] > 0


@pytest.mark.slow
@pytest.mark.chaos
class TestFleetReplicaKillSmoke:
    """The serving fleet's pin (chaos-marker pattern): the serving fleet
    under live fire through a real `python -m dcgan_tpu.serve --fleet 3`
    subprocess — a chaos kill of replica 1 mid-trace must become a
    failover (ZERO failed client requests, completed == submitted), the
    dead replica must be drained from rotation and logged, and the
    mid-trace checkpoint injection must be hot-swapped onto EXACTLY the
    survivors with compile_requests_delta == 0 per replica (the
    zero-recompile promotion literal, proven by the compile-cache
    monitor, not assumed). Slow tier: tier-1 holds the failover and the
    promotion in-process (tests/test_fleet.py::TestFleetOverFakeSources::
    test_replica_death_fails_over_zero_failed_requests,
    ::test_promote_targets_only_healthy_replicas, ::TestPromotionEndToEnd::
    test_zero_recompile_promotion_serves_new_weights)."""

    def test_fleet_replica_kill_within_budget(self):
        # two trainer launches + one 3-replica serve: 70.5 s alone, 88.5 s
        # under six workers
        res = run_tool(["tools/chaos_drill.py", "--only",
                        "fleet-replica-kill"], timeout=180)
        lines = json_rows(res.stdout)
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        row = next(p for p in lines
                   if p.get("scenario") == "fleet-replica-kill")
        assert row["failed"] == 0
        assert row["completed"] == row["submitted"] > 0
        assert row["unhealthy"] == [1]
        assert row["promoted_replicas"] == [0, 2]
        assert row["promoted_step"] == 2
        assert row["compile_requests_delta"] == 0
