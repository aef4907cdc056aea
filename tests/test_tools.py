"""The measurement tools behind DESIGN.md §1b and the captures table.

What must hold: the spread/aggregation math the docs tables are rendered
from (tools/capture_all.py), the trainer-log parsing bench_trainer_loop's
throughput derivation rests on, and a CPU execution of the matmul-rate
tool end to end (tiny shapes — the contract is "runs and
prints well-formed JSON", the numbers only mean anything on a chip).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.capture_all import (  # noqa: E402
    _best_bench_rows,
    _label_output_size,
    _mpx_cell,
    _render_roofline,
    _spread,
)


class TestSpread:
    def test_odd_even_and_single(self):
        assert _spread([3.0]) == {"n": 1, "median": 3.0, "min": 3.0,
                                  "max": 3.0}
        assert _spread([1.0, 9.0]) == {"n": 2, "median": 5.0, "min": 1.0,
                                       "max": 9.0}
        s = _spread([5.0, 1.0, 3.0])
        assert s["median"] == 3.0 and s["min"] == 1.0 and s["max"] == 5.0

    def test_best_rows_carry_spread(self):
        rows = [
            {"section": "matrix", "label": "a", "rc": 0, "date": "d1",
             "ms_per_step": 3.0,
             "parsed": [{"value": 10.0, "unit": "u", "vs_baseline": 5.0,
                         "metric": "m"}]},
            {"section": "matrix", "label": "a", "rc": 0, "date": "d2",
             "ms_per_step": 2.0,
             "parsed": [{"value": 20.0, "unit": "u", "vs_baseline": 10.0,
                         "metric": "m"}]},
            # failures and other sections must not count
            {"section": "matrix", "label": "a", "rc": 1, "date": "d3",
             "parsed": [{"value": 99.0}]},
            {"section": "fid", "label": "a", "rc": 0, "date": "d4",
             "parsed": [{"value": 77.0}]},
        ]
        best = _best_bench_rows(rows)
        a = best["a"]
        # best row's metadata comes from the winning capture
        assert a["value"] == 20.0 and a["ms"] == 2.0 and a["date"] == "d2"
        assert a["n"] == 2 and a["min"] == 10.0 and a["max"] == 20.0
        assert a["median"] == 15.0

    def test_best_rows_publish_highest_generation_only(self):
        """VERDICT r4 #1's contract: an attention label's best AND spread
        come from the highest kernel generation on record — a median over
        mixed generations describes no code that exists. Unstamped history
        is gen 0 (superseded once stamps appear)."""
        rows = [
            {"section": "matrix", "label": "attn", "rc": 0, "date": "d1",
             "parsed": [{"value": 3250.0}]},                  # pre-stamp
            {"section": "matrix", "label": "attn", "rc": 0, "date": "d2",
             "parsed": [{"value": 3260.0, "gen": 1}]},        # superseded
            {"section": "matrix", "label": "attn", "rc": 0, "date": "d3",
             "parsed": [{"value": 4050.0, "gen": 2}]},
            {"section": "matrix", "label": "attn", "rc": 0, "date": "d4",
             "parsed": [{"value": 4080.0, "gen": 2}]},
        ]
        a = _best_bench_rows(rows)["attn"]
        assert a["value"] == 4080.0 and a["gen"] == 2
        assert a["n"] == 2 and a["min"] == 4050.0  # gen<2 rows excluded

    def test_best_rows_preset_revision_default_is_one(self):
        """Unlisted presets ARE revision 1, so pre-stamp history of
        UNCHANGED presets must stay in the spread when a stamped rev-1
        capture arrives — only history behind an explicit bump retires
        (advisor r5 fix: a default of 0 silently discarded every unchanged
        preset's history on the first stamped harvest)."""
        rows = [
            {"section": "matrix", "label": "p", "rc": 0, "date": "d1",
             "parsed": [{"value": 100.0}]},                   # pre-stamp
            {"section": "matrix", "label": "p", "rc": 0, "date": "d2",
             "parsed": [{"value": 110.0, "rev": 1}]},         # same config
        ]
        p = _best_bench_rows(rows)["p"]
        assert p["n"] == 2 and p["min"] == 100.0 and p["value"] == 110.0
        # an explicit bump DOES retire older rows
        rows.append({"section": "matrix", "label": "p", "rc": 0,
                     "date": "d3", "parsed": [{"value": 90.0, "rev": 2}]})
        p = _best_bench_rows(rows)["p"]
        assert p["n"] == 1 and p["value"] == 90.0 and p["rev"] == 2

    def test_roofline_render(self):
        rows = [
            {"section": "roofline", "label": "matmul-rate", "rc": 0,
             "date": "d1", "parsed": [
                 # pre-K capture (square chain): treated as K = N
                 {"form": "matmul", "m": 8, "n": 8, "tflops": 1.0,
                  "ms_per_matmul": 0.5},
                 {"form": "matmul", "m": 8, "k": 8, "n": 8, "tflops": 2.0,
                  "ms_per_matmul": 0.25}]},  # best per shape wins
            {"section": "roofline", "label": "trainer-loop", "rc": 0,
             "date": "d1", "parsed": [
                 {"label": "trainer-loop", "images_per_sec_chip": 19000.0,
                  "ms_per_step": 3.3, "steps_per_call": 50}]},
            # a failed roofline row contributes nothing
            {"section": "roofline", "label": "trainer-loop", "rc": 1,
             "date": "d2", "parsed": [
                 {"label": "trainer-loop", "images_per_sec_chip": 9e9}]},
        ]
        text = "\n".join(_render_roofline(rows))
        assert "| 8×8×8 | 2.0 | 0.25 |" in text   # best-per-shape
        assert "19000 img/s/chip" in text
        assert "9000000000" not in text

    def test_roofline_render_empty(self):
        assert _render_roofline([]) == []

    def test_label_output_size_and_mpx(self):
        """The Mpx/s column's resolution join (VERDICT Weak #2): presets
        resolve through the registry, family tokens by their trailing
        digits, and the b<batch>/attn<res> knob tokens must NEVER be read
        as resolutions."""
        assert _label_output_size("wgan-gp") == 64          # preset lookup
        assert _label_output_size("dcgan64-b256") == 64     # b256 is batch
        assert _label_output_size("dcgan256-attn128-flash") == 256
        assert _label_output_size("sngan-cifar10") == 32
        assert _label_output_size("unknowable") is None
        assert _mpx_cell("dcgan256-attn128-flash", 48.9) == "3.2"
        assert _mpx_cell("dcgan64-headline", 20000.0) == "81.9"
        assert _mpx_cell("unknowable", 100.0) == "—"

    def test_render_docs_end_to_end(self, tmp_path, monkeypatch):
        """render_docs over a synthetic captures log into temp docs: every
        fid-trajectory label renders its own table (a latest-run-wins
        render would let one ladder evict the other), and loader spreads
        group per wire format (pooling float64 and uint8 into one min-max
        would fabricate a range no format has)."""
        import tools.capture_all as ca

        rows = [
            {"section": "fid", "label": "long", "rc": 0, "date": "d1",
             "cmd": "c1", "parsed": [{"step": 0, "fid": 0.5},
                                     {"monotonic": True,
                                      "spearman_steps_vs_fid": -1.0,
                                      "snapshots": 1}]},
            {"section": "fid", "label": "early", "rc": 0, "date": "d2",
             "cmd": "c2", "parsed": [{"step": 0, "fid": 0.4}]},
            {"section": "fid", "label": "long", "rc": 0, "date": "d3",
             "cmd": "c3", "parsed": [{"step": 0, "fid": 0.3}]},
            {"section": "loader", "label": "loader-ceiling", "rc": 0,
             "date": "d1", "cmd": "c", "parsed": [
                 {"images_per_sec": 15000.0, "record_dtype": "float64",
                  "threads": 16}]},
            {"section": "loader", "label": "loader-ceiling-uint8", "rc": 0,
             "date": "d1", "cmd": "c", "parsed": [
                 {"images_per_sec": 27000.0, "record_dtype": "uint8",
                  "threads": 16}]},
        ]
        captures = tmp_path / "captures.jsonl"
        captures.write_text("".join(json.dumps(r) + "\n" for r in rows))
        baseline = tmp_path / "B.md"
        design = tmp_path / "D.md"
        baseline.write_text("# B\n")
        design.write_text("# D\n")
        monkeypatch.setattr(ca, "CAPTURES", str(captures))
        monkeypatch.setattr(ca, "BASELINE_MD", str(baseline))
        monkeypatch.setattr(ca, "DESIGN_MD", str(design))
        ca.render_docs()
        text = baseline.read_text()
        assert "Chip FID/KID trajectory (long" in text
        assert "Chip FID/KID trajectory (early" in text
        assert "`c3`" in text and "`c1`" not in text  # latest long run wins
        assert "- float64: best 15000 img/s" in text
        assert "- uint8: best 27000 img/s" in text
        # spreads are per-format: no pooled 15000-27000 range anywhere
        assert "15000–27000" not in text


class TestTraceSummary:
    def test_committed_chip_trace_parses(self):
        """The committed v5e trace artifact must keep yielding the step-time
        evidence DESIGN.md §1b cites: 5 per-step train_step executions at
        ~2.845 ms on the device's own timeline (now through the shared
        dcgan_tpu/utils/trace.py parser — satellite reroute)."""
        from tools.trace_summary import find_trace, summarize

        rows = summarize(find_trace(os.path.join(
            REPO, "docs", "assets", "trace_train_step_v5e.json.gz")))
        step = next(r for r in rows if "train_step" in r["program"])
        assert step["n"] == 5
        assert 2.8 < step["ms_min"] <= step["ms_max"] < 2.9

    def test_find_trace_dir_and_missing(self, tmp_path):
        from tools.trace_summary import find_trace

        with pytest.raises(FileNotFoundError):
            find_trace(str(tmp_path))
        d = tmp_path / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        p = d / "vm.trace.json.gz"
        p.write_bytes(b"")
        assert find_trace(str(tmp_path)) == str(p)

    def _write_trace(self, path, events):
        import gzip

        with gzip.open(str(path), "wt") as f:
            json.dump({"traceEvents": events}, f)
        return str(path)

    def test_cpu_capture_falls_back_instead_of_printing_nothing(
            self, tmp_path):
        """Satellite fix: a no-TPU capture used to print NOTHING and exit
        0 — now it reports the busiest fallback track with a stderr note."""
        path = self._write_trace(tmp_path / "c.trace.json.gz", [
            {"ph": "M", "pid": 7, "name": "process_name",
             "args": {"name": "/host:CPU"}},
            {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
             "args": {"name": "tf_XLATfrtCpuClient/1"}},
            {"ph": "X", "pid": 7, "tid": 2, "name": "dot.1",
             "ts": 0, "dur": 500}])
        res = subprocess.run(
            [sys.executable, "tools/trace_summary.py", path], cwd=REPO,
            capture_output=True, text=True, timeout=120)
        assert res.returncode == 0, res.stderr
        rows = [json.loads(l) for l in res.stdout.splitlines()]
        assert rows and rows[0]["program"] == "dot.1"
        assert "no TPU-named process" in res.stderr

    def test_no_device_events_exits_nonzero_with_hint(self, tmp_path):
        path = self._write_trace(tmp_path / "e.trace.json.gz", [
            {"ph": "M", "pid": 7, "name": "process_name",
             "args": {"name": "/host:CPU"}}])
        res = subprocess.run(
            [sys.executable, "tools/trace_summary.py", path], cwd=REPO,
            capture_output=True, text=True, timeout=120)
        assert res.returncode == 1
        assert res.stdout.strip() == ""
        assert "no duration events" in res.stderr
        assert "--profile_dir" in res.stderr  # the usage hint

    def test_committed_chip_trace_digest(self):
        """The v5e artifact is also the DIGEST regression fixture (ISSUE 6
        satellite): device attribution over the capture — ~14.25 ms busy
        across 5 steps, the rest idle between dispatches."""
        from dcgan_tpu.utils.trace import digest

        d = digest(os.path.join(REPO, "docs", "assets",
                                "trace_train_step_v5e.json.gz"))
        assert d["source"] == "tpu"
        assert 2.8 < d["program_ms_median"] < 2.9  # devstep_ms source
        assert 14.0 < d["compute_ms"] < 15.0
        assert 40.0 < d["idle_gap_ms"] < 50.0
        assert d["collective_ms"] == 0.0


class TestTrainerLoopParsing:
    def test_log_regex_and_window(self):
        from tools.bench_trainer_loop import LOG_RE

        out = ("[dcgan_tpu] epoch 0 step 500 time 30.0s d_loss 1.0 "
               "g_loss 1.0\n"
               "[dcgan_tpu] epoch 0 step 1000 time 33.2s d_loss 1.0 "
               "g_loss 1.0\n"
               "[dcgan_tpu] epoch 1 step 5000 time 46.0s d_loss 1.0 "
               "g_loss 1.0\n")
        pts = [(int(m.group(1)), float(m.group(2)))
               for m in LOG_RE.finditer(out)]
        assert pts == [(500, 30.0), (1000, 33.2), (5000, 46.0)]


class TestAnalysisAllSmoke:
    """THE consolidated analyzer pin (ISSUE 14, replacing the separate
    AST + semantic subprocess pins): ONE `python -m dcgan_tpu.analysis
    --all` subprocess must run every tier CLEAN — zero non-baselined
    findings across DCG001-015 — AND regenerate BOTH committed contracts
    (analysis/programs.lock.jsonl, analysis/protocol.lock.jsonl)
    byte-identically. `--write-manifest/--write-lock <tmp>` recompute
    every row (exit code still gated on the non-drift findings), and the
    byte compares against the committed files ARE the drift checks at
    full strength. The CLI arranges its own canonical topology (CPU, 2
    virtual devices) before jax initializes, so the pin is
    environment-stable. Per-tier flags keep working and are covered
    in-process (tests/test_analysis.py, tests/test_protocol.py) plus the
    dedicated --protocol subprocess pin below."""

    def test_all_tiers_clean_and_locks_reproducible_within_budget(
            self, tmp_path):
        import time

        committed_manifest = os.path.join(
            REPO, "dcgan_tpu", "analysis", "programs.lock.jsonl")
        committed_lock = os.path.join(
            REPO, "dcgan_tpu", "analysis", "protocol.lock.jsonl")
        out_manifest = str(tmp_path / "programs.lock.jsonl")
        out_lock = str(tmp_path / "protocol.lock.jsonl")
        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "-m", "dcgan_tpu.analysis", "--all",
             "--json", "--write-manifest", out_manifest,
             "--write-lock", out_lock],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=510)
        elapsed = time.monotonic() - t0
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-800:])
        summary = json.loads(
            [l for l in res.stdout.splitlines()
             if l.startswith("{")][-1])
        assert summary["label"] == "dcgan-analysis-all"
        assert summary["new_findings"] == 0
        tiers = summary["tiers"]
        # per-tier timing is part of the contract: a tier that silently
        # stopped running would report no timing row
        assert set(tiers) == {"ast", "semantic", "protocol"}
        assert all(t["ms"] > 0 for t in tiers.values())
        assert tiers["ast"]["files"] > 50
        assert tiers["semantic"]["programs"] > 60
        # the protocol lattice really explored (ISSUE 14 acceptance:
        # >= 4 configs x >= 6 interleavings); the stderr line makes
        # silent shrinkage visible in CI logs
        assert tiers["protocol"]["configs"] >= 4
        assert tiers["protocol"]["interleavings"] >= 24
        assert "explored" in res.stderr and "interleaving" in res.stderr
        for out, committed, what in (
                (out_manifest, committed_manifest, "programs.lock.jsonl"),
                (out_lock, committed_lock, "protocol.lock.jsonl")):
            with open(out, "rb") as f_new, open(committed, "rb") as f_old:
                assert f_new.read() == f_old.read(), (
                    f"regenerated {what} differs from the committed file "
                    "— either the contract drifted (regenerate "
                    "deliberately and review the diff) or determinism "
                    "broke")
        # The budget keeps the tier-1 pin from quietly eating the tier.
        # Recalibrated as the tiers grew (the semantic tier compiles
        # every dispatchable program: 70 -> 97 manifest rows across the
        # pallas/precision, progressive, and live-elastic PRs, then
        # 97 -> 124 with the collective-overlap variants; the protocol
        # lattice is 129 interleavings with the serving-fleet
        # promotion-drain configs): measured ~380 s quiet / 444 s under
        # contention on a 1-core host at 124 rows, where ~370 s quiet
        # was the 97-row measurement and the original 300 s bound — set
        # when the tier took ~65 s on 2 cores — already failed BEFORE
        # the live-elastic rows landed (339 s at that commit on the
        # same host).
        assert elapsed < 530, f"--all took {elapsed:.0f}s"


class TestProtocolAnalysisSmoke:
    """ISSUE 14's dedicated tier pin: `--protocol --json` alone must run
    clean inside a tight budget (the simulator is pure host code — if it
    slows down, its lattice grew in a way someone should look at), and
    must PRINT the explored-interleaving counts so lattice shrinkage can
    never be silent in logs."""

    def test_protocol_clean_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "-m", "dcgan_tpu.analysis", "--protocol",
             "--json"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=180)
        elapsed = time.monotonic() - t0
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-800:])
        summary = json.loads(
            [l for l in res.stdout.splitlines()
             if l.startswith("{")][-1])
        assert summary["label"] == "dcgan-analysis-protocol"
        assert summary["new_findings"] == 0
        assert summary["configs"] >= 4
        assert summary["interleavings"] >= 24
        import re as _re

        m = _re.search(r"explored (\d+) interleaving\(s\) across (\d+) "
                       r"knob config\(s\)", res.stderr)
        assert m, f"no interleaving-count line in stderr: {res.stderr}"
        assert int(m.group(1)) == summary["interleavings"]
        assert elapsed < 120, f"protocol tier took {elapsed:.0f}s"


@pytest.mark.chaos
class TestChaosDrillSmoke:
    """tools/chaos_drill.py --smoke pinned into tier-1 (not slow, per the
    chaos-marker contract in pytest.ini): the cheap scenario subset —
    corrupt-record quarantine, transient-IO retry, services-crash
    surfacing — must keep passing end to end through real trainer
    subprocesses. The full 9-scenario matrix (rollback + checkpoint
    fallback + the ISSUE 6 observability trio included) runs standalone:
    `python tools/chaos_drill.py`."""

    def test_smoke_matrix_passes(self):
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--smoke"], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=600)
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["label"] == "chaos-drill"
        assert summary["scenarios"] == 3 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"corrupt-record", "io-error-once",
                                  "services-crash"}
        assert scenarios["corrupt-record"]["corrupt_records"] >= 1

    def test_multihost_smoke_passes_within_budget(self):
        """tools/chaos_drill.py --multihost --smoke pinned into tier-1
        (ISSUE 4): the cheapest coordinated-recovery scenario — SIGTERM on
        one host of a real 2-process localhost-gRPC job becomes a
        collective stop + bit-exact resume — with an explicit runtime
        budget so the pin can never quietly eat the tier. The full
        3-scenario matrix (coordinated rollback + watchdog trip included)
        runs standalone: `python tools/chaos_drill.py --multihost`."""
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--multihost",
             "--smoke"], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["label"] == "chaos-drill-multihost"
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"mh-sigterm-stop"}
        assert scenarios["mh-sigterm-stop"]["resumed"] is True
        # runtime budget: two tiny 2-process launches; 300 s is ~4x the
        # measured cost on a quiet host, headroom for CI contention
        assert elapsed < 300, f"multihost smoke took {elapsed:.0f}s"


@pytest.mark.chaos
class TestObservabilitySmoke:
    """ISSUE 6's tier-1 pin (chaos-marker pattern from PRs 3-5): the
    trigger-file capture -> in-process digest loop and the flight-recorder
    dump triggers must keep working end to end through real trainer
    subprocesses, inside an explicit runtime budget. The full matrix runs
    standalone: `JAX_PLATFORMS=cpu python tools/chaos_drill.py`."""

    def test_trace_trigger_and_flight_recorder_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--only",
             "flight-recorder", "watchdog-dump", "trace-trigger"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 3 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"flight-recorder", "watchdog-dump",
                                  "trace-trigger"}
        assert scenarios["flight-recorder"]["failing_step"] == 3
        assert scenarios["watchdog-dump"]["phase"] == "step-dispatch"
        assert scenarios["trace-trigger"]["device_compute_ms"] > 0
        # three tiny trainer subprocesses (~15 s each on a quiet host,
        # compile-dominated); ~4x headroom for CI contention
        assert elapsed < 300, f"observability smoke took {elapsed:.0f}s"


@pytest.mark.chaos
class TestPipelineRollbackSmoke:
    """ISSUE 7's tier-1 pin (chaos-marker pattern): a NaN fault under
    --pipeline_gd must drain the in-flight fake stack at the rollback,
    refill from the restored state, complete, and replay bit-exactly —
    through real trainer subprocesses, inside an explicit runtime budget.
    The full matrix runs standalone:
    `JAX_PLATFORMS=cpu python tools/chaos_drill.py`."""

    def test_pipeline_rollback_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--only",
             "pipeline-rollback"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"pipeline-rollback"}
        assert scenarios["pipeline-rollback"]["rollbacks"] >= 1
        assert scenarios["pipeline-rollback"]["replay_bit_exact"] is True
        # two tiny trainer subprocesses (the replay pair, ~20 s each on a
        # quiet host, compile-dominated); ~4x headroom for CI contention
        assert elapsed < 300, f"pipeline-rollback smoke took {elapsed:.0f}s"


@pytest.mark.chaos
class TestProgressiveSwitchSmoke:
    """ISSUE 15's tier-1 pin (chaos-marker pattern): a NaN at the first
    step after a progressive phase switch must roll back to the
    POST-switch snapshot (the new phase's tree), complete, replay
    STATE_SUM bit-exactly, and keep the pre-switch phase's losses
    bit-exact against an unfaulted control — through real trainer
    subprocesses, inside an explicit runtime budget. The full matrix
    runs standalone: `JAX_PLATFORMS=cpu python tools/chaos_drill.py`."""

    def test_progressive_switch_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--only",
             "progressive-switch"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"progressive-switch"}
        row = scenarios["progressive-switch"]
        assert row["rollbacks"] >= 1
        assert row["replay_bit_exact"] is True
        assert row["preswitch_losses_bit_exact"] is True
        # three tiny trainer subprocesses (faulted pair + control, each
        # compiling two phase surfaces); ~4x headroom for CI contention
        assert elapsed < 300, f"progressive-switch smoke took {elapsed:.0f}s"


@pytest.mark.chaos
class TestZeroRollbackSmoke:
    """ISSUE 13's tier-1 pin (chaos-marker pattern): a NaN fault under
    --zero_stage 3 must restore the data-SHARDED state from the rollback
    snapshot, complete, and replay losses + STATE_SUM bit-exactly against
    a --zero_stage 1 control — through real trainer subprocesses, inside
    an explicit runtime budget. The full matrix runs standalone:
    `JAX_PLATFORMS=cpu python tools/chaos_drill.py`."""

    def test_zero_rollback_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--only",
             "zero-rollback"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        assert set(scenarios) == {"zero-rollback"}
        assert scenarios["zero-rollback"]["rollbacks"] >= 1
        # to 1e-5, not to the bit: see the scenario's docstring for what
        # the installed XLA:CPU does to the shard-local update
        assert scenarios["zero-rollback"]["replay_rtol"] == 1e-5
        # two tiny 2-device trainer subprocesses (~25 s each on a quiet
        # host, compile-dominated); ~4x headroom for CI contention
        assert elapsed < 300, f"zero-rollback smoke took {elapsed:.0f}s"


@pytest.mark.chaos
class TestElasticShrinkSmoke:
    """ISSUE 12's tier-1 pin (chaos-marker pattern): a checkpoint saved
    by 2 processes must resume on 1 process (2 virtual devices — same
    2-way data mesh, different process census) through the sharding
    sidecar's host-staged reshard, with post-resume losses and final
    STATE_SUM replaying against a same-topology control resume to within
    ulp-scale reduction-order tolerances (the cross-process collective
    may sum partials in a different order than the intra-process one —
    the drill documents the bound; see chaos_drill._elastic_scenario) —
    through real trainer subprocesses, inside an explicit runtime budget.
    The grow direction (and the rest of the matrix) runs standalone:
    `JAX_PLATFORMS=cpu python tools/chaos_drill.py`."""

    def test_elastic_shrink_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--only",
             "elastic-shrink"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
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
        # five tiny trainer launches (one 2-proc save pair, a 1-proc
        # cross resume, a 2-proc control pair; ~20 s measured total on a
        # quiet host) — generous headroom for CI contention
        assert elapsed < 300, f"elastic-shrink smoke took {elapsed:.0f}s"


@pytest.mark.chaos
class TestLiveNoticeShrinkSmoke:
    """ISSUE 18's tier-1 pin: a chaos preemption notice at step 3 drives
    a LIVE t2x1 -> t1x1 mesh switch in one uninterrupted trainer process
    (no restart), the run completes to step 6, the switch line reports
    compile_requests_delta=0 (both topologies AOT-warmed+primed up
    front), pre-notice losses replay bit-exactly against an
    armed-but-unnotified control, and elastic/live_* event keys appear
    only in the notified run. The grow-back direction runs standalone:
    `JAX_PLATFORMS=cpu python tools/chaos_drill.py --only grow-back`."""

    def test_live_notice_shrink_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--only",
             "notice-shrink"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenarios = {p["scenario"]: p for p in lines if "scenario" in p}
        row = scenarios["notice-shrink"]
        assert row["compile_requests_delta"] == 0
        assert row["final_step"] == 6
        assert row["switch_ms"] > 0
        assert row["state_sum_rel"] <= 5e-4
        # two tiny 2-device trainer launches (control + notified, ~25 s
        # measured total on a quiet host, warmup-dominated) — generous
        # headroom for CI contention
        assert elapsed < 300, f"notice-shrink smoke took {elapsed:.0f}s"


@pytest.mark.slow
class TestBenchProgressiveAB:
    """ISSUE 15's bench contract: `PROGRESSIVE=1 python bench.py` prints
    the progressive A/B row (fixed-res arm vs per-phase ms_per_step +
    switch_ms, driven through the shipped PhaseRuntime) and a standalone
    256px single-phase row, both BEFORE the headline row. Slow tier:
    a 256px compile in a subprocess."""

    def test_progressive_rows_before_headline(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_PLATFORM="cpu",
                   BENCH_BATCH="4", BENCH_STEPS="2", BENCH_WINDOWS="1",
                   BENCH_DEVSTEP="0", BENCH_SIZE="16", PROGRESSIVE="1",
                   BENCH_PROGRESSIVE_STEPS="2", BENCH_256_BATCH="2",
                   BENCH_256_STEPS="1")
        res = subprocess.run([sys.executable, "bench.py"], cwd=REPO,
                             env=env, capture_output=True, text=True,
                             timeout=900)
        assert res.returncode == 0, (res.stdout[-800:], res.stderr[-800:])
        rows = [json.loads(l) for l in res.stdout.splitlines()
                if l.startswith("{")]
        # both extra rows precede the headline row (last-line parse)
        ab = next(r for r in rows if "progressive" in r["metric"])
        r256 = next(r for r in rows if r["metric"].startswith("DCGAN-256"))
        assert rows.index(ab) < len(rows) - 1
        assert rows.index(r256) < len(rows) - 1
        assert ab["switch_ms"] > 0 and ab["carried_leaves"] > 0
        assert ab["fixed16"]["ms_per_step"] > 0
        assert ab["phase_r16"]["ms_per_step"] > 0
        assert ab["phase_r32"]["ms_per_step"] > 0
        assert r256["ms_per_step"] > 0 and r256["peak_state_mib"] > 0


@pytest.mark.slow
class TestBenchZeroAB:
    """ISSUE 13's bench contract: `ZERO_STAGE=3 python bench.py` prints
    the state-sharding A/B row (before the headline row) with
    peak_state_mib per arm STRICTLY DECREASING from stage 1 -> 3 —
    the ZeRO win as a number, not a claim. Slow tier: six multi-device
    step compiles in a subprocess."""

    def test_zero_ab_row_state_strictly_decreasing(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_PLATFORM="cpu",
                   BENCH_BATCH="8", BENCH_STEPS="4", BENCH_WINDOWS="1",
                   BENCH_ZERO_STEPS="3", BENCH_DEVSTEP="0",
                   BENCH_SIZE="16", ZERO_STAGE="3",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        res = subprocess.run([sys.executable, "bench.py"], cwd=REPO,
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert res.returncode == 0, (res.stdout[-800:], res.stderr[-800:])
        rows = [json.loads(l) for l in res.stdout.splitlines()
                if l.startswith("{")]
        # the A/B row precedes the headline row (last-line parse contract)
        ab = next(r for r in rows if "ZeRO" in r["metric"])
        assert rows[-1]["metric"].endswith("(batch 8/chip, bf16)")
        mibs = [ab[f"zero{s}"]["peak_state_mib"] for s in (1, 2, 3)]
        assert mibs[0] > mibs[1] > mibs[2], mibs
        # headline row carries the per-chip resident state too
        assert rows[-1]["peak_state_mib"] == pytest.approx(mibs[0])


@pytest.mark.chaos
class TestBenchStartupSmoke:
    """tools/bench_startup.py --smoke pinned into tier-1 (ISSUE 5,
    mirroring the chaos_drill pattern): the cold-vs-warm trainer A/B must
    keep proving the warm-start invariants end to end through real trainer
    subprocesses — warm compile strictly lower with a primed cache, zero
    warm cache misses, and the fused verified restore reading each
    manifest byte exactly once — inside an explicit runtime budget so the
    pin can never quietly eat the tier. The full-size run is standalone:
    `JAX_PLATFORMS=cpu python tools/bench_startup.py`."""

    def test_cold_warm_ab_passes_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/bench_startup.py", "--smoke"], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        row = json.loads(res.stdout.strip().splitlines()[-1])
        assert row["label"] == "bench-startup" and row["ok"] is True
        assert row["checks"]["warm_compile_strictly_lower"]
        assert row["checks"]["warm_zero_misses"]
        assert row["checks"]["restore_bytes_read_once"]
        assert row["warm"]["cache"]["hits"] > 0
        # the cross-topology arm (ISSUE 12): save@2-dev -> restore@1-dev
        # must take the sidecar reshard path, and the same-topology warm
        # arm must NOT
        assert row["checks"]["cross_resharded"]
        assert row["checks"]["warm_no_reshard"]
        assert row["cross"]["reshard_ms"] > 0
        # three tiny trainer subprocesses; ~4x measured cost (quiet host)
        assert elapsed < 240, f"bench_startup smoke took {elapsed:.0f}s"


@pytest.mark.chaos
class TestBenchServeSmoke:
    """tools/bench_serve.py --smoke pinned into tier-1 (ISSUE 9, the
    chaos-marker pattern): the cold-vs-warm serving A/B over a bursty
    Poisson trace must keep proving the serving-plane invariants end to
    end through real subprocesses — zero sampler recompiles after the
    AOT bucket warmup on BOTH arms (every served batch hits a
    precompiled bucket), warm cache hits with zero misses, and the
    finite-trace drain losing nothing — inside an explicit runtime
    budget so the pin can never quietly eat the tier. The full-size run
    is standalone: `JAX_PLATFORMS=cpu python tools/bench_serve.py`."""

    def test_cold_warm_serve_ab_passes_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/bench_serve.py", "--smoke"], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        row = json.loads(res.stdout.strip().splitlines()[-1])
        assert row["label"] == "bench-serve" and row["ok"] is True
        assert row["checks"]["cold_zero_recompiles_after_warmup"]
        assert row["checks"]["warm_zero_recompiles_after_warmup"]
        assert row["checks"]["warm_has_hits"]
        assert row["checks"]["warm_zero_misses"]
        assert row["cold"]["p99_ms"] >= row["cold"]["p50_ms"] > 0
        assert row["warm"]["completed"] == row["trace"]["requests"]
        # three tiny subprocesses (1 trainer + 2 serve arms, ~40 s on a
        # quiet host, compile-dominated); ~4x headroom for CI contention
        assert elapsed < 240, f"bench_serve smoke took {elapsed:.0f}s"

    def test_serve_drain_scenario_within_budget(self):
        """chaos_drill serve-drain pinned alongside: SIGTERM mid-load ->
        in-flight requests complete, queue drains, clean exit (the
        serving plane's first chaos consumer)."""
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--only",
             "serve-drain"], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        summary = lines[-1]
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-500:])
        assert summary["scenarios"] == 1 and summary["failed"] == 0
        scenario = next(p for p in lines if p.get("scenario") == "serve-drain")
        assert scenario["clean_exit"] is True
        assert scenario["completed"] == scenario["submitted"] > 0
        # two tiny subprocesses (1 trainer + 1 serve under SIGTERM);
        # ~4x headroom for CI contention
        assert elapsed < 240, f"serve-drain smoke took {elapsed:.0f}s"


@pytest.mark.chaos
class TestFleetReplicaKillSmoke:
    """ISSUE 19's tier-1 pin (chaos-marker pattern): the serving fleet
    under live fire through a real `python -m dcgan_tpu.serve --fleet 3`
    subprocess — a chaos kill of replica 1 mid-trace must become a
    failover (ZERO failed client requests, completed == submitted), the
    dead replica must be drained from rotation and logged, and the
    mid-trace checkpoint injection must be hot-swapped onto EXACTLY the
    survivors with compile_requests_delta == 0 per replica (the
    zero-recompile promotion literal, proven by the compile-cache
    monitor, not assumed). Inside an explicit runtime budget so the pin
    can never quietly eat the tier."""

    def test_fleet_replica_kill_within_budget(self):
        import time

        t0 = time.monotonic()
        res = subprocess.run(
            [sys.executable, "tools/chaos_drill.py", "--only",
             "fleet-replica-kill"], cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=420)
        elapsed = time.monotonic() - t0
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
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
        # three tiny subprocesses (2 trainer runs for the checkpoint
        # lineage + 1 fleet serve; ~21 s measured total on a quiet
        # 1-core host); ~4x headroom for CI contention
        assert elapsed < 240, f"fleet-replica-kill took {elapsed:.0f}s"


@pytest.mark.slow
class TestToolsRunOnCpu:
    def test_loader_scale_two_processes(self):
        """The multi-process loader-scaling tool end to end on tiny shards:
        two workers own disjoint `shard_for_process` slices, measure over
        one shared wall window, and the parent emits well-formed aggregate
        rows (the numbers only mean anything on a quiet multi-core host —
        the contract here is protocol + JSON shape)."""
        res = subprocess.run(
            [sys.executable, "tools/bench_loader_scale.py",
             "--processes", "1", "2", "--seconds", "1.5", "--warmup_s", "5",
             "--num_examples", "512", "--num_shards", "8", "--threads",
             "4"],
            cwd=REPO, env=dict(os.environ), capture_output=True, text=True,
            timeout=300)
        assert res.returncode == 0, res.stderr[-800:]
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        assert [p["processes"] for p in lines] == [1, 2]
        for p in lines:
            assert p["label"] == "loader-scale"
            assert len(p["per_process_images_per_sec"]) == p["processes"]
            assert p["aggregate_images_per_sec"] == pytest.approx(
                sum(p["per_process_images_per_sec"]), abs=0.5)
            assert p["cores_visible"] >= 1

    def test_canonical_50k_tool_cpu(self):
        """tools/canonical_50k.py end to end at toy scale: random torch
        tower -> convert_torch_embedder .npz -> step-0 checkpoint ->
        `python -m dcgan_tpu.evals --feature_npz` — the exact pipeline the
        tool runs at 50k on a chip, pinned here so the tool
        cannot rot (the score is arbitrary; the contract is that the
        canonical path executes and reports the requested sample count)."""
        res = subprocess.run(
            [sys.executable, "tools/canonical_50k.py"], cwd=REPO,
            env=dict(os.environ, BENCH_PLATFORM="cpu", JAX_PLATFORMS="cpu",
                     CANON_SAMPLES="64"),
            capture_output=True, text=True, timeout=900)
        assert res.returncode == 0, (res.stderr[-800:], res.stdout[-300:])
        row = json.loads(res.stdout.strip().splitlines()[-1])
        assert row["label"] == "canonical-npz-50k"
        assert row["num_samples"] == 64
        assert row["fid"] > 0 and row["feature_dim"] == 512
        assert "torch" in row["embedder"]

    def test_matmul_rate_cpu(self):
        env = dict(os.environ, BENCH_PLATFORM="cpu", JAX_PLATFORMS="cpu",
                   MATMUL_SHAPES="64x64,64x128", MATMUL_ITERS="2",
                   MATMUL_WINDOWS="1")
        res = subprocess.run(
            [sys.executable, "tools/matmul_rate.py"], cwd=REPO, env=env,
            capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, res.stderr[-500:]
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        shapes = [(p["m"], p["n"]) for p in lines if p.get("form")]
        assert shapes == [(64, 64), (64, 128)]
        summ = lines[-1]
        assert summ["label"] == "matmul-rate" and summ["peak_tflops"] > 0

    def test_attention_memory_cpu(self):
        """attention_memory compiles both forms and prints well-formed
        rows; where the backend reports temp sizes, dense must grow with
        S while flash stays bounded (the O(S^2)-vs-O(S) claim's shape)."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        res = subprocess.run(
            [sys.executable, "tools/attention_memory.py",
             "--platform", "cpu", "--seq", "256", "512"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, res.stderr[-500:]
        lines = [json.loads(l) for l in res.stdout.splitlines()
                 if l.startswith("{")]
        rows = {(p["form"], p["seq"]): p for p in lines if "form" in p}
        assert set(rows) == {("dense", 256), ("dense", 512),
                             ("flash", 256), ("flash", 512)}
        # both forms must actually compile on CPU — an error row also
        # carries form/seq, so key equality alone would mask a regression
        for key, p in rows.items():
            assert "error" not in p, (key, p)
        d256 = rows[("dense", 256)].get("temp_mib")
        d512 = rows[("dense", 512)].get("temp_mib")
        if d256 is not None and d512 is not None and d512 > 0:
            assert d512 >= d256


class TestBenchEnvLabels:
    """bench_model_config's label keys the capture rows; it must reflect
    the attention that actually runs AFTER the BENCH_ATTN_RES override
    (ADVICE r5 #2)."""

    def _label(self, **env):
        from dcgan_tpu.utils.bench_env import bench_model_config
        return bench_model_config(env)[1]

    def test_base_labels_unchanged(self):
        assert self._label() == "headline"
        assert self._label(BENCH_SIZE="128") == "dcgan128"
        assert self._label(BENCH_ATTN="1") == "sagan64-attn"
        assert self._label(BENCH_ATTN="1", BENCH_PALLAS="1") \
            == "sagan64-attn-flash"
        # without attention the flag selects nothing: the same program, the
        # same row
        assert self._label(BENCH_PALLAS="1") == "headline"
        assert self._label(BENCH_ATTN="1", BENCH_SN="1") \
            == "sagan64-attn-sn"

    def test_attn_res_override_labels_match_bench_matrix(self):
        """The ADVICE r5 #2 scenario: a BENCH_ATTN_RES config running flash
        attention is labeled by the attention that runs; long-context
        labels match capture_all's '<family>-attn<R>-{flash,dense}'
        naming."""
        assert self._label(BENCH_SIZE="256", BENCH_ATTN_RES="128",
                           BENCH_PALLAS="1") == "dcgan256-attn128-flash"
        assert self._label(BENCH_SIZE="256", BENCH_ATTN_RES="128") \
            == "dcgan256-attn128-dense"
