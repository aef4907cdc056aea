"""The measurement tools behind DESIGN.md §1b and the captures table.

What must hold: the spread/aggregation math the docs tables are rendered
from (tools/capture_all.py), the trainer-log parsing bench_trainer_loop's
throughput derivation rests on, and a CPU execution of the matmul-rate
tool end to end (tiny shapes — the contract is "runs and
prints well-formed JSON", the numbers only mean anything on a chip).
"""

import json
import os
import sys

import pytest

from tool_smoke import REPO, json_rows, run_tool

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
        res = run_tool(["tools/trace_summary.py", path], timeout=120)
        assert res.returncode == 0, res.stderr
        rows = [json.loads(l) for l in res.stdout.splitlines()]
        assert rows and rows[0]["program"] == "dot.1"
        assert "no TPU-named process" in res.stderr

    def test_no_device_events_exits_nonzero_with_hint(self, tmp_path):
        path = self._write_trace(tmp_path / "e.trace.json.gz", [
            {"ph": "M", "pid": 7, "name": "process_name",
             "args": {"name": "/host:CPU"}}])
        res = run_tool(["tools/trace_summary.py", path], timeout=120)
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


@pytest.mark.slow
class TestBenchProgressiveAB:
    """ISSUE 15's bench contract: `PROGRESSIVE=1 python bench.py` prints
    the progressive A/B row (fixed-res arm vs per-phase ms_per_step +
    switch_ms, driven through the shipped PhaseRuntime) and a standalone
    256px single-phase row, both BEFORE the headline row. Slow tier:
    a 256px compile in a subprocess."""

    def test_progressive_rows_before_headline(self):
        res = run_tool(["bench.py"], timeout=900, BENCH_PLATFORM="cpu",
                       BENCH_BATCH="4", BENCH_STEPS="2", BENCH_WINDOWS="1",
                       BENCH_DEVSTEP="0", BENCH_SIZE="16", PROGRESSIVE="1",
                       BENCH_PROGRESSIVE_STEPS="2", BENCH_256_BATCH="2",
                       BENCH_256_STEPS="1")
        assert res.returncode == 0, (res.stdout[-800:], res.stderr[-800:])
        rows = json_rows(res.stdout)
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
        res = run_tool(["bench.py"], timeout=600, BENCH_PLATFORM="cpu",
                       BENCH_BATCH="8", BENCH_STEPS="4", BENCH_WINDOWS="1",
                       BENCH_ZERO_STEPS="3", BENCH_DEVSTEP="0",
                       BENCH_SIZE="16", ZERO_STAGE="3",
                       XLA_FLAGS="--xla_force_host_platform_device_count=2")
        assert res.returncode == 0, (res.stdout[-800:], res.stderr[-800:])
        rows = json_rows(res.stdout)
        # the A/B row precedes the headline row (last-line parse contract)
        ab = next(r for r in rows if "ZeRO" in r["metric"])
        assert rows[-1]["metric"].endswith("(batch 8/chip, bf16)")
        mibs = [ab[f"zero{s}"]["peak_state_mib"] for s in (1, 2, 3)]
        assert mibs[0] > mibs[1] > mibs[2], mibs
        # headline row carries the per-chip resident state too
        assert rows[-1]["peak_state_mib"] == pytest.approx(mibs[0])


@pytest.mark.slow
class TestToolsRunOnCpu:
    def test_loader_scale_two_processes(self):
        """The multi-process loader-scaling tool end to end on tiny shards:
        two workers own disjoint `shard_for_process` slices, measure over
        one shared wall window, and the parent emits well-formed aggregate
        rows (the numbers only mean anything on a quiet multi-core host —
        the contract here is protocol + JSON shape)."""
        res = run_tool(
            ["tools/bench_loader_scale.py",
             "--processes", "1", "2", "--seconds", "1.5", "--warmup_s", "5",
             "--num_examples", "512", "--num_shards", "8", "--threads",
             "4"], timeout=300)
        assert res.returncode == 0, res.stderr[-800:]
        lines = json_rows(res.stdout)
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
        res = run_tool(["tools/canonical_50k.py"], timeout=900,
                       BENCH_PLATFORM="cpu", CANON_SAMPLES="64")
        assert res.returncode == 0, (res.stderr[-800:], res.stdout[-300:])
        row = json.loads(res.stdout.strip().splitlines()[-1])
        assert row["label"] == "canonical-npz-50k"
        assert row["num_samples"] == 64
        assert row["fid"] > 0 and row["feature_dim"] == 512
        assert "torch" in row["embedder"]

    def test_matmul_rate_cpu(self):
        res = run_tool(["tools/matmul_rate.py"], timeout=300,
                       BENCH_PLATFORM="cpu", MATMUL_SHAPES="64x64,64x128",
                       MATMUL_ITERS="2", MATMUL_WINDOWS="1")
        assert res.returncode == 0, res.stderr[-500:]
        lines = json_rows(res.stdout)
        shapes = [(p["m"], p["n"]) for p in lines if p.get("form")]
        assert shapes == [(64, 64), (64, 128)]
        summ = lines[-1]
        assert summ["label"] == "matmul-rate" and summ["peak_tflops"] > 0

    def test_attention_memory_cpu(self):
        """attention_memory compiles both forms and prints well-formed
        rows; where the backend reports temp sizes, dense must grow with
        S while flash stays bounded (the O(S^2)-vs-O(S) claim's shape)."""
        res = run_tool(["tools/attention_memory.py", "--platform", "cpu",
                        "--seq", "256", "512"], timeout=300)
        assert res.returncode == 0, res.stderr[-500:]
        lines = json_rows(res.stdout)
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
