"""Shared trace parser (ISSUE 6, dcgan_tpu/utils/trace.py): track
selection, per-program rows, and the compute/collective/idle-gap digest —
against both the committed v5e chip capture (the regression fixture) and
synthetic CPU-shaped traces."""

import gzip
import json
import os

import pytest

from dcgan_tpu.utils.trace import (
    devstep_ms,
    digest,
    find_trace,
    is_collective,
    select_device_tracks,
    summarize,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = os.path.join(REPO, "docs", "assets", "trace_train_step_v5e.json.gz")


def write_trace(path, events):
    with gzip.open(str(path), "wt") as f:
        json.dump({"traceEvents": events}, f)
    return str(path)


def meta(pid, name, tid=None):
    if tid is None:
        return {"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": name}}
    return {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
            "args": {"name": name}}


def span(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name,
            "ts": ts, "dur": dur}


class TestV5eFixture:
    """The committed chip capture is the parser's ground truth: 5 train
    steps at ~2.845 ms on the XLA Modules track, with most of the span
    idle between dispatches (per-step dispatch on the previous
    machine)."""

    def test_summarize_keeps_the_headline_step_time(self):
        rows, source = summarize(V5E)
        assert source == "tpu"
        step = next(r for r in rows if "train_step" in r["program"])
        assert step["n"] == 5
        assert 2.8 < step["ms_min"] <= step["ms_max"] < 2.9

    def test_digest_attribution(self):
        d = digest(V5E)
        assert d["source"] == "tpu"
        assert "train_step" in d["program"] and d["program_n"] == 5
        assert 2.8 < d["program_ms_median"] < 2.9
        # 5 steps of ~2.845 ms + tiny helper programs ~= 14.25 ms busy
        assert 14.0 < d["compute_ms"] < 15.0
        # the capture spans ~57.8 ms: the device sat idle most of it —
        # exactly the gap ROADMAP item 3's overlapped execution targets
        assert 40.0 < d["idle_gap_ms"] < 50.0
        assert abs((d["compute_ms"] + d["idle_gap_ms"]) - d["span_ms"]) < 0.1
        assert d["collective_ms"] == 0.0  # single-chip capture

    def test_devstep_helper_shared_by_the_bench_rows(self):
        """devstep_ms is THE definition bench.py / bench_trainer_loop.py /
        the trainer's perf/device/step_ms share: median busiest-program
        execution over per_exec steps."""
        assert devstep_ms(V5E) == pytest.approx(2.8449)
        assert devstep_ms(V5E, per_exec=5) == pytest.approx(2.8449 / 5)

    def test_steps_track_is_not_the_program_track(self):
        """The 'Steps' thread's whole-timeline spans must not leak into
        program accounting (they would zero out the idle gap)."""
        rows, _ = summarize(V5E)
        assert not any(r["program"].isdigit() for r in rows)


class TestSyntheticTraces:
    def test_tpu_pid_preferred_even_when_host_busier(self, tmp_path):
        ev = [meta(1, "/device:TPU:0"), meta(7, "/host:CPU"),
              meta(1, "XLA Modules", tid=2),
              span(1, 2, "jit_step", 0, 100),
              span(7, 9, "host_stuff", 0, 100000)]
        rows, source = summarize(write_trace(tmp_path / "t.json.gz", ev))
        assert source == "tpu"
        assert [r["program"] for r in rows] == ["jit_step"]

    def test_cpu_fallback_prefers_xla_thread_over_python(self, tmp_path):
        """CPU captures: the python thread's whole-call spans dominate by
        duration but the XLA executor thread is the device-work proxy."""
        ev = [meta(7, "/host:CPU"),
              meta(7, "python", tid=1),
              meta(7, "tf_XLATfrtCpuClient/123", tid=2),
              span(7, 1, "PjitFunction(step)", 0, 50000),
              span(7, 2, "dot.3", 100, 400),
              span(7, 2, "dot.3", 1000, 400)]
        rows, source = summarize(write_trace(tmp_path / "t.json.gz", ev))
        assert source == "xla-thread"
        assert rows[0]["program"] == "dot.3" and rows[0]["n"] == 2

    def test_xla_fallback_merges_the_thread_group(self, tmp_path):
        """Executor pools name threads '<pool>/<id>'; a capture whose
        programs spread across a pool's threads (the pipelined G/D stage
        dispatch does) must account the WHOLE group — a busiest-single-
        thread pick would leave roughly half the busy time invisible and
        inflate idle_gap_ms as a measurement artifact (ISSUE 7)."""
        ev = [meta(7, "/host:CPU"),
              meta(7, "tf_XLAEigen/111", tid=1),
              meta(7, "tf_XLAEigen/222", tid=2),
              span(7, 1, "d_update", 0, 400),
              span(7, 2, "g_update", 500, 400),
              span(7, 1, "d_update", 1000, 400)]
        d = digest(write_trace(tmp_path / "t.json.gz", ev))
        assert d["source"] == "xla-thread"
        # all three executions counted: 1.2 ms busy over a 1.4 ms span
        assert d["compute_ms"] == pytest.approx(1.2)
        assert d["idle_gap_ms"] == pytest.approx(0.2)
        assert {r["program"] for r in d["rows"]} == {"d_update", "g_update"}

    def test_xla_fallback_excludes_wait_spans(self, tmp_path):
        """Client '(wait for …)' spans are the executor WAITING, not
        executing: they must neither crown the wait-dominated client
        group during selection nor count as busy time."""
        ev = [meta(7, "/host:CPU"),
              meta(7, "tf_XLATfrtCpuClient/1", tid=1),
              meta(7, "tf_XLAEigen/1", tid=2),
              span(7, 1, "ThunkExecutor::Execute (wait for ready)",
                   0, 10000),
              span(7, 2, "conv.1", 0, 300),
              span(7, 2, "conv.1", 600, 300)]
        d = digest(write_trace(tmp_path / "t.json.gz", ev))
        assert d["source"] == "xla-thread"
        assert d["program"] == "conv.1" and d["program_n"] == 2
        assert d["compute_ms"] == pytest.approx(0.6)

    def test_busiest_nonpython_fallback(self, tmp_path):
        ev = [meta(7, "/host:CPU"),
              meta(7, "python", tid=1), meta(7, "worker", tid=2),
              span(7, 1, "trace_overhead", 0, 9000),
              span(7, 2, "exec", 0, 100)]
        rows, source = summarize(write_trace(tmp_path / "t.json.gz", ev))
        assert source == "busiest-thread"
        assert rows[0]["program"] == "exec"

    def test_no_duration_events_is_none(self, tmp_path):
        path = write_trace(tmp_path / "t.json.gz", [meta(7, "/host:CPU")])
        rows, source = summarize(path)
        assert rows == [] and source == "none"
        d = digest(path)
        assert d["source"] == "none" and d["rows"] == []
        assert devstep_ms(path) is None  # publish null, never fabricate

    def test_digest_merges_overlaps_and_measures_gaps(self, tmp_path):
        """Overlapping spans must not double count busy time; the idle gap
        is span minus the merged union."""
        ev = [meta(1, "/device:TPU:0"), meta(1, "XLA Modules", tid=2),
              span(1, 2, "jit_step", 0, 1000),
              span(1, 2, "overlap", 500, 1000),    # overlaps jit_step
              span(1, 2, "jit_step", 3000, 1000)]
        d = digest(write_trace(tmp_path / "t.json.gz", ev))
        assert d["compute_ms"] == pytest.approx(2.5)   # union, not 3.0
        assert d["idle_gap_ms"] == pytest.approx(1.5)  # [1500, 3000)
        assert d["span_ms"] == pytest.approx(4.0)

    def test_collectives_counted_from_ops_track(self, tmp_path):
        ev = [meta(1, "/device:TPU:0"),
              meta(1, "XLA Modules", tid=2), meta(1, "XLA Ops", tid=3),
              span(1, 2, "jit_step", 0, 2000),
              span(1, 3, "fusion.1", 0, 900),
              span(1, 3, "all-reduce.7", 900, 600),
              span(1, 3, "all-gather-start.2", 1500, 300)]
        d = digest(write_trace(tmp_path / "t.json.gz", ev))
        assert d["collective_ms"] == pytest.approx(0.9)
        assert d["compute_ms"] == pytest.approx(2.0)  # module track

    def test_overlap_frac_attributes_hidden_collective_time(self, tmp_path):
        """ISSUE 20's A/B attribution: collective busy time COVERED by
        non-collective ops counts as hidden, exposed tail does not —
        here [200, 800) of the 1000 us all-gather runs under fusion.1,
        so 0.6 of the collective time is hidden."""
        ev = [meta(1, "/device:TPU:0"),
              meta(1, "XLA Modules", tid=2), meta(1, "XLA Ops", tid=3),
              span(1, 2, "jit_step", 0, 3000),
              span(1, 3, "fusion.1", 0, 800),
              span(1, 3, "all-gather-start.2", 200, 1000),
              span(1, 3, "fusion.2", 2000, 500)]
        d = digest(write_trace(tmp_path / "t.json.gz", ev))
        assert d["collective_ms"] == pytest.approx(1.0)
        assert d["overlap_frac"] == pytest.approx(0.6)

    def test_overlap_frac_zero_without_collectives(self, tmp_path):
        ev = [meta(1, "/device:TPU:0"),
              meta(1, "XLA Modules", tid=2), meta(1, "XLA Ops", tid=3),
              span(1, 2, "jit_step", 0, 1000),
              span(1, 3, "fusion.1", 0, 900)]
        d = digest(write_trace(tmp_path / "t.json.gz", ev))
        assert d["overlap_frac"] == 0.0

    def test_is_collective_names(self):
        assert is_collective("all-reduce.13")
        assert is_collective("ALL-GATHER-start")
        assert is_collective("reduce-scatter.2")
        assert is_collective("collective-permute-done.1")
        assert not is_collective("fusion.4")
        assert not is_collective("jit_train_step(123)")

    def test_select_tracks_falls_back_without_module_thread(self, tmp_path):
        """Older capture layouts without an 'XLA Modules' thread name:
        everything on the TPU pid except 'Steps' spans counts."""
        ev = [meta(1, "/device:TPU:0"), meta(1, "Steps", tid=1),
              span(1, 1, "0", 0, 10000),
              span(1, 5, "jit_step", 0, 1000)]
        programs, ops, source = select_device_tracks(ev)
        assert source == "tpu"
        assert [e["name"] for e in programs] == ["jit_step"]
        assert ops == programs


class TestFindTrace:
    def test_file_dir_and_missing(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            find_trace(str(tmp_path))
        d = tmp_path / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        p = d / "vm.trace.json.gz"
        p.write_bytes(b"")
        assert find_trace(str(tmp_path)) == str(p)
        assert find_trace(str(p)) == str(p)

    def test_host_filter_prefers_own_file(self, tmp_path):
        """Shared-filesystem fleets: every process writes
        <hostname>.trace.json.gz into one session dir — the chief must
        digest ITS host's timeline, not whichever peer sorts last."""
        d = tmp_path / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        mine = d / "host-a.trace.json.gz"
        peer = d / "host-z.trace.json.gz"
        mine.write_bytes(b"")
        peer.write_bytes(b"")
        assert find_trace(str(tmp_path)) == str(peer)  # plain tail
        assert find_trace(str(tmp_path), host="host-a") == str(mine)
        # no filename matches the host: fall back to the newest hit
        assert find_trace(str(tmp_path), host="elsewhere") == str(peer)
