"""The per-layer readers that read the program's own names and spans
(ISSUE 24): `flash_fwd_ms`, `flash_bwd_ms`, `flash_fwd_calls` from the
kernel names in the trace reduction, `feed_wait_share` and
`feed_produce_ms` from the program's span store."""

import json
import os
import sys
import time

import pytest

from bench_testlib import REPO, make_root

sys.path.insert(0, REPO)

from benchmark import manifest, tracing  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FLASH = ("flash_fwd_ms", "flash_bwd_ms", "flash_fwd_calls")
FEED = ("feed_wait_share", "feed_produce_ms")


def reader(name):
    return manifest.layer_metric_reader(REPO, name)


def recorded_trace(named: bool) -> tracing.Trace:
    """The chip trace recorded by PR 23 (kernels unnamed: `jvp__.N` forward,
    `transpose_jvp___.N` backward, even N the dK/dV pass and odd N the dQ
    pass, told apart by their durations then), or the same trace as a
    program that names its kernels writes it. The recording runs 50 ms
    into a second step; a run's window holds whole steps only, so the cut
    ends here with the first step program."""
    with open(os.path.join(DATA, "trace_sagan128_1chip.json")) as f:
        obj = json.load(f)
    for dev in obj["devices"].values():
        end = sum(dev["modules"][0][2:4])
        dev["ops"] = [row for row in dev["ops"] if row[2] < end]
        if named:
            for row in dev["ops"]:
                base, _, n = row[0].rpartition(".")
                if base == "jvp__":
                    row[0] = f"flash_fwd.{n}"
                elif base == "transpose_jvp___":
                    row[0] = ("flash_dkv." if int(n) % 2 == 0
                              else "flash_dq.") + n
    return tracing.Trace.from_json(obj)


def ctx_of(reduced, **over):
    ctx = {"reduced": reduced, "spans": {"next": [], "step": [],
                                         "readback": []},
           "window_s": 0.43, "steps": 1, "global_batch": 256, "chips": 1,
           "config": {}, "traffic": {"feed": "resident"}, "peaks": None,
           "memory_peak_bytes": 0}
    ctx.update(over)
    return ctx


# --- kernel names ---------------------------------------------------------------

def test_flash_readers_on_the_recorded_trace_with_names():
    r = tracing.reduce(recorded_trace(named=True))
    ctx = ctx_of(r)
    fwd, bwd, calls = (reader(n)(ctx) for n in FLASH)
    # five forwards of 16.6 ms, four dK/dV passes of 29.5 and four dQ
    # passes of 19.8 in the one step the cut holds (PERF.md section 5)
    assert fwd == pytest.approx(5 * 16.6, rel=0.01)
    assert bwd == pytest.approx(4 * (29.5 + 19.8), rel=0.01)
    assert calls == 5.0
    # together they are the Pallas class's time per step
    steps = tracing.step_module(r)[1]["count"]
    assert fwd + bwd == pytest.approx(1e3 * r["kind_s"]["pallas"] / steps,
                                      rel=0.01)
    # and the breakdown names the kernels
    ops = [n for n, _ in tracing.breakdown(r)["device_ops"]]
    assert any(n.startswith("pallas:flash_dkv") for n in ops)
    assert not any("jvp__" in n for n in ops)


@pytest.mark.parametrize("name", FLASH)
def test_a_parents_unnamed_kernels_give_nothing(name):
    r = tracing.reduce(recorded_trace(named=False))
    assert r["kind_s"]["pallas"] > 0          # the kernels ran ...
    assert reader(name)(ctx_of(r)) is None    # ... under no name to read


@pytest.mark.parametrize("name", FLASH)
def test_flash_readers_need_a_trace_kernels_and_a_step_program(name):
    assert reader(name)(ctx_of(None)) is None
    ev = lambda n, k, s, d: (n, k, float(s), float(d))
    # a program without attention: fusions only
    dev = tracing.DeviceTrace(
        modules=[ev("jit_train_step", "module", 0, 100)],
        ops=[ev("fusion.1", "convolution", 0, 60),
             ev("bn_apply.2", "pallas", 60, 40)], async_ops=[])
    r = tracing.reduce(tracing.Trace({"/device:TPU:0": dev}, host=[]))
    assert reader(name)(ctx_of(r)) is None
    # a name alone is not enough: the kind has to be a Pallas custom call
    dev.ops.append(ev("flash_fwd_like_fusion", "other", 100, 5))
    r = tracing.reduce(tracing.Trace({"/device:TPU:0": dev}, host=[]))
    assert reader(name)(ctx_of(r)) is None


def test_flash_time_is_per_execution_of_the_step_program():
    ev = lambda n, k, s, d: (n, k, float(s), float(d))
    ops, modules = [], []
    for i in range(3):    # three steps, each 2 forwards and 1 + 1 backward
        t = 1000 * i
        modules.append(ev("jit_train_step", "module", t, 900))
        ops += [ev("flash_fwd.1", "pallas", t, 100),
                ev("flash_fwd.2", "pallas", t + 100, 100),
                ev("flash_dq.1", "pallas", t + 200, 150),
                ev("flash_dkv.1", "pallas", t + 350, 250),
                ev("fusion.7", "convolution", t + 600, 300)]
    r = tracing.reduce(tracing.Trace(
        {"/device:TPU:0": tracing.DeviceTrace(modules, ops, [])}, host=[]))
    ctx = ctx_of(r, steps=3)
    assert reader("flash_fwd_ms")(ctx) == pytest.approx(200e-6)
    assert reader("flash_bwd_ms")(ctx) == pytest.approx(400e-6)
    assert reader("flash_fwd_calls")(ctx) == 2.0


# --- spans of the feed ------------------------------------------------------------

@pytest.fixture
def span_store(monkeypatch):
    """A program's span store holding what `records` says."""
    from dcgan_tpu.utils import profiling

    records = {}

    def put(name, rows):
        records[name] = [profiling.SpanRecord(name, s, d, c)
                         for s, d, c in rows]

    monkeypatch.setattr(profiling, "spans",
                        lambda name=None: list(records.get(name, ())))
    return put


FED = {"feed": "records"}


def test_feed_readers_on_hand_made_spans(span_store):
    # three warm-up batches, then a window of 4 steps that starts at t=10
    # and lasts 2 s; the producer ran ahead of it and goes on after it
    span_store("feed/wait", [(1.0, 0.5, 0), (2.0, 0.1, 1), (3.0, 0.1, 2),
                             (10.0, 0.004, 2), (10.5, 0.002, 2),
                             (11.0, 0.010, 1), (11.5, 0.004, 2)])
    span_store("feed/load", [(0.5, 0.9, 256), (9.0, 0.05, 256),
                             (10.2, 0.030, 256), (10.7, 0.050, 256),
                             (11.9, 0.040, 256), (12.5, 0.9, 256)])
    span_store("feed/h2d", [(0.6, 0.5, 256), (10.25, 0.010, 256),
                            (10.8, 0.020, 256), (12.6, 0.5, 256)])
    ctx = ctx_of(None, traffic=FED, steps=4, window_s=2.0)
    assert reader("feed_wait_share")(ctx) == pytest.approx(
        100 * 0.020 / 2.0)
    assert reader("feed_produce_ms")(ctx) == pytest.approx(
        1e3 * (0.040 + 0.015))


@pytest.mark.parametrize("name", FEED)
def test_feed_readers_return_nothing_where_nothing_was_fed(
        name, span_store, monkeypatch):
    rows = [(float(i), 0.01, 2) for i in range(8)]
    for n in ("feed/wait", "feed/load", "feed/h2d"):
        span_store(n, rows)
    fed = ctx_of(None, traffic=FED, steps=4, window_s=4.0)
    assert reader(name)(fed) is not None
    assert reader(name)(ctx_of(None, steps=4, window_s=4.0)) is None  # resident
    assert reader(name)(dict(fed, steps=0)) is None
    assert reader(name)(dict(fed, steps=9)) is None   # fewer records than steps
    if name == "feed_produce_ms":
        span_store("feed/h2d", [])
        assert reader(name)(fed) is None
    # a parent's program has the module and no span store
    from dcgan_tpu.utils import profiling

    monkeypatch.delattr(profiling, "spans")
    assert reader(name)(fed) is None


def test_feed_readers_after_a_fed_rehearsal(tmp_path):
    """A whole fed run at a tiny size on the CPU leaves the spans the
    readers need: one wait per step of the window, producer records in it."""
    import jax

    from dcgan_tpu.utils import profiling

    root = make_root(str(tmp_path))
    cell = manifest.cell(root, "tiny_dcgan.fed")
    t0 = time.perf_counter()
    line = manifest.driver(root, "train").run(
        cell, root=root, seed=3_000_000_021, seconds=0.3, trace=False,
        t_start=time.time(), devices=jax.devices(),
        cache_root=os.path.join(str(tmp_path), "cache"),
        device_metrics=False)
    assert line["correct"] is True
    steps = line["run"]["steps"]
    waits = [r for r in profiling.spans("feed/wait") if r.start >= t0]
    assert len(waits) == steps + 3     # the warm-up's three and the window's
    ctx = ctx_of(None, traffic=cell.traffic, steps=steps,
                 window_s=line["run"]["window_s"])
    share = reader("feed_wait_share")(ctx)
    produce = reader("feed_produce_ms")(ctx)
    assert 0 <= share <= 100 and produce > 0
    # no number of a CPU run goes under a device metric's name
    assert line["metrics"] == {}


# --- the entries --------------------------------------------------------------------

def test_new_entries_name_their_cells_and_layers():
    bench = manifest.load(REPO)
    by = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    sagan = [n for n, w in cells.items() if w["config"] == "sagan128"]
    for name in FLASH:
        assert by[name]["workloads"] == sagan
        assert by[name]["source"] == "device_trace"
    assert by["flash_fwd_calls"]["layer"] == "step programs"
    assert by["flash_fwd_ms"]["layer"] == by["flash_bwd_ms"]["layer"] \
        == by["flash_attn_roofline"]["layer"] == "kernels"
    four = [n for n, w in cells.items() if w["chips"] == 4]
    assert by["collective_exposed_share"]["workloads"] == four
    assert by["flash_attn_roofline"]["workloads"] == \
        ["sagan128.resident-b256"]       # the accepted entry is as it was
    # the fed cell was measured and left out (PERF.md section 7 row 1b): its
    # readers, its mix and its limits wait as files, with no entry
    fed = {"loader_wait_share", *FEED}
    assert not fed & set(by)
    for name in fed:
        assert callable(reader(name))
    assert not any(manifest.cell(REPO, n, bench).traffic["feed"] == "records"
                   for n in cells)
