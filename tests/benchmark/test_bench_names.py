"""The per-layer readers that read the program's own names and spans
(ISSUE 24): `flash_fwd_ms`, `flash_bwd_ms`, `flash_fwd_calls` from the
kernel names in the trace reduction, `feed_wait_share` and
`feed_produce_ms` from the program's span store; and what the reduction
keeps of the program's scopes and host spans (ISSUE 26): `scope_s` from the
`op_name` of each operation, idle gaps put down to `feed/*` and `train/*`."""

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


def shipped_config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def ctx_of(reduced, **over):
    ctx = {"reduced": reduced, "spans": {"next": [], "step": [],
                                         "readback": []},
           "window_s": 0.43, "steps": 1, "global_batch": 256, "chips": 1,
           "config": {}, "family": manifest.family(REPO, {"family": "gan"}),
           "traffic": {"feed": "resident"}, "peaks": None,
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


# --- scopes: the trace recorded from the PR 25 step -------------------------------

@pytest.fixture(scope="module")
def scoped():
    """The first whole step (260 ms) of a sagan128 batch-256 window on one
    v5e chip, recorded by PR 26 from the PR 25 step with each operation's
    scope (`tracing.load_xplane`, then `Trace.to_json`)."""
    with open(os.path.join(DATA, "trace_sagan128_1chip_scopes.json")) as f:
        return tracing.reduce(tracing.Trace.from_json(json.load(f)))


def test_scope_seconds_on_the_recorded_trace(scoped):
    r = scoped
    name, step = tracing.step_module(r)
    assert name == "jit_train_step" and step["count"] == 1
    assert step["total_s"] == pytest.approx(0.258197, abs=1e-6)
    # the two halves of the step hold all of it but the weight average,
    # the key's split and the copies that carry no op_name (PERF.md 3)
    halves = r["scope_s"]["d_step"] + r["scope_s"]["g_step"]
    assert 0.9 * step["total_s"] <= halves <= step["total_s"]
    assert r["scope_s"]["d_step"] > r["scope_s"]["g_step"] > 0.1
    # by path prefix: a scope holds what its children hold
    assert r["scope_s"]["d_step"] >= r["scope_s"]["d_step/loss"] \
        >= r["scope_s"]["d_step/loss/disc"] \
        >= r["scope_s"]["d_step/loss/disc/attn"] > 0
    # under `attn`, wherever it lies: the kernels and the projections,
    # transposes and relayouts around them
    ctx = ctx_of(r, window_s=r["window_s"], config=shipped_config("sagan128"))
    fwd, bwd = reader("flash_fwd_ms")(ctx), reader("flash_bwd_ms")(ctx)
    assert fwd == pytest.approx(83.006, abs=0.01)
    assert bwd == pytest.approx(86.27, abs=0.01)
    attn = tracing.under(r, "attn")
    assert attn == sum(r["scope_s"][p] for p in (
        "d_step/loss/disc/attn", "d_step/loss/gen/attn",
        "g_step/loss/disc/attn", "g_step/loss/gen/attn"))
    assert 1e-3 * (fwd + bwd) <= attn <= halves
    # a scope is no kernel name: the kernels' `pallas_call` scope also holds
    # 2.8 ms of XLA `reduce` instructions that kept the call's op_name, so
    # a kernel's time is read by instruction name and a scope's by scope
    calls = sum(s for p, s in r["scope_s"].items()
                if p.endswith(("/flash_fwd/pallas_call",
                               "/flash_dq_dkv/pallas_call")))
    assert 1e-3 * (fwd + bwd) < calls < 1.03e-3 * (fwd + bwd)
    assert calls == pytest.approx(tracing.under(r, "pallas_call"))
    assert tracing.under(r, "no_such_scope") == 0.0


def test_flash_roofline_reads_its_kernels_by_name(scoped):
    """Equal to the Pallas class's time where every custom call is a flash
    kernel (12.364 in PERF.md section 5), and blind to another kernel's."""
    peaks = manifest.peaks(REPO, "TPU v5 lite")
    ctx = ctx_of(scoped, window_s=scoped["window_s"], peaks=peaks,
                 config=shipped_config("sagan128"))
    got = reader("flash_attn_roofline")(ctx)
    cost = ctx["family"].kernel_costs(ctx["config"], 256)["flash_attn"]
    least = cost["ops"] / peaks["bf16_flops_per_s"]
    assert got == pytest.approx(100 * least / scoped["kind_s"]["pallas"],
                                rel=1e-9)
    assert got == pytest.approx(12.364, abs=2e-3)
    # four chips: one chip's kernels against one chip's share of the batch
    assert reader("flash_attn_roofline")(
        dict(ctx, chips=4, global_batch=1024)) == pytest.approx(got)
    other = dict(scoped, ops=scoped["ops"] + [("pallas:bn_apply.3", 0.05)])
    assert reader("flash_attn_roofline")(dict(ctx, reduced=other)) == got
    # nothing to read without the peaks, in a family that counts no flash
    # kernel for the configuration, or with no kernel of that name
    assert reader("flash_attn_roofline")(dict(ctx, peaks=None)) is None
    assert reader("flash_attn_roofline")(
        dict(ctx, config=shipped_config("dcgan128"))) is None
    unnamed = tracing.reduce(recorded_trace(named=False))
    assert reader("flash_attn_roofline")(dict(ctx, reduced=unnamed)) is None


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/d_step/loss/jvp(disc)/conv3/conv_general_dilated:",
     "d_step/loss/disc/conv3/conv_general_dilated"),
    ("jit(train_step)/d_step/loss/transpose(jvp(disc))/attn/dot_general:",
     "d_step/loss/disc/attn/dot_general"),
    # the backward pass repeats the path it was traced under
    ("jit(train_step)/d_step/loss/transpose(d_step)/loss/jvp(disc)/attn/"
     "flash_dq_dkv/pallas_call:",
     "d_step/loss/disc/attn/flash_dq_dkv/pallas_call"),
    ("jit(train_step)/g_step/loss/jvp(gen)/attn/shard_map/flash_fwd/"
     "pallas_call", "g_step/loss/gen/attn/shard_map/flash_fwd/pallas_call"),
    ("jit(train_step)/jit(_uniform)/while", "while"),
    ("jit(train_step)/ema/mul:", "ema/mul"),
    ("state['params']['gen']['deconv1']['w']", ""),     # a parameter's name
    ("", ""),
])
def test_scope_of_an_op_name(op_name, scope):
    assert tracing.scope_of(op_name) == scope


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of (number, int | bytes | str) fields."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def test_op_names_from_the_metadata_of_a_hand_made_xplane(tmp_path):
    """The wire reader against an XSpace written field by field: the
    `tf_op` stat of an event's metadata, as a string and as a reference to
    the plane's stat names; events without one and host planes are left."""
    stat_meta = [_msg((1, k), (2, _msg((1, k), (2, n))))
                 for k, n in ((7, "hlo_category"), (9, "tf_op"),
                              (300, "jit(f)/g_step/adam/mul:"))]

    def event_meta(key, name, *stats):
        return _msg((1, key), (2, _msg((1, key), (2, name),
                                       *[(5, st) for st in stats])))

    plane = _msg(
        (1, 0), (2, "/device:TPU:0"),
        (4, event_meta(1, "%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop",
                       _msg((1, 7), (5, "loop fusion")),
                       _msg((1, 9), (5, "jit(f)/d_step/loss/jvp(disc)/mul:")))),
        (4, event_meta(2, "%fusion.2 = f32[8] fusion(f32[8] %q), kind=kLoop",
                       _msg((1, 9), (7, 300)))),
        (4, event_meta(4000, "%copy.3 = f32[8] copy(f32[8] %r)",
                       _msg((1, 7), (5, "copy")))),
        *[(5, m) for m in stat_meta])
    host = _msg((2, "/host:CPU"), (4, event_meta(1, "bench_step")))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_msg((1, plane), (1, host)))
    got = tracing.op_names(str(path))
    assert got == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop":
            "jit(f)/d_step/loss/jvp(disc)/mul:",
        "%fusion.2 = f32[8] fusion(f32[8] %q), kind=kLoop":
            "jit(f)/g_step/adam/mul:"}}
    assert [tracing.scope_of(v) for v in got["/device:TPU:0"].values()] == [
        "d_step/loss/disc/mul", "g_step/adam/mul"]


def test_idle_gaps_are_put_down_to_the_programs_spans():
    """Host events named `feed/*` and `train/*` lie beside the harness's
    own: of two spans that cover a gap alike the inner one names it."""
    us = 1e3
    dev = tracing.DeviceTrace(
        modules=[("jit_train_step", "module", 0.0, 100 * us)],
        ops=[("fusion.1", "convolution", 0.0, 40 * us, "d_step/loss/mul"),
             ("fusion.2", "other", 60 * us, 20 * us, ""),
             ("fusion.3", "other", 90 * us, 10 * us)], async_ops=[])
    trace = tracing.Trace({"/device:TPU:0": dev}, [
        ("bench_window", "host", 0.0, 100 * us),
        ("bench_next", "host", 38 * us, 24 * us),
        ("feed/wait", "host", 39 * us, 22 * us),
        ("train/consume", "host", 79 * us, 12 * us)])
    r = tracing.reduce(trace)
    assert dict(r["idle_by_host"]) == pytest.approx(
        {"in_feed/wait": 20e-6, "in_train/consume": 10e-6})
    assert r["scope_s"] == pytest.approx(
        {"d_step": 40e-6, "d_step/loss": 40e-6, "d_step/loss/mul": 40e-6})
    # a trace without scopes (the PR 23 fixture) has none to give
    assert tracing.reduce(recorded_trace(named=True))["scope_s"] == {}
    back = tracing.Trace.from_json(json.loads(json.dumps(trace.to_json())))
    assert back == trace


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
        cell, seed=3_000_000_021, seconds=0.3, trace=False,
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
    # since PR 26 the roofline is read by kernel name, on the first device
    # against one chip's share of the batch: every sagan128 cell reports it
    assert by["flash_attn_roofline"]["workloads"] == sagan
    # the fed cell was measured and left out (PERF.md section 7 row 1b): its
    # readers, its mix and its limits wait as files, with no entry
    fed = {"loader_wait_share", *FEED}
    assert not fed & set(by)
    for name in fed:
        assert callable(reader(name))
    assert not any(manifest.cell(REPO, n, bench).traffic["feed"] == "records"
                   for n in cells)
