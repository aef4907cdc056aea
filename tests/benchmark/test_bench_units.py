"""The benchmark's yardstick: trace reduction, operation counts, the data
set writer, the comparison's arithmetic, and the by-name lookup."""

import json
import math
import os
import re
import sys

import numpy as np
import pytest

from bench_testlib import REPO, make_root

sys.path.insert(0, REPO)

from benchmark import check, flops, manifest, tracing, traffic  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# --- trace reduction ----------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """430 ms of a sagan128 batch-256 trace from one v5e chip (PR 23)."""
    with open(os.path.join(DATA, "trace_sagan128_1chip.json")) as f:
        return tracing.Trace.from_json(json.load(f))


def test_recorded_trace_busy_idle_and_program(recorded):
    r = tracing.reduce(recorded)
    assert r["window_s"] == pytest.approx(0.43)
    assert r["devices"] == 1
    # one whole train step of 377 ms lies in the cut, and the chip is busy
    # for all of the window but the start-up gaps
    name, mod = tracing.step_module(r)
    assert name == "jit_train_step" and mod["count"] == 1
    assert mod["total_s"] == pytest.approx(0.37698, abs=1e-4)
    assert 0.425 < r["busy_s"] < 0.43
    assert sum(s for _, s in r["idle_by_host"]) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-9)


def test_recorded_trace_classes(recorded):
    r = tracing.reduce(recorded)
    # the flash kernels (every tpu_custom_call of sagan128) take 73% of it
    assert r["kind_s"]["pallas"] / r["busy_s"] == pytest.approx(0.73, abs=0.02)
    assert r["kind_s"]["convolution"] > 0.05
    assert r["kind_s"]["collective"] == 0 and r["collective_exposed_s"] == 0
    b = tracing.breakdown(r)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "class:pallas"


@pytest.mark.parametrize("text, kind", [
    ('%jvp__.5 = (f32[256,4096,32]{2,1,0}) custom-call(bf16[256,4096,8] %a), '
     'custom_call_target="tpu_custom_call", operand_layout_constraints={}',
     "pallas"),
    ("%multiply_maximum_fusion.2 = (bf16[256,64,64,64]{0,3,2,1}) fusion("
     "bf16[64] %p), kind=kOutput, calls=%fused_computation.9", "convolution"),
    ("%convolution_add_fusion.10 = bf16[256,4096,8] fusion(bf16[1] %x), "
     "kind=kLoop, calls=%f", "convolution"),
    ("%all-reduce.3 = f32[1024] all-reduce(f32[1024] %g), channel_id=1",
     "collective"),
    ("%ar-start = (f32[8]) all-reduce-start(f32[8] %g), channel_id=2",
     "collective"),
    ("%fusion = (u32[1]) fusion(u32[2] %copy-done), kind=kLoop, calls=%f",
     "other"),
    ("%copy.732 = bf16[256,128,128,3] copy(bf16[256,128,128,3] %c)", "other"),
])
def test_classify(text, kind):
    assert tracing.classify(text) == kind


def test_exposed_collectives_and_idle_attribution():
    """Hand-made: a 100 us window; compute 0-40 and 60-90; an async
    all-reduce in flight 30-70 (20 of it with the core idle); the host in
    `next()` during the 40-60 gap and reading back during 90-100."""
    us = 1e3
    dev = tracing.DeviceTrace(
        modules=[("jit_train_step", "module", 0.0, 90 * us)],
        ops=[("fusion.1", "convolution", 0.0, 40 * us),
             ("fusion.2", "other", 60 * us, 30 * us)],
        async_ops=[("all-reduce-start.1", "collective", 30 * us, 40 * us)])
    trace = tracing.Trace(
        {"/device:TPU:0": dev, "/device:TPU:1": dev},
        [("bench_window", "host", 0.0, 100 * us),
         ("bench_next", "host", 38 * us, 24 * us),
         ("bench_readback", "host", 88 * us, 12 * us)])
    r = tracing.reduce(trace)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(70e-6)
    assert r["collective_s"] == pytest.approx(40e-6)
    assert r["collective_exposed_s"] == pytest.approx(20e-6)
    assert dict(r["idle_by_host"]) == pytest.approx(
        {"in_next": 20e-6, "in_readback": 10e-6})


def test_no_device_operation_is_nothing_to_report():
    trace = tracing.Trace({"/device:TPU:0": tracing.DeviceTrace([], [], [])},
                          [("bench_window", "host", 0.0, 1e6)])
    assert tracing.reduce(trace) is None


def test_interval_arithmetic():
    assert tracing.merge([(5, 7), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 7)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]


# --- operations and bytes from shapes -------------------------------------------

DCGAN128 = dict(output_size=128, base_size=4, gf_dim=64, df_dim=64, c_dim=3,
                z_dim=100, kernel_size=5, attn_res=0, attn_qk_div=8,
                attn_v_div=2)


def test_conv_ops_against_hand_worked_shapes():
    # 8x8 -> 4x4, 5x5 stride 2 SAME: outputs read 4,5,5,3 valid taps a side
    assert flops._taps(8, 5, 2) == 17
    assert flops.conv_ops(8, 256, 512, 5) == 2 * 17 * 17 * 256 * 512
    # 128 -> 64: 64 outputs x 5 taps less 1 at the low and 2 at the high edge
    assert flops._taps(128, 5, 2) == 317
    t = flops.layer_table(DCGAN128)
    assert t["disc"]["conv0"] == 2 * 317 ** 2 * 3 * 64
    assert t["gen"]["proj"] == 2 * 100 * 1024 * 16
    assert t["gen"]["deconv5"] == t["disc"]["conv0"]    # mirror stages


@pytest.mark.parametrize("batch, xla_gflop", [(64, 1056), (256, 4221),
                                              (512, 8440)])
def test_step_ops_at_or_below_xla_count(batch, xla_gflop):
    """XLA's cost analysis of the compiled dcgan128 step for a v5e (ISSUE
    23's table): the count from shapes lies under it, within 1.5%."""
    ops = flops.step_ops(DCGAN128, batch)["total"] / 1e9
    assert 0.985 * xla_gflop < ops <= xla_gflop


def test_attention_ops_and_bytes():
    m = dict(DCGAN128, attn_res=64)
    t = flops.layer_table(m)
    assert t["gen_attn"]["tokens"] == 4096
    assert (t["gen_attn"]["dqk"], t["gen_attn"]["dv"]) == (8, 32)
    assert t["disc_attn"]["scores"] == 2 * 4096 * 4096 * 40
    ops = flops.step_ops(m, 256)
    # forward 1 G + 3 D, backward 1 G + 3 D at twice a forward: 12 forwards
    assert ops["attn_scores"] == 12 * 2 * 4096 ** 2 * 40 * 256
    assert ops["conv"] == flops.step_ops(DCGAN128, 256)["conv"]
    cost = flops.flash_step_cost(m, 256)
    assert cost["ops"] == ops["attn_scores"]
    assert cost["bytes"] == 12 * 4096 * 80 * 2 * 256
    # the operations bound the kernels' roofline, not the bytes
    assert cost["ops"] / 197e12 > 5 * cost["bytes"] / 819e9


@pytest.mark.parametrize("config, batch, total", [
    ("sagan128", 256, 8438050029568), ("dcgan128", 512, 8372064813056)])
def test_step_ops_of_the_shipped_cells_through_their_family(config, batch,
                                                            total):
    """What `step_mfu` divides by the peak, to the last digit as it was
    before the count was looked up by the configuration's family (PR 25)."""
    with open(os.path.join(REPO, "benchmark", "configs", config + ".json")) as f:
        conf = json.load(f)
    fam = manifest.family(REPO, conf)
    assert fam is manifest.family(REPO, {"family": "gan"})   # loaded once
    assert fam.step_ops(conf, batch)["total"] == total
    model = dict(conf["model"], attn_qk_div=conf["attn_qk_div"],
                 attn_v_div=conf["attn_v_div"])
    assert fam.step_ops(conf, batch) == flops.step_ops(model, batch)
    costs = fam.kernel_costs(conf, batch)
    if config == "sagan128":
        assert costs == {"flash_attn": flops.flash_step_cost(model, batch)}
        assert costs["flash_attn"]["ops"] == 4123168604160.0
        # no flash kernel runs where attention is dense
        dense = dict(conf, model=dict(conf["model"], use_pallas=False))
        assert fam.kernel_costs(dense, batch) == {}
    else:
        assert costs == {}


# --- the data set writer ----------------------------------------------------------

def test_records_read_back_by_the_programs_reader(tmp_path):
    from dcgan_tpu.data.example_proto import parse_example
    from dcgan_tpu.data.tfrecord import masked_crc32c, read_tfrecords

    spec = {"count": 10, "shards": 3, "dtype": "uint8", "seed": 5}
    out = traffic.ensure_records(str(tmp_path), spec, 8, 3)
    assert traffic.ensure_records(str(tmp_path), spec, 8, 3) == out  # reused
    imgs = traffic.record_images(spec, 8, 3)
    got = []
    for shard in sorted(p for p in os.listdir(out) if p.endswith(".tfrecord")):
        for rec in read_tfrecords(os.path.join(out, shard), verify_crc=True):
            raw = parse_example(rec)[traffic.FEATURE][0]
            got.append(np.frombuffer(raw, np.uint8).reshape(8, 8, 3))
    assert np.array_equal(np.stack(got), imgs)
    with open(os.path.join(out, "dataset.json")) as f:
        man = json.load(f)
    assert (man["num_examples"], man["num_shards"], man["record_dtype"],
            man["image_size"], man["feature_name"]) == (10, 3, "uint8", 8,
                                                        "image_raw")
    rows = np.frombuffer(b"hello world, crc", np.uint8)[None]
    assert int(traffic.masked_crc32c_rows(rows)[0]) == masked_crc32c(
        b"hello world, crc")
    assert list(traffic.record_ids(traffic.normalize(imgs))) == list(range(10))


# --- the comparison's arithmetic ------------------------------------------------

def test_worst_leaf_gap_and_nought_leaves():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    assert check.worst_leaf_gap(ref, ref) == 0.0
    # the all-but-zero leaf is measured against the median leaf, not itself
    assert check.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 2e-9}, ref) < 1e-8
    assert check.worst_leaf_gap({"a": 1.5, "b": 2.0, "c": 0.0}, ref) == 0.5
    assert check.worst_leaf_gap({"a": 1.0, "b": 2.0}, ref) == math.inf
    assert check.nought_leaves(ref) == ["c"]
    # a state left unchanged reads 1 by this measure
    assert check.worst_leaf_gap(dict.fromkeys(ref, 0.0), ref) == pytest.approx(1)


def test_grad_err_is_of_the_vector_not_of_its_norm():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    same = {"losses": [{"d_loss": 1.0, "g_loss": 1.0}] * 2, "grad": ref,
            "delta": ref, "grad_diff": dict.fromkeys(ref, 0.0),
            "stat": {"m": 2.0}, "stat_diff": {"m": 0.0}}
    assert set(check.training_numbers(same, same).values()) == {0.0}
    # a leaf turned by a right angle keeps its norm: no gap, all error
    turned = dict(same, grad_diff={"a": 0.0, "b": 2.0 * math.sqrt(2), "c": 0.0})
    got = check.training_numbers(turned, same)
    assert got["grad_gap"] == 0.0 and got["grad_err"] == 0.0
    assert got["grad_err_worst"] == pytest.approx(math.sqrt(2))
    moved = check.training_numbers(dict(same, stat_diff={"m": 0.5}), same)
    assert moved["stat_err"] == moved["stat_err_med"] == 0.25
    # the all-but-zero leaf is measured against the median leaf; a leaf
    # that is missing fails; a gradient of nought (state unchanged) reads 1
    assert check.leaf_errors({"a": 0.0, "b": 0.0, "c": 1e-9}, ref)[2] == 1e-9
    assert check.leaf_errors({"a": 0.0}, ref) == [math.inf]
    assert check.leaf_errors(ref, ref)[:2] == [1.0, 1.0]


def test_judge_needs_every_limited_number():
    limits = {"loss_gap": 0.1, "grad_gap": 0.1}
    ok = check.judge({"loss_gap": 0.01, "grad_gap": 0.1, "extra": 9.0}, limits)
    assert ok["correct"] and set(ok["compared"]) == set(limits)
    assert not check.judge({"loss_gap": 0.01}, limits)["correct"]
    assert not check.judge({"loss_gap": 0.01, "grad_gap": math.nan},
                           limits)["correct"]
    assert not check.judge({"loss_gap": 0.2, "grad_gap": 0.0}, limits)["correct"]
    assert not check.judge({"loss_gap": 0.0}, {})["correct"]


@pytest.mark.parametrize("control, as_due", [(0.2, True), (0.05, False)])
def test_readings_judge_every_variant_with_the_cells_limits(control, as_due):
    """`readings.py` holds sound runs, control and faults to the committed
    limits: a control that passes is reported, not overlooked."""
    from benchmark import readings

    limits = {"stat_err": 0.1, "grad_gap": 0.1}
    rows = {"7": {"stated": {"stat_err": 0.01, "grad_gap": 0.04, "loss_gap": 9.0},
                  "reference_bf16": {"stat_err": 0.005, "grad_gap": 0.01},
                  "reference_fp8": {"stat_err": control, "grad_gap": 0.05},
                  "half_batch": {"stat_err": 0.02, "grad_gap": 0.3},
                  "raw": {}},
            "8": {"stated": {"stat_err": 0.02, "grad_gap": 0.03, "loss_gap": 1.0},
                  "raw": {}}}
    gan = manifest.family(REPO, {"family": "gan"})
    must_pass = readings.must_pass_of(gan.variants({}, 8, 4))
    assert must_pass == ("reference_bf16",)
    got = readings.judge_rows(rows, limits, check, must_pass)
    assert got["all_as_due"] is as_due
    assert got["lower"]["stat_err"] == 0.02 and got["lower"]["loss_gap"] == 9.0
    assert got["reference_fp8"]["stat_err"] == control
    assert got["correct"]["stated"] == {"7": True, "8": True}
    assert got["correct"]["reference_fp8"] == {"7": not as_due}
    assert got["correct"]["half_batch"] == {"7": False}


# --- BENCHMARK.json and the lookup by name ------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_keeps_to_the_contract():
    bench = manifest.load(REPO)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(REPO, "benchmark", "layer_metrics",
                                           m["name"] + ".py"))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = manifest.cell(REPO, w["name"], bench)      # every file is there
        assert cell.traffic["chips"] == w["chips"]
        assert any(m["name"] == "step_mfu" for m in cell.per_layer)
    assert len(json.dumps(bench)) < 64 * 1024


def _departures(cfg, preset) -> set:
    """The keys in which a cell's TrainConfig departs from its preset,
    beside what the traffic mix sets (batch, mesh, backend)."""
    import dataclasses

    out = {f.name for f in dataclasses.fields(cfg.model)
           if getattr(cfg.model, f.name) != getattr(preset.model, f.name)}
    lr = lambda c, net: getattr(c, net) or c.learning_rate
    for f in dataclasses.fields(cfg):
        if f.name in ("model", "batch_size", "mesh", "backend"):
            continue
        a, b = getattr(cfg, f.name), getattr(preset, f.name)
        if f.name in ("d_learning_rate", "g_learning_rate"):
            a, b = lr(cfg, f.name), lr(preset, f.name)
        if a != b:
            out.add(f.name)
    return out


@pytest.mark.parametrize("config", [c["name"] for c in
                                    manifest.load(REPO)["configs"]])
def test_shipped_configs_are_the_presets_as_shipped(config):
    """A configuration file differs from its preset in exactly the keys its
    `reduced` lists (none for the two shipped): applying it to the preset
    changes nothing else but batch, mesh and backend."""
    from dcgan_tpu.presets import get_preset

    bench = manifest.load(REPO)
    entry = next(c for c in bench["configs"] if c["name"] == config)
    train = manifest.driver(REPO, "train")
    cells = [w["name"] for w in bench["workloads"] if w["config"] == config]
    assert cells
    for name in cells:
        cell = manifest.cell(REPO, name)
        assert cell.config["reduced"] == entry["reduced"]
        cfg = train.program_config(cell)
        preset = get_preset(cell.config["preset"])
        assert _departures(cfg, preset) == set(entry["reduced"])
        assert cfg.batch_size == (cell.traffic["per_chip_batch"]
                                  * cell.traffic["chips"])


def test_a_cut_configuration_departs_in_the_keys_it_lists(tmp_path):
    """The rule on a file that IS cut: the tiny configurations of the tests
    depart from their presets in the keys `tiny_config` changes."""
    from dcgan_tpu.presets import get_preset

    root = make_root(str(tmp_path))
    cell = manifest.cell(root, "tiny_sagan.resident")
    cfg = manifest.driver(root, "train").program_config(cell)
    assert _departures(cfg, get_preset(cell.config["preset"])) == {
        "output_size", "gf_dim", "df_dim", "attn_res", "compute_dtype"}


def test_a_cell_a_mix_and_a_metric_are_added_as_new_files(tmp_path):
    root = make_root(str(tmp_path))
    before = _shipped_files()
    # a later PR's per-layer metric: one new file and one new entry
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "steps_per_dispatch.train.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['steps'] / len("
                "ctx['spans']['step']) if ctx['spans']['step'] else None\n")
    bench = manifest.load(root)
    bench["per_layer"].append(
        {"name": "steps_per_dispatch.train", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "trainer loop",
         "moves": "train_images_per_s", "workloads": ["tiny_dcgan.fed"]})
    cell = manifest.cell(root, "tiny_dcgan.fed", bench)
    assert cell.config["model"]["output_size"] == 16
    assert cell.traffic["records"]["count"] == 64 and cell.limits["feed_gap"]
    mine = [m["name"] for m in cell.per_layer]
    assert "steps_per_dispatch.train" in mine and "loader_wait_share" not in mine
    read = manifest.layer_metric_reader(root, "steps_per_dispatch.train")
    assert read({"steps": 6, "spans": {"step": [0.1] * 6}}) == 1.0
    assert read({"steps": 0, "spans": {"step": []}}) is None
    # the shipped files were copied, not edited
    for rel, content in before.items():
        assert open(os.path.join(root, rel), "rb").read() == content
    with pytest.raises(manifest.ManifestError, match="unknown workload"):
        manifest.cell(root, "no_such.cell", bench)


TOY_FAMILY = '''"""A later PR's family, as one new file: the `gan` family's functions with
an operation count and one leaf rule of its own."""
import os

from benchmark import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_gan = manifest.family(_ROOT, {"family": "gan"})
globals().update({name: getattr(_gan, name) for name in manifest.FAMILY_API})


def step_ops(config, global_batch):
    return {"total": 1000.0 * global_batch}


def draw_leaf(path, shape, key):
    if path == "params/disc/head/b":
        import jax.numpy as jnp
        return jnp.full(shape, 0.25, jnp.float32)
    return _gan.draw_leaf(path, shape, key)
'''


def _shipped_files():
    out = {}
    for d, _, files in os.walk(os.path.join(REPO, "benchmark")):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, REPO)] = fh.read()
    return out


def test_a_family_is_added_as_new_files(tmp_path):
    """A configuration of a new model family lands as `families/<family>.py`,
    a configuration file that names it, a cell and its limits: the driver,
    the readers and the check run it without an edit."""
    import time

    import jax
    import numpy as np

    from bench_testlib import TIGHT, tiny_config

    root = make_root(str(tmp_path))
    before = _shipped_files()
    with open(os.path.join(root, "benchmark", "families", "toy.py"), "w") as f:
        f.write(TOY_FAMILY)
    conf = dict(tiny_config("dcgan128", 0), name="toy_dcgan", family="toy")
    with open(os.path.join(root, "benchmark", "configs", "toy_dcgan.json"),
              "w") as f:
        json.dump(conf, f)
    with open(os.path.join(root, "benchmark", "limits",
                           "toy_dcgan.resident.json"), "w") as f:
        json.dump(TIGHT, f)
    bench = manifest.load(root)
    bench["configs"].append({"name": "toy_dcgan", "source": "test",
                             "file": "benchmark/configs/toy_dcgan.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy_dcgan.resident",
                               "config": "toy_dcgan",
                               "traffic": "tiny-resident", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = manifest.cell(root, "toy_dcgan.resident")
    toy = manifest.family(root, cell.config)
    gan = manifest.family(root, {"family": "gan"})
    assert toy.__file__.endswith("families/toy.py") and toy is not gan
    assert toy.numbers is gan.numbers and toy.step_ops is not gan.step_ops
    # the whole run on the CPU, through the one training driver
    train = manifest.driver(root, "train")
    line = train.run(cell, seed=3_000_000_023, seconds=0.3,
                     trace=False, t_start=time.time(), devices=jax.devices(),
                     cache_root=os.path.join(str(tmp_path), "cache"),
                     device_metrics=False)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["check"]) == set(TIGHT)
    # program and reference both started from the toy's own leaf rule ...
    inp = train.build_program(cell, jax.devices()).inputs
    assert inp.family is toy
    drawn = inp.draw(jax.random.key(1))["params"]["disc"]["head"]
    assert np.all(np.asarray(drawn["b"]) == 0.25)
    assert np.std(np.asarray(drawn["w"])) == pytest.approx(0.02, rel=0.2)
    # ... and the share of the peak is worked out from the toy's count
    ctx = {"family": toy, "config": cell.config, "global_batch": 8, "chips": 1,
           "steps": 5, "window_s": 2.0, "peaks": {"bf16_flops_per_s": 1e6}}
    assert manifest.layer_metric_reader(root, "step_mfu")(ctx) == \
        pytest.approx(100.0 * 8000.0 * 5 / 2.0 / 1e6)
    assert manifest.layer_metric_reader(root, "step_mfu")(
        dict(ctx, family=gan)) != manifest.layer_metric_reader(
            root, "step_mfu")(ctx)
    with pytest.raises(manifest.ManifestError, match="no module yet"):
        manifest.family(root, dict(conf, family="no_such_family"))
    with pytest.raises(manifest.ManifestError, match="names no `family`"):
        manifest.family(root, {k: v for k, v in conf.items()
                               if k != "family"})
    with open(os.path.join(root, "benchmark", "families", "half.py"),
              "w") as f:
        f.write("def step_ops(config, global_batch):\n    return {}\n")
    with pytest.raises(manifest.ManifestError, match="lacks"):
        manifest.family(root, dict(conf, family="half"))
    # every shipped file is there byte for byte
    for rel, content in before.items():
        with open(os.path.join(root, rel), "rb") as f:
            assert f.read() == content
    assert _shipped_files() == before


def test_family_bound_code_is_reached_through_the_lookup_only():
    """The driver, `readings.py` and the readers import neither the GAN's
    reference nor its operation count, and name no leaf rule: what depends
    on the model family goes through `manifest.family`."""
    bench_dir = os.path.join(REPO, "benchmark")
    files = [os.path.join(bench_dir, "drivers", "train.py"),
             os.path.join(bench_dir, "readings.py")]
    files += [os.path.join(bench_dir, "layer_metrics", f)
              for f in sorted(os.listdir(os.path.join(bench_dir,
                                                      "layer_metrics")))
              if f.endswith(".py")]
    assert len(files) > 10
    banned = re.compile(
        r"^\s*(from\s+benchmark(\.\w+)*\s+import\s+.*\b(reference|flops)\b"
        r"|import\s+benchmark\.(reference|flops)\b"
        r"|from\s+benchmark\.(reference|flops)\s+import\b"
        r"|from\s+benchmark\.families\b|import\s+benchmark\.families\b)",
        re.M)
    for path in files:
        with open(path) as f:
            text = f.read()
        assert not banned.search(text), path
        assert "draw_leaf(" not in text and "_leaf(" not in text, path
    with open(files[0]) as f:
        assert "manifest.family(" in f.read()


def test_a_serving_mix_is_data_the_harness_refuses_to_run(tmp_path):
    root = make_root(str(tmp_path))
    mix = {"kind": "serve", "arrivals": "poisson", "rate_per_s": 400,
           "burst": {"factor": 4, "from": 0.4, "to": 0.6},
           "request_images": [1, 16], "buckets": [1, 4, 16, 64, 256, 1024],
           "chips": 1}
    with open(os.path.join(root, "benchmark", "traffic", "open-loop.json"),
              "w") as f:
        json.dump(mix, f)
    with pytest.raises(manifest.ManifestError, match="no driver module yet"):
        manifest.driver(root, mix["kind"])
    with pytest.raises(ValueError, match="lacks"):
        traffic.check_mix({"kind": "train", "feed": "records", "chips": 1})
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "resident-b256.json")) as f:
        shipped = json.load(f)
    traffic.check_mix(shipped)
    for key in ("in_flight", "resident_batches"):    # no default in code
        with pytest.raises(ValueError, match=key):
            traffic.check_mix({k: v for k, v in shipped.items() if k != key})


def test_unknown_device_has_no_peaks():
    assert manifest.peaks(REPO, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(manifest.ManifestError, match="not in peaks.json"):
        manifest.peaks(REPO, "cpu")


def test_readers_return_nothing_where_there_is_nothing_to_read():
    ctx = {"reduced": None, "spans": {"next": [], "step": [], "readback": []},
           "window_s": 1.0, "steps": 0, "global_batch": 8, "chips": 1,
           "config": {"model": dict(DCGAN128), "attn_qk_div": 8,
                      "attn_v_div": 2},
           "family": manifest.family(REPO, {"family": "gan"}),
           "traffic": {"feed": "resident"}, "peaks": None,
           "memory_peak_bytes": 0}
    for m in manifest.load(REPO)["per_layer"]:
        assert manifest.layer_metric_reader(REPO, m["name"])(ctx) is None
