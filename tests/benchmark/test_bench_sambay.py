"""The `sambay` family (`benchmark/families/sambay.py`) and its shipped
configuration: the family's surface whole, the yardstick against hand
counts, the file against its preset, the CPU rehearsal of a whole run at a
tiny size to `correct: true` with every kernel in interpret mode, the
control and every planted fault to `correct: false` through the cell's own
limits (each moving the number meant for it), and the eight readers the
cell adds."""

import dataclasses
import json
import math
import os
import sys
import time

import pytest

from bench_testlib import REPO, make_root

sys.path.insert(0, REPO)

from benchmark import check, manifest  # noqa: E402

CELL = "tiny_sambay.resident"
SHIPPED_CELL = "phi-4-mini-flash.resident-b1-s8192"
KINDS = ["mamba", "attn_win", "mamba", "attn_full", "gmu", "attn_cross"]
TINY = dict(vocab_size=64, hidden_size=64, num_hidden_layers=6,
            layer_types=KINDS, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, sliding_window=8, mamba_dt_rank=0,
            seq_len=32, compute_dtype="float32")
LIMITS = {"loss_gap": 1e-5, "loss2_gap": 1e-5, "mem_gap": 1e-5,
          "grad_gap": 1e-4, "delta_gap": 1e-4, "grad_err": 1e-4,
          "grad_err_worst": 1e-3}
NEW_METRICS = ("ssm_scan_ms", "ssm_scan_roofline", "window_flash_roofline",
               "hybrid_flash_roofline", "mamba_ms", "diff_attn_ms", "gmu_ms",
               "mlp_ms")


def shipped():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "phi-4-mini-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary root with the shipped configuration cut to a size the
    CPU runs, ADDED as new files and entries."""
    root = make_root(str(tmp_path_factory.mktemp("bench")))
    conf = shipped()
    conf["model"].update(TINY)
    conf["seq_len"] = TINY["seq_len"]
    path = "benchmark/configs/tiny_sambay.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump(conf, f)
    mix = {"kind": "train", "feed": "resident", "per_chip_batch": 2,
           "chips": 1, "mesh": {"data": 1, "model": 1}, "backend": "gspmd",
           "resident_batches": 2, "in_flight": 2}
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-sambay-ids.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark", "limits", CELL + ".json"),
              "w") as f:
        json.dump(LIMITS, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_sambay", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_sambay",
                               "traffic": "tiny-sambay-ids", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_family_has_the_whole_surface():
    fam = manifest.family(REPO, shipped())
    for name in manifest.FAMILY_API:
        assert callable(getattr(fam, name)), name
    assert fam.batch_shape(shipped(), 1) == (1, 8192)
    assert set(fam.variants(shipped(), 1, 1)) == {
        "reference_fp8", "reference_bf16", "no_window", "second_map_dropped",
        "m_detached", "kv_detached", "dt_without_softplus"}


def test_step_ops_and_kernel_costs_against_hand_counts():
    """37.6 TFLOP a step of one 8,192-token row, by hand: per token and
    layer 6 x 2560 x 10240 in the SwiGLU, (2 x 2560 x 10240 + 5120 x 192 +
    160 x 5120) in a Mamba layer's projections, 2560 x (5120 + 2560) in a
    self-attention layer's, 2 x 2560^2 in the cross layer's, 2 x 2560 x
    5120 in the gated memory unit; 20 pairs x 2 maps x 3 x 64 wide over a
    triangle of 33,558,528 pairs of positions or a band of 4,063,488;
    forward and backward 3 x."""
    conf = shipped()
    fam = manifest.family(REPO, conf)
    ops = fam.step_ops(conf, 1)
    s, three = 8192, 3.0
    assert ops["total"] == sum(v for k, v in ops.items() if k != "total")
    assert ops["mlp"] == three * 2 * 6 * s * 3 * 2560 * 10240
    assert ops["mamba_proj"] == three * 2 * 2 * s * (
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    assert ops["attn_proj"] == three * 2 * s * 2560 * (2 * 7680 + 2 * 2560)
    assert ops["gmu"] == three * 2 * s * 2 * 2560 * 5120
    assert ops["head"] == three * 2 * s * 2560 * 25008
    triangle, band = s * (s + 1) // 2, 512 * 513 // 2 + (s - 512) * 512
    assert (triangle, band) == (33558528, 4063488)
    assert ops["scores_full"] == three * 2 * 20 * 2 * 2 * 192 * triangle
    assert ops["scores_window"] == three * 20 * 2 * 2 * 192 * band
    assert ops["scan"] == three * 2 * s * 5120 * (7 * 16 + 3)
    assert round(ops["total"] / 1e12, 1) == 37.6
    trunk = ops["mlp"] + ops["mamba_proj"] + ops["attn_proj"] + ops["gmu"]
    assert round(trunk / 1e12, 1) == 31.1 and round(ops["mlp"] / trunk, 2) \
        == 0.75
    assert fam.step_ops(conf, 2)["total"] == 2 * ops["total"]
    costs = fam.kernel_costs(conf, 1)
    assert set(costs) == {"causal_flash", "window_flash", "ssm_scan"}
    assert costs["causal_flash"]["ops"] == ops["scores_full"] \
        + ops["scores_window"]
    assert costs["window_flash"]["ops"] == ops["scores_window"]
    # q 64, k 64, v 128, o 128 a row of 40 folded rows, and their gradients,
    # bfloat16; three attention layers, one of them windowed
    layer = 2 * 40 * s * 384 * 2
    assert costs["causal_flash"]["bytes"] == 3 * layer
    assert costs["window_flash"]["bytes"] == layer
    # u, dt, y 5120 wide and B, C 16 wide, and their gradients, float32
    assert costs["ssm_scan"]["bytes"] == 2 * 2 * s * (3 * 5120 + 32) * 4
    assert costs["ssm_scan"]["ops"] == ops["scan"]
    # operations bound the attention (16.6 ms against 1.8), bytes the scan
    # (2.5 ms against 0.15): neither peak is the scan's (VPU work)
    flash, scan = costs["causal_flash"], costs["ssm_scan"]
    assert flash["ops"] / 197e12 > 5 * flash["bytes"] / 819e9
    assert scan["bytes"] / 819e9 > 10 * scan["ops"] / 197e12


def test_the_file_differs_from_its_preset_in_exactly_the_keys_of_reduced():
    """Every number of the catalog row as published but the depth and the
    vocabulary; the layout beside them; `reduced` is exactly those three, in
    the file and in `BENCHMARK.json`; the file states source, `published`,
    `deployment`, `held`, `assumed`, and its counts are the program's."""
    conf = shipped()
    m = conf["model"]
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_hidden_layers": 32, "num_key_value_heads": 20,
               "resid_pdrop": 0, "sliding_window": 512,
               "tie_word_embeddings": True, "mlp_bias": False,
               "lm_head_bias": False, "vocab_size": 200064}
    for key, value in catalog.items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
            assert m.get(key, value) == value, key
    assert conf["reduced"] == ["num_hidden_layers", "layer_types",
                               "vocab_size"]
    assert conf["num_hidden_layers"] == m["num_hidden_layers"] == 6
    assert conf["vocab_size"] == m["vocab_size"] == 200064 // 8
    assert conf["layer_types"] == m["layer_types"] == KINDS
    assert conf["seq_len"] == m["seq_len"] == 8192
    entry = manifest._by_name(manifest.load(REPO)["configs"],
                              "phi-4-mini-flash", "config")
    assert entry["reduced"] == conf["reduced"]
    assert entry["source"] in conf["source"] and len(conf["source"]) <= 200
    for key in ("published", "deployment", "held", "assumed"):
        assert conf[key], key
    assert "NOT six consecutive published layers" in conf["deployment"]
    for said in ("d_state 16", "dt_rank ceil(2560/16) = 160",
                 "0.356, 0.556, 0.666", "BEFORE the gate",
                 "no positional encoding", "log of 1..16"):
        assert any(said in a for a in conf["assumed"]), said

    from dcgan_tpu.presets import get_preset

    preset = get_preset(conf["preset"])
    as_file = lambda v: list(v) if isinstance(v, tuple) else v
    differs = {k for k, v in m.items()
               if as_file(getattr(preset.model, k)) != v}
    assert differs == set(conf["reduced"])
    assert dataclasses.asdict(preset.model).keys() == m.keys()
    for key in ("loss", "beta1", "learning_rate"):
        assert conf["train"][key] == getattr(preset, key), key

    # the file's own arithmetic, recounted from the program's shapes
    import jax

    from dcgan_tpu.models.sambay import sambay_init

    count = lambda cfg: sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda k: sambay_init(k, cfg), jax.random.key(0))))
    held = count(dataclasses.replace(preset.model, **m))
    assert held == conf["held"]["parameters"] == 697094272
    assert conf["held"]["state_bytes_at_16_per_parameter"] == 16 * held
    assert count(preset.model) == conf["published"]["parameters"] \
        == 3852562944
    per = manifest.family(REPO, conf).reference.parameter_count(m)
    assert (per["mamba"], per["attn_win"], per["gmu"], per["attn_cross"]) \
        == tuple(conf["held"][k] for k in (
            "mamba_layer", "attention_layer", "gmu_layer", "cross_layer"))
    assert per["layers"] == conf["held"]["layers"] == 633068672
    assert per["embedding"] == conf["held"]["embedding"] == 64020480


def test_the_cell_is_added_as_entries():
    bench = manifest.load(REPO)
    cell = manifest.cell(REPO, SHIPPED_CELL, bench)
    assert cell.chips == 1 and cell.traffic["per_chip_batch"] == 1
    assert cell.traffic_name == "resident-b1-s8192"
    assert {m["name"] for m in cell.end_to_end} == {"train_images_per_s",
                                                    "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert reported == set(NEW_METRICS) | {
        "dispatch_ms", "step_mfu", "device_step_ms", "device_idle_share",
        "hbm_peak_mib"}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [SHIPPED_CELL]
    assert bench["workloads"][-1]["name"] == SHIPPED_CELL
    assert [m["name"] for m in bench["per_layer"][-8:]] == list(NEW_METRICS)
    assert set(cell.limits) == {"loss_gap", "mem_gap", "grad_gap",
                                "delta_gap", "grad_err"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_reference_imports_nothing_of_the_program():
    import re

    for name in ("sambay.py", "sambay_reference.py"):
        with open(os.path.join(REPO, "benchmark", "families", name)) as f:
            assert not re.search(r"^\s*(from|import)\s+dcgan_tpu", f.read(),
                                 re.M), name


def rehearse(root, tmp):
    import jax

    cell = manifest.cell(root, CELL)
    return manifest.driver(root, "train").run(
        cell, seed=3_000_000_019, seconds=0.3, trace=False,
        t_start=time.time(), devices=jax.devices(),
        cache_root=os.path.join(str(tmp), "cache"), device_metrics=False)


def test_rehearsal_to_correct(root, tmp_path):
    """A whole run on the CPU: program (scan and flash kernels in interpret
    mode, float32) against the reference computed in blocks to 1e-5 on the
    losses and the memory's per-channel mean, 1e-4 on gradients and the
    two-step change."""
    line = json.loads(json.dumps(rehearse(root, tmp_path)))
    assert line["correct"] is True and line["failed"] == 0, line["check"]
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert set(line["check"]) == set(LIMITS)
    for c in line["check"].values():
        assert 0 <= c["value"] <= c["limit"]


@pytest.fixture(scope="module")
def sound(root):
    """Reference readings of the tiny cell, and the inputs to make more."""
    import jax

    cell = manifest.cell(root, CELL)
    train = manifest.driver(root, "train")
    prog = train.build_program(cell, jax.devices())
    seed = 3_000_000_033
    batches = train.resident_batches(cell, prog.inputs, seed)[:2]
    ref = train.reference_readings(cell, prog.inputs, seed, batches)
    return cell, train, prog.inputs, seed, batches, ref


def test_the_drawn_model_is_in_a_regime_that_reads(sound):
    """The loss sits measurably above ln(vocabulary) and far under the
    largest logit's reach, every channel of the memory is alive, the keys'
    bias is read as a part and has no gradient, and nothing else is
    nought."""
    _, _, _, _, _, ref = sound
    loss = ref["losses"][0]["loss"]
    assert math.log(TINY["vocab_size"]) + 0.05 < loss < 3 * math.log(
        TINY["vocab_size"])
    assert float(ref["mem_abs"].min()) > 0
    nought = check.nought_leaves(ref["grad"])
    assert sorted(nought) == ["block1/mixer/qkv_proj/b:k",
                              "block3/mixer/qkv_proj/b:k"]
    assert "block1/mixer/qkv_proj/b:q" in ref["delta"]
    assert "block1/mixer/qkv_proj/b" in ref["gvec"]


# variant -> the number meant for it
MEANT = {"reference_fp8": "grad_err", "no_window": "loss_gap",
         "second_map_dropped": "loss_gap", "m_detached": "grad_gap",
         "kv_detached": "grad_gap", "dt_without_softplus": "mem_gap"}


@pytest.mark.parametrize("variant", sorted(MEANT))
def test_control_and_faults_fail(sound, variant):
    """Each variant put in the program's place and judged by limits a sound
    float32 run meets: the fp8 control and every planted fault come out not
    correct, by the number meant for it (the detached cotangents leave the
    forward pass, and so the loss and the memory, as they were), and the
    reference held against itself correct. (The bfloat16 witness is judged
    on the chip, by limits that leave room for bfloat16.)"""
    cell, train, inputs, seed, batches, ref = sound
    variants = inputs.family.variants(cell.config, 2, 1)
    assert variants["reference_bf16"]["must_pass"]
    assert not variants[variant]["must_pass"]
    got = train.reference_readings(cell, inputs, seed, batches,
                                   **variants[variant]["kwargs"])
    numbers = inputs.family.numbers(got, ref, inputs.mesh)
    assert check.judge(numbers, LIMITS)["correct"] is False, numbers
    meant = numbers[MEANT[variant]]
    assert not meant <= LIMITS[MEANT[variant]], numbers
    if variant in ("m_detached", "kv_detached"):
        assert numbers["loss_gap"] == numbers["mem_gap"] == 0.0
    same = inputs.family.numbers(ref, ref, inputs.mesh)
    assert check.judge(same, LIMITS)["correct"]


# --- the readers -----------------------------------------------------------------

def _ctx(scope_s, ops):
    conf = shipped()
    return {"reduced": {"modules": {"jit_train_step":
                                    {"count": 5, "total_s": 2.5}},
                        "scope_s": scope_s, "ops": ops},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "family": manifest.family(REPO, conf), "config": conf,
            "global_batch": 1, "chips": 1}


RECORDED = {
    "block0": 0.5, "block0/mamba": 0.2, "block0/mamba/scan": 0.05,
    "block0/mlp": 0.3, "block1/attn_win": 0.1, "block1/attn_win/attn": 0.03,
    "block1/mlp": 0.3, "block2/mamba": 0.25, "block2/mlp": 0.3,
    "block3/attn_full": 0.15, "block3/mlp": 0.3, "block4/gmu": 0.05,
    "block4/mlp": 0.3, "block5/attn_cross": 0.125, "block5/mlp": 0.3,
    "head": 0.2, "head/loss": 0.05, "adam": 0.05}
OPS = [("pallas:flash_dq_dkv.1", 0.1), ("pallas:flash_fwd.2", 0.07),
       ("pallas:ssm_scan_bwd.3", 0.045), ("pallas:flash_dq_dkv_win.4", 0.02),
       ("pallas:ssm_scan_fwd.5", 0.02), ("pallas:flash_fwd_win.6", 0.015),
       ("convolution:fusion.7", 0.3)]


@pytest.mark.parametrize("metric,value", [
    ("mamba_ms", 90.0), ("diff_attn_ms", 75.0), ("gmu_ms", 10.0),
    ("mlp_ms", 360.0), ("ssm_scan_ms", 13.0),
    # 2.017 GB are 2.463 ms at the HBM peak; the scans took 13 ms a step
    ("ssm_scan_roofline", 18.95),
    # 0.187 TFLOP of band are 0.950 ms at the peak; the windowed kernels
    # took 7 ms a step
    ("window_flash_roofline", 13.58),
    # 3.28 TFLOP of triangles and bands are 16.65 ms; every flash kernel
    # together 41 ms a step
    ("hybrid_flash_roofline", 40.61)])
def test_reader_reads_a_recorded_reduction(metric, value):
    read = manifest.layer_metric_reader(REPO, metric)
    assert read(_ctx(RECORDED, OPS)) == pytest.approx(value, rel=5e-3)
    assert read(_ctx(RECORDED, OPS)) <= 100 or metric.endswith("_ms")
    # a program that lacks the scopes and the kernels (the parent), or no
    # trace
    assert read(_ctx({"adam": 0.05}, OPS[-1:])) is None
    assert read({**_ctx(RECORDED, OPS), "reduced": None}) is None
