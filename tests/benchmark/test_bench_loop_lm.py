"""The `loop_lm` family (`benchmark/families/loop_lm.py`) and its shipped
configuration: the yardstick pinned, the CPU rehearsal of a whole run at a
tiny size to `correct: true` with the kernels in interpret mode, the control
and every planted fault to `correct: false` through the cell's own limits,
and the five readers the cell adds."""

import dataclasses
import json
import os
import sys
import time

import pytest

from bench_testlib import REPO, make_root

sys.path.insert(0, REPO)

from benchmark import check, manifest  # noqa: E402

CELL = "tiny_loop_lm.resident"
SHIPPED_CELL = "ouro-2.6b.resident-b1-s4096"
TINY = dict(vocab_size=64, hidden_size=64, num_hidden_layers=2,
            intermediate_size=128, num_attention_heads=2,
            num_key_value_heads=2, head_dim=32, total_ut_steps=4, seq_len=32,
            compute_dtype="float32")
LIMITS = {"loss_gap": 1e-5, "loss2_gap": 1e-5, "exit_gap": 1e-5,
          "grad_gap": 1e-4, "delta_gap": 1e-4, "grad_err": 1e-4,
          "grad_err_worst": 1e-3}
NEW_METRICS = ("loop_stack_ms", "loop_attn_ms", "loop_ffn_ms",
               "exit_heads_ms", "loop_flash_roofline")


def shipped():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary root with the shipped configuration cut to a size the
    CPU runs, ADDED as new files and entries."""
    root = make_root(str(tmp_path_factory.mktemp("bench")))
    conf = shipped()
    conf["model"].update(TINY)
    conf["seq_len"] = TINY["seq_len"]
    path = "benchmark/configs/tiny_loop_lm.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump(conf, f)
    mix = {"kind": "train", "feed": "resident", "per_chip_batch": 2,
           "chips": 1, "mesh": {"data": 1, "model": 1}, "backend": "gspmd",
           "resident_batches": 2, "in_flight": 2}
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-loop-ids.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark", "limits", CELL + ".json"),
              "w") as f:
        json.dump(LIMITS, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_loop_lm", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_loop_lm",
                               "traffic": "tiny-loop-ids", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_step_ops_total_is_pinned():
    """56.9 TFLOP a step of one 4,096-token row: 12.28 GFLOP a token in the
    matmuls (2.05 G applied parameters, forward and backward), 6.6 TFLOP of
    causal scores; the four heads 17%, the loop the rest."""
    conf = shipped()
    fam = manifest.family(REPO, conf)
    ops = fam.step_ops(conf, 1)
    assert ops["total"] == sum(v for k, v in ops.items() if k != "total")
    assert round(ops["total"] / 1e12, 1) == 56.9
    matmuls = ops["qkvo"] + ops["ffn"] + ops["heads"] + ops["gate"]
    assert round(matmuls / 4096 / 1e9, 2) == 12.28
    assert round(ops["heads"] / 4096 / 1e9, 2) == 2.42
    assert round(ops["scores"] / 1e12, 1) == 6.6
    assert round(ops["heads"] / ops["total"], 2) == 0.17
    assert fam.step_ops(conf, 2)["total"] == 2 * ops["total"]
    flash = fam.kernel_costs(conf, 1)["causal_flash"]
    assert flash["ops"] == ops["scores"]
    # q, k, v, o and their gradients, 16 heads x 128, 32 layer-passes, bf16
    assert flash["bytes"] == 8 * 32 * 4096 * 2048 * 2
    # operations bound the attention: 33.5 ms against 5.2 ms of bytes
    assert flash["ops"] / 197e12 > 5 * flash["bytes"] / 819e9
    assert fam.kernel_costs(
        {**conf, "model": dict(conf["model"], use_pallas=False)}, 1) == {}


def test_the_configuration_holds_the_published_widths():
    """Every key of the catalog row as published but the depth; `reduced`
    is exactly that, in the file and in `BENCHMARK.json`; the file differs
    from its preset in exactly that key and states source, `published`,
    `deployment`, `held`, `assumed`."""
    conf = shipped()
    m = conf["model"]
    published = dict(hidden_size=2048, num_attention_heads=16,
                     num_key_value_heads=16, head_dim=128,
                     intermediate_size=5632, vocab_size=49152,
                     rms_norm_eps=1e-6, rope_theta=1000000, total_ut_steps=4,
                     early_exit_threshold=1, max_position_embeddings=65536)
    for key, value in published.items():
        assert conf[key] == value, key
        assert m.get(key, value) == value, key
    assert conf["tie_word_embeddings"] is False
    assert conf["layer_types"] == ["full_attention"] * 48
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["num_hidden_layers"] == m["num_hidden_layers"] == 8
    assert conf["seq_len"] == m["seq_len"] == 4096
    assert conf["published"]["num_hidden_layers"] == 48
    entry = manifest._by_name(manifest.load(REPO)["configs"], "ouro-2.6b",
                              "config")
    assert entry["reduced"] == conf["reduced"]
    assert entry["source"] in conf["source"] and len(conf["source"]) <= 200
    for key in ("published", "deployment", "held", "assumed"):
        assert conf[key], key
    assert "Six pipeline stages" in conf["deployment"]

    from dcgan_tpu.presets import get_preset

    preset = get_preset(conf["preset"])
    differs = {k for k, v in m.items() if getattr(preset.model, k) != v}
    assert differs == set(conf["reduced"])
    assert dataclasses.asdict(preset.model).keys() == m.keys()
    for key in ("loss", "beta1", "learning_rate"):
        assert conf["train"][key] == getattr(preset, key), key

    # the file's own arithmetic
    import jax

    from dcgan_tpu.models.loop_lm import loop_init

    count = lambda cfg: sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda k: loop_init(k, cfg), jax.random.key(0))))
    held = count(dataclasses.replace(preset.model, **m))
    assert held == conf["held"]["parameters"] == 612438017
    assert conf["held"]["state_bytes_at_16_per_parameter"] == 9799008272
    assert count(preset.model) == conf["published"]["parameters"] \
        == 2667974657


def test_the_cell_is_added_as_entries():
    bench = manifest.load(REPO)
    cell = manifest.cell(REPO, SHIPPED_CELL, bench)
    assert cell.chips == 1 and cell.traffic["per_chip_batch"] == 1
    assert {m["name"] for m in cell.end_to_end} == {"train_images_per_s",
                                                    "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert reported == set(NEW_METRICS) | {
        "dispatch_ms", "step_mfu", "device_step_ms", "device_idle_share",
        "hbm_peak_mib"}
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [SHIPPED_CELL]
    assert set(cell.limits) <= set(LIMITS)


def test_reference_imports_nothing_of_the_program():
    import re

    for name in ("loop_lm.py", "loop_lm_reference.py"):
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
    """A whole run on the CPU: program (the scan over four passes, flash
    kernels in interpret mode, float32) against the reference computed in
    blocks to 1e-5 on the objective, each exit's loss and the exit mass,
    1e-4 on gradients and the two-step change."""
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


def test_no_exit_is_starved_and_the_last_takes_the_rest(sound):
    """The drawn gate leaves every exit at least a tenth of the mass, the
    masses add up to the positions scored, and the losses sit measurably
    above ln(vocabulary)."""
    import math

    _, _, _, _, _, ref = sound
    mass = ref["exit_mass"]
    scored = 2 * (TINY["seq_len"] - 1)
    assert abs(float(mass.sum()) - scored) < 1e-3
    assert float(mass.min()) >= 0.1 * scored
    first = ref["losses"][0]
    assert all(first[f"loss_ut{t}"] > math.log(TINY["vocab_size"]) + 0.1
               for t in range(1, 5))


@pytest.mark.parametrize("variant", ["reference_fp8", "three_passes",
                                     "last_pass_grad", "gate_detached",
                                     "no_causal_mask"])
def test_control_and_faults_fail(sound, variant):
    """Each variant put in the program's place and judged by limits a sound
    float32 run meets: the fp8 control and every planted fault come out not
    correct, and the reference held against itself correct. (The bfloat16
    witness is judged on the chip, by limits that leave room for bfloat16.)"""
    cell, train, inputs, seed, batches, ref = sound
    variants = inputs.family.variants(cell.config, 2, 1)
    assert variants["reference_bf16"]["must_pass"]
    assert not variants[variant]["must_pass"]
    got = train.reference_readings(cell, inputs, seed, batches,
                                   **variants[variant]["kwargs"])
    numbers = inputs.family.numbers(got, ref, inputs.mesh)
    assert check.judge(numbers, LIMITS)["correct"] is False, numbers
    same = inputs.family.numbers(ref, ref, inputs.mesh)
    assert check.judge(same, LIMITS)["correct"]


# --- the readers -----------------------------------------------------------------

def _ctx(scope_s, ops):
    conf = shipped()
    return {"reduced": {"modules": {"jit_train_step":
                                    {"count": 5, "total_s": 3.5}},
                        "scope_s": scope_s, "ops": ops},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "family": manifest.family(REPO, conf), "config": conf,
            "global_batch": 1, "chips": 1}


RECORDED = {
    "while/body/loop": 3.0,
    "while/body/loop/block0": 1.5,
    "while/body/loop/block0/attn_block": 0.8,
    "while/body/loop/block0/ffn": 0.7,
    "while/body/loop/block1/attn_block": 0.7,
    "while/body/loop/block1/ffn": 0.6,
    "while/body/exit": 0.02, "while/body/head": 0.4,
    "while/body/head/loss": 0.1, "adam": 0.05}
OPS = [("pallas:flash_dq_dkv.1", 0.6), ("pallas:flash_fwd.2", 0.5),
       ("convolution:fusion.7", 0.3)]


@pytest.mark.parametrize("metric,value", [
    ("loop_stack_ms", 600.0), ("loop_attn_ms", 300.0),
    ("loop_ffn_ms", 260.0), ("exit_heads_ms", 84.0),
    # 6.6 TFLOP of causal scores are 33.5 ms at the peak; the kernels took
    # 220 ms a step
    ("loop_flash_roofline", 15.2)])
def test_reader_reads_a_recorded_reduction(metric, value):
    read = manifest.layer_metric_reader(REPO, metric)
    assert read(_ctx(RECORDED, OPS)) == pytest.approx(value, rel=5e-3)
    # a program that lacks the scope (the parent) or the kernels, or no trace
    assert read(_ctx({"adam": 0.05}, OPS[2:])) is None
    assert read({**_ctx(RECORDED, OPS), "reduced": None}) is None
