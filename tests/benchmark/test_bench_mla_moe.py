"""The `mla_moe` family (`benchmark/families/mla_moe.py`) and its shipped
configuration: the yardstick pinned, the CPU rehearsal of a whole run at a
tiny size to `correct: true` with the kernels in interpret mode, and the
control and every planted fault to `correct: false` through the cell's own
limits."""

import json
import os
import sys
import time

import pytest

from bench_testlib import REPO, make_root

sys.path.insert(0, REPO)

from benchmark import check, manifest  # noqa: E402

CELL = "tiny_mla_moe.resident"
TINY = dict(vocab_size=64, hidden_size=64, num_hidden_layers=3,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2, num_attention_heads=2,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, experts_held=4, first_expert=2,
            seq_len=32, compute_dtype="float32")
LIMITS = {"loss_gap": 1e-5, "loss2_gap": 1e-5, "route_diff": 0.0,
          "grad_gap": 1e-4, "delta_gap": 1e-4, "grad_err": 1e-4,
          "grad_err_worst": 1e-3}


def shipped():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "joyai-llm-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A temporary root with the shipped configuration cut to a size the
    CPU runs, ADDED as new files and entries."""
    root = make_root(str(tmp_path_factory.mktemp("bench")))
    conf = shipped()
    conf["model"].update(TINY)
    conf["seq_len"] = TINY["seq_len"]
    path = "benchmark/configs/tiny_mla_moe.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump(conf, f)
    mix = {"kind": "train", "feed": "resident", "per_chip_batch": 2,
           "chips": 1, "mesh": {"data": 1, "model": 1}, "backend": "gspmd",
           "resident_batches": 2, "in_flight": 2}
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-ids.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark", "limits", CELL + ".json"),
              "w") as f:
        json.dump(LIMITS, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_mla_moe", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_mla_moe",
                               "traffic": "tiny-ids", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_step_ops_total_is_pinned():
    """27.84 TFLOP a step of one 8,192-token row: 1,132.8 MFLOP a token
    forward, times three; latent attention 72% of it."""
    conf = shipped()
    fam = manifest.family(REPO, conf)
    ops = fam.step_ops(conf, 1)
    assert ops["total"] == 27839340478464.0
    assert ops["total"] == sum(v for k, v in ops.items() if k != "total")
    share = lambda *names: sum(ops[n] for n in names) / ops["total"]
    assert round(share("mla_scores"), 2) == 0.44
    assert round(share("mla_proj"), 2) == 0.28
    assert round(share("heads"), 2) == 0.12
    assert round(share("dense_ffn"), 2) == 0.08
    assert round(share("routed"), 2) == 0.02
    assert fam.step_ops(conf, 2)["total"] == 2 * ops["total"]
    costs = fam.kernel_costs(conf, 1)
    assert costs["causal_flash"]["ops"] == ops["mla_scores"]
    assert costs["moe_gmm"]["ops"] == ops["routed"]
    # operations bound the attention (62.8 ms against 4.9 ms of bytes);
    # the grouped products at 256 pairs an expert are bound by the experts'
    # matrices crossing HBM (3.6 ms against 2.9 ms of operations)
    flash, gmm = costs["causal_flash"], costs["moe_gmm"]
    assert flash["ops"] / 197e12 > 10 * flash["bytes"] / 819e9
    assert gmm["bytes"] / 819e9 > gmm["ops"] / 197e12


def test_the_configuration_holds_the_published_widths():
    """Every width as published; `reduced` is depth, experts held and
    vocabulary, within the guide's floors; the file states the deployment
    and the state it adds up to."""
    conf = shipped()
    m = conf["model"]
    widths = dict(hidden_size=2048, num_attention_heads=32, q_lora_rank=1536,
                  kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128, intermediate_size=7168,
                  moe_intermediate_size=768, n_routed_experts=256,
                  num_experts_per_tok=8, n_shared_experts=1,
                  routed_scaling_factor=2.5, rope_theta=32000000,
                  num_nextn_predict_layers=1)
    for key, value in widths.items():
        assert m[key] == value and conf[key] == value, key
    assert conf["reduced"] == ["num_hidden_layers", "experts_held",
                               "vocab_size"]
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] >= 4
    assert m["experts_held"] >= 8
    assert m["vocab_size"] * 8 >= conf["published"]["vocab_size"]
    for key in conf["reduced"]:
        assert conf[key] == m[key] != conf["published"][key]
    assert "16 chips" in conf["deployment"]
    assert len(manifest._by_name(manifest.load(REPO)["configs"],
                                 "joyai-llm-flash", "config")["source"]) <= 200
    # the file's own arithmetic: parameters held, the selection biases too
    import jax

    from dcgan_tpu.models.mla_moe import token_init
    from dcgan_tpu.presets import get_preset
    import dataclasses

    cfg = dataclasses.replace(get_preset(conf["preset"]).model, **m)
    params, bias = jax.eval_shape(lambda k: token_init(k, cfg),
                                  jax.random.key(0))
    count = sum(x.size for x in jax.tree.leaves((params, bias)))
    assert count == conf["held"]["parameters"] == 680441088
    assert conf["held"]["state_bytes_at_16_per_parameter"] == 16 * count


def test_reference_imports_nothing_of_the_program():
    import re

    for name in ("mla_moe.py", "mla_moe_reference.py"):
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
    """A whole run on the CPU: program (flash and grouped kernels in
    interpret mode, float32) against the plain reference to 1e-5 on both
    losses, no pair routed differently, 1e-4 on gradients and the two-step
    change."""
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


@pytest.mark.parametrize("variant", ["reference_fp8", "no_mtp_loss",
                                     "held_norm", "no_causal_mask"])
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
