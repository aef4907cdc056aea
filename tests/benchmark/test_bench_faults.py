"""The comparison that decides `correct`, shown to fail: the control (the
reference computed below the stated precision) and, with the harness's look
for a chip skipped, a whole run with the timed path broken underneath, once
for each fault a training cell can have."""

import dataclasses
import os
import sys
import time

import pytest

from bench_testlib import REPO, TIGHT, make_root

sys.path.insert(0, REPO)

from benchmark import check, manifest  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))


def test_control_in_lower_precision_is_not_correct(root):
    """The tiny configuration states float32, so its control is the
    reference with bfloat16 operands in the program's place: the family's
    `reference_bf16` variant, which at the shipped bfloat16 is the witness."""
    import jax

    cell = manifest.cell(root, "tiny_dcgan.resident")
    train = manifest.driver(root, "train")
    inp = train.build_program(cell, jax.devices()).inputs
    seed = 2 ** 31 + 77
    assert inp.batch_shape == (8, 16, 16, 3)
    batches = train.resident_batches(cell, inp, seed)[:3]
    bf16 = inp.family.variants(cell.config, 8, 1)["reference_bf16"]["kwargs"]
    ref = train.reference_readings(cell, inp, seed, batches)
    numbers = inp.family.numbers
    same = check.judge(numbers(ref, ref, inp.mesh), TIGHT)
    low = check.judge(numbers(
        train.reference_readings(cell, inp, seed, batches, **bf16), ref,
        inp.mesh), TIGHT)
    assert same["correct"] and not low["correct"]
    over = {n: c["value"] / c["limit"] for n, c in low["compared"].items()}
    # it fails by a margin, not by a hair; a leaf's vector error is never
    # under the gap of its norms, in which rounding all but cancels
    assert over["grad_err"] > 3
    assert over["grad_err_worst"] >= over["grad_gap"] > 0


def _run(root, name, tmp, monkeypatch, breaker):
    import jax

    import dcgan_tpu.parallel as parallel

    real = parallel.make_parallel_train

    def broken(cfg, mesh=None):
        pt = real(cfg, mesh)
        return dataclasses.replace(pt, step=breaker(pt, cfg, mesh),
                                   programs=dict(pt.programs))

    monkeypatch.setattr(parallel, "make_parallel_train", broken)
    cell = manifest.cell(root, name)
    return manifest.driver(root, "train").run(
        cell, seed=2 ** 31 + 5, seconds=0.2, trace=False,
        t_start=time.time(), devices=jax.devices(),
        cache_root=os.path.join(str(tmp), "cache"), device_metrics=False)


def state_unchanged(pt, cfg, mesh):
    import jax
    import jax.numpy as jnp

    def step(state, images, key):
        _, metrics = pt.step(jax.tree.map(jnp.copy, state), images, key)
        return state, metrics
    return step


def half_batch_left_out(pt, cfg, mesh):
    def step(state, images, key):
        return pt.step(state, images[:images.shape[0] // 2], key)
    return step


def exchange_left_out(pt, cfg, mesh):
    """Every chip keeps to its own rows: what the first chip then holds is
    one-chip training on the first shard."""
    import jax

    import dcgan_tpu.parallel as parallel
    from dcgan_tpu.config import MeshConfig

    n = mesh.shape["data"]
    one = parallel.make_mesh(MeshConfig(data=1, model=1),
                             [mesh.devices.flat[0]])
    pt1 = real_make(dataclasses.replace(
        cfg, batch_size=cfg.batch_size // n, mesh=MeshConfig(data=1, model=1)),
        one)

    def step(state, images, key):
        local = jax.device_put(state, pt1.shardings)
        rows = jax.device_put(images[:images.shape[0] // n],
                              parallel.batch_sharding(one, 4))
        new, metrics = pt1.step(local, rows,
                                jax.device_put(key, parallel.replicated(one)))
        return (jax.device_put(new, pt.shardings),
                jax.device_put(metrics, parallel.replicated(mesh)))
    return step


import dcgan_tpu.parallel as _parallel  # noqa: E402

real_make = _parallel.make_parallel_train


@pytest.mark.parametrize("name, breaker, fails", [
    ("tiny_dcgan.resident", state_unchanged,
     ("grad_err", "grad_gap", "delta_gap")),
    ("tiny_dcgan.resident", half_batch_left_out, ("grad_err", "grad_gap")),
    ("tiny_dcgan.dp4", exchange_left_out, ("grad_err", "grad_gap")),
])
def test_broken_timed_path_is_not_correct(root, tmp_path, monkeypatch, name,
                                          breaker, fails):
    line = _run(root, name, tmp_path, monkeypatch, breaker)
    # (sound runs of these cells come out correct in test_bench_rehearsal)
    assert line["correct"] is False
    for number in fails:
        c = line["check"][number]
        assert c["value"] is None or c["value"] > c["limit"]
    if breaker is state_unchanged:
        assert line["check"]["delta_gap"]["value"] == pytest.approx(1.0)
        assert line["check"]["grad_gap"]["value"] == pytest.approx(1.0)
