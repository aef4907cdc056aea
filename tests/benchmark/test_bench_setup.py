"""The set-up readers (`benchmark/compile_reader.py` and the four files
`layer_metrics/setup_*.py`): after the harness builds a tiny GAN cell's
program and a tiny token model's, and runs `pt.init` and one `pt.step`,
each reads seconds above 0 and the misses as an integer; none reads
anything where the program's store holds no compile records."""

import os
import sys

import pytest

from bench_testlib import REPO, make_root

sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402

SECONDS = ("setup_trace_s", "setup_lower_s", "setup_compile_s")
READERS = SECONDS + ("setup_cache_misses",)


def read_all():
    return {name: manifest.layer_metric_reader(REPO, name)({"steps": 1})
            for name in READERS}


@pytest.fixture
def fresh_store(monkeypatch, tmp_path):
    """An empty span store, no program compiled in memory yet, and a
    persistent cache of the test's own, so the readings are this test's."""
    import jax

    from dcgan_tpu.train import warmup
    from dcgan_tpu.utils import profiling

    monkeypatch.setattr(profiling, "_rings", {})
    warmup.configure_compile_cache(str(tmp_path / "cc"))
    jax.clear_caches()
    return profiling


def check_readings(got):
    for name in SECONDS:
        assert isinstance(got[name], float) and got[name] > 0, got
    assert isinstance(got["setup_cache_misses"], int), got
    assert got["setup_cache_misses"] >= 2, got   # both programs compiled


def test_a_gan_cell_reads_its_setup(fresh_store, tmp_path):
    import jax

    root = make_root(str(tmp_path))
    cell = manifest.cell(root, "tiny_dcgan.resident")
    train = manifest.driver(root, "train")
    prog = train.build_program(cell, jax.devices())
    state = train.initial_state(prog, 3_000_000_019)
    (batch,) = train.resident_batches(cell, prog.inputs, 3)[:1]
    prog.pt.step(state, batch, jax.random.key(0))
    check_readings(read_all())
    labels = {r.label for r in fresh_store.compile_records()}
    assert {"init", "train_step"} <= labels


def test_a_token_model_reads_its_setup(fresh_store):
    import jax
    import jax.numpy as jnp

    from dcgan_tpu.parallel import make_mesh, make_parallel_train
    from dcgan_tpu.presets import get_preset

    cfg = get_preset("mla_moe_tiny")
    pt = make_parallel_train(cfg, make_mesh(cfg.mesh))
    state = pt.init(jax.random.key(0))
    ids = jnp.zeros((cfg.batch_size, cfg.model.seq_len), jnp.int32)
    pt.step(state, ids, jax.random.key(1))
    check_readings(read_all())


def test_nothing_to_read_without_compile_records(monkeypatch):
    from dcgan_tpu.utils import profiling

    monkeypatch.setattr(profiling, "_rings", {})
    assert set(read_all().values()) == {None}
    # nor with no steps, whatever the store holds
    assert {manifest.layer_metric_reader(REPO, name)({"steps": 0})
            for name in READERS} == {None}


def test_the_readers_wait_as_files():
    """No entry in `BENCHMARK.json` yet: the tests that pin each token
    cell's reported metrics (`test_bench_sambay.py`, `test_bench_loop_lm.py`)
    have to take them in first."""
    bench = manifest.load(REPO)
    assert not set(READERS) & {m["name"] for m in bench["per_layer"]}
    for name in READERS:
        assert os.path.isfile(os.path.join(REPO, "benchmark", "layer_metrics",
                                           name + ".py"))
