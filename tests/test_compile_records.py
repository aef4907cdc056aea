"""JAX's compile stages as records of the one span store
(`utils/profiling.py`): `compile/trace`, `compile/lower`, `compile/backend`,
labelled by program, a nested trace folded into the stage that holds it;
the persistent cache's misses on the backend record; and the set-up timers
(`StartupProfile`, `CompileCacheMonitor`, chip_smoke.py's `Probe`) that read
the store and register nothing of their own."""

import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from dcgan_tpu.train import warmup
from dcgan_tpu.utils import profiling
from dcgan_tpu.utils.profiling import span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("compile/trace", "compile/lower", "compile/backend")


def mine(t0, label=None):
    """The records since `t0` (the store is the process's, shared by
    tests), of one program or of all."""
    return [r for r in profiling.compile_records(t0)
            if label is None or r.label == label]


def test_a_nested_jit_folds_into_the_trace_that_holds_it():
    inner = jax.jit(lambda x: jnp.tanh(x) * 2.0)   # a fresh jit: it traces

    def outer_fn(x):
        return inner(x) @ jnp.ones((8, 8))

    seen = []

    def spy(event, start, end, **kw):
        if event.endswith("jaxpr_trace_duration"):
            seen.append((kw.get("fun_name"), start, end))

    jax.monitoring.register_event_time_span_listener(spy)
    t0 = time.perf_counter()
    try:
        jax.jit(outer_fn)(jnp.ones((4, 8)))
    finally:
        jax.monitoring.unregister_event_time_span_listener(spy)
    assert [r.name for r in mine(t0, "outer_fn")] == list(STAGES)
    (trace,) = [r for r in mine(t0, "outer_fn") if r.name == "compile/trace"]
    # JAX reported the inner trace, inside the outer one's interval ...
    assert "<lambda>" in [name for name, _, _ in seen]
    wall = {name: (s, e) for name, s, e in seen}
    lo, hi = wall["outer_fn"]
    assert lo <= wall["<lambda>"][0] <= wall["<lambda>"][1] <= hi
    # ... and the store kept no trace record inside the outer one
    end = trace.start + trace.duration
    assert [r for r in profiling.spans("compile/trace")
            if trace.start < r.start <= end] == []
    assert trace.duration == pytest.approx(hi - lo)


def test_a_call_that_compiles_nothing_records_nothing():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.ones((16,))
    f(x).block_until_ready()
    before = len(profiling.spans())
    t0 = time.perf_counter()
    for _ in range(5):
        f(x).block_until_ready()
    assert len(profiling.spans()) == before and mine(t0) == []


def test_a_span_around_the_first_call_holds_its_compile_records():
    """The records' starts are on the clock of every other record."""
    def shared_clock(x):
        return jnp.sin(x) + 1.0

    with span("t/first_call") as outer:
        jax.jit(shared_clock)(jnp.ones((8,))).block_until_ready()
    records = mine(outer.start, "shared_clock")
    assert [r.name for r in records] == list(STAGES)
    for r in records:
        assert outer.start <= r.start
        assert r.start + r.duration <= outer.start + outer.duration + 1e-3


def test_the_backend_record_counts_persistent_cache_misses(tmp_path):
    def cached_program(x):
        return jnp.cos(x) * 5.0

    f = jax.jit(cached_program)
    x = jnp.ones((32,))
    enabled = jax.config.jax_enable_compilation_cache
    t0 = time.perf_counter()
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        warmup._reset_cache_object()
        f(x).block_until_ready()           # no persistent cache: no count
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        warmup._reset_cache_object()
    warmup.configure_compile_cache(str(tmp_path / "cc"))
    jax.clear_caches()
    t1 = time.perf_counter()
    f(x).block_until_ready()               # compiles and writes the entry
    jax.clear_caches()
    t2 = time.perf_counter()
    f(x).block_until_ready()               # read back from the entry

    def counts(since, until=float("inf")):
        return [r.count for r in mine(since, "cached_program")
                if r.name == "compile/backend" and r.start < until]

    assert counts(t0, t1) == [None]
    assert counts(t1, t2) == [1]
    assert counts(t2) == [0]


def test_the_monitor_reads_the_backend_records(tmp_path):
    warmup.configure_compile_cache(str(tmp_path / "cc"))
    t0 = time.perf_counter()
    mon = warmup.CompileCacheMonitor()
    jax.jit(lambda x: x * 7.0 - 2.0)(jnp.ones((8, 8))).block_until_ready()
    asked = [r.count for r in mine(t0)
             if r.name == "compile/backend" and r.count is not None]
    assert mon.counters() == {"requests": len(asked),
                              "hits": asked.count(0), "misses": sum(asked)}
    assert mon.counters()["misses"] >= 1


def test_init_programs_are_labelled_init():
    """The GAN step's `init` and the token steps' (a `def`, not a lambda)
    carry the program's name into their records."""
    from dcgan_tpu.config import ModelConfig, TrainConfig
    from dcgan_tpu.parallel import make_mesh, make_parallel_train
    from dcgan_tpu.presets import get_preset
    from dcgan_tpu.train.steps import make_lm_train_step

    gan = TrainConfig(model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                                        compute_dtype="float32"),
                      batch_size=8)
    token = get_preset("mla_moe_tiny")
    assert make_lm_train_step(token).init.__name__ == "init"
    for cfg in (gan, token):
        t0 = time.perf_counter()
        make_parallel_train(cfg, make_mesh(cfg.mesh))  # eval_shape of init
        assert [r.name for r in mine(t0, "init")] == ["compile/trace"]
        assert not [r for r in mine(t0) if r.label == "<lambda>"]


def test_startup_phases_are_spans():
    sp = profiling.StartupProfile()
    t0 = time.perf_counter()
    with sp.phase("restore"):
        time.sleep(0.002)
    (rec,) = [r for r in profiling.spans("startup/restore") if r.start >= t0]
    assert sp.summary()["perf/startup/restore_ms"] == \
        pytest.approx(rec.duration * 1e3)
    with pytest.raises(RuntimeError):
        with sp.phase("data"):
            raise RuntimeError("a phase that fails still counts its time")
    assert sp.summary()["perf/startup/data_ms"] >= 0


def test_the_probe_reads_compile_seconds_from_the_store():
    import chip_smoke

    probe = chip_smoke.Probe()
    jax.jit(lambda x: x / 3.0 + 4.0)(jnp.ones((8,))).block_until_ready()
    snap = probe.snapshot()
    backend = [r.duration for r in profiling.spans("compile/backend")
               if r.start >= probe.since]
    assert sum(backend) > 0
    assert snap["compile_s"] == pytest.approx(sum(backend))
    assert {"requests", "hits", "misses"} <= set(snap)


def test_jax_monitoring_is_registered_in_one_place():
    """The listeners of utils/profiling.py are the package's only ones."""
    found = []
    paths = [os.path.join(ROOT, "chip_smoke.py")]
    for base, _, files in os.walk(os.path.join(ROOT, "dcgan_tpu")):
        paths += [os.path.join(base, f) for f in files if f.endswith(".py")]
    for path in paths:
        with open(path) as f:
            if re.search(r"monitoring\.register_", f.read()):
                found.append(os.path.relpath(path, ROOT))
    assert found == [os.path.join("dcgan_tpu", "utils", "profiling.py")]
