"""Test env: 8 virtual CPU devices — the JAX-native "fake cluster" (SURVEY.md §4).

Must run before the first `import jax` anywhere in the test process.
"""

import os

# Force CPU: tests run on the virtual 8-device CPU mesh wherever they are
# started. The variable is all it takes — nothing pins a platform at
# interpreter start-up — and subprocesses inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)

# Thread-discipline tripwire (ISSUE 8): the whole tier runs with the
# runtime collective-thread checks armed — every trainer/coordination/
# pipeline test doubles as a zero-trips proof at its knobs, and trainer
# SUBPROCESSES (chaos drill, bench pins) inherit the env var and arm
# themselves in train(). setdefault so DCGAN_THREAD_CHECKS=0 can switch
# it off for a bisection run.
os.environ.setdefault("DCGAN_THREAD_CHECKS", "1")

from dcgan_tpu.analysis import tripwire  # noqa: E402

tripwire.maybe_install()


_CACHE_KNOBS = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _compile_cache_placement_restored():
    """The persistent-cache placement is process-global, and an empty
    `compile_cache_dir` leaves it alone (train/warmup.py) — so a test that
    points it somewhere must not hand that placement (a tmp dir about to
    vanish) to every later test."""
    prev = {k: getattr(jax.config, k) for k in _CACHE_KNOBS}
    yield
    if jax.config.jax_compilation_cache_dir != \
            prev["jax_compilation_cache_dir"]:
        from dcgan_tpu.train import warmup

        for k, v in prev.items():
            jax.config.update(k, v)
        warmup._reset_cache_object()


def pytest_collection_modifyitems(config, items):
    """Two-tier suite (markers registered in pytest.ini): anything not
    explicitly marked `slow` is the smoke tier, so `-m smoke` and `-m slow`
    partition the suite exactly."""
    for item in items:
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.smoke)
