"""Shared bring-up for multi-process CPU harnesses (ISSUE 4).

Every harness that forms a real 2+ process jax.distributed job over
localhost gRPC — tests/multihost_worker.py, tests/test_multihost.py's
launcher, and tools/chaos_drill.py's --multihost driver — needs the same
three version-sensitive pieces; keeping them here means a jax upgrade that
changes any of them is a one-site edit instead of a silent third-copy
drift:

- the CPU platform pin (jax is imported by then, so the config — not
  JAX_PLATFORMS — is what can still say it),
- `jax_cpu_collectives_implementation=gloo`, the cross-process CPU
  collectives these harnesses run on,
- the partitionable threefry flag the test env standardizes on.

Callers must still set XLA_FLAGS/JAX_PLATFORMS env *before* the first
`import jax` in their process (the device-count flag is read at backend
init) — this module deliberately takes the already-imported `jax` so it
cannot hide that ordering requirement.
"""

from __future__ import annotations

import socket


def configure_cpu_multiprocess(jax) -> None:
    """Apply the CPU multi-process config trio to an imported jax."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_threefry_partitionable", True)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def free_port() -> int:
    """An OS-assigned localhost port for the coordinator address."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
