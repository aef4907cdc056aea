"""NaN rollback-and-skip: the fail-operational alternative to gate-abort.

The numerical-health gate (trainer `_consume_metrics`, SURVEY.md §5) turns a
non-finite loss into a `FloatingPointError` with step context. Under the
default `--nan_policy abort` that kills the job — correct for debugging,
wasteful for a multi-day run where one pathological batch (or one cosmic-ray
bit) poisons a step that a different batch window would have sailed through
(ParaGAN's recovery argument for long GAN runs, PAPERS.md arxiv 2411.03999).

`--nan_policy rollback` keeps a copy of the last gate-verified state every
`rollback_snapshot_steps` steps; when the gate trips, the manager puts the
snapshot back, rewinds the host's step counter, and training continues —
the data iterator is NOT rewound, so the batches that fed the poisoned
window are naturally skipped, and the trainer folds the rollback count into
its step-key stream so the replayed steps also draw fresh z (a bitwise
replay would deterministically re-diverge). Optional LR backoff multiplies
both nets' base rates per rollback. `max_rollbacks` bounds the whole
mechanism: persistent divergence is a real bug and must still abort.

Two snapshot representations, same restore contract:

- host (`device_resident=False`, single-process default): a host copy via
  `jax.device_get`, put back with the captured shardings. Zero extra HBM;
  requires every leaf to be fully addressable from this process.
- sharded device-resident (`device_resident=True`, the multi-host mode —
  ISSUE 4): a jitted identity copy keeps each host's *addressable shards*
  on its own devices, restored through the same jitted copy so the
  returned buffers are fresh (the step's donate_argnums invalidates only
  the arrays actually passed in — the snapshot survives to serve a second
  rollback). No process ever holds the full state, which is exactly what
  unblocked multi-host rollback: the snapshot/restore dispatches run on
  every process at the same consensus-agreed point, so they are ordinary
  mesh-consistent programs. Costs one extra copy of the train state in
  device memory — the price of a restore that needs no host gather.

Accounting: `rollbacks` is surfaced as the `anomaly/rollbacks` scalar
through utils/metrics.MetricWriter — one event at each rollback plus the
running value on every scalars row while nonzero.

Protocol contract (ISSUE 14): restore()'s ordering — budget check, then
the `on_restore` drain hook, then the snapshot copy-back — is part of
the audited multi-host protocol: the simulator
(analysis/simulate.py) runs THIS class (host mode over numpy leaves, so
the ordering executes with zero device dispatches) inside its virtual
trainer, and the committed schedules in analysis/protocol.lock.jsonl pin
where the drain and the delete barrier land relative to every
collective. Reordering here drifts the lock (a DCG012 finding).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax

Pytree = Any


class RollbackExhausted(FloatingPointError):
    """The gate tripped more than max_rollbacks times; carries the last
    gate failure as __cause__."""


_DEVICE_COPY = None


def device_copy(tree: Pytree) -> Pytree:
    """Fresh device buffers with the same values/shardings: `a + 0` under
    jit compiles to a copy whose outputs alias nothing a later step program
    can donate. One module-level jitted identity shared by every caller
    (the rollback snapshot here, the trainer's param-histogram capture) —
    jax caches per tree structure/shape, so one function serves them all,
    and a future fix to the copy idiom lands in one place."""
    global _DEVICE_COPY
    if _DEVICE_COPY is None:
        _DEVICE_COPY = jax.jit(
            lambda t: jax.tree_util.tree_map(lambda a: a + 0, t))
    return _DEVICE_COPY(tree)


class RollbackManager:
    """Last-good snapshot keeper + restore executor for one training run."""

    def __init__(self, *, every: int, max_rollbacks: int,
                 lr_backoff: float = 1.0, chief: bool = True,
                 device_resident: bool = False):
        if every < 1:
            raise ValueError(f"snapshot cadence must be >= 1, got {every}")
        self.every = every
        self.max_rollbacks = max_rollbacks
        self.lr_backoff = lr_backoff
        self.chief = chief
        self.device_resident = device_resident
        self.rollbacks = 0
        self._snap: Optional[Pytree] = None
        self._snap_step: Optional[int] = None
        self._shardings = None
        # drain-before-restore hook (ISSUE 7): invoked once per consumed
        # rollback, BEFORE any restore copy dispatches. The trainer's
        # pipelined dispatch mode parks its fake-buffer invalidation here
        # so the stale in-flight stack (generated by the diverged weights
        # the rollback is fleeing) is both dropped — it must never train
        # the restored state — and has its device memory released before
        # the restore copies allocate. Structurally tied to restore() so
        # no future rollback call site can forget the drain.
        self.on_restore: Optional[Callable[[], None]] = None

    @property
    def snapshot_step(self) -> Optional[int]:
        return self._snap_step

    def due(self, step: int) -> bool:
        return step % self.every == 0

    def snapshot(self, step: int, state: Pytree) -> None:
        """Capture `state` as the new restore point. The caller passes only
        gate-verified state (the trainer forces a finiteness check at
        snapshot boundaries). The captured shardings are the state's OWN —
        so a live topology switch (elastic/live.py) makes any prior
        snapshot stale (its shardings name the old mesh); the trainer
        re-snapshots immediately after the switch so a post-switch gate
        trip restores onto the mesh it will keep training on."""
        if self.device_resident:
            self._snap = device_copy(state)
        else:
            self._shardings = jax.tree_util.tree_map(
                lambda x: x.sharding if hasattr(x, "sharding") else None,
                state)
            self._snap = jax.device_get(state)
        self._snap_step = int(step)

    def restore(self, exc: FloatingPointError) -> tuple:
        """Consume one rollback: returns (state, step) rebuilt on device
        from the snapshot. Raises RollbackExhausted (from `exc`) once the
        budget is spent."""
        if self._snap is None:
            raise exc  # no restore point was ever armed
        self.rollbacks += 1
        if self.rollbacks > self.max_rollbacks:
            raise RollbackExhausted(
                f"NaN gate tripped {self.rollbacks} times with "
                f"max_rollbacks={self.max_rollbacks} — persistent "
                f"divergence, aborting (last failure: {exc})") from exc
        if self.chief:
            print(f"[dcgan_tpu] NaN gate tripped ({exc}); rolling back to "
                  f"last-good snapshot at step {self._snap_step} "
                  f"(rollback {self.rollbacks}/{self.max_rollbacks}, "
                  f"offending batch window will be skipped)", flush=True)
        if self.on_restore is not None:
            # drain-before-restore (ISSUE 7, see __init__): runs after the
            # budget check (an exhausted budget aborts — nothing restores,
            # so nothing needs draining first) and before any restore copy
            # dispatches, so the dropped buffer's device memory is free
            # when the copies allocate
            self.on_restore()
        if self.device_resident:
            state = device_copy(self._snap)
        else:
            state = jax.tree_util.tree_map(
                lambda host, sh: jax.device_put(host, sh)
                if sh is not None else host,
                self._snap, self._shardings)
        return state, self._snap_step

    def lr_scale(self) -> float:
        """Cumulative LR multiplier after the rollbacks so far."""
        return self.lr_backoff ** self.rollbacks
