"""Runtime thread-discipline tripwire: DCG001's dynamic complement.

The static call-graph checker (analysis/threads.py) terminates at every
dynamic call (`task.fn()`, `self._hook(...)`) — exactly the indirection
the services worker and the watchdog are built from. This module closes
that gap at runtime: with `DCGAN_THREAD_CHECKS=1`, the known collective
entry points are wrapped to assert they execute on the dispatch thread —
the thread that entered `train()` — and any off-thread collective raises
`ThreadDisciplineError` naming the entry point and both threads, instead
of deadlocking a mesh some minutes later.

Zero cost when off: nothing is wrapped unless the env var is set, so the
default trainer runs the original callables with no indirection at all.

Wrapped entry points (install()):
- coordination's collective transports and helpers (`_allgather_i32`,
  `_allgather_f32`, `fleet_health_gather`, `anomaly_consensus`,
  `warmup_barrier`),
- Checkpointer's collective methods (save / restore_latest /
  delete_steps_after / wait — Orbax array gathers),
- every compiled ParallelTrain program (`pt.step`, `pt.sample`, ... —
  wrapped at construction by `wrap_parallel_train`, called from
  ParallelTrain.__post_init__ so both backends are covered; the wrapper
  object forwards attribute access, so AOT warmup's `.lower()` path is
  untouched).

The assertion is scoped: checks fire only inside a `dispatch_scope()` —
entered by trainer.train() on its calling thread — so unit tests and
tools that legitimately call collectives from their own (single) thread
outside a training run are never tripped. Tier-1 runs the whole test
suite with the tripwire armed (tests/conftest.py) and must record zero
trips at default knobs; `tools/chaos_drill.py thread-checks` proves the
same end to end through a real trainer subprocess.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import Optional

ENV_VAR = "DCGAN_THREAD_CHECKS"


class ThreadDisciplineError(AssertionError):
    """A mesh-wide collective entry point ran off the dispatch thread."""


_installed = False
_wrapped_count = 0
#: the set of threads currently inside a dispatch_scope(). A SET, not a
#: single slot: the serving fleet (ISSUE 19) runs N replica dispatch
#: threads in one process, each a legitimate owner of ITS replica's
#: programs — a single global owner would make replica A's collectives
#: trip the moment replica B entered its scope. Membership is per-thread
#: (add on enter, remove on outermost exit), guarded by _owners_lock;
#: check() reads the set without the lock (a stale read can only happen
#: during scope enter/exit, where the caller by definition owns or owned
#: the scope).
_dispatch_owners: set = set()
_owners_lock = threading.Lock()

#: coordination-module collective entry points install() wraps. Module
#: constant (not an install()-local literal) because the semantic tier
#: cross-checks coordination.TRANSPORT_CENSUS against it (DCG008): every
#: declared transport must also be thread-policed here.
WRAPPED_TRANSPORTS = ("_allgather_i32", "_allgather_f32",
                      "fleet_health_gather", "anomaly_consensus",
                      "warmup_barrier")


def enabled() -> bool:
    """Whether the env knob asks for runtime thread checks."""
    return os.environ.get(ENV_VAR, "") == "1"


def installed() -> bool:
    return _installed


def check(what: str) -> None:
    """Assert the caller is a dispatch thread (no-op while no
    dispatch_scope is active — tools and tests own their single
    thread)."""
    owners = _dispatch_owners
    if not owners:
        return
    cur = threading.current_thread()
    if cur not in owners:
        names = sorted(t.name for t in owners)
        raise ThreadDisciplineError(
            f"collective entry point {what!r} called from thread "
            f"{cur.name!r} while the dispatch thread owner(s) are "
            f"{names} — mesh-wide collectives must stay on the dispatch "
            "thread (DESIGN.md §6b): a background thread's collectives "
            "have no cross-process ordering against the dispatch stream "
            "and two processes interleaving them differently deadlock "
            "the mesh")


def dispatch_owners() -> frozenset:
    """The current dispatch-scope owner threads (empty = no active
    scope). Read surface for tests; never mutate through this."""
    return frozenset(_dispatch_owners)


@contextlib.contextmanager
def dispatch_scope():
    """Mark the current thread as A dispatch thread for the duration
    (re-entrant per thread: the outermost exit removes it). Each scoped
    thread is an independent owner — trainer.train() scopes its calling
    thread, and every serve replica's worker scopes its own dispatch
    thread. A no-op when the tripwire is off."""
    if not _installed:
        yield
        return
    cur = threading.current_thread()
    with _owners_lock:
        already_owner = cur in _dispatch_owners
        _dispatch_owners.add(cur)
    try:
        yield
    finally:
        if not already_owner:
            with _owners_lock:
                _dispatch_owners.discard(cur)


class _GuardedFn:
    """A callable wrapper that runs the thread check, then delegates —
    including attribute access, so jitted programs keep `.lower()` and
    friends for the AOT warmup path."""

    __slots__ = ("_fn", "_what")

    def __init__(self, fn, what: str):
        self._fn = fn
        self._what = what

    def __call__(self, *args, **kwargs):
        check(self._what)
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return f"<thread-checked {self._what}: {self._fn!r}>"


def _wrap_function(fn, what: str):
    """Plain-function wrapper (used for methods — a _GuardedFn object
    would not bind `self` through the descriptor protocol)."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        check(what)
        return fn(*args, **kwargs)

    wrapped.__dcgan_tripwire__ = True
    return wrapped


def install() -> int:
    """Wrap the module/class-level collective entry points; returns the
    number of wrapped callables. Idempotent — a second call is a no-op
    (re-wrapping would capture test shims installed in between)."""
    global _installed, _wrapped_count
    if _installed:
        return _wrapped_count
    from dcgan_tpu.train import coordination
    from dcgan_tpu.utils import checkpoint

    count = 0
    for name in WRAPPED_TRANSPORTS:
        setattr(coordination, name,
                _wrap_function(getattr(coordination, name),
                               f"coordination.{name}"))
        count += 1
    for name in ("save", "restore_latest", "delete_steps_after", "wait"):
        setattr(checkpoint.Checkpointer, name,
                _wrap_function(getattr(checkpoint.Checkpointer, name),
                               f"Checkpointer.{name}"))
        count += 1
    _installed = True
    _wrapped_count = count
    return count


def maybe_install() -> bool:
    """Env-gated install; prints one armed line so drills can assert the
    tripwire was live. Returns whether the tripwire is installed."""
    if not enabled():
        return _installed
    if not _installed:
        n = install()
        print(f"[dcgan_tpu] thread-discipline tripwire armed "
              f"({n} module entry points + ParallelTrain programs; "
              f"{ENV_VAR}=1)", flush=True)
    return True


#: ParallelTrain fields that dispatch compiled mesh programs
_PROGRAM_FIELDS = ("init", "step", "sample", "summarize", "eval_losses",
                   "multi_step", "gen_fakes", "d_update", "g_update")


def wrap_parallel_train(pt) -> None:
    """Wrap every program field of a ParallelTrain in place (frozen
    dataclass — object.__setattr__). Called from __post_init__ BEFORE the
    `programs` dict is derived, so the dict picks up the wrapped
    callables too. No-op unless the tripwire is installed."""
    if not _installed:
        return
    for name in _PROGRAM_FIELDS:
        fn = getattr(pt, name)
        if fn is None or isinstance(fn, _GuardedFn):
            continue        # None: a program this family does not have
        object.__setattr__(pt, name, _GuardedFn(fn, f"pt.{name}"))
