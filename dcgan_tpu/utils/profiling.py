"""Tracing / profiling: per-step timing stats + jax.profiler trace capture.

The reference has no tracing or profiling subsystem (SURVEY.md §5); its nearest
artifacts are the elapsed-time stamp in the per-step log (image_train.py:148,162)
and the dead `log_device_placement` flag (image_train.py:36). SURVEY.md names
the TPU-native equivalent explicitly — "jax.profiler trace capture + per-step
timing" — and this module is it:

- `span`: the one span primitive (ISSUE 24). A context manager that puts a
  `jax.profiler.TraceAnnotation` on the profiler's clock (so any capture —
  --profile_dir, --profile_trigger, a benchmark's traced window — shows the
  span on the host line above the device's kernels) and appends one
  `SpanRecord` to a bounded in-memory ring per name; `spans()` hands the
  records out. The feed (`feed/*`, data/pipeline.py) and the trainer loop
  (`train/*`, train/trainer.py) record through it.
- Compile records: the listeners this module registers with
  `jax.monitoring` when it is imported turn each compile stage JAX reports
  into a record of the same store: `compile/trace` (Python tracing to a
  jaxpr), `compile/lower` (jaxpr to MLIR, the Pallas kernels' Mosaic
  lowering with it), `compile/backend` (XLA's compile on a persistent-
  cache miss, the fetch and load of the executable on a hit), each
  labelled with the program's name; a trace that runs inside another
  stage of its thread is folded into that stage. Nothing else in the
  package listens to JAX's compile events: `StartupProfile`,
  train/warmup.py's `CompileCacheMonitor` and chip_smoke.py read these
  records.
- `StepTimer`: rolling per-step wall-time statistics (mean/p50/p90/max,
  steps/sec, images/sec) over a sliding window, emitted through the
  MetricWriter alongside the loss scalars.
- `TraceCapture`: captures a jax.profiler trace (XLA device + host timelines,
  viewable in TensorBoard/Perfetto) for a configured window of steps, e.g.
  steps [10, 15) once compilation has settled — or ON DEMAND (ISSUE 6):
  with a trigger path configured, touching that file starts a capture of
  the next `num_steps` steps mid-run, no restart or pre-chosen
  --profile_start_step needed; each completed capture fires `on_capture`
  so the trainer can digest it in-process (utils/trace.py).

Timing caveat: step dispatch is async; host-side wall time per step is only
meaningful when something syncs the host to the device each iteration. The
trainer's per-step metric logging (float() on the loss scalars) provides that
sync, so the timer measures true steady-state step latency including data-feed
time — which is the point: a rising step time with constant device time is the
input-bound signature (the reference's own pathology, SURVEY.md §2.4 #10).
With async_services (the default) the sync is lag-by-one — step N's metrics
materialize while step N+1 runs — so each tick still follows exactly one
device-progress point per step; steady-state rates are unchanged, only the
attribution of an individual slow step can shift by one tick.

`note_host` feeds the dispatch-thread occupancy channel: the trainer stamps
the wall time its dispatch thread spends executing host-side service work
(metric materialization, submissions, inline writers) per loop iteration, and
summary() reports it as perf/host_ms_mean plus perf/dispatch_occupancy (the
fraction of step time the dispatch thread is busy with non-dispatch work —
the number the async services layer exists to drive toward zero;
tools/bench_trainer_loop.py's occupancy mode records it on/off).
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional

from jax import monitoring
from jax.profiler import TraceAnnotation

SPAN_RING = 4096  # records kept per span name; older ones fall off


class SpanRecord(NamedTuple):
    name: str
    start: float             # time.perf_counter() at entry
    duration: float          # seconds
    count: Optional[int]     # what was counted at this boundary (the
    #                          feed's queue depth; a compile's persistent-
    #                          cache misses), where there is one
    label: Optional[str] = None  # the program a compile record is of


# One ring per name. No lock: a name's ring is made by dict.setdefault and
# filled by deque.append, both atomic in CPython, so the threads that record
# (the feed's producer, the dispatch thread) share nothing they could wait on.
_rings: Dict[str, collections.deque] = {}


class span:
    """`with span("train/dispatch"):` — one span at a layer boundary.

    Always on: with no capture running the TraceAnnotation is a no-op and
    the cost is two clock reads and a deque append. `duration` is readable
    after the block. A block left by an exception closes its annotation and
    leaves no record.
    """

    __slots__ = ("name", "count", "start", "duration", "_annotation")

    def __init__(self, name: str, count: Optional[int] = None):
        self.name, self.count = name, count
        self.duration = 0.0

    def __enter__(self) -> "span":
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.duration = time.perf_counter() - self.start
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is None:
            _append(SpanRecord(self.name, self.start, self.duration,
                               self.count))


def _append(record: SpanRecord) -> None:
    ring = _rings.get(record.name)
    if ring is None:
        ring = _rings.setdefault(record.name,
                                 collections.deque(maxlen=SPAN_RING))
    ring.append(record)


def spans(name: Optional[str] = None) -> List[SpanRecord]:
    """The records kept, oldest first: of one name, or of every name (by
    start time). Nothing is written anywhere unless a caller asks here."""
    if name is not None:
        return list(_rings.get(name, ()))
    out = [r for ring in list(_rings.values()) for r in list(ring)]
    return sorted(out, key=lambda r: r.start)


# --- JAX's compile stages as records -----------------------------------------

COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# JAX stamps its stages with time.time(); every record is on perf_counter
_WALL_TO_PERF = time.perf_counter() - time.time()
# per thread: `depth`, the compile stages open now; `cache`, [cache requests,
# cache hits] since the thread's last `compile/backend` record (both events
# fire inside that stage, before it ends)
_thread = threading.local()


def _program(fun_name) -> Optional[str]:
    """`jit(train_step)` -> `train_step`: the trace stage names the
    function, the later stages the program made of it."""
    if not isinstance(fun_name, str):
        return None
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _on_stage_start(event: str, value, **_) -> None:
    if event in COMPILE_STAGES:
        _thread.depth = getattr(_thread, "depth", 0) + 1


def _on_cache_event(event: str, **_) -> None:
    if event == _CACHE_REQUEST or event == _CACHE_HIT:
        seen = getattr(_thread, "cache", None)
        if seen is None:
            seen = _thread.cache = [0, 0]
        seen[event == _CACHE_HIT] += 1


def _on_compile_stage(event: str, start_time: float, end_time: float,
                      **kw) -> None:
    name = COMPILE_STAGES.get(event)
    if name is None:
        return
    _thread.depth = max(getattr(_thread, "depth", 0) - 1, 0)
    if _thread.depth and name == "compile/trace":
        # a trace inside another stage of this thread (the jits `jnp` calls
        # while a function is traced or lowered): its seconds are that
        # stage's, so summing `compile/trace` counts each second once
        return
    count = None
    if name == "compile/backend":
        seen = getattr(_thread, "cache", None)
        if seen and seen[0]:
            count = seen[0] - seen[1]    # 1 on a miss, 0 on a hit
        _thread.cache = [0, 0]
    _append(SpanRecord(name, start_time + _WALL_TO_PERF,
                       end_time - start_time, count,
                       _program(kw.get("fun_name"))))


# once, at import: a compile then records itself (JAX reports a stage's
# start as a scalar, its interval when it ends); a call that compiles
# nothing emits no event and costs nothing here
monitoring.register_scalar_listener(_on_stage_start)
monitoring.register_event_time_span_listener(_on_compile_stage)
monitoring.register_event_listener(_on_cache_event)


def compile_records(since: float = float("-inf")) -> List[SpanRecord]:
    """The `compile/*` records that started at or after `since`
    (perf_counter), by start time."""
    return sorted((r for name in COMPILE_STAGES.values()
                   for r in spans(name) if r.start >= since),
                  key=lambda r: r.start)


class StepTimer:
    """Sliding-window wall-time stats for the training hot loop."""

    def __init__(self, *, window: int = 50,
                 images_per_step: Optional[int] = None):
        self.window = window
        self.images_per_step = images_per_step
        self._durations: collections.deque = collections.deque(maxlen=window)
        self._host: collections.deque = collections.deque(maxlen=window)
        self._host_pending = 0.0
        self._last: Optional[float] = None

    def tick(self, now: Optional[float] = None, steps: int = 1) -> None:
        """Mark the end of `steps` training steps (a multi-step dispatch
        counts each scanned step); the first call only arms the timer."""
        now = time.perf_counter() if now is None else now
        if self._last is not None:
            per_step = (now - self._last) / max(1, steps)
            host_per_step = self._host_pending / max(1, steps)
            for _ in range(max(1, steps)):
                self._durations.append(per_step)
                self._host.append(host_per_step)
        self._host_pending = 0.0
        self._last = now

    def note_host(self, seconds: float) -> None:
        """Accumulate dispatch-thread host-work time attributed to the
        steps of the NEXT tick (call any number of times per iteration)."""
        self._host_pending += seconds

    @property
    def last_step_ms(self):
        """Most recent per-step wall ms (None before the second tick) —
        the flight recorder's per-record step time."""
        return 1e3 * self._durations[-1] if self._durations else None

    @property
    def last_host_ms(self):
        """Most recent per-step dispatch-thread host-work ms."""
        return 1e3 * self._host[-1] if self._host else None

    def __len__(self) -> int:
        return len(self._durations)

    def summary(self, prefix: str = "perf/") -> Dict[str, float]:
        """Stats over the current window; empty dict until 2+ ticks."""
        if not self._durations:
            return {}
        ds = sorted(self._durations)
        n = len(ds)
        mean = sum(ds) / n
        out = {
            f"{prefix}step_ms_mean": 1e3 * mean,
            f"{prefix}step_ms_p50": 1e3 * ds[n // 2],
            f"{prefix}step_ms_p90": 1e3 * ds[min(n - 1, (9 * n) // 10)],
            f"{prefix}step_ms_max": 1e3 * ds[-1],
            f"{prefix}steps_per_sec": 1.0 / mean if mean > 0 else 0.0,
        }
        if self.images_per_step and mean > 0:
            out[f"{prefix}images_per_sec"] = self.images_per_step / mean
        if self._host:
            host_mean = sum(self._host) / len(self._host)
            out[f"{prefix}host_ms_mean"] = 1e3 * host_mean
            out[f"{prefix}dispatch_occupancy"] = \
                host_mean / mean if mean > 0 else 0.0
        return out


class StartupProfile:
    """Named-phase wall-clock breakdown of time-to-first-step (ISSUE 5).

    The trainer brackets each startup phase (`init`, `restore`, `data`,
    `warmup`) with `phase()` and stamps `first_step()` at the first proven
    device-progress point; `summary()` is the breakdown the warm-start
    bench (tools/bench_startup.py) A/Bs cold-vs-warm. Phases are additive
    and disjoint; `total_ms` runs from construction to the first-step
    stamp, so untracked gaps (imports inside phases, loader thread spin-up)
    are visible as total minus the named parts rather than hidden. Each
    phase is a `span("startup/<name>")`, so it sits in `spans()` beside
    the compile records it holds, and in any capture.
    """

    def __init__(self) -> None:
        self._t0 = time.perf_counter()
        self._phases: Dict[str, float] = {}
        self._first_step_ms: Optional[float] = None

    def phase(self, name: str):
        """Context manager accumulating wall time under `name`."""
        @contextlib.contextmanager
        def _cm():
            timed = span(f"startup/{name}")
            try:
                with timed:
                    yield self
            finally:
                self._phases[name] = self._phases.get(name, 0.0) \
                    + timed.duration * 1e3
        return _cm()

    def first_step(self) -> None:
        """Stamp the first completed training step (idempotent — the first
        call wins; later materializations are steady state)."""
        if self._first_step_ms is None:
            self._first_step_ms = (time.perf_counter() - self._t0) * 1e3

    @property
    def done(self) -> bool:
        return self._first_step_ms is not None

    def summary(self, prefix: str = "perf/startup/") -> Dict[str, float]:
        out = {f"{prefix}{k}_ms": v for k, v in self._phases.items()}
        if self._first_step_ms is not None:
            out[f"{prefix}total_ms"] = self._first_step_ms
        return out


class TraceCapture:
    """jax.profiler capture windows: one scheduled, any number triggered.

    Call maybe_start(step) before dispatching the step and maybe_stop(step)
    after it; each capture brackets exactly `num_steps` steps. Two ways a
    window opens (ISSUE 6):

    - scheduled (the PR-1 behavior): with `schedule=True` and a logdir, one
      one-shot capture starts at the first boundary >= start_step;
    - triggered: with `trigger_path` set, touching that file starts a
      capture at the next boundary (one touch, one capture; touch again
      for another). The poll is one os.stat per boundary, and only when a
      trigger path is configured, so default runs pay nothing.

    Trigger consumption is mtime-keyed, not remove-keyed: each process
    captures when it sees a NEW mtime and remembers it, and only the
    `consume` process (the trainer passes the chief) deletes the file —
    at the END of its capture, not the start. Multi-process jobs sharing
    a filesystem would otherwise race: an at-start remove wins on
    whichever boundary stats first, and every later-polling peer
    (possibly the chief, the only process that digests) silently did
    nothing. Deferring removal to capture-end leaves the file visible for
    the full num_steps window — SPMD hosts run boundaries in near-
    lockstep, so every peer's poll lands inside it. One mtime serves one
    capture per process (a touch DURING a capture is absorbed by the
    removal at its end), and an undeletable file degrades to
    once-per-touch instead of a capture loop.

    `on_capture(stop_step)` fires after each capture closes — the trainer
    hands the trace to the services worker for in-process digestion.
    Inactive (and free) when logdir is empty.
    """

    def __init__(self, logdir: str, *, start_step: int = 10,
                 num_steps: int = 5, schedule: bool = True,
                 trigger_path: str = "", consume: bool = True,
                 on_capture: Optional[Callable[[int], None]] = None):
        self.logdir = logdir
        self.start_step = start_step
        self.num_steps = num_steps
        self.trigger_path = trigger_path if logdir else ""
        self.consume = consume
        self.on_capture = on_capture
        self._active = False
        self._scheduled_done = not (schedule and logdir and num_steps > 0)
        self._stop_at = 0
        self._served_mtime: Optional[int] = None
        self._consume_pending = False
        self.captures = 0

    @property
    def active(self) -> bool:
        return self._active

    def _begin(self, step: int) -> None:
        import jax

        jax.profiler.start_trace(self.logdir)
        self._active = True
        self._stop_at = step + self.num_steps

    def maybe_start(self, step: int) -> None:
        if self._active:
            return
        if not self._scheduled_done and step >= self.start_step:
            self._scheduled_done = True
            self._begin(step)
            return
        if self.trigger_path and self.num_steps > 0:
            try:
                mtime = os.stat(self.trigger_path).st_mtime_ns
            except OSError:
                return  # absent (or unreadable): nothing to serve
            if mtime == self._served_mtime:
                return  # this touch already got its capture
            self._served_mtime = mtime
            self._consume_pending = self.consume
            self._begin(step)

    def _consume_trigger(self) -> None:
        if not self._consume_pending:
            return
        self._consume_pending = False
        try:
            os.remove(self.trigger_path)
        except OSError:
            pass  # mtime guard prevents a re-trigger loop

    def maybe_stop(self, step: int, sync=None) -> None:
        """`step` is the number of steps completed so far; pass the step's
        outputs as `sync` so the trace contains the device execution, not just
        its dispatch (the train step is pure, so only blocking on its results
        guarantees completion)."""
        if not self._active or step < self._stop_at:
            return
        import jax

        if sync is not None:
            jax.block_until_ready(sync)
        jax.profiler.stop_trace()
        self._active = False
        self.captures += 1
        self._consume_trigger()
        if self.on_capture is not None:
            self.on_capture(step)

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._consume_trigger()
