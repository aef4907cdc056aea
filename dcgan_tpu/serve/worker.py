"""The serving plane's dispatch thread: cold start, batch loop, drain.

The collective-thread rule (DESIGN.md §6b) says every device program and
every collective stays on ONE thread per process. In the trainer that
thread is the one that entered `train()`; in the serving plane it is this
worker: the checkpoint restore (an Orbax collective on multi-host
topologies), the AOT bucket compiles, and every sampler dispatch all run
here, while callers only touch the thread-safe queue. The thread is a
DECLARED dispatch-thread owner — `analysis/core.py`'s
`Config.dispatch_thread_targets` names `ServeWorker._run`, so DCG001
does not flag the collectives reachable from this thread target (they
are exactly where the rule wants them), and at runtime the worker enters
`tripwire.dispatch_scope()` so under DCGAN_THREAD_CHECKS=1 any OTHER
thread touching a wrapped collective entry point trips loudly.

Lifecycle owned here:
- cold start: (optional) persistent-compile-cache wiring + monitor, the
  source's restore/deserialize, ladder resolution, AOT compile of every
  bucket rung — timed into the server's cold_ms/compile_ms breakdown;
- warm serving: `server._next_batch()` -> assemble z/labels -> bucketed
  dispatch -> split images back per request, resolving Responses with
  latency accounting;
- weight promotion (ISSUE 19): a PromotionTicket control op popped from
  the batcher IS the drain barrier — the loop is sequential, so the
  in-flight dispatch has fully resolved before the swap. `_promote`
  reloads the newest finalized step into the existing state template
  (same avals/shardings — no new programs), re-primes every rung with a
  throwaway dispatch (the PR 14 prime() trick re-links the swapped
  weights through every cached executable), and resumes; the compile
  cache monitor's request delta proves zero recompiles across the swap.
- drain: once the server stops intake, the loop keeps flushing until the
  queue is empty (FIFO, same batching rules), then exits cleanly.

A failure anywhere fails the in-flight requests and poisons the server —
never a silent half-service. (Exception: a reload that fails BEFORE the
state swap fails only its ticket — the old weights are intact, so the
replica keeps serving them; the fleet surfaces the error.)

Chaos hooks (testing/chaos.py, fleet drills): the per-dispatch counter
feeds `should_kill_replica` / `maybe_replica_hang` /
`maybe_replica_slow_beat`, so a FaultPlan can crash, wedge, or
heartbeat-mute exactly one replica at its n-th dispatch.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Tuple

import numpy as np

from dcgan_tpu.serve.server import PromotionTicket, ServeError
from dcgan_tpu.testing import chaos


class ServeWorker:
    """Single dispatch thread bound to one SamplerServer."""

    def __init__(self, server):
        self._server = server
        self._dispatch_index = 0   # 1-based, bumped per request batch
        name = "dcgan-serve-dispatch" if server.replica_index == 0 \
            else f"dcgan-serve-dispatch-{server.replica_index}"
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    def is_alive(self) -> bool:
        return self._thread.is_alive()

    # -- the dispatch thread ------------------------------------------------

    def _run(self) -> None:
        # declared dispatch-thread owner (DCG001 allowlist): collectives
        # REACHED FROM here are on the right thread by definition
        from dcgan_tpu.analysis import tripwire

        s = self._server
        with tripwire.dispatch_scope():
            try:
                self._cold_start()
            except BaseException as e:  # noqa: BLE001 — reported to callers
                s._fail_all(e)
                s._ready.set()
                return
            s._t_warm = time.monotonic()
            s._ready.set()
            while True:
                batch = s._next_batch()
                if batch is None:
                    return
                if isinstance(batch, PromotionTicket):
                    try:
                        self._promote(batch)
                    except BaseException as e:  # noqa: BLE001
                        batch._fail(e)
                        s._fail_all(e)
                        return
                    continue
                spans, total = batch
                self._dispatch_index += 1
                idx = self._dispatch_index
                try:
                    mute = chaos.maybe_replica_slow_beat(
                        s.replica_index, idx)
                    if mute:
                        s._mute_beats(mute)
                    chaos.maybe_replica_hang(s.replica_index, idx)
                    if chaos.should_kill_replica(s.replica_index, idx):
                        raise ServeError(
                            f"chaos: replica {s.replica_index} killed "
                            f"before dispatch {idx}")
                    self._dispatch(spans, total)
                    s._bump_beat()
                except BaseException as e:  # noqa: BLE001
                    for p, _ in spans:
                        p.resp._fail(e)
                    s._fail_all(e)
                    return

    def _cold_start(self) -> None:
        s = self._server
        t0 = time.perf_counter()
        from dcgan_tpu.train import warmup

        if warmup.configure_compile_cache(
                warmup.resolve_cache_dir(s.cache_dir)) is not None:
            s._monitor = warmup.CompileCacheMonitor()
        s.meta = s.source.prepare()
        t_restore = time.perf_counter()
        s.ladder = s._resolve_ladder()
        from dcgan_tpu.serve.buckets import compile_buckets

        compiled, timings = compile_buckets(s.source.bucket_plan(s.ladder))
        s.source.bind(compiled)
        s.compile_ms = timings
        # prime every COMPILED rung with one throwaway end-to-end
        # dispatch: the FIRST execution of a compiled sharded program
        # also compiles the input-resharding transfer for host-built args
        # (one tiny program per bucket shape) — paying it here keeps the
        # zero-recompile guarantee literal for live traffic, and a broken
        # rung fails the cold start loudly instead of the first request.
        # (Sources with an empty bucket plan — test fakes — have no
        # executables to prime.)
        for b in sorted(compiled):
            z0 = np.zeros((b, s.source.z_dim), np.float32)
            lbl0 = np.zeros((b,), np.int32) \
                if s.source.num_classes else None
            s.source.sample(b, z0, lbl0)
        t_warm = time.perf_counter()
        s.cold_ms = {
            "restore_ms": (t_restore - t0) * 1e3,
            "warmup_ms": (t_warm - t_restore) * 1e3,
            "cold_start_ms": (t_warm - t0) * 1e3,
        }
        if s._monitor is not None:
            s._cache_post_warmup = s._monitor.counters()

    def _promote(self, ticket: PromotionTicket) -> None:
        """Hot-swap weights to the newest finalized checkpoint step.
        Runs ON the dispatch thread, after the in-flight batch resolved
        (the drain barrier). A reload failure BEFORE the swap fails only
        the ticket — old weights intact, the replica keeps serving; a
        re-prime failure raises (caller poisons the server: the swapped
        state could not dispatch)."""
        s = self._server
        reload_fn = getattr(s.source, "reload", None)
        if reload_fn is None:
            ticket._fail(ServeError(
                f"{type(s.source).__name__} does not support weight "
                "promotion (no reload())"))
            return
        base = s._monitor.counters()["requests"] \
            if s._monitor is not None else None
        t0 = time.perf_counter()
        try:
            meta = reload_fn()
        except BaseException as e:  # noqa: BLE001 — replica survives
            ticket._fail(e)
            return
        # re-prime every rung: the first execution of a cached program
        # with the swapped host-built args re-links the input-resharding
        # transfer — a throwaway dispatch per bucket keeps the
        # zero-recompile guarantee literal for the first real request
        # after the swap
        rungs = getattr(s.source, "compiled_buckets", tuple)()
        for b in rungs:
            z0 = np.zeros((b, s.source.z_dim), np.float32)
            lbl0 = np.zeros((b,), np.int32) \
                if s.source.num_classes else None
            s.source.sample(b, z0, lbl0)
        swap_ms = (time.perf_counter() - t0) * 1e3
        delta = (s._monitor.counters()["requests"] - base) \
            if base is not None else None
        s.meta.update(meta)
        s.promotions += 1
        s.promote_swap_ms = swap_ms
        s._bump_beat()
        ticket._resolve({"replica": s.replica_index,
                         "step": meta.get("step"),
                         "swap_ms": swap_ms,
                         "compile_requests_delta": delta})

    def _dispatch(self, spans: List[Tuple], total: int) -> None:
        s = self._server
        # re-check caller-provided latent widths against the now-resolved
        # z_dim: submit() can only validate once the cold start has run,
        # so a bad-width request that slipped in during the cold-start
        # window fails ITS response here — one malformed request must
        # never poison the server for everyone else
        bad = [(p, take) for p, take in spans
               if p.z is not None and p.z.shape[1] != s.source.z_dim]
        if bad:
            for p, _ in bad:
                p.resp._fail(ValueError(
                    f"z width {p.z.shape[1]} != source z_dim "
                    f"{s.source.z_dim}"))
            spans = [sp for sp in spans if sp not in bad]
            total = sum(take for _, take in spans)
            if not spans:
                return
        bucket = s.ladder.snap(total)
        t0 = time.monotonic()
        z_rows = []
        lbl_rows = []
        conditional = s.source.num_classes > 0
        for p, take in spans:
            if p.t_first_dispatch is None:
                p.t_first_dispatch = t0
            z_rows.append(p.take_z(take, s.source.z_dim, s.seed))
            if conditional:
                lbl_rows.append(p.take_labels(take))
        pad = bucket - total
        if pad:
            # padding rows are throwaway work: z=0 is a valid latent, the
            # rows are sliced off before any response sees them
            z_rows.append(np.zeros((pad, s.source.z_dim), np.float32))
            if conditional:
                lbl_rows.append(np.zeros((pad,), np.int32))
        z = np.concatenate(z_rows)
        labels = np.concatenate(lbl_rows) if conditional else None
        imgs = s.source.sample(bucket, z, labels)
        infer_ms = (time.monotonic() - t0) * 1e3
        s._record_batch(bucket, pad)
        offset = 0
        for p, take in spans:
            p.parts.append(imgs[offset:offset + take])
            p.buckets.append(bucket)
            p.infer_ms += infer_ms
            p.delivered += take
            offset += take
            if p.delivered == p.num_images:
                now = time.monotonic()
                total_ms = (now - p.t_submit) * 1e3
                p.resp._resolve(
                    np.concatenate(p.parts) if len(p.parts) > 1
                    else p.parts[0],
                    {"queue_ms": (p.t_first_dispatch - p.t_submit) * 1e3,
                     "infer_ms": p.infer_ms,
                     "total_ms": total_ms,
                     "buckets": list(p.buckets)})
                s._record_done(p, total_ms)
