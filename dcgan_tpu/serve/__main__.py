"""`python -m dcgan_tpu.serve`: the generation-as-a-service entry point.

The first non-training entry point with its own lifecycle:

  cold start   restore the checkpoint ONCE through the single-pass
               verified restore (or deserialize a `.jaxexport` artifact +
               sidecar — no checkpoint needed), AOT-compile the sampler
               at every bucket rung (persistent compile cache honored:
               warm restarts deserialize instead of compiling);
  warm serving replay a recorded arrival trace (`--trace`) or generate a
               deterministic Poisson demo load (`--demo_requests` /
               `--demo_rps`), requests flowing through the continuous
               batcher onto the precompiled buckets;
  drain        SIGTERM/SIGINT stops intake, in-flight and queued
               requests complete in FIFO order, the report/events land,
               and the process exits 0 — a preemption notice becomes a
               clean handoff, not dropped requests.

Usage:
    python -m dcgan_tpu.serve --checkpoint_dir ckpt --demo_requests 64
    python -m dcgan_tpu.serve --artifact sampler.jaxexport \
        --trace trace.json --report report.json --platform cpu

`--report` writes one JSON object (the serve/* metric row + request
accounting) and `--events_dir` mirrors the same row through MetricWriter
into an events.jsonl any existing tooling can tail.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
import time
from typing import List, Optional


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dcgan_tpu.serve",
        description="continuous-batching sampler server with AOT bucket "
                    "plans")
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--checkpoint_dir",
                     help="serve a trained checkpoint (verified restore)")
    src.add_argument("--artifact",
                     help="serve a .jaxexport artifact (+ .json sidecar); "
                          "no checkpoint directory needed")
    p.add_argument("--use_ema", action="store_true",
                   help="checkpoint source: serve the EMA generator")
    p.add_argument("--quantize", default="", choices=["", "int8"],
                   help="checkpoint source: post-training quantize the "
                        "served generator weights (int8 symmetric "
                        "per-channel quantize-dequantize at load; the "
                        "report rides the warm banner)")
    p.add_argument("--preset", default=None,
                   help="named config supplying the architecture instead "
                        "of the checkpoint's config.json")
    from dcgan_tpu.config import add_model_override_flags

    add_model_override_flags(p)
    p.add_argument("--buckets", default=None,
                   help="explicit bucket ladder, e.g. 8,16,32 (default: "
                        "the artifact sidecar's hint, else a doubling "
                        "ladder under --max_batch)")
    p.add_argument("--max_batch", type=int, default=64,
                   help="top bucket of the default ladder")
    p.add_argument("--max_queue", type=int, default=256,
                   help="request-queue bound (drop-oldest past it)")
    p.add_argument("--max_wait_ms", type=float, default=10.0,
                   help="deadline flush: max time the oldest request "
                        "waits for batchmates")
    p.add_argument("--compile_cache_dir", default="",
                   help="persistent compile cache: warm restarts "
                        "deserialize the bucket programs (default: "
                        "JAX_COMPILATION_CACHE_DIR when set, else "
                        ".jax_cache/ in the checkout)")
    p.add_argument("--fleet", type=int, default=0,
                   help="run N health-checked replicas behind the "
                        "failover router (0 = single bare server)")
    p.add_argument("--heartbeat_secs", type=float, default=0.25,
                   help="fleet health-poll cadence")
    p.add_argument("--miss_beats", type=int, default=4,
                   help="consecutive silent health polls before a "
                        "replica is drained from rotation")
    p.add_argument("--watch_promotions", action="store_true",
                   help="fleet mode: watch the checkpoint dir for newly "
                        "finalized steps and hot-swap weights live "
                        "(zero recompiles, zero dropped requests)")
    p.add_argument("--watch_interval_secs", type=float, default=0.5,
                   help="promotion-watcher poll interval")
    p.add_argument("--trace", default=None,
                   help="JSON arrival trace to replay: {\"arrivals\": "
                        "[{\"t_ms\": ..., \"num_images\": ...}, ...]}")
    p.add_argument("--demo_requests", type=int, default=0,
                   help="generate this many Poisson-arrival demo "
                        "requests instead of a trace")
    p.add_argument("--demo_rps", type=float, default=20.0,
                   help="demo load mean arrival rate (requests/sec)")
    p.add_argument("--demo_max_images", type=int, default=8,
                   help="demo load per-request image count is uniform "
                        "in [1, this]")
    p.add_argument("--report", default=None,
                   help="write the final JSON report row here")
    p.add_argument("--events_dir", default=None,
                   help="mirror the serve/* row into events.jsonl here "
                        "(MetricWriter)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None)
    return p


def _load_arrivals(args) -> List[dict]:
    """[{t_ms, num_images}, ...] from --trace or the demo generator."""
    if args.trace:
        with open(args.trace) as f:
            arrivals = json.load(f)["arrivals"]
        return sorted(arrivals, key=lambda a: a["t_ms"])
    if args.demo_requests <= 0:
        return []
    import numpy as np

    rng = np.random.default_rng(args.seed)
    t = 0.0
    out = []
    for _ in range(args.demo_requests):
        t += float(rng.exponential(1e3 / args.demo_rps))
        out.append({"t_ms": t,
                    "num_images": int(rng.integers(
                        1, args.demo_max_images + 1))})
    return out


def build_server(args: argparse.Namespace):
    """(server, fleet-or-None) from parsed flags — the service as `main`
    starts it. Separate from `main` so a caller that wants the responses
    themselves and not a load replay (chip_smoke.py) still constructs
    exactly what `python -m dcgan_tpu.serve` constructs."""
    from dcgan_tpu.config import MODEL_OVERRIDE_FLAGS
    from dcgan_tpu.serve.buckets import parse_buckets
    from dcgan_tpu.serve.fleet import ServeFleet
    from dcgan_tpu.serve.server import SamplerServer
    from dcgan_tpu.serve.sources import ArtifactSource, CheckpointSource
    from dcgan_tpu.train.warmup import resolve_cache_dir

    cache_dir = resolve_cache_dir(args.compile_cache_dir, entry_point=True)

    def _make_source():
        if args.artifact:
            return ArtifactSource(args.artifact)
        return CheckpointSource(
            args.checkpoint_dir, use_ema=args.use_ema, preset=args.preset,
            overrides={n: getattr(args, n) for n in MODEL_OVERRIDE_FLAGS},
            max_batch=args.max_batch, quantize=args.quantize)

    ladder = parse_buckets(args.buckets) if args.buckets else None
    fleet_n = max(0, args.fleet)
    if fleet_n:
        fleet = ServeFleet(
            [_make_source() for _ in range(fleet_n)],
            buckets=(ladder.buckets if ladder is not None else None),
            max_batch=args.max_batch, max_queue=args.max_queue,
            max_wait_ms=args.max_wait_ms,
            cache_dir=cache_dir, seed=args.seed,
            heartbeat_secs=args.heartbeat_secs,
            miss_beats=args.miss_beats,
            watch_promotions=args.watch_promotions,
            watch_interval_secs=args.watch_interval_secs)
        return fleet.servers[0], fleet   # servers[0]: banner/cold-start
    return SamplerServer(_make_source(), ladder=ladder,
                         max_batch=args.max_batch,
                         max_queue=args.max_queue,
                         max_wait_ms=args.max_wait_ms,
                         cache_dir=cache_dir, seed=args.seed), None


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from dcgan_tpu.analysis import tripwire

    tripwire.maybe_install()  # DCGAN_THREAD_CHECKS=1 honors the drill env
    server, fleet = build_server(args)
    fleet_n = len(fleet.servers) if fleet is not None else 0

    # graceful drain on SIGTERM/SIGINT: the handler only flips a flag —
    # the main thread breaks out of the load loop and runs the drain
    stop_event = threading.Event()

    def _on_signal(signum, frame):
        print(f"[dcgan_tpu.serve] received signal {signum}: stopping "
              "intake, draining in-flight requests", flush=True)
        stop_event.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    t0 = time.perf_counter()
    if fleet is not None:
        metas = fleet.start()
        meta = metas[0]
    else:
        meta = server.start()
    cold = server.cold_ms
    cache_note = ""
    if server._monitor is not None:
        c = server._monitor.counters()
        cache_note = (f", cache {int(c['hits'])} hit(s) / "
                      f"{int(c['misses'])} miss(es)")
    print(f"[dcgan_tpu.serve] cold start in "
          f"{cold.get('cold_start_ms', 0.0):.0f} ms "
          f"(restore {cold.get('restore_ms', 0.0):.0f} ms, "
          f"{len(server.ladder.buckets)} bucket(s) "
          f"{list(server.ladder.buckets)} warm in "
          f"{cold.get('warmup_ms', 0.0):.0f} ms{cache_note}) — "
          f"{meta.get('source')} step {meta.get('step')} "
          f"{meta.get('weights')} weights", flush=True)
    if meta.get("resharded"):
        # elastic cold start (ISSUE 12): the checkpoint was saved on a
        # different topology and restored through the sidecar reshard
        rs = meta["resharded"]
        print(f"[dcgan_tpu.serve] cross-topology cold start: checkpoint "
              f"saved on {rs['saved_processes']} process(es) x "
              f"{rs['saved_devices']} device(s), resharded onto this "
              f"host's mesh in {rs['reshard_ms']:.0f} ms", flush=True)
    if fleet is not None:
        print(f"[dcgan_tpu.serve] fleet: {fleet_n} replica(s) warm, "
              f"heartbeat {args.heartbeat_secs:.2f}s x "
              f"{args.miss_beats} miss(es)"
              + (", promotion watcher on" if args.watch_promotions
                 else ""), flush=True)
    print("[dcgan_tpu.serve] warm: serving", flush=True)

    arrivals = _load_arrivals(args)
    intake = fleet if fleet is not None else server
    responses = []
    submitted = 0
    t_load = time.monotonic()
    for arrival in arrivals:
        wait = arrival["t_ms"] / 1e3 - (time.monotonic() - t_load)
        if wait > 0 and stop_event.wait(wait):
            break
        if stop_event.is_set():
            break
        if fleet is not None:
            responses.append(fleet.submit(
                arrival["num_images"],
                client_id=arrival.get("client")))
        else:
            responses.append(server.submit(arrival["num_images"]))
        submitted += 1
    if not arrivals:
        # no load source: idle-serve until a signal arrives
        stop_event.wait()

    interrupted = stop_event.is_set()
    if fleet is not None:
        fleet.stop(drain=True)
    else:
        server.stop(drain=True)
    completed = sum(1 for r in responses if r.done() and r.error is None)
    failed = sum(1 for r in responses if r.done() and r.error is not None)
    report = intake.report()
    row = {
        "label": "serve-report",
        "buckets": list(server.ladder.buckets),
        "meta": meta,
        "devices": _device_count(),
        "submitted": submitted,
        "unsubmitted": len(arrivals) - submitted,
        "completed": completed,
        "failed": failed,
        "interrupted": interrupted,
        "wall_s": round(time.perf_counter() - t0, 3),
        **{k: (round(v, 3) if isinstance(v, float) else v)
           for k, v in report.items()},
    }
    if fleet is not None:
        row["fleet"] = {
            "replicas": fleet_n,
            "unhealthy": [[i, reason] for i, reason
                          in fleet.router.unhealthy_events],
            "failovers": fleet.router.failovers,
            "stop_errors": fleet.stop_errors,
            "promotions": fleet.promotion_results,
            "per_replica": [
                {k: (round(v, 3) if isinstance(v, float) else v)
                 for k, v in r.items()}
                for r in fleet.per_replica_reports()],
        }
    if args.report:
        with open(args.report, "w") as f:
            json.dump(row, f)
            f.write("\n")
    if args.events_dir:
        from dcgan_tpu.utils.metrics import MetricWriter

        writer = MetricWriter(args.events_dir, every_secs=0.0,
                              tensorboard=False)
        writer.write_scalars(int(meta.get("step") or 0), report)
        writer.close()
    print(f"[dcgan_tpu.serve] drain: {int(report['serve/completed'])} "
          f"request(s) completed, {int(report['serve/dropped'])} dropped, "
          "queue empty, clean exit", flush=True)
    return 0


def _device_count() -> int:
    import jax

    return jax.device_count()


if __name__ == "__main__":
    sys.exit(main())
