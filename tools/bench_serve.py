"""Serving-plane load bench: bursty trace replay, cold vs warm start.

ROADMAP item 1's measurement: the continuous-batching sampler server
(`dcgan_tpu/serve`, ISSUE 9) replaying a heavy-traffic arrival trace —
Poisson steady-state with a burst segment at several times the base rate
— against one persistent compile cache, twice:

  arm "cold": fresh cache dir — every bucket program compiles, the cache
      is primed (the first-deploy cost);
  arm "warm": same cache dir — the restart path: bucket programs
      deserialize, cold-start drops to restore + bounded IO.

and emits ONE BENCH-style JSON line: per-arm p50/p99 request latency,
samples/sec/chip, queue depth, the cold-start breakdown, compile-cache
hit counters, and the pass/fail of the invariants the serving plane
exists to hold:

  - zero sampler recompiles after the AOT bucket warmup on BOTH arms
    (every served batch hits a precompiled bucket);
  - the warm arm's cache has zero misses and nonzero hits (the restart
    actually deserialized);
  - every submitted request completed (the drain contract under a finite
    trace).

`--fleet N` (ISSUE 19) swaps the cold/warm pair for a SOLO vs FLEET
comparison over the same burst trace: one bare server vs N replicas
behind the failover router — with live fire in the fleet arm: a chaos
fault kills replica 1 mid-trace (must become a failover, zero failed
requests) and a newly finalized checkpoint step is injected on disk
mid-trace for the promotion watcher to hot-swap onto the survivors
(must be recompile-free: compile_requests_delta == 0 per survivor).
One BENCH line: per-arm p50/p99, dropped/failed, recompile counters,
and the promotion swap time.

`--smoke` shrinks the model, trace, and budgets to the tier-1 pin
(tests/test_tools_serve.py, the chaos-marker pattern); the full-size run is
the standalone capture. CPU-only by design — the serving economics
story on chips comes from the module tracks; this tool certifies the
MECHANISM.

    JAX_PLATFORMS=cpu python tools/bench_serve.py [--smoke] [--fleet 3]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_ckpt(ckpt_dir: str, workdir: str, *, size: int, batch: int,
               timeout: float, max_steps: int = 1) -> None:
    """One tiny trainer run to produce the checkpoint both arms serve."""
    argv = [
        sys.executable, "-m", "dcgan_tpu.train",
        "--synthetic", "--max_steps", str(max_steps),
        "--batch_size", str(batch), "--output_size", str(size),
        "--gf_dim", "8", "--df_dim", "8",
        "--sample_every_steps", "0", "--activation_summary_steps", "0",
        "--save_summaries_secs", "0", "--save_model_secs", "1e9",
        "--no_tensorboard",
        "--checkpoint_dir", ckpt_dir,
        "--sample_dir", os.path.join(workdir, "samples"),
    ]
    res = subprocess.run(argv, cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=timeout)
    if res.returncode != 0:
        raise RuntimeError(f"checkpoint trainer rc={res.returncode}: "
                           f"{(res.stderr or '')[-800:]}")


def make_trace(path: str, *, requests: int, rps: float, burst_factor: float,
               burst_frac: float, max_images: int, seed: int) -> dict:
    """Deterministic Poisson arrivals with a mid-trace burst segment at
    burst_factor x the base rate — the 'heavy traffic' shape: steady
    load, then a spike that must ride the batcher + backpressure instead
    of a queue blowup. Returns the trace summary."""
    rng = np.random.default_rng(seed)
    burst_start = int(requests * (0.5 - burst_frac / 2))
    burst_end = int(requests * (0.5 + burst_frac / 2))
    t = 0.0
    arrivals = []
    for i in range(requests):
        rate = rps * (burst_factor if burst_start <= i < burst_end else 1.0)
        t += float(rng.exponential(1e3 / rate))
        arrivals.append({"t_ms": t,
                         "num_images": int(rng.integers(1, max_images + 1))})
    with open(path, "w") as f:
        json.dump({"arrivals": arrivals}, f)
    return {"requests": requests,
            "images": sum(a["num_images"] for a in arrivals),
            "span_ms": round(t, 1),
            "burst": {"factor": burst_factor,
                      "requests": burst_end - burst_start}}


def _run_arm(name: str, *, ckpt_dir: str, cache_dir: str, trace: str,
             workdir: str, max_batch: int, max_wait_ms: float,
             timeout: float) -> dict:
    report = os.path.join(workdir, f"report-{name}.json")
    argv = [
        sys.executable, "-m", "dcgan_tpu.serve",
        "--checkpoint_dir", ckpt_dir,
        "--compile_cache_dir", cache_dir,
        "--trace", trace,
        "--max_batch", str(max_batch),
        "--max_wait_ms", str(max_wait_ms),
        "--report", report,
        "--platform", "cpu",
    ]
    t0 = time.perf_counter()
    res = subprocess.run(argv, cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=timeout)
    if res.returncode != 0:
        raise RuntimeError(f"{name} serve rc={res.returncode}: "
                           f"{(res.stdout or '')[-400:]} "
                           f"{(res.stderr or '')[-800:]}")
    with open(report) as f:
        row = json.load(f)
    row["process_wall_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    return row


def _inject_step(donor_dir: str, serve_dir: str, step: int) -> None:
    """Deliver `step` into `serve_dir` the way a trainer would: integrity
    sidecars first, then the step dir copied under a tmp name and RENAMED
    in (the Orbax finalization contract) — the fleet's promotion watcher
    can never see a half-copied step."""
    import shutil

    integ = os.path.join(donor_dir, "integrity")
    if os.path.isdir(integ):
        dst = os.path.join(serve_dir, "integrity")
        os.makedirs(dst, exist_ok=True)
        for name in os.listdir(integ):
            if name.startswith(f"{step}."):
                shutil.copy2(os.path.join(integ, name),
                             os.path.join(dst, name))
    tmp = os.path.join(serve_dir, f"tmp.promote.{step}")
    shutil.copytree(os.path.join(donor_dir, str(step)), tmp)
    os.rename(tmp, os.path.join(serve_dir, str(step)))


def _run_fleet_arm(*, replicas: int, ckpt_dir: str, donor_dir: str,
                   cache_dir: str, trace: str, workdir: str,
                   max_batch: int, max_wait_ms: float,
                   timeout: float) -> dict:
    """The live-fire arm: N replicas replay the trace while a chaos
    fault kills replica 1 mid-trace and a newly finalized step-2
    checkpoint is injected for the promotion watcher. Orchestrated via
    Popen (the injection must land WHILE the fleet serves); ends with a
    SIGTERM drain once the promotion is observed."""
    import signal
    import threading

    report = os.path.join(workdir, "report-fleet.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["DCGAN_CHAOS"] = json.dumps(
        {"fault_replica": 1, "replica_kill_at_dispatch": 2})
    argv = [
        sys.executable, "-m", "dcgan_tpu.serve",
        "--checkpoint_dir", ckpt_dir,
        "--compile_cache_dir", cache_dir,
        "--fleet", str(replicas),
        "--watch_promotions", "--watch_interval_secs", "0.25",
        "--trace", trace,
        "--max_batch", str(max_batch),
        "--max_wait_ms", str(max_wait_ms),
        "--report", report,
        "--platform", "cpu",
    ]
    t0 = time.perf_counter()
    proc = subprocess.Popen(argv, cwd=REPO, env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    lines = []
    reader = threading.Thread(
        target=lambda: [lines.append(l) for l in proc.stdout], daemon=True)
    reader.start()

    def _wait_for(token: str, secs: float) -> None:
        deadline = time.monotonic() + secs
        while time.monotonic() < deadline \
                and not any(token in l for l in lines):
            if proc.poll() is not None:
                break
            time.sleep(0.1)
        if not any(token in l for l in lines):
            raise RuntimeError(
                f"fleet arm never saw {token!r}: {''.join(lines)[-1200:]}")

    try:
        _wait_for("warm: serving", timeout)
        _wait_for("replica 1 UNHEALTHY", 60)
        _inject_step(donor_dir, ckpt_dir, 2)
        _wait_for("serve fleet: promoted", 120)
        time.sleep(1.0)  # some post-promotion load on the new weights
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
    reader.join(timeout=10)
    if rc != 0:
        raise RuntimeError(f"fleet serve rc={rc}: "
                           f"{''.join(lines)[-1200:]}")
    with open(report) as f:
        row = json.load(f)
    row["process_wall_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    return row


def _arm_summary(r: dict) -> dict:
    return {
        "p50_ms": r.get("serve/p50_ms"),
        "p99_ms": r.get("serve/p99_ms"),
        "samples_per_sec_chip": round(
            r.get("serve/samples_per_sec", 0.0) / max(1, r["devices"]), 2),
        "queue_depth_max": r.get("serve/queue_depth_max"),
        "pad_frac": round(r.get("serve/pad_frac", 0.0), 4),
        "batches": r.get("serve/batches"),
        "completed": r.get("serve/completed"),
        "dropped": r.get("serve/dropped"),
        "cold_start_ms": round(r.get("serve/cold_start_ms", 0.0), 1),
        "restore_ms": round(r.get("serve/restore_ms", 0.0), 1),
        "warmup_ms": round(r.get("serve/warmup_ms", 0.0), 1),
        "recompiles_after_warmup": r.get("serve/recompiles_after_warmup"),
        "cache": {k: int(r.get(f"perf/compile_cache_{k}", 0))
                  for k in ("requests", "hits", "misses")},
        "process_wall_ms": r["process_wall_ms"],
    }


def _with_idle_tail(src: str, dst: str, *, every_ms: float,
                    count: int) -> None:
    """Copy a trace and append a low-rate single-image tail: headroom
    for the fleet arm's mid-trace orchestration (kill -> inject ->
    promote) so the process cannot drain before the promotion lands.
    The arm is SIGTERMed once the promotion is observed, so the tail's
    length bounds the wait, not the runtime."""
    with open(src) as fh:
        arrivals = json.load(fh)["arrivals"]
    t = arrivals[-1]["t_ms"]
    for _ in range(count):
        t += every_ms
        arrivals.append({"t_ms": t, "num_images": 1})
    with open(dst, "w") as fh:
        json.dump({"arrivals": arrivals}, fh)


def _run_fleet_bench(args, *, size: int, batch: int, requests: int,
                     rps: float, max_images: int, max_batch: int,
                     max_wait_ms: float) -> dict:
    """The --fleet comparison: a bare server vs N replicas over the same
    burst trace, with the kill + promotion live fire in the fleet arm."""
    import shutil

    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "ckpt")
        donor = os.path.join(tmp, "donor")
        cache = os.path.join(tmp, "compile-cache")
        trace = os.path.join(tmp, "trace.json")
        trace_fleet = os.path.join(tmp, "trace-fleet.json")
        _make_ckpt(ckpt, tmp, size=size, batch=batch, timeout=args.timeout)
        # the donor lineage: resume the same run one step further — its
        # step-2 dir is the "newly finalized" step injected mid-trace
        shutil.copytree(ckpt, donor)
        _make_ckpt(donor, tmp, size=size, batch=batch,
                   timeout=args.timeout, max_steps=2)
        trace_meta = make_trace(trace, requests=requests, rps=rps,
                                burst_factor=8.0, burst_frac=0.25,
                                max_images=max_images, seed=0)
        _with_idle_tail(trace, trace_fleet, every_ms=400.0, count=150)
        solo = _run_arm("solo", ckpt_dir=ckpt, cache_dir=cache,
                        trace=trace, workdir=tmp, max_batch=max_batch,
                        max_wait_ms=max_wait_ms, timeout=args.timeout)
        fleet = _run_fleet_arm(replicas=args.fleet, ckpt_dir=ckpt,
                               donor_dir=donor, cache_dir=cache,
                               trace=trace_fleet, workdir=tmp,
                               max_batch=max_batch,
                               max_wait_ms=max_wait_ms,
                               timeout=args.timeout)

    s, f = _arm_summary(solo), _arm_summary(fleet)
    fl = fleet["fleet"]
    last = fl["promotions"][-1] if fl["promotions"] else []
    survivors = sorted(i for i in range(args.fleet) if i != 1)
    checks = {
        # the solo arm replays the finite burst trace to completion
        "solo_all_completed": s["completed"] == requests
                              and s["dropped"] == 0,
        # the kill became a failover, not client-visible failures
        "fleet_zero_failed": fleet["failed"] == 0,
        "fleet_all_submitted_completed":
            fleet["completed"] == fleet["submitted"]
            and f["dropped"] == 0,
        "fleet_killed_replica_drained":
            any(i == 1 for i, _ in fl["unhealthy"]),
        # the watcher promoted exactly the survivors to the new step
        "fleet_promoted_survivors":
            sorted(r.get("replica", -1) for r in last) == survivors
            and all("error" not in r and r.get("step") == 2
                    for r in last),
        "fleet_promotion_zero_recompiles":
            all(r.get("compile_requests_delta") == 0 for r in last),
        "zero_recompiles_after_warmup":
            s["recompiles_after_warmup"] == 0
            and f["recompiles_after_warmup"] == 0,
        "latency_percentiles_present":
            bool(s["p50_ms"] and s["p99_ms"] and f["p50_ms"]
                 and f["p99_ms"]),
    }
    return {
        "label": "bench-serve-fleet",
        "platform": "cpu",
        "model": f"dcgan{size}",
        "replicas": args.fleet,
        "buckets": solo.get("buckets"),
        "trace": trace_meta,
        "solo": s,
        "fleet": {**f,
                  "submitted": fleet["submitted"],
                  "failed": fleet["failed"],
                  "unhealthy": fl["unhealthy"],
                  "failovers": fl["failovers"],
                  "promote_swap_ms": fleet.get("serve/promote_swap_ms"),
                  "promotions": fl["promotions"]},
        "checks": checks,
        "ok": all(checks.values()),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + short trace (the tier-1 pin)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="N replicas: run the solo-vs-fleet live-fire "
                         "comparison (replica kill + weight promotion "
                         "mid-trace) instead of the cold/warm pair")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-subprocess budget (seconds)")
    args = ap.parse_args()
    if args.smoke:
        size, batch, requests, rps, max_images = 16, 8, 24, 40.0, 8
        max_batch, max_wait_ms = 16, 5.0
    else:
        size, batch, requests, rps, max_images = 64, 16, 200, 50.0, 16
        max_batch, max_wait_ms = 64, 10.0

    if args.fleet:
        row = _run_fleet_bench(args, size=size, batch=batch,
                               requests=requests, rps=rps,
                               max_images=max_images, max_batch=max_batch,
                               max_wait_ms=max_wait_ms)
        print(json.dumps(row))
        sys.exit(0 if row["ok"] else 1)

    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "ckpt")
        cache = os.path.join(tmp, "compile-cache")
        trace = os.path.join(tmp, "trace.json")
        _make_ckpt(ckpt, tmp, size=size, batch=batch, timeout=args.timeout)
        trace_meta = make_trace(trace, requests=requests, rps=rps,
                                burst_factor=8.0, burst_frac=0.25,
                                max_images=max_images, seed=0)
        cold = _run_arm("cold", ckpt_dir=ckpt, cache_dir=cache, trace=trace,
                        workdir=tmp, max_batch=max_batch,
                        max_wait_ms=max_wait_ms, timeout=args.timeout)
        warm = _run_arm("warm", ckpt_dir=ckpt, cache_dir=cache, trace=trace,
                        workdir=tmp, max_batch=max_batch,
                        max_wait_ms=max_wait_ms, timeout=args.timeout)

    c, w = _arm_summary(cold), _arm_summary(warm)
    checks = {
        # every served batch hit a precompiled bucket — on both arms
        "cold_zero_recompiles_after_warmup":
            c["recompiles_after_warmup"] == 0,
        "warm_zero_recompiles_after_warmup":
            w["recompiles_after_warmup"] == 0,
        # the warm restart actually deserialized from the primed cache
        "warm_has_hits": w["cache"]["hits"] > 0,
        "warm_zero_misses": w["cache"]["misses"] == 0,
        "cold_has_misses": c["cache"]["misses"] > 0,
        # finite trace + drain: nothing lost, nothing left queued
        "cold_all_completed": c["completed"] == requests
                              and c["dropped"] == 0,
        "warm_all_completed": w["completed"] == requests
                              and w["dropped"] == 0,
        "latency_percentiles_present":
            bool(c["p50_ms"] and c["p99_ms"] and w["p50_ms"]
                 and w["p99_ms"]),
    }
    row = {
        "label": "bench-serve",
        "platform": "cpu",
        "model": f"dcgan{size}",
        "buckets": cold.get("buckets"),
        "trace": trace_meta,
        "cold": c,
        "warm": w,
        "speedup": {
            "warmup_ms": round(c["warmup_ms"] / max(w["warmup_ms"], 1e-9),
                               2),
            "cold_start_ms": round(
                c["cold_start_ms"] / max(w["cold_start_ms"], 1e-9), 2),
        },
        "checks": checks,
        "ok": all(checks.values()),
    }
    print(json.dumps(row))
    sys.exit(0 if row["ok"] else 1)


if __name__ == "__main__":
    main()
