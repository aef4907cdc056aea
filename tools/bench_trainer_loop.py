"""Measure the REAL trainer hot loop at bench throughput (VERDICT r3 #4).

Every chip img/s number in the captures table comes from bench.py's scanned
harness; the trainer's equivalent path (`--steps_per_call`,
train/trainer.py) was equivalence-tested on CPU but never captured on the
chip — leaving a "the fast path exists only in the benchmark" doubt. This
tool runs the actual `python -m dcgan_tpu.train` entry (synthetic stream so
the host->device feed is not what gets measured — that regime is
bench_realdata.py's row) with the same scan width bench.py uses, and
derives steady-state throughput from the trainer's own stdout step log
(each logged line follows a float() metric sync, so its timestamp is a true
device-progress point, not a dispatch-queue artifact).

Observability cadences are left at measurement-friendly values (no sample
grids, no activation summaries, no TensorBoard histogram pulls) — those
paths carry host transfers; their cost is the trainer's documented
per-cadence overhead, not loop speed.

Prints one JSON line:
  {"label": "trainer-loop", "images_per_sec_chip": R, "window_steps": [a,b],
   "ms_per_step": t, ...}

TRAINER_BENCH_OCCUPANCY=1 switches to the host-services A/B mode (ISSUE 2):
the same trainer runs twice — --async_services=true then =false — with
per-step logging and frequent summary ticks enabled (the observability
regime the async layer exists for), and the row reports each run's
perf/dispatch_occupancy and perf/step_ms_mean from its own metrics JSONL,
so the dispatch-thread overlap win is a recorded number, not a claim:
  {"label": "trainer-loop-occupancy",
   "services_on":  {"dispatch_occupancy": ..., "step_ms_mean": ...},
   "services_off": {"dispatch_occupancy": ..., "step_ms_mean": ...}, ...}

TRAINER_BENCH_PIPELINE=1 switches to the pipelined-G/D A/B mode (ISSUE 7):
the same trainer runs twice — --pipeline_gd=false then =true — with a
mid-run trace window each, and the row reports both arms' recorded
perf/device/{step_ms,idle_gap_ms} digests and host occupancy (see
_pipeline_mode).

Workload anchor: the hot loop being replaced, image_train.py:147-194.
"""

from __future__ import annotations

import json
import os
import re
import shlex
import subprocess
import sys
import tempfile

MAX_STEPS = int(os.environ.get("TRAINER_BENCH_STEPS", 5000))
SCAN = int(os.environ.get("TRAINER_BENCH_SCAN", 50))
# first sync point at/after this step starts the measurement window,
# excluding compile + the first dispatches' pipeline fill
WARMUP_STEPS = int(os.environ.get("TRAINER_BENCH_WARMUP", 1000))

LOG_RE = re.compile(r"\[dcgan_tpu\] epoch \d+ step (\d+) time ([0-9.]+)s")


def _occupancy_mode() -> None:
    """A/B the async host-services layer under an observability-heavy
    regime and report recorded dispatch-thread occupancy for both arms."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    steps = int(os.environ.get("TRAINER_BENCH_STEPS", 300))
    batch = os.environ.get("BENCH_BATCH", "64")
    row = {"label": "trainer-loop-occupancy", "batch": int(batch),
           "total_steps": steps}
    for arm, async_flag in (("services_on", "true"),
                            ("services_off", "false")):
        with tempfile.TemporaryDirectory() as tmp:
            ckpt = os.path.join(tmp, "ckpt")
            argv = [
                sys.executable, "-m", "dcgan_tpu.train",
                "--synthetic",
                "--synthetic_device_cache",
                os.environ.get("TRAINER_BENCH_CACHE", "8"),
                "--max_steps", str(steps),
                "--batch_size", batch,
                "--async_services", async_flag,
                # the observability regime the async layer targets:
                # per-step logging (the reference's contract) + a summary
                # tick (scalars AND full param histograms) every ~2 s
                "--log_every_steps",
                os.environ.get("TRAINER_BENCH_LOG", "1"),
                "--nan_check_steps", "100",
                "--save_summaries_secs",
                os.environ.get("TRAINER_BENCH_SUMMARY_SECS", "2"),
                "--sample_every_steps", "0",
                "--activation_summary_steps", "0",
                "--save_model_secs", "1e9",
                "--no_tensorboard",
                "--checkpoint_dir", ckpt,
                "--sample_dir", os.path.join(tmp, "samples"),
            ]
            res = subprocess.run(
                argv, cwd=repo, capture_output=True, text=True,
                timeout=float(os.environ.get("TRAINER_BENCH_TIMEOUT", 900)))
            if res.returncode != 0:
                print(json.dumps({**row, "error":
                                  f"{arm} trainer rc={res.returncode}",
                                  "stderr_tail": (res.stderr or "")[-300:]}))
                sys.exit(1)
            # last perf summary of the run = steady state (the sliding
            # window has long since shed warmup/compile iterations)
            perf = None
            with open(os.path.join(ckpt, "events.jsonl")) as f:
                for line in f:
                    e = json.loads(line)
                    if e["kind"] == "scalars" and \
                            "perf/dispatch_occupancy" in e["values"]:
                        perf = e["values"]
            if perf is None:
                print(json.dumps({**row, "error":
                                  f"{arm}: no perf scalars in events.jsonl"}))
                sys.exit(1)
            row[arm] = {
                "dispatch_occupancy":
                    round(perf["perf/dispatch_occupancy"], 4),
                "host_ms_mean": round(perf["perf/host_ms_mean"], 3),
                "step_ms_mean": round(perf["perf/step_ms_mean"], 2),
                "images_per_sec": round(perf.get("perf/images_per_sec", 0.0),
                                        1),
            }
    print(json.dumps(row))


def _pipeline_run(repo: str, flag: str, *, steps: int, trace_steps: int,
                  batch: str) -> dict:
    """One A/B arm: a trainer subprocess with a mid-run scheduled trace
    window, returning the arm's recorded perf + device-digest fields."""
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "ckpt")
        argv = [
            sys.executable, "-m", "dcgan_tpu.train",
            "--synthetic",
            "--synthetic_device_cache",
            os.environ.get("TRAINER_BENCH_CACHE", "8"),
            "--max_steps", str(steps),
            "--batch_size", batch,
            "--pipeline_gd", flag,
            # the pipelined mode's dispatch shape; the fused arm runs
            # the same so the A/B isolates the stage split, not scan
            # amortization (that regime is the main trainer-loop row)
            "--steps_per_call", "1",
            # value syncs OUT of the trace window (cadences past
            # max_steps): the window measures the steady dispatch stream,
            # not readback stalls — which hit both arms but add variance
            "--log_every_steps",
            os.environ.get("TRAINER_BENCH_LOG", str(steps * 2)),
            "--nan_check_steps", str(steps * 2),
            # one summary tick fires immediately (warmup) and the next
            # lands near end-of-run — the last perf row is steady-state
            # and the mid-run window stays summary-free on CPU smoke
            # timings; the median-of-reps absorbs a straggler tick
            "--save_summaries_secs",
            os.environ.get("TRAINER_BENCH_SUMMARY_SECS", "4"),
            "--sample_every_steps", "0",
            "--activation_summary_steps", "0",
            "--save_model_secs", "1e9",
            "--no_tensorboard",
            # mid-run scheduled window: past compile, the fill, and the
            # occupancy-timer warmup
            "--profile_dir", os.path.join(tmp, "trace"),
            "--profile_start_step", str(max(1, steps // 2)),
            "--profile_num_steps", str(trace_steps),
            "--checkpoint_dir", ckpt,
            "--sample_dir", os.path.join(tmp, "samples"),
        ]
        # extra trainer flags for smoke runs (e.g. a tiny model:
        # "--output_size 16 --gf_dim 8 --df_dim 8" — the flagship
        # 64x64 model runs ~10 s/step on a CPU test host)
        argv += shlex.split(os.environ.get("TRAINER_BENCH_EXTRA", ""))
        res = subprocess.run(
            argv, cwd=repo, capture_output=True, text=True,
            timeout=float(os.environ.get("TRAINER_BENCH_TIMEOUT", 900)))
        if res.returncode != 0:
            raise RuntimeError(f"trainer rc={res.returncode}: "
                               f"{(res.stderr or '')[-300:]}")
        perf, device = None, None
        with open(os.path.join(ckpt, "events.jsonl")) as f:
            for line in f:
                e = json.loads(line)
                if e["kind"] != "scalars":
                    continue
                if "perf/dispatch_occupancy" in e["values"]:
                    perf = e["values"]
                if "perf/device/step_ms" in e["values"]:
                    device = e["values"]
        if perf is None or device is None:
            raise RuntimeError(
                f"no {'perf' if perf is None else 'device'} scalars "
                "in events.jsonl")
        span = device["perf/device/span_ms"]
        return {
            "devstep_ms": device["perf/device/step_ms"],
            "compute_ms": device["perf/device/compute_ms"],
            "idle_gap_ms": device["perf/device/idle_gap_ms"],
            "span_ms": span,
            # the share of the captured window the device sat between
            # dispatches — THE number the pipeline exists to shrink
            "idle_share": (device["perf/device/idle_gap_ms"] / span
                           if span > 0 else None),
            "step_ms_mean": perf["perf/step_ms_mean"],
            "images_per_sec": perf.get("perf/images_per_sec", 0.0),
            "dispatch_occupancy": perf["perf/dispatch_occupancy"],
        }


def _pipeline_mode() -> None:
    """A/B the pipelined G/D dispatch (ISSUE 7) against the fused step.

    TRAINER_BENCH_REPS (default 3) INTERLEAVED trainer-run pairs —
    --pipeline_gd=false then =true per rep, both at steps_per_call=1 (the
    pipelined mode's dispatch shape) — each run with a mid-run scheduled
    trace window. The row reports each arm's per-field MEDIAN across the
    reps (plus the per-rep idle shares for spread): on a contended CPU
    smoke host the per-window idle share swings several points run to
    run, and interleaving + medians is what makes the A/B a number
    instead of a coin flip. The fields are the trainer's OWN recorded
    perf/device/{step_ms,idle_gap_ms,compute_ms,span_ms} digest next to
    the host-side occupancy numbers — the same measurement path the
    fleet runs, not a bench-only harness. Per-step FLOPs are
    conservation-equal across the arms, so the A/B is a regression guard: the
    device idle share of the window must not grow and devstep_ms must be
    no worse. NOTE: on CPU test hosts the capture falls back to the
    op-level executor thread-group track (utils/trace.py), so the device
    fields prove the path end-to-end rather than attributing real device
    time; the attributing numbers come from TPU module tracks.
      {"label": "trainer-loop-pipeline",
       "fused":     {"devstep_ms": ..., "idle_share": ..., ...},
       "pipelined": {"devstep_ms": ..., "idle_share": ..., ...},
       "idle_shares": {"fused": [...], "pipelined": [...]},
       "idle_share_delta": ...}
    """
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    steps = int(os.environ.get("TRAINER_BENCH_STEPS", 200))
    trace_steps = int(os.environ.get("TRAINER_BENCH_TRACE_STEPS", 60))
    reps = max(1, int(os.environ.get("TRAINER_BENCH_REPS", 3)))
    batch = os.environ.get("BENCH_BATCH", "64")
    row = {"label": "trainer-loop-pipeline", "batch": int(batch),
           "total_steps": steps, "reps": reps}
    samples = {"fused": [], "pipelined": []}
    for rep in range(reps):
        for arm, flag in (("fused", "false"), ("pipelined", "true")):
            try:
                samples[arm].append(_pipeline_run(
                    repo, flag, steps=steps, trace_steps=trace_steps,
                    batch=batch))
            except (RuntimeError, OSError,
                    subprocess.TimeoutExpired) as e:
                print(json.dumps({**row, "error": f"{arm} rep {rep}: {e}"}))
                sys.exit(1)

    def median(vals):
        vs = sorted(v for v in vals if v is not None)
        return vs[len(vs) // 2] if vs else None

    for arm, runs in samples.items():
        row[arm] = {k: (round(median([r[k] for r in runs]), 4)
                        if median([r[k] for r in runs]) is not None
                        else None)
                    for k in runs[0]}
    row["idle_shares"] = {
        arm: [round(r["idle_share"], 4) for r in runs
              if r["idle_share"] is not None]
        for arm, runs in samples.items()}
    f, p = row["fused"], row["pipelined"]
    if f["idle_share"] is not None and p["idle_share"] is not None:
        row["idle_share_delta"] = round(p["idle_share"] - f["idle_share"], 4)
    print(json.dumps(row))


def main() -> None:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    devstep_ms = None
    with tempfile.TemporaryDirectory() as tmp:
        trace_dir = os.path.join(tmp, "trace")
        argv = [
            sys.executable, "-m", "dcgan_tpu.train",
            "--synthetic",
            # pre-staged device batch pool: without it the synthetic feed
            # itself is host->device traffic and the row measures the
            # feed, not the loop. Set TRAINER_BENCH_CACHE=0 to measure
            # the fed regime.
            "--synthetic_device_cache",
            os.environ.get("TRAINER_BENCH_CACHE", "8"),
            "--steps_per_call", str(SCAN),
            "--max_steps", str(MAX_STEPS),
            "--batch_size", os.environ.get("BENCH_BATCH", "64"),
            # value-sync cadence 500 (log + NaN gate together): each metric
            # read drains the dispatch queue, so a tight cadence taxes
            # the loop it is there to measure.
            "--log_every_steps", "500",
            "--nan_check_steps", "500",
            "--sample_every_steps", "0",
            "--activation_summary_steps", "0",
            "--save_summaries_secs", "1e9",
            "--save_model_secs", "1e9",
            "--no_tensorboard",
            "--checkpoint_dir", os.path.join(tmp, "ckpt"),
            "--sample_dir", os.path.join(tmp, "samples"),
        ]
        if os.environ.get("TRAINER_BENCH_DEVSTEP", "1") != "0":
            # devstep_ms (ISSUE 6): one scanned call traced at the very
            # END of the run (steady state; the capture's overhead sits in
            # <=SCAN of the MAX_STEPS-step measurement window) and
            # digested through the shared parser — the BENCH row carries
            # the device's own step time next to the host-derived number
            argv += ["--profile_dir", trace_dir,
                     "--profile_start_step", str(max(0, MAX_STEPS - SCAN)),
                     "--profile_num_steps", str(SCAN)]
        res = subprocess.run(argv, cwd=repo, capture_output=True, text=True,
                             timeout=float(os.environ.get(
                                 "TRAINER_BENCH_TIMEOUT", 900)))
        if os.path.isdir(trace_dir):
            try:
                sys.path.insert(0, repo)
                from dcgan_tpu.utils.trace import devstep_ms as devstep_of

                # the captured window is one steps_per_call scan program
                devstep_ms = devstep_of(trace_dir, per_exec=SCAN)
            except Exception as e:  # noqa: BLE001 — the field is optional
                print(f"devstep digest failed: {e!r}", file=sys.stderr)
    sys.stderr.write((res.stderr or "")[-2000:])
    if res.returncode != 0:
        print(json.dumps({"label": "trainer-loop", "error":
                          f"trainer rc={res.returncode}",
                          "stderr_tail": (res.stderr or "")[-300:]}))
        sys.exit(1)

    points = [(int(m.group(1)), float(m.group(2)))
              for m in LOG_RE.finditer(res.stdout or "")]
    window = [(s, t) for s, t in points if s >= WARMUP_STEPS]
    if len(window) < 2:
        print(json.dumps({"label": "trainer-loop",
                          "error": f"only {len(points)} log points "
                          f"({len(window)} after warmup)"}))
        sys.exit(1)
    (s1, t1), (s2, t2) = window[0], window[-1]
    batch = int(os.environ.get("BENCH_BATCH", "64"))
    steps = s2 - s1
    rate = steps * batch / (t2 - t1)
    print(json.dumps({
        "label": "trainer-loop",
        "images_per_sec_chip": round(rate, 1),
        "ms_per_step": round((t2 - t1) / steps * 1e3, 2),
        "devstep_ms": round(devstep_ms, 4) if devstep_ms else None,
        "window_steps": [s1, s2],
        "batch": batch, "steps_per_call": SCAN,
        "total_steps": MAX_STEPS,
    }))
    # context for the captures log
    print(f"ms_per_step={(t2 - t1) / steps * 1e3:.2f}", file=sys.stderr)


if __name__ == "__main__":
    if os.environ.get("TRAINER_BENCH_OCCUPANCY") == "1":
        _occupancy_mode()
    elif os.environ.get("TRAINER_BENCH_PIPELINE") == "1":
        _pipeline_mode()
    else:
        main()
