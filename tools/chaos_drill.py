"""Chaos drill: the fail-operational layer's scenario matrix, end to end.

Each scenario arms ONE deterministic fault (dcgan_tpu/testing/chaos.py,
selected per subprocess through the DCGAN_CHAOS env var, or applied to the
bytes on disk between launches) and runs the REAL trainer on CPU, then
asserts the recovery contract: the run either completes with the right
final step and recovery counters, or fails loudly with the right error —
never silently trains garbage, never hangs.

    scenario              fault                          asserted recovery
    --------------------  -----------------------------  --------------------
    nan-rollback          NaN into the health gate       rollback to last-good
                          mid-run                        snapshot, run
                                                         completes, anomaly/
                                                         rollbacks surfaced
    corrupt-record        payload bit-flip in a shard    record skipped +
                          (within budget)                data/corrupt_records
                                                         counted, run completes
    corrupt-budget        same flip, budget exhausted    hard failure naming
                                                         the budget
    truncate-checkpoint   newest checkpoint truncated    integrity fallback to
                          between runs                   the previous step,
                                                         step marked .corrupt,
                                                         resume completes
    io-error-once         one transient OSError in the   retried with backoff,
                          manifest write path            run completes
    services-crash        background services worker     ServiceError surfaces
                          dies                           on the dispatch
                                                         thread, run aborts
    flight-recorder       NaN under the default abort    flight-recorder dump
                          policy                         written; last record
                                                         = the failing step
    watchdog-dump         hang inside the guarded        watchdog trip dumps
                          dispatch window (1-process)    stacks AND the
                                                         telemetry ring
    trace-trigger         (no fault) pre-touched         N-step capture +
                          --profile_trigger file         in-process digest ->
                                                         perf/device/* events
    pipeline-rollback     NaN mid-run under              rollback drains the
                          --pipeline_gd                  in-flight fake stack,
                                                         refills from the
                                                         restored state, run
                                                         completes; replay is
                                                         bit-exact
    zero-rollback         NaN mid-run under              sharded snapshot
                          --zero_stage 3 (shard_map,     restores, run
                          2 virtual devices)             completes; losses +
                                                         STATE_SUM replay
                                                         BIT-EXACT vs a
                                                         --zero_stage 1
                                                         control (ISSUE 13)
    thread-checks         (no fault) DCGAN_THREAD_       tripwire arms, wraps
                          CHECKS=1 runtime tripwire      every collective
                                                         entry point, run
                                                         completes with zero
                                                         trips (ISSUE 8)
    serve-drain           SIGTERM mid-load to the        intake stops, every
                          sampler server                 in-flight/queued
                          (`python -m dcgan_tpu.serve`)  request completes,
                                                         queue drains, report
                                                         lands, clean exit 0
                                                         (ISSUE 9)
    fleet-replica-kill    chaos kill of one of 3 serve   router drains the dead
                          replicas mid-trace, then a     replica into failover
                          newly finalized checkpoint     (ZERO failed client
                          step lands on disk             requests), watcher
                                                         hot-swaps the
                                                         survivors to the new
                                                         step with zero
                                                         recompiles (ISSUE 19)
    elastic-shrink        2-proc save resumed by 1       sidecar-driven
                          proc (2 devices — same mesh,   host-staged reshard;
                          different process census)      losses + STATE_SUM
                                                         replay BIT-EXACT vs
                                                         a 2-proc control
                                                         resume (ISSUE 12)
    elastic-grow          1-proc (2-device) save         same contract, the
                          resumed by 2 procs            other direction

Multi-host matrix (ISSUE 4, `--multihost`): the same contract under a REAL
2-process jax.distributed job over localhost gRPC (tests/multihost_worker.py
style — each subprocess owns one virtual CPU device, faults armed on ONE
process via the per-process DCGAN_CHAOS map keyed by MH_PID):

    scenario              fault                          asserted recovery
    --------------------  -----------------------------  --------------------
    mh-nan-rollback       NaN into ONE process's gate    consensus spreads the
                          view mid-run                   verdict; both hosts
                                                         roll back together,
                                                         complete, and end
                                                         with IDENTICAL state
    mh-sigterm-stop       SIGTERM delivered to host 1    stop consensus breaks
                          only                           both hosts together
                                                         through a collective
                                                         final save host 0
                                                         resumes BIT-EXACT
    mh-watchdog           host 1 goes silent inside a    watchdog trips on
                          collective window              every process: stack
                                                         dumps + exit 43, no
                                                         hang

Usage:
    JAX_PLATFORMS=cpu python tools/chaos_drill.py            # full matrix
    JAX_PLATFORMS=cpu python tools/chaos_drill.py --smoke    # CI subset
    ... --multihost                                  # 2-process matrix
    ... --multihost --smoke                          # cheapest MH scenario
    ... --only nan-rollback truncate-checkpoint              # cherry-pick

Prints one JSON row per scenario and exits nonzero if any scenario's
contract does not hold. Tiny model (16px, gf/df 8, batch 8): the matrix is a
protocol check, ~10 s/launch on CPU — the numbers mean nothing, the
recovery paths everything.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# jax-free import (config never touches jax at module scope): the
# zero-rollback scenario passes a MeshConfig — and progressive-switch a
# ModelConfig — through the driver's repr-round-tripped `extra` dict
from dcgan_tpu.config import MeshConfig, ModelConfig  # noqa: E402

# CI subset (tests/test_tools_chaos.py pins --smoke into tier-1): the cheapest
# scenarios that still cross every new layer — quarantine (data), retry
# (checkpoint IO), worker-crash surfacing (services). The two-phase
# checkpoint-fallback and rollback scenarios run in the full matrix (and
# in-process in tests/test_chaos.py).
SMOKE_SCENARIOS = ("corrupt-record", "io-error-once", "services-crash")

_DRIVER = """
import os
import jax; jax.config.update("jax_platforms", "cpu")
if os.environ.get("DRILL_THREEFRY_PARTITIONABLE"):
    # the elastic cross-topology arms compare losses bit-exactly against
    # 2-process phases, whose workers standardize on partitionable
    # threefry (testing/multihost.py) — the flag changes the generated
    # random STREAM, so both layouts must agree on it
    jax.config.update("jax_threefry_partitionable", True)
from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig
from dcgan_tpu.train.trainer import train
base = dict(model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                              compute_dtype="float32"),
            batch_size=8, tensorboard=False, sample_every_steps=0,
            save_summaries_secs=0.0, log_every_steps=1)
base.update({extra!r})  # scenario overrides WIN over the driver defaults
cfg = TrainConfig(**base)
state = train(cfg, synthetic_data={synthetic!r}, max_steps={max_steps!r})
import numpy as np
total = sum(float(np.abs(np.asarray(jax.device_get(leaf),
                                    np.float64)).sum())
            for leaf in jax.tree_util.tree_leaves(state["params"]))
print("STATE_SUM=%.9e" % total, flush=True)
print("TRAIN_DONE step=%d" % int(jax.device_get(state["step"])), flush=True)
"""


def _state_sum(out: str) -> str:
    """The driver's STATE_SUM line (full-precision text — compared for
    bit-exact equality where the contract supports it)."""
    return next(line for line in out.splitlines()
                if line.startswith("STATE_SUM="))


def _state_sum_value(out: str) -> float:
    """The STATE_SUM line parsed back to a float — for the contracts that
    compare across DIFFERENT reduction orders, where the right check is a
    tight relative tolerance, not text equality."""
    return float(_state_sum(out).split("=", 1)[1])


def _run_train(extra: dict, *, max_steps: int, synthetic: bool = True,
               chaos: dict = None, timeout: int = 600,
               env_extra: dict = None):
    """One trainer subprocess; returns (rc, combined output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DCGAN_CHAOS", None)
    if chaos:
        env["DCGAN_CHAOS"] = json.dumps(chaos)
    if env_extra:
        env.update(env_extra)
    code = _DRIVER.format(extra=extra, synthetic=synthetic,
                          max_steps=max_steps)
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=timeout)
    return res.returncode, res.stdout + res.stderr


def _events(ckpt_dir: str):
    path = os.path.join(ckpt_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f]


def _scalar_values(events, key):
    return [e["values"][key] for e in events
            if e["kind"] == "scalars" and key in e["values"]]


class Failure(AssertionError):
    pass


def _check(cond, why):
    if not cond:
        raise Failure(why)


# -- scenarios ---------------------------------------------------------------

def scenario_nan_rollback(root: str) -> dict:
    """NaN mid-run -> rollback to last-good snapshot, training resumes and
    completes; anomaly/rollbacks lands in the event stream."""
    ck = os.path.join(root, "ck")
    rc, out = _run_train(
        dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
             nan_policy="rollback", nan_check_steps=1,
             rollback_snapshot_steps=2, max_rollbacks=2,
             rollback_lr_backoff=0.5, save_model_secs=1e9),
        max_steps=6, chaos={"nan_at_step": 3})
    _check(rc == 0, f"trainer failed (rc={rc}): {out[-800:]}")
    _check("rolling back to last-good snapshot at step 2" in out,
           f"no rollback message in output: {out[-800:]}")
    _check("TRAIN_DONE step=6" in out, f"run did not complete: {out[-400:]}")
    rollbacks = _scalar_values(_events(ck), "anomaly/rollbacks")
    _check(rollbacks and max(rollbacks) >= 1,
           f"anomaly/rollbacks missing from events (got {rollbacks})")
    return {"rollbacks": max(rollbacks), "final_step": 6}


def _make_corrupt_shards(root: str) -> str:
    from dcgan_tpu.data.synthetic import write_image_tfrecords
    from dcgan_tpu.testing.chaos import corrupt_tfrecord_payload

    data_dir = os.path.join(root, "data")
    paths = write_image_tfrecords(data_dir, num_examples=64, image_size=16,
                                  num_shards=2)
    for p in paths:   # one bad record per shard
        corrupt_tfrecord_payload(p, record_index=2)
    return data_dir


def scenario_corrupt_record(root: str) -> dict:
    """Flipped payload bytes within budget -> records skipped, counter
    surfaced, run completes."""
    data_dir = _make_corrupt_shards(root)
    ck = os.path.join(root, "ck")
    rc, out = _run_train(
        dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
             data_dir=data_dir, max_corrupt_records=1000,
             shuffle_buffer=16, num_loader_threads=2, save_model_secs=1e9),
        max_steps=6, synthetic=False)
    _check(rc == 0, f"trainer failed (rc={rc}): {out[-800:]}")
    _check("quarantined corrupt record" in out,
           f"no quarantine log line: {out[-800:]}")
    _check("TRAIN_DONE step=6" in out, f"run did not complete: {out[-400:]}")
    counts = _scalar_values(_events(ck), "data/corrupt_records")
    _check(counts and max(counts) >= 1,
           f"data/corrupt_records missing from events (got {counts})")
    return {"corrupt_records": int(max(counts)), "final_step": 6}


def scenario_corrupt_budget(root: str) -> dict:
    """Same corruption with budget 1 and >1 bad records on disk -> the run
    must HARD-FAIL naming the budget (bounded quarantine, not unbounded
    tolerance)."""
    data_dir = _make_corrupt_shards(root)
    rc, out = _run_train(
        dict(checkpoint_dir=os.path.join(root, "ck"),
             sample_dir=os.path.join(root, "sm"),
             data_dir=data_dir, max_corrupt_records=1,
             shuffle_buffer=16, num_loader_threads=2, save_model_secs=1e9),
        max_steps=200, synthetic=False)
    _check(rc != 0, "budget-exhausted run unexpectedly succeeded")
    _check("budget" in out, f"failure does not name the budget: {out[-800:]}")
    return {"failed_as_required": True}


def scenario_truncate_checkpoint(root: str) -> dict:
    """Truncate the newest checkpoint between runs -> integrity fallback
    restores the previous step, marks .corrupt, resume completes."""
    from dcgan_tpu.testing.chaos import truncate_file

    ck = os.path.join(root, "ck")
    common = dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
                  save_model_secs=0.0)  # save (and manifest) every step
    rc, out = _run_train(common, max_steps=4)
    _check(rc == 0, f"phase-A trainer failed (rc={rc}): {out[-800:]}")
    _check(os.path.isdir(os.path.join(ck, "4")), "no step-4 checkpoint")
    _check(os.path.exists(os.path.join(ck, "integrity", "4.json")),
           "no integrity manifest for step 4")
    # truncate the biggest array file in the newest step
    files = [p for p in glob.glob(os.path.join(ck, "4", "**"),
                                  recursive=True) if os.path.isfile(p)]
    victim = max(files, key=os.path.getsize)
    truncate_file(victim, drop_bytes=max(64, os.path.getsize(victim) // 2))

    rc, out = _run_train(common, max_steps=6)
    _check(rc == 0, f"phase-B trainer failed (rc={rc}): {out[-800:]}")
    _check("failed integrity check" in out,
           f"no integrity-failure message: {out[-800:]}")
    _check(os.path.isdir(os.path.join(ck, "4.corrupt")),
           "truncated step was not marked .corrupt")
    _check("restored checkpoint at step 3" in out,
           f"did not fall back to step 3: {out[-800:]}")
    _check("TRAIN_DONE step=6" in out, f"resume did not complete: "
           f"{out[-400:]}")
    return {"fell_back_to": 3, "final_step": 6}


def scenario_io_error_once(root: str) -> dict:
    """One transient OSError in the checkpoint-manifest write -> retried
    with backoff, run completes, manifests intact."""
    ck = os.path.join(root, "ck")
    rc, out = _run_train(
        dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
             save_model_secs=0.0),
        max_steps=3, chaos={"io_error_once": "ckpt-manifest"})
    _check(rc == 0, f"trainer failed (rc={rc}): {out[-800:]}")
    _check("transient IO error at 'ckpt-manifest'" in out
           and "retrying" in out, f"no retry log line: {out[-800:]}")
    _check("TRAIN_DONE step=3" in out, f"run did not complete: {out[-400:]}")
    _check(glob.glob(os.path.join(ck, "integrity", "*.json")),
           "no integrity manifests written")
    return {"retried": True, "final_step": 3}


def scenario_services_crash(root: str) -> dict:
    """Background services worker dies -> the error surfaces on the
    DISPATCH thread (ServiceError) and the run aborts loudly."""
    rc, out = _run_train(
        dict(checkpoint_dir=os.path.join(root, "ck"),
             sample_dir=os.path.join(root, "sm"), save_model_secs=1e9),
        max_steps=50, chaos={"services_worker_crash": 1})
    _check(rc != 0, "run with a dead services worker unexpectedly succeeded")
    _check("ServiceError" in out and "background host service" in out,
           f"worker crash did not surface as ServiceError: {out[-800:]}")
    _check("TRAIN_DONE" not in out, "run claimed completion after crash")
    return {"failed_as_required": True}


def scenario_flight_recorder(root: str) -> dict:
    """NaN under the default abort policy -> the run dies loudly AND
    leaves a parseable flight-recorder dump whose LAST record is the
    failing step with a tripped gate verdict (ISSUE 6: the stacks' missing
    telemetry context)."""
    from dcgan_tpu.train.flight_recorder import read_dump

    ck = os.path.join(root, "ck")
    rc, out = _run_train(
        dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
             nan_check_steps=1, save_model_secs=1e9),
        max_steps=6, chaos={"nan_at_step": 3})
    _check(rc != 0, "NaN-abort run unexpectedly succeeded")
    _check("non-finite training metrics at step 3" in out,
           f"no NaN abort message: {out[-800:]}")
    path = os.path.join(ck, "flight_recorder.jsonl")
    _check(os.path.exists(path), "no flight-recorder dump after NaN abort")
    header, records = read_dump(path)
    _check(header["reason"] == "nan-abort" and header["step"] == 3,
           f"dump header misattributes the abort: {header}")
    _check(records and records[-1]["step"] == 3
           and records[-1]["gate"] == "trip",
           f"last record is not the tripped step: {records[-1:]}")
    _check(all("counters" in r for r in records),
           "records missing the counter-registry snapshot")
    return {"reason": header["reason"], "dump_records": len(records),
            "failing_step": records[-1]["step"]}


def scenario_watchdog_dump(root: str) -> dict:
    """Single-process watchdog trip (a hang inside the guarded dispatch
    window) -> stack dump + exit 43 as before, now joined by a
    flight-recorder dump naming the phase (ISSUE 6)."""
    from dcgan_tpu.train.flight_recorder import read_dump

    ck = os.path.join(root, "ck")
    rc, out = _run_train(
        dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
             collective_timeout_secs=3.0, save_model_secs=1e9),
        max_steps=20, chaos={"hang_at_step": 3, "hang_secs": 60},
        timeout=180)
    _check(rc != 0, "hung run unexpectedly succeeded")
    _check("hung-collective watchdog" in out or "Timeout (" in out,
           f"no watchdog diagnostic: {out[-800:]}")
    _check("TRAIN_DONE" not in out, "hung run claimed completion")
    path = os.path.join(ck, "flight_recorder.jsonl")
    _check(os.path.exists(path), "no flight-recorder dump on watchdog trip")
    header, records = read_dump(path)
    _check(header["reason"] == "watchdog"
           and header.get("phase") == "step-dispatch",
           f"dump header misattributes the trip: {header}")
    _check(header["step"] == 3, f"dump header wrong step: {header}")
    _check(records and records[-1]["step"] >= 1,
           f"ring empty at trip: {records[-1:]}")
    return {"rc": rc, "phase": header["phase"],
            "dump_records": len(records)}


def scenario_trace_trigger(root: str) -> dict:
    """A touched --profile_trigger file -> the next boundary starts an
    N-step device capture, the services worker digests it in-process, and
    perf/device/* attribution (compute/collective/idle-gap/step) lands in
    the event stream; the trigger file is consumed as the ack."""
    trig = os.path.join(root, "trigger")
    open(trig, "w").close()   # pre-touched: fires at the first boundary
    ck = os.path.join(root, "ck")
    rc, out = _run_train(
        dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
             profile_trigger=trig, profile_num_steps=2, save_model_secs=1e9),
        max_steps=6)
    _check(rc == 0, f"trainer failed (rc={rc}): {out[-800:]}")
    _check("TRAIN_DONE step=6" in out, f"run did not complete: {out[-400:]}")
    _check(not os.path.exists(trig), "trigger file was not consumed")
    _check("trace digest" in out, f"no digest log line: {out[-800:]}")
    keys = ("perf/device/compute_ms", "perf/device/collective_ms",
            "perf/device/idle_gap_ms", "perf/device/step_ms")
    rows = [e["values"] for e in _events(ck) if e["kind"] == "scalars"
            and "perf/device/compute_ms" in e["values"]]
    _check(rows, "no perf/device/* events after the trigger capture")
    missing = [k for k in keys if k not in rows[-1]]
    _check(not missing, f"digest row missing {missing}")
    _check(rows[-1]["perf/device/compute_ms"] > 0,
           f"empty device attribution: {rows[-1]}")
    return {"device_compute_ms": round(rows[-1][keys[0]], 3),
            "device_idle_gap_ms": round(rows[-1][keys[2]], 3)}


def scenario_pipeline_rollback(root: str) -> dict:
    """NaN mid-run under --pipeline_gd (ISSUE 7) -> the anomaly rollback
    DRAINS the in-flight fake stack (generated by the diverged weights the
    rollback is fleeing — it must never train the restored state), refills
    from the restored generator at the next dispatch, and the run
    completes with the same rollback protocol as fused mode. Determinism
    is asserted the strong way: a second identical pipelined run must
    reproduce STATE_SUM to the printed digit — the drain/refill schedule
    is part of the deterministic replay, not a wall-clock accident. (The
    pipelined and fused final states legitimately differ: staleness-1
    fakes are a different — equally valid — training trajectory.)"""
    knobs = dict(pipeline_gd=True, nan_policy="rollback", nan_check_steps=1,
                 rollback_snapshot_steps=2, max_rollbacks=2,
                 save_model_secs=1e9)

    def one(tag):
        ck = os.path.join(root, f"ck-{tag}")
        rc, out = _run_train(
            dict(checkpoint_dir=ck,
                 sample_dir=os.path.join(root, f"sm-{tag}"), **knobs),
            max_steps=6, chaos={"nan_at_step": 3})
        _check(rc == 0, f"{tag}: trainer failed (rc={rc}): {out[-800:]}")
        _check("rolling back to last-good snapshot at step 2" in out,
               f"{tag}: no rollback message: {out[-800:]}")
        _check("rollback drained the in-flight pipelined fake stack" in out,
               f"{tag}: rollback did not drain the fake buffer: "
               f"{out[-800:]}")
        _check("TRAIN_DONE step=6" in out,
               f"{tag}: run did not complete: {out[-400:]}")
        rollbacks = _scalar_values(_events(ck), "anomaly/rollbacks")
        _check(rollbacks and max(rollbacks) >= 1,
               f"{tag}: anomaly/rollbacks missing (got {rollbacks})")
        return _state_sum(out), max(rollbacks)

    sum_a, rollbacks = one("a")
    sum_b, _ = one("b")
    _check(sum_a == sum_b,
           f"pipelined rollback replay diverged: {sum_a} != {sum_b}")
    return {"rollbacks": rollbacks, "final_step": 6,
            "replay_bit_exact": True}


def scenario_zero_rollback(root: str) -> dict:
    """NaN mid-run under --zero_stage 3 (ISSUE 13): the anomaly rollback
    snapshots and restores the data-SHARDED state (params, EMA, and both
    Adam moments live as rule-engine shards between steps), training
    completes, and the post-rollback losses AND final STATE_SUM replay
    a --zero_stage 1 control fed the same fault to 1e-5 — the state
    sharding is a layout, not a different trajectory. backend=shard_map:
    its explicit psum_scatter/all_gather round trip hands Adam the
    stage-1 pmean's gradients to the last bit on CPU (the gspmd
    partitioner reassociates reductions, so stage parity there is
    tolerance-level throughout — tests/test_zero.py); what is no longer
    bit-exact under the installed XLA:CPU is the update itself, whose
    elementwise loop rounds a shard's scalar tail differently from its
    vector body (1 ulp on a few elements of a leaf per step, seen as the
    last digit of g_loss from step 3 on), hence the tolerance. Both arms
    run single-process over 2 virtual devices, the 2-way data axis stage
    3 needs."""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
           "DRILL_THREEFRY_PARTITIONABLE": "1"}
    knobs = dict(backend="shard_map", nan_policy="rollback",
                 nan_check_steps=1, rollback_snapshot_steps=2,
                 max_rollbacks=2, save_model_secs=1e9,
                 save_summaries_secs=0.0)

    def one(tag, stage):
        ck = os.path.join(root, f"ck-{tag}")
        rc, out = _run_train(
            dict(checkpoint_dir=ck,
                 sample_dir=os.path.join(root, f"sm-{tag}"),
                 mesh=MeshConfig(zero_stage=stage), **knobs),
            max_steps=6, chaos={"nan_at_step": 3}, env_extra=env)
        _check(rc == 0, f"{tag}: trainer failed (rc={rc}): {out[-800:]}")
        _check("rolling back to last-good snapshot at step 2" in out,
               f"{tag}: no rollback message: {out[-800:]}")
        _check("TRAIN_DONE step=6" in out,
               f"{tag}: run did not complete: {out[-400:]}")
        rollbacks = _scalar_values(_events(ck), "anomaly/rollbacks")
        _check(rollbacks and max(rollbacks) >= 1,
               f"{tag}: anomaly/rollbacks missing (got {rollbacks})")
        return (_state_sum_value(out), _loss_rows(_events(ck)),
                max(rollbacks))

    def close(a, b):
        return abs(a - b) <= 1e-5 * max(abs(b), 1e-12)

    sum_z, loss_z, rollbacks = one("zero3", 3)
    sum_c, loss_c, _ = one("zero1", 1)
    for s in sorted(loss_c):
        _check(s in loss_z and all(map(close, loss_z[s], loss_c[s])),
               f"step-{s} losses diverged across zero stages: "
               f"{loss_z.get(s)} != {loss_c[s]}")
    _check(close(sum_z, sum_c),
           f"zero_stage=3 rollback state diverged from the stage-1 "
           f"control: {sum_z} != {sum_c}")
    return {"rollbacks": rollbacks, "final_step": 6,
            "replay_rtol": 1e-5, "state_sum": sum_z}


def scenario_progressive_switch(root: str) -> dict:
    """NaN at the step right AFTER a progressive phase switch (ISSUE 15):
    the rollback must restore the POST-switch snapshot (taken at the
    boundary, the new phase's tree — restoring the old tree would feed
    r16 state to r32 programs), the run completes, and determinism holds
    two ways: the faulted run replays STATE_SUM bit-exactly, and the
    pre-switch phase's losses are bit-exact against an UNFAULTED control
    (the rollback re-keys the replayed window by design, so post-rollback
    steps legitimately diverge from the control — the unpoisoned phase
    must not)."""
    model = ModelConfig(output_size=32, gf_dim=8, df_dim=8,
                        compute_dtype="float32")
    knobs = dict(model=model, progressive="16:3,32:*",
                 nan_policy="rollback", nan_check_steps=1,
                 rollback_snapshot_steps=100,  # only init + switch snapshots
                 max_rollbacks=2, save_model_secs=1e9)
    switch_step = 3

    def one(tag, chaos_plan):
        ck = os.path.join(root, f"ck-{tag}")
        rc, out = _run_train(
            dict(checkpoint_dir=ck,
                 sample_dir=os.path.join(root, f"sm-{tag}"), **knobs),
            max_steps=6, chaos=chaos_plan)
        _check(rc == 0, f"{tag}: trainer failed (rc={rc}): {out[-800:]}")
        _check(f"progressive phase 1 at step {switch_step}: r16 -> r32"
               in out, f"{tag}: no phase-switch line: {out[-800:]}")
        _check("TRAIN_DONE step=6" in out,
               f"{tag}: run did not complete: {out[-400:]}")
        return _state_sum(out), _loss_rows(_events(ck)), out

    sum_a, loss_a, out_a = one("a", {"nan_at_step": switch_step + 1})
    _check(f"rolling back to last-good snapshot at step {switch_step}"
           in out_a,
           f"rollback did not restore the post-switch snapshot: "
           f"{out_a[-800:]}")
    rollbacks = _scalar_values(_events(os.path.join(root, "ck-a")),
                               "anomaly/rollbacks")
    _check(rollbacks and max(rollbacks) >= 1,
           f"anomaly/rollbacks missing (got {rollbacks})")
    sum_b, _loss_b, _out_b = one("b", {"nan_at_step": switch_step + 1})
    _check(sum_a == sum_b,
           f"faulted progressive replay diverged: {sum_a} != {sum_b}")
    sum_c, loss_c, _out_c = one("control", None)
    for s in range(1, switch_step + 1):
        _check(loss_a.get(s) == loss_c.get(s),
               f"pre-switch phase losses diverged at step {s}: "
               f"{loss_a.get(s)} != {loss_c.get(s)}")
    _check(sum_a != sum_c or loss_a == loss_c,
           "sanity: faulted and control runs are byte-identical yet a "
           "rollback fired")
    return {"rollbacks": max(rollbacks), "final_step": 6,
            "replay_bit_exact": True, "preswitch_losses_bit_exact": True}


def scenario_thread_checks(root: str) -> dict:
    """(no fault) a short train under DCGAN_THREAD_CHECKS=1 (ISSUE 8): the
    runtime thread-discipline tripwire wraps every collective entry point
    (coordination transports, Checkpointer save/restore, the pt.* program
    dispatches) and the DEFAULT dispatch path must complete with zero
    trips — the end-to-end proof that the collective-thread rule
    (DESIGN.md §6b) holds on the paths the AST walk cannot resolve. The
    per-step save cadence exercises the wrapped Checkpointer.save on
    every boundary."""
    ck = os.path.join(root, "ck")
    rc, out = _run_train(
        dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
             save_model_secs=0.0),
        max_steps=6, env_extra={"DCGAN_THREAD_CHECKS": "1"})
    _check(rc == 0, f"trainer failed (rc={rc}): {out[-800:]}")
    _check("thread-discipline tripwire armed" in out,
           f"tripwire never armed: {out[-800:]}")
    _check("ThreadDisciplineError" not in out,
           f"tripwire tripped on the default dispatch path: {out[-800:]}")
    _check("TRAIN_DONE step=6" in out, f"run did not complete: {out[-400:]}")
    return {"tripwire_armed": True, "trips": 0, "final_step": 6}


def scenario_serve_drain(root: str) -> dict:
    """SIGTERM mid-load to the serving plane (ISSUE 9) -> the graceful
    drain contract: intake stops, every already-submitted request
    completes (none dropped, none stranded), the report row lands, and
    the process exits 0 — a preemption notice becomes a clean handoff.
    The demo load is sized so the signal always lands mid-trace."""
    import signal
    import threading
    import time

    ck = os.path.join(root, "ck")
    rc, out = _run_train(
        dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
             save_model_secs=1e9),
        max_steps=1)
    _check(rc == 0, f"checkpoint trainer failed (rc={rc}): {out[-800:]}")

    report = os.path.join(root, "serve-report.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DCGAN_CHAOS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "dcgan_tpu.serve",
         "--checkpoint_dir", ck, "--max_batch", "8", "--max_wait_ms", "20",
         "--demo_requests", "2000", "--demo_rps", "25",
         "--report", report, "--platform", "cpu"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    lines = []
    reader = threading.Thread(
        target=lambda: [lines.append(l) for l in proc.stdout], daemon=True)
    reader.start()
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline \
                and not any("warm: serving" in l for l in lines):
            if proc.poll() is not None:
                break
            time.sleep(0.2)
        _check(any("warm: serving" in l for l in lines),
               f"server never turned warm: {''.join(lines)[-800:]}")
        time.sleep(1.5)           # let some of the load land first
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    reader.join(timeout=10)
    out = "".join(lines)
    _check(rc == 0, f"serve exited rc={rc} after SIGTERM: {out[-800:]}")
    _check("received signal 15" in out,
           f"no signal acknowledgement: {out[-800:]}")
    _check("drain:" in out and "clean exit" in out,
           f"no drain summary line: {out[-800:]}")
    _check(os.path.exists(report), "no report row written after the drain")
    with open(report) as f:
        row = json.load(f)
    _check(row["interrupted"] is True, f"report not marked interrupted: "
           f"{row}")
    _check(0 < row["submitted"] < 2000,
           f"signal did not land mid-load (submitted={row['submitted']})")
    _check(row["completed"] == row["submitted"],
           f"in-flight requests lost: submitted {row['submitted']}, "
           f"completed {row['completed']}")
    _check(row["serve/dropped"] == 0,
           f"drain dropped requests: {row['serve/dropped']}")
    return {"submitted": row["submitted"], "completed": row["completed"],
            "unsubmitted": row["unsubmitted"], "clean_exit": True}


def _inject_step(donor_dir: str, serve_dir: str, step: int) -> None:
    """Deliver `step` into `serve_dir` the way a trainer would: integrity
    sidecars first, then the step dir copied under a tmp name and RENAMED
    in — a digit-named dir is finalized by the Orbax contract, so the
    fleet's promotion watcher can never see a half-copied step."""
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


def scenario_fleet_replica_kill(root: str) -> dict:
    """Serving fleet under fire (ISSUE 19): 3 replicas behind the
    failover router; a chaos fault kills replica 1's dispatch thread
    mid-trace, then a newly finalized checkpoint step lands on disk and
    the promotion watcher hot-swaps the SURVIVORS' weights live. The
    contract: zero failed client requests (the kill becomes failover,
    the promotion a drain), the dead replica is drained from rotation
    and logged, and every surviving replica's promotion result proves
    compile_requests_delta == 0 — fleet weight delivery mid-trace is
    recompile-free."""
    import shutil
    import signal
    import threading
    import time

    # two checkpoint dirs from one training lineage: the fleet serves
    # step 1; the donor's step 2 is the "newly finalized" step injected
    # mid-trace for the watcher to promote
    ck = os.path.join(root, "ck")
    rc, out = _run_train(
        dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
             save_model_secs=1e9),
        max_steps=1)
    _check(rc == 0, f"checkpoint trainer failed (rc={rc}): {out[-800:]}")
    donor = os.path.join(root, "donor")
    shutil.copytree(ck, donor)
    rc, out = _run_train(
        dict(checkpoint_dir=donor, sample_dir=os.path.join(root, "sm"),
             save_model_secs=1e9),
        max_steps=2)  # resumes @1 -> finalizes step 2
    _check(rc == 0, f"donor trainer failed (rc={rc}): {out[-800:]}")
    _check(os.path.isdir(os.path.join(donor, "2")),
           "donor run left no finalized step-2 dir")

    report = os.path.join(root, "serve-report.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["DCGAN_CHAOS"] = json.dumps(
        {"fault_replica": 1, "replica_kill_at_dispatch": 2})
    proc = subprocess.Popen(
        [sys.executable, "-m", "dcgan_tpu.serve",
         "--checkpoint_dir", ck, "--fleet", "3",
         "--compile_cache_dir", os.path.join(root, "cache"),
         "--watch_promotions", "--watch_interval_secs", "0.25",
         "--max_batch", "8", "--max_wait_ms", "20",
         "--demo_requests", "2000", "--demo_rps", "25",
         "--report", report, "--platform", "cpu"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
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
            time.sleep(0.2)
        _check(any(token in l for l in lines),
               f"never saw {token!r}: {''.join(lines)[-1200:]}")

    try:
        # 3 sequential cold starts share one compile cache; the 1-core
        # CI host still pays replica 0's compiles in full
        _wait_for("warm: serving", 300)
        # phase 1: load lands, replica 1's 2nd dispatch fires the kill,
        # the router drains it from rotation and hedges its work over
        _wait_for("replica 1 UNHEALTHY", 60)
        # phase 2: step 2 lands FINALIZED (sidecars first, then the
        # digit rename) and the watcher promotes the two survivors
        _inject_step(donor, ck, 2)
        _wait_for("serve fleet: promoted", 120)
        time.sleep(1.0)  # a little post-promotion load on new weights
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=240)
    finally:
        if proc.poll() is None:
            proc.kill()
    reader.join(timeout=10)
    out = "".join(lines)
    _check(rc == 0, f"serve exited rc={rc} after SIGTERM: {out[-1200:]}")
    _check(os.path.exists(report), "no report row written after the drain")
    with open(report) as f:
        row = json.load(f)
    _check(row["interrupted"] is True,
           f"report not marked interrupted: {row}")
    _check(0 < row["submitted"] < 2000,
           f"signal did not land mid-load (submitted={row['submitted']})")
    _check(row["failed"] == 0,
           f"{row['failed']} client request(s) FAILED — the kill leaked "
           f"past the failover router")
    _check(row["completed"] == row["submitted"],
           f"in-flight requests lost: submitted {row['submitted']}, "
           f"completed {row['completed']}")
    _check(row["serve/dropped"] == 0,
           f"fleet dropped requests: {row['serve/dropped']}")
    fl = row["fleet"]
    _check(fl["replicas"] == 3, f"wrong fleet size in report: {fl}")
    unhealthy = {i for i, _ in fl["unhealthy"]}
    _check(1 in unhealthy,
           f"killed replica missing from unhealthy events: "
           f"{fl['unhealthy']}")
    # the chaos kill surfaces exactly once, as the DEAD replica's stop
    # error (stop() re-raises the worker's failure; fleet.stop collects)
    _check(all(i == 1 for i, _ in fl["stop_errors"]),
           f"a SURVIVOR failed to stop cleanly: {fl['stop_errors']}")
    _check(any("chaos: replica 1 killed" in err
               for _, err in fl["stop_errors"]),
           f"chaos kill never fired (stop_errors={fl['stop_errors']}, "
           f"unhealthy={fl['unhealthy']})")
    _check(fl["promotions"], "watcher never promoted the injected step")
    last = fl["promotions"][-1]
    _check({r.get("replica") for r in last} == {0, 2},
           f"promotion did not target exactly the survivors: {last}")
    _check(all("error" not in r and r["step"] == 2 for r in last),
           f"a survivor's promotion failed or got the wrong step: {last}")
    _check(all(r.get("compile_requests_delta") == 0 for r in last),
           f"promotion compiled something: {last}")
    _check(row["serve/recompiles_after_warmup"] == 0,
           f"post-warmup recompiles: "
           f"{row['serve/recompiles_after_warmup']}")
    return {"submitted": row["submitted"], "completed": row["completed"],
            "failed": 0, "unhealthy": sorted(unhealthy),
            "failovers": fl["failovers"],
            "promoted_replicas": sorted(r["replica"] for r in last),
            "promoted_step": 2, "compile_requests_delta": 0}


SCENARIOS = {
    "nan-rollback": scenario_nan_rollback,
    "serve-drain": scenario_serve_drain,
    "fleet-replica-kill": scenario_fleet_replica_kill,
    "thread-checks": scenario_thread_checks,
    "pipeline-rollback": scenario_pipeline_rollback,
    "zero-rollback": scenario_zero_rollback,
    "progressive-switch": scenario_progressive_switch,
    "corrupt-record": scenario_corrupt_record,
    "corrupt-budget": scenario_corrupt_budget,
    "truncate-checkpoint": scenario_truncate_checkpoint,
    "io-error-once": scenario_io_error_once,
    "services-crash": scenario_services_crash,
    "flight-recorder": scenario_flight_recorder,
    "watchdog-dump": scenario_watchdog_dump,
    "trace-trigger": scenario_trace_trigger,
}


# -- multi-host scenarios (ISSUE 4) ------------------------------------------
#
# Two real OS processes form a jax.distributed job over localhost gRPC (one
# virtual CPU device each — the cheapest topology that still makes every
# save/allgather a true cross-process collective). Faults arm on process 1
# only, through the per-process DCGAN_CHAOS map ({"1": {...}} keyed by
# MH_PID), so every scenario proves a LOCAL fault becoming a GLOBAL,
# deterministic decision.

# cheapest multi-host scenario, pinned into tier-1 (tests/test_tools_chaos.py)
MH_SMOKE_SCENARIOS = ("mh-sigterm-stop",)

_MH_DRIVER = """
import json, os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1")
import jax
from dcgan_tpu.testing.multihost import configure_cpu_multiprocess
configure_cpu_multiprocess(jax)
jax.distributed.initialize(
    coordinator_address=os.environ["MH_COORD"],
    num_processes=int(os.environ["MH_NPROC"]),
    process_id=int(os.environ["MH_PID"]))
import numpy as np
from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig
from dcgan_tpu.train.trainer import train
base = dict(batch_size=8, tensorboard=False, sample_every_steps=0,
            activation_summary_steps=0, save_summaries_secs=1e9,
            log_every_steps=1, save_model_steps=10_000)
base.update(json.loads(os.environ["MH_EXTRA"]))  # scenario overrides WIN
cfg = TrainConfig(model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                                    compute_dtype="float32"),
                  **base)
state = train(cfg, synthetic_data=True,
              max_steps=int(os.environ["MH_MAX_STEPS"]))
total = sum(float(np.abs(np.asarray(jax.device_get(leaf),
                                    np.float64)).sum())
            for leaf in jax.tree_util.tree_leaves(state["params"]))
print("STATE_SUM=%.9e" % total, flush=True)
print("TRAIN_DONE step=%d" % int(jax.device_get(state["step"])), flush=True)
"""


def _free_port() -> int:
    from dcgan_tpu.testing.multihost import free_port

    return free_port()


def _run_mh_train(extra: dict, *, max_steps: int, chaos: dict = None,
                  nproc: int = 2, timeout: int = 600,
                  extra_per_pid: dict = None, env_common: dict = None):
    """One 2-process trainer job; returns [(rc, output) per process].

    `chaos` may be a flat FaultPlan dict (armed on every process) or a
    per-process map like {"1": {...}} (armed on that MH_PID only).
    `extra_per_pid` ({pid: {config overrides}}) layers per-process config
    on top of `extra` — only for knobs that are legitimately per-process
    (watchdog deadlines); anything steering collectives must stay common.
    `env_common` adds environment variables to EVERY process (the
    protocol-replay scenario arms DCGAN_PROTOCOL_LOG this way)."""
    port = _free_port()
    procs = []
    for pid in range(nproc):
        cfg_extra = dict(extra, **(extra_per_pid or {}).get(pid, {}))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   MH_COORD=f"127.0.0.1:{port}", MH_NPROC=str(nproc),
                   MH_PID=str(pid), MH_EXTRA=json.dumps(cfg_extra),
                   MH_MAX_STEPS=str(max_steps))
        env.pop("DCGAN_CHAOS", None)
        env.pop("JAX_COORDINATOR_ADDRESS", None)
        env.pop("DCGAN_PROTOCOL_LOG", None)
        if env_common:
            env.update(env_common)
        if chaos:
            env["DCGAN_CHAOS"] = json.dumps(chaos)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _MH_DRIVER], cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    results = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            results.append((p.returncode, out))
    except subprocess.TimeoutExpired:
        raise Failure(
            f"multihost job hung past {timeout}s — the exact failure the "
            "watchdog exists to prevent")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return results


def scenario_mh_nan_rollback(root: str) -> dict:
    """NaN visible on process 1's gate only -> the allgathered verdict makes
    BOTH hosts roll back to the same sharded device-resident snapshot; the
    job completes and both hosts end bit-identical."""
    results = _run_mh_train(
        dict(checkpoint_dir=os.path.join(root, "ck"),
             sample_dir=os.path.join(root, "sm"),
             nan_policy="rollback", nan_check_steps=1,
             rollback_snapshot_steps=2, max_rollbacks=2),
        max_steps=6, chaos={"1": {"nan_at_step": 3}})
    for pid, (rc, out) in enumerate(results):
        _check(rc == 0, f"process {pid} failed (rc={rc}): {out[-800:]}")
        _check("TRAIN_DONE step=6" in out,
               f"process {pid} did not complete: {out[-400:]}")
    chief_out = results[0][1]
    _check("rolling back to last-good snapshot at step 2" in chief_out,
           f"no rollback message on chief: {chief_out[-800:]}")
    _check("process(es) [1]" in chief_out,
           f"consensus did not attribute the trip to process 1: "
           f"{chief_out[-800:]}")
    sums = [next(line for line in out.splitlines()
                 if line.startswith("STATE_SUM=")) for _, out in results]
    _check(len(set(sums)) == 1,
           f"post-restore states diverged across hosts: {sums}")
    return {"rollbacks": 1, "final_step": 6, "state_sum": sums[0]}


def scenario_mh_sigterm_stop(root: str) -> dict:
    """SIGTERM on host 1 only -> the stop consensus breaks both hosts at
    the same boundary, the collective final save lands, and a fresh job
    restores it bit-exact.

    Protocol replay (ISSUE 14): phase A runs with DCGAN_PROTOCOL_LOG
    armed, so every real stop-consensus allgather logs its logical op;
    both processes' logged sequences must be identical AND equal to the
    committed simulator schedule for this exact scenario
    (analysis/protocol.lock.jsonl, drill-defaults/sigterm@p1@3) — the
    proof the simulated trainer mirror and the live trainer issue the
    same collective stream."""
    common = dict(checkpoint_dir=os.path.join(root, "ck"),
                  sample_dir=os.path.join(root, "sm"))
    sched = os.path.join(root, "sched.log")
    results = _run_mh_train(common, max_steps=6,
                            chaos={"1": {"sigterm_at_step": 3}},
                            env_common={"DCGAN_PROTOCOL_LOG": sched})
    for pid, (rc, out) in enumerate(results):
        _check(rc == 0, f"process {pid} failed (rc={rc}): {out[-800:]}")
        _check("TRAIN_DONE step=3" in out,
               f"process {pid} did not stop at step 3: {out[-400:]}")
    chief_out = results[0][1]
    _check("received signal" in chief_out
           and "on process(es) [1]" in chief_out,
           f"chief did not log the coordinated stop: {chief_out[-800:]}")
    _check(os.path.isdir(os.path.join(root, "ck", "3")),
           "no collective final checkpoint at the stop step")
    saved_sum = next(line for line in chief_out.splitlines()
                     if line.startswith("STATE_SUM="))

    # replay: live collective sequence == committed simulator schedule
    from dcgan_tpu.analysis import protocol as protocol_lib

    logs = []
    for pid in range(2):
        path = f"{sched}.{pid}"
        _check(os.path.exists(path),
               f"process {pid} logged no collective sequence at {path}")
        with open(path, encoding="utf-8") as f:
            logs.append([ln.strip() for ln in f if ln.strip()])
    _check(logs[0] == logs[1],
           f"per-process collective logs diverged: {logs[0]} vs {logs[1]}")
    expected = protocol_lib.drill_replay_ops()
    _check(logs[0] == expected,
           f"live collective sequence {logs[0]} != the committed "
           f"simulator schedule {expected} — the trainer's boundary "
           "protocol and analysis/simulate.py's mirror drifted apart")

    # phase B: resume lands exactly on the stop step -> the printed state
    # is the restored checkpoint, byte-for-byte the state phase A saved
    results = _run_mh_train(common, max_steps=3)
    for pid, (rc, out) in enumerate(results):
        _check(rc == 0, f"resume process {pid} failed (rc={rc}): "
                        f"{out[-800:]}")
        _check("TRAIN_DONE step=3" in out,
               f"resume process {pid} wrong step: {out[-400:]}")
    _check("restored checkpoint at step 3" in results[0][1],
           f"resume did not restore the stop checkpoint: "
           f"{results[0][1][-800:]}")
    restored_sum = next(line for line in results[0][1].splitlines()
                        if line.startswith("STATE_SUM="))
    _check(restored_sum == saved_sum,
           f"resume is not bit-exact: saved {saved_sum}, restored "
           f"{restored_sum}")
    return {"stopped_at": 3, "resumed": True, "state_sum": saved_sum,
            "replayed_collectives": len(logs[0])}


def scenario_mh_watchdog(root: str) -> dict:
    """Process 1 goes silent inside a collective window -> process 0's
    watchdog trips while BLOCKED in the collective process 1 never joined:
    diagnostic header (phase + step), all-thread stack dump, exit 43. The
    whole job then dies fast — once one process is gone, jax's own
    coordination client reaps the others with a fatal error — instead of
    the pre-watchdog outcome: every host wedged in a dead collective until
    an operator notices.

    Staggered deadlines (8 s on the blocked process, 20 s on the hung one)
    make the trip order deterministic: the blocked process — the
    interesting one, proving the watchdog fires DURING a dead collective,
    not just during a Python-level sleep — always trips first."""
    results = _run_mh_train(
        dict(checkpoint_dir=os.path.join(root, "ck"),
             sample_dir=os.path.join(root, "sm"),
             collective_timeout_secs=8.0),
        max_steps=8, chaos={"1": {"hang_at_step": 3, "hang_secs": 300}},
        extra_per_pid={1: dict(collective_timeout_secs=20.0)},
        timeout=180)
    for pid, (rc, out) in enumerate(results):
        _check(rc != 0, f"process {pid} exited 0 despite the hang")
        _check("TRAIN_DONE" not in out,
               f"process {pid} claimed completion: {out[-400:]}")
    rc0, out0 = results[0]
    # the Python watchdog thread prints the full diagnostic header and
    # exits 43; the GIL-immune faulthandler backstop prints "Timeout
    # (...)!" and exits 1 — either way process 0 dies WITH a stack dump
    # while blocked, never hangs
    _check("hung-collective watchdog" in out0 or "Timeout (" in out0,
           f"blocked process 0 missing watchdog diagnostic: {out0[-800:]}")
    _check("Thread" in out0 or "Current thread" in out0,
           f"blocked process 0 missing stack dump: {out0[-800:]}")
    _check(rc0 in (43, 1),
           f"process 0 died by something other than the watchdog "
           f"(rc={rc0}): {out0[-800:]}")
    if rc0 == 43:
        _check("step-dispatch" in out0 or "stop-consensus" in out0
               or "collective-save" in out0,
               f"watchdog header does not name the blocked phase: "
               f"{out0[-800:]}")
        # ISSUE 6: the Python-watchdog trip path (not the GIL-immune
        # C backstop, which cannot run Python) also ships the chief's
        # flight-recorder ring
        from dcgan_tpu.train.flight_recorder import read_dump

        dump = os.path.join(root, "ck", "flight_recorder.jsonl")
        _check(os.path.exists(dump),
               "no flight-recorder dump on the blocked chief")
        header, _ = read_dump(dump)
        _check(header["reason"] == "watchdog",
               f"dump header misattributes the trip: {header}")
    return {"exit_codes": [rc for rc, _ in results],
            "watchdog_rc": rc0}


MH_SCENARIOS = {
    "mh-nan-rollback": scenario_mh_nan_rollback,
    "mh-sigterm-stop": scenario_mh_sigterm_stop,
    "mh-watchdog": scenario_mh_watchdog,
}


# -- elastic-topology scenarios (ISSUE 12) -----------------------------------
#
# A checkpoint saved on one topology resumes on another THROUGH the
# sharding sidecar + rule-engine reshard (utils/checkpoint.py,
# dcgan_tpu/elastic/). Both directions pin the strongest contract
# available on CPU: the shrink/grow pair keeps the MESH identical (2-way
# "data" axis) and changes only the process census (2 proc x 1 dev <->
# 1 proc x 2 dev), so the compiled SPMD programs — and therefore the
# post-resume losses — replay against a same-topology control resume of
# the same checkpoint to within reduction-order noise: the HLO is
# identical, but the cross-PROCESS collective implementation may reduce
# partials in a different order than the intra-process one, so individual
# reduced scalars (a logged loss, the host-side param sum) can differ in
# the last ulp — the diffs below use ulp-scale relative tolerances, not
# text equality, and any REAL divergence (wrong batch, wrong shard, wrong
# step) is orders of magnitude beyond them. `synthetic_global_stream`
# makes the data stream layout-invariant (every process draws the full
# global batch and cuts its block), which is what makes the comparison
# meaningful at all. The scenarios live in the single-process matrix:
# each orchestrates its own 2-process phases.

#: knobs common to every elastic arm — scalar rows every step (the loss
#: replay is diffed from events.jsonl), no periodic saves (one final save
#: per phase), layout-invariant synthetic stream
_ELASTIC_KNOBS = dict(save_summaries_secs=0.0, save_model_secs=1e9,
                      save_model_steps=10_000, activation_summary_steps=0,
                      synthetic_global_stream=True)

#: a single process with TWO virtual CPU devices — the other layout of
#: the same 2-way data mesh the 2-process phases train on (full replace,
#: not append: the ambient test env may pin 8 devices). Partitionable
#: threefry matches the multihost workers' standard, so the two layouts
#: draw identical random streams (the bit-exact replay rides on it).
_TWO_DEV_ENV = {"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "DRILL_THREEFRY_PARTITIONABLE": "1"}


def _loss_rows(events) -> dict:
    """{step: (d_loss, g_loss)} from scalar rows — the replay record."""
    return {e["step"]: (e["values"]["d_loss"], e["values"]["g_loss"])
            for e in events
            if e["kind"] == "scalars" and "d_loss" in e["values"]}


def _elastic_scenario(root: str, *, shrink: bool) -> dict:
    """Save at 3 steps on the source topology, then resume to step 6 twice
    from clones of that checkpoint: once on the OTHER process layout
    (cross arm — must reshard through the sidecar's host-staged path) and
    once on the saving layout (control arm — sidecar present, reshard
    path NOT taken). Post-resume losses and final STATE_SUM must match
    bit-exactly; elastic/* keys must appear in the cross arm's events
    and nowhere in the control's."""
    from dcgan_tpu.testing.chaos import clone_checkpoint_dir

    ck = os.path.join(root, "ck")
    name = "shrink" if shrink else "grow"

    def run_two_proc(ckpt_dir, max_steps):
        results = _run_mh_train(
            dict(checkpoint_dir=ckpt_dir,
                 sample_dir=os.path.join(root, "sm"), **_ELASTIC_KNOBS),
            max_steps=max_steps)
        for pid, (rc, out) in enumerate(results):
            _check(rc == 0, f"{name}: 2-proc process {pid} failed "
                            f"(rc={rc}): {out[-800:]}")
            _check(f"TRAIN_DONE step={max_steps}" in out,
                   f"{name}: 2-proc process {pid} did not reach step "
                   f"{max_steps}: {out[-400:]}")
        return results[0][1]  # the chief's output (it logs and writes)

    def run_one_proc(ckpt_dir, max_steps):
        rc, out = _run_train(
            dict(checkpoint_dir=ckpt_dir,
                 sample_dir=os.path.join(root, "sm"), **_ELASTIC_KNOBS),
            max_steps=max_steps, env_extra=_TWO_DEV_ENV)
        _check(rc == 0,
               f"{name}: 1-proc trainer failed (rc={rc}): {out[-800:]}")
        _check(f"TRAIN_DONE step={max_steps}" in out,
               f"{name}: 1-proc run did not reach step {max_steps}: "
               f"{out[-400:]}")
        return out

    save, resume_cross = (run_two_proc, run_one_proc) if shrink \
        else (run_one_proc, run_two_proc)

    # phase A: train 3 steps on the source topology; the final forced
    # save carries the sharding sidecar
    save(ck, 3)
    _check(os.path.exists(os.path.join(ck, "integrity",
                                       "3.sharding.json")),
           f"{name}: no sharding sidecar beside the step-3 manifest")
    ck_cross = clone_checkpoint_dir(ck, os.path.join(root, "ck-cross"))
    ck_ctrl = clone_checkpoint_dir(ck, os.path.join(root, "ck-control"))

    # cross arm: the OTHER process layout of the same 2-way data mesh —
    # the process census changed, so the reshard must take the
    # host-staged path
    out_cross = resume_cross(ck_cross, 6)
    _check("cross-topology restore of step 3" in out_cross,
           f"{name}: resume did not take the reshard path: "
           f"{out_cross[-800:]}")
    _check("host-staged path" in out_cross,
           f"{name}: process-count change did not use the host-staged "
           f"reshard: {out_cross[-800:]}")
    _check("restored checkpoint at step 3" in out_cross,
           f"{name}: cross arm did not restore step 3: {out_cross[-800:]}")

    # control arm: the saving layout — sidecar present, reshard NOT taken
    out_ctrl = save(ck_ctrl, 6)
    _check("cross-topology restore" not in out_ctrl,
           f"{name}: same-topology control unexpectedly resharded: "
           f"{out_ctrl[-800:]}")
    _check("restored checkpoint at step 3" in out_ctrl,
           f"{name}: control arm did not restore step 3: "
           f"{out_ctrl[-800:]}")

    # loss replay: the same mesh ran the same programs over the same
    # (layout-invariant) batches — losses must agree to ulp scale. Not
    # text-exact: a loss reduced across PROCESSES (the 2-proc arm) may sum
    # partials in a different order than the intra-process all-reduce, and
    # float addition does not associate, so single-ulp diffs in a logged
    # scalar are legitimate (observed: g_loss, one ulp, grow direction).
    # 1e-6 relative is ~10 ulps of float32 — far above that noise, far
    # below any real divergence (wrong batch/shard/step shifts losses at
    # the 1e-2 scale here).
    lx, lc = _loss_rows(_events(ck_cross)), _loss_rows(_events(ck_ctrl))
    for s in (4, 5, 6):
        _check(s in lx and s in lc,
               f"{name}: missing step-{s} loss row (cross has "
               f"{sorted(lx)}, control {sorted(lc)})")
        _check(all(abs(a - b) <= 1e-6 * max(abs(a), abs(b), 1e-3)
                   for a, b in zip(lx[s], lc[s])),
               f"{name}: step-{s} losses diverged across topologies: "
               f"cross {lx[s]} != control {lc[s]}")
    # final params, same root cause wider window: the driver's host-side
    # STATE_SUM accumulates ~75 gathered leaves whose low-bit history
    # includes every boundary-order difference of the run, so it gets a
    # looser (still tiny) tolerance; 5e-4 is ~100x the observed drift and
    # far below any real state divergence.
    sum_cross = _state_sum_value(out_cross)
    sum_ctrl = _state_sum_value(out_ctrl)
    rel = abs(sum_cross - sum_ctrl) / max(abs(sum_ctrl), 1e-30)
    _check(rel <= 5e-4,
           f"{name}: post-resume states diverged beyond reduction-order "
           f"noise: {sum_cross!r} vs {sum_ctrl!r} (rel={rel:.2e})")

    # key gating: the reshard event surfaces elastic/*; the control stream
    # stays byte-identical in KEY SET to a pre-elastic resume
    cross_elastic = [e for e in _events(ck_cross) if e["kind"] == "scalars"
                     and "elastic/resharded" in e["values"]]
    ctrl_elastic = [e for e in _events(ck_ctrl) if e["kind"] == "scalars"
                    and any(k.startswith("elastic/") for k in e["values"])]
    _check(cross_elastic, f"{name}: no elastic/* event row in the cross "
                          "arm's stream")
    _check(not ctrl_elastic, f"{name}: elastic/* keys leaked into the "
                             f"same-topology control: {ctrl_elastic[:1]}")
    row = cross_elastic[-1]["values"]
    _check(row["elastic/host_stage"] == 1.0,
           f"{name}: elastic row does not record the host-staged path: "
           f"{row}")
    return {"direction": "2proc->1proc" if shrink else "1proc->2proc",
            "final_step": 6, "replay_within_tolerance": True,
            "state_sum_rel": rel,
            "reshard_ms": round(row["perf/restore/reshard_ms"], 1),
            "state_sum": sum_cross}


def scenario_elastic_shrink(root: str) -> dict:
    """2-process save -> 1-process (2-device) resume: the preemptible-
    fleet shrink. Ulp-tolerance loss replay vs a 2-process control
    resume."""
    return _elastic_scenario(root, shrink=True)


def scenario_elastic_grow(root: str) -> dict:
    """1-process (2-device) save -> 2-process resume: scale back out after
    a degraded period. Ulp-tolerance loss replay vs a 1-process control."""
    return _elastic_scenario(root, shrink=False)


SCENARIOS["elastic-shrink"] = scenario_elastic_shrink
SCENARIOS["elastic-grow"] = scenario_elastic_grow


# -- live in-run elasticity (ISSUE 18, dcgan_tpu/elastic/live.py) ------------
#
# No restart in these drills: ONE trainer process with two virtual devices
# receives a chaos preemption notice mid-run and switches its live mesh
# (t2x1 -> t1x1, and back on a grow notice) at a step boundary. The
# contract stack, strongest first:
#   1. pre-notice losses replay BIT-EXACTLY against an armed-but-unnotified
#      control (same config, no fault) — arming elasticity is free;
#   2. the switch dispatches only warmup-cached executables:
#      compile_requests_delta=0 printed on the switch line (a persistent
#      compile cache is configured so the delta is measured, not assumed);
#   3. post-switch the run COMPLETES, and the final params stay within the
#      same reduction-order tolerance as the restart-based arms above —
#      a 1-device and a 2-device data axis reduce the global batch in
#      different orders, so post-switch trajectories are near, not equal
#      (the state MOVE itself is bit-lossless — pinned in-process by
#      tests/test_live_elastic.py, where both sides are observable);
#   4. elastic/live_* event keys appear ONLY in the notified run.

#: the live-elastic arm's extra knobs: elasticity armed at 1 device,
#: AOT warmup on (the switch contract is warm-both-topologies), metrics
#: every step for the loss diff
def _live_knobs(root: str, ck: str) -> dict:
    return dict(checkpoint_dir=ck, sample_dir=os.path.join(root, "sm"),
                compile_cache_dir=os.path.join(root, "cache"),
                elastic_target_devices=1, aot_warmup=True,
                **_ELASTIC_KNOBS)


def _run_live(root: str, ck: str, *, chaos: dict = None):
    rc, out = _run_train(_live_knobs(root, ck), max_steps=6, chaos=chaos,
                         env_extra=_TWO_DEV_ENV)
    _check(rc == 0, f"live trainer failed (rc={rc}): {out[-800:]}")
    _check("TRAIN_DONE step=6" in out,
           f"live run did not reach step 6: {out[-400:]}")
    _check("live-elastic warmup primed" in out,
           f"live run did not prime both topologies: {out[-800:]}")
    return out


def _switch_line(out: str, step: int, arrow: str) -> str:
    want = f"live elastic switch at step {step}: {arrow}"
    line = next((ln for ln in out.splitlines() if want in ln), None)
    _check(line is not None,
           f"no '{want}' line in output: {out[-800:]}")
    _check("compile_requests_delta=0" in line,
           f"switch at step {step} compiled something: {line}")
    return line


def _live_compare(name: str, ck_fault: str, ck_ctrl: str,
                  out_fault: str, out_ctrl: str) -> float:
    lf, lc = _loss_rows(_events(ck_fault)), _loss_rows(_events(ck_ctrl))
    for s in (1, 2, 3):
        _check(s in lf and s in lc,
               f"{name}: missing step-{s} loss row (fault has "
               f"{sorted(lf)}, control {sorted(lc)})")
        _check(lf[s] == lc[s],
               f"{name}: PRE-notice step-{s} losses diverged — arming "
               f"elasticity must be free: {lf[s]} != {lc[s]}")
    sum_f, sum_c = _state_sum_value(out_fault), _state_sum_value(out_ctrl)
    rel = abs(sum_f - sum_c) / max(abs(sum_c), 1e-30)
    _check(rel <= 5e-4,
           f"{name}: post-switch state outside reduction-order tolerance: "
           f"{sum_f!r} vs {sum_c!r} (rel={rel:.2e})")
    live_rows = [e for e in _events(ck_fault) if e["kind"] == "scalars"
                 and "elastic/live_switch_ms" in e["values"]]
    _check(live_rows, f"{name}: no elastic/live_* event row in the "
                      "notified run's stream")
    ctrl_rows = [e for e in _events(ck_ctrl) if e["kind"] == "scalars"
                 and any(k.startswith("elastic/live_")
                         for k in e["values"])]
    _check(not ctrl_rows, f"{name}: elastic/live_* keys leaked into the "
                          f"unnotified control: {ctrl_rows[:1]}")
    return rel


def scenario_live_notice_shrink(root: str) -> dict:
    """Chaos preemption notice at step 3 -> live t2x1 -> t1x1 switch, no
    restart; completes to step 6 with zero compile requests across the
    switch, vs an armed-but-unnotified control."""
    out_ctrl = _run_live(root, os.path.join(root, "ck-control"))
    _check("live elastic switch" not in out_ctrl,
           f"control switched without a notice: {out_ctrl[-800:]}")
    ck = os.path.join(root, "ck")
    out = _run_live(root, ck, chaos={"preempt_notice_at_step": 3})
    _switch_line(out, 3, "t2x1 -> t1x1")
    rel = _live_compare("notice-shrink", ck,
                        os.path.join(root, "ck-control"), out, out_ctrl)
    row = [e for e in _events(ck) if e["kind"] == "scalars"
           and "elastic/live_switch_ms" in e["values"]][-1]["values"]
    _check(row["elastic/live_target_mesh"] == 1.0,
           f"live event row does not record the 1-device target: {row}")
    return {"final_step": 6, "compile_requests_delta": 0,
            "switch_ms": round(row["elastic/live_switch_ms"], 1),
            "state_sum_rel": rel}


def scenario_live_grow_back(root: str) -> dict:
    """Shrink notice at step 3 + grow notice at step 5: t2x1 -> t1x1 ->
    t2x1 in one uninterrupted run, both switches compile-free. The t1x1
    leg (steps 4-5) must replay BIT-EXACTLY against a shrink-only run —
    the grow-back surface was warmed at startup, and being ABLE to grow
    must not perturb the shrunken trajectory."""
    out_ctrl = _run_live(root, os.path.join(root, "ck-control"))
    ck_s = os.path.join(root, "ck-shrink")
    out_s = _run_live(root, ck_s, chaos={"preempt_notice_at_step": 3})
    ck = os.path.join(root, "ck")
    out = _run_live(root, ck, chaos={"preempt_notice_at_step": 3,
                                     "grow_notice_at_step": 5})
    _switch_line(out, 3, "t2x1 -> t1x1")
    _switch_line(out, 5, "t1x1 -> t2x1")
    rel = _live_compare("grow-back", ck, os.path.join(root, "ck-control"),
                        out, out_ctrl)
    lg, ls = _loss_rows(_events(ck)), _loss_rows(_events(ck_s))
    for s in (4, 5):
        _check(s in lg and s in ls,
               f"grow-back: missing step-{s} loss row (grow has "
               f"{sorted(lg)}, shrink-only {sorted(ls)})")
        _check(lg[s] == ls[s],
               f"grow-back: shrunken-leg step-{s} losses diverged from "
               f"the shrink-only run: {lg[s]} != {ls[s]}")
    return {"final_step": 6, "switches": 2, "compile_requests_delta": 0,
            "shrunken_leg_bit_exact": True, "state_sum_rel": rel}


SCENARIOS["notice-shrink"] = scenario_live_notice_shrink
SCENARIOS["grow-back"] = scenario_live_grow_back


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="chaos_drill",
        description="fault-injection scenario matrix for the trainer's "
                    "fail-operational layer (CPU)")
    p.add_argument("--smoke", action="store_true",
                   help=f"CI subset: {', '.join(SMOKE_SCENARIOS)} "
                        f"(with --multihost: "
                        f"{', '.join(MH_SMOKE_SCENARIOS)})")
    p.add_argument("--multihost", action="store_true",
                   help="run the 2-process coordinated-recovery matrix "
                        f"({', '.join(sorted(MH_SCENARIOS))}) instead of "
                        "the single-process one")
    p.add_argument("--only", nargs="+",
                   choices=sorted(SCENARIOS) + sorted(MH_SCENARIOS),
                   default=None, help="run just these scenarios")
    args = p.parse_args(argv)
    table = MH_SCENARIOS if args.multihost else SCENARIOS
    smoke = MH_SMOKE_SCENARIOS if args.multihost else SMOKE_SCENARIOS
    if args.only:
        bad = [n for n in args.only if n not in table]
        if bad:
            p.error(f"scenario(s) {bad} are not in the "
                    f"{'multihost' if args.multihost else 'single-process'} "
                    f"matrix; choose from {sorted(table)}")
        names = args.only
    else:
        names = smoke if args.smoke else sorted(table)
    failures = 0
    for name in names:
        with tempfile.TemporaryDirectory(prefix=f"chaos_{name}_") as root:
            row = {"scenario": name}
            try:
                row.update(table[name](root))
                row["ok"] = True
            except Failure as e:
                row.update(ok=False, error=str(e))
                failures += 1
            print(json.dumps(row), flush=True)
    print(json.dumps({"label": "chaos-drill-multihost" if args.multihost
                      else "chaos-drill", "scenarios": len(names),
                      "failed": failures}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
