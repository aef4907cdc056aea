"""The trainer's declared JSONL event-key inventory (ISSUE 8, DCG004).

One entry per metric key (or wildcard prefix) the trainer can emit,
mapped to the knob that gates it — "always" means the key may appear in a
default-flags run and is therefore covered by the byte-parity contract
(tests/test_services.py async-vs-inline, tests/test_chaos.py
rollback-armed-vs-default). Everything else must be invisible until its
knob activates, which is exactly what the gating annotation documents.

The static half of the enforcement is analysis/parity.py (DCG004): every
namespaced key literal in trainer.py/coordination.py must appear here, so
a new ungated key fails the lint before it fails the parity A/B. The
runtime half is tests/test_analysis.py's completeness tests: the keys
StepTimer / StartupProfile / fleet_metrics actually produce are checked
against this inventory, closing the loop for keys built from prefix
parameters the static pass cannot see.

Un-namespaced scalar keys (d_loss, g_loss, r1, gp, ...) are the device
metric dict from train/steps.py — replicated program outputs, identical
in every mode by the step-equivalence tests — and are deliberately
outside this inventory.

This module must stay import-light (no jax): the analyzer loads it on
every lint pass.
"""

from __future__ import annotations

from typing import Dict

EVENT_KEYS: Dict[str, str] = {
    # -- StepTimer window stats (utils/profiling.py, prefix "perf/") -----
    "perf/step_ms_mean": "always",
    "perf/step_ms_p50": "always",
    "perf/step_ms_p90": "always",
    "perf/step_ms_max": "always",
    "perf/steps_per_sec": "always",
    "perf/images_per_sec": "always",
    "perf/host_ms_mean": "always",
    "perf/dispatch_occupancy": "always",

    # -- startup report (written only when a warm-start knob is active;
    #    always printed to stdout) ---------------------------------------
    "perf/startup/*": "compile_cache_dir|aot_warmup",
    "perf/compile_cache_requests": "compile_cache_dir",
    "perf/compile_cache_hits": "compile_cache_dir",
    "perf/compile_cache_misses": "compile_cache_dir",
    "perf/compile_ms/*": "aot_warmup",
    "perf/restore/verify_files": "compile_cache_dir|aot_warmup",
    "perf/restore/verify_bytes": "compile_cache_dir|aot_warmup",
    "perf/restore/verify_cached_bytes": "compile_cache_dir|aot_warmup",
    "perf/restore/verify_ms": "compile_cache_dir|aot_warmup",

    # -- on-demand device-trace digest (ISSUE 6) -------------------------
    "perf/device/compute_ms": "profile_dir|profile_trigger",
    "perf/device/collective_ms": "profile_dir|profile_trigger",
    "perf/device/idle_gap_ms": "profile_dir|profile_trigger",
    "perf/device/span_ms": "profile_dir|profile_trigger",
    "perf/device/step_ms": "profile_dir|profile_trigger",
    # collective-time-hidden-behind-compute fraction (ISSUE 20): the
    # `--comm_overlap` A/B's trace-level attribution; rides the same
    # digest row as the other perf/device keys, so it stays gated on
    # the capture knobs and out of default streams
    "perf/device/overlap_frac": "profile_dir|profile_trigger",

    # -- recovery counters (absent until nonzero — the parity contract's
    #    "new keys only when the feature activates" clause) --------------
    "anomaly/rollbacks": "nan_policy=rollback",
    "data/corrupt_records": "nonzero quarantine count",

    # -- elastic topology (ISSUE 12): a restore that RESHARDED because the
    #    checkpoint's sharding sidecar names a different topology. Gated by
    #    the reshard event itself, never by a knob — same-topology streams
    #    (sidecar present, reshard path not taken) stay byte-identical ----
    "elastic/resharded": "cross-topology restore",
    "elastic/saved_processes": "cross-topology restore",
    "elastic/saved_devices": "cross-topology restore",
    "elastic/host_stage": "cross-topology restore",
    "perf/restore/reshard_ms": "cross-topology restore",
    "perf/restore/reshard_leaves": "cross-topology restore",

    # -- live in-run elasticity (ISSUE 18): one scalar row per
    #    notice-driven topology switch. Gated by the switch EVENT, not
    #    the knob — an armed-but-unnotified run emits none of these, so
    #    its stream stays byte-identical to an unarmed run (the
    #    default-off parity A/B in tests/test_live_elastic.py) ------------
    "elastic/live_notice_step": "live elasticity switch",
    "elastic/live_switch_ms": "live elasticity switch",
    "elastic/live_target_mesh": "live elasticity switch",
    "elastic/live_resumed_step": "live elasticity switch",

    # -- fleet health plane (ISSUE 6, coordination.fleet_metrics) --------
    "fleet/step_ms_max": "fleet_health_steps",
    "fleet/step_ms_min": "fleet_health_steps",
    "fleet/step_ms_skew": "fleet_health_steps",
    "fleet/slowest_host": "fleet_health_steps",
    "fleet/host_ms_max": "fleet_health_steps",
    "fleet/queue_depth_max": "fleet_health_steps",
    "fleet/dropped_total": "fleet_health_steps",
    "fleet/rollbacks_total": "fleet_health_steps",
    "fleet/corrupt_total": "fleet_health_steps",

    # -- progressive-resolution schedule (ISSUE 15): the active phase /
    #    resolution ride every scalar row of a progressive run, alpha
    #    only inside a fade window, switch_ms once per phase switch.
    #    Gated on the knob — default (fixed-resolution) streams carry
    #    none of these (parity-pinned) -------------------------------------
    "progressive/phase": "progressive schedule",
    "progressive/resolution": "progressive schedule",
    "progressive/alpha": "progressive schedule (fade window)",
    "progressive/switch_ms": "progressive schedule",

    # -- fleet health: the active progressive phase (0 in fixed-resolution
    #    runs; max across hosts — the switch is step-keyed so max == min) -
    "fleet/phase": "fleet_health_steps",

    # -- reduced-precision ladder (ISSUE 17): one startup row naming the
    #    active policy (numeric code: 0=f32, 1=bf16) and the f32
    #    master-moment census from elastic/rules.py. Gated on the knob —
    #    precision="" (the default) emits neither, so default streams stay
    #    byte-identical (parity A/B-pinned); the policy STRING rides the
    #    flight-recorder header, which is crash-path-only IO ---------------
    "perf/precision/policy": "precision",
    "perf/precision/master_f32_leaves": "precision",

    # -- probes ----------------------------------------------------------
    "sample/*": "sample_every_steps",
    "eval/fid": "fid_every_steps",
    "eval/kid": "fid_every_steps",

    # -- serving plane (ISSUE 9, dcgan_tpu/serve) ------------------------
    # These keys appear only in the serve entry point's own event stream
    # (`python -m dcgan_tpu.serve --events_dir`/`--report`), never in the
    # trainer's JSONL — the trainer parity contract cannot see them by
    # construction; the annotation names the subsystem that emits them.
    # DCG004 lints serve/server.py and serve/__main__.py against this
    # inventory the same way it lints the trainer.
    "serve/requests": "serve entrypoint",
    "serve/completed": "serve entrypoint",
    "serve/dropped": "serve entrypoint",
    "serve/batches": "serve entrypoint",
    "serve/images": "serve entrypoint",
    "serve/queue_depth_max": "serve entrypoint",
    "serve/pad_frac": "serve entrypoint",
    "serve/samples_per_sec": "serve entrypoint",
    "serve/p50_ms": "serve entrypoint",
    "serve/p99_ms": "serve entrypoint",
    "serve/mean_ms": "serve entrypoint",
    "serve/restore_ms": "serve entrypoint",
    "serve/warmup_ms": "serve entrypoint",
    "serve/cold_start_ms": "serve entrypoint",
    "serve/compile_ms/*": "serve entrypoint",
    "serve/recompiles_after_warmup": "serve entrypoint (compile cache on)",

    # -- serving fleet (ISSUE 19, serve/fleet.py + router.py): the drop
    #    split makes fleet shedding attributable (overload = deliberate
    #    backpressure, failover = no healthy peer could absorb), and the
    #    fleet_* / promotion keys ride only the fleet-mode report row.
    #    DCG004 lints serve/fleet.py and serve/router.py against this
    #    inventory too. -------------------------------------------------
    "serve/dropped_overload": "serve entrypoint",
    "serve/dropped_failover": "serve entrypoint (--fleet)",
    "serve/fleet_replicas": "serve entrypoint (--fleet)",
    "serve/fleet_unhealthy": "serve entrypoint (--fleet)",
    "serve/fleet_failovers": "serve entrypoint (--fleet)",
    "serve/promotions": "serve entrypoint (weight promotion)",
    "serve/promote_swap_ms": "serve entrypoint (weight promotion)",
}
