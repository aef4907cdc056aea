"""Warm-start subsystem: persistent compile cache + AOT pre-compilation.

PRs 3-4 made restarts the NORMAL response to faults (watchdog exit 43,
coordinated preemption stop, rollback recompiles), which moves the dominant
cost of a preemptible fleet from steady-state step time to startup: every
restart re-pays full XLA compilation of every program plus the checkpoint
read. The pjit/TPUv4 scaling work (PAPERS.md, arxiv 2204.06514) treats
compilation caching as first-class throughput infrastructure and ParaGAN
(arxiv 2411.03999) frames GAN efficiency as end-to-end goodput; this module
is that discipline for tpu-dcgan's time-to-first-step:

- `resolve_cache_dir` + `configure_compile_cache` place JAX's persistent
  compilation cache, for every entry point alike: `--compile_cache_dir`,
  else `JAX_COMPILATION_CACHE_DIR`, else (entry points only) the fixed
  `CHECKOUT_CACHE_DIR`. The
  multi-host keying is safe by construction: JAX's cache layer only WRITES
  entries from process 0 (chief-writes) while every process reads, so one
  shared directory never sees write contention; for fleets without a shared
  filesystem, `--compile_cache_per_process` gives each process its own
  subdirectory instead (`proc<i>/` — same cache keys, disjoint stores).
  The min-compile-time threshold is dropped to 0: this trainer runs a
  handful of long-lived programs, every one of which is re-lowered on every
  restart, so "too cheap to cache" (JAX's default 1 s floor, tuned for
  jit-churn workloads) is the wrong default here.

- `CompileCacheMonitor` turns the `compile/backend` records of
  utils/profiling.py's span store into the
  `perf/compile_cache_{requests,hits,misses}` counters the trainer
  surfaces as JSONL events — cache effectiveness is a recorded number per
  run, not a log grep.

- `build_warmup_plan` + `aot_compile` are the explicit AOT warmup phase
  (`--aot_warmup`): every program the run can dispatch — the k=1 n_critic
  tail, the `steps_per_call` scan variant, the sampler/probe/summarize
  shapes, and the LR-backoff rebuild variant (`backoff_config`, shared with
  the trainer's rollback executor so the two constructions cannot drift) —
  is `.lower().compile()`d up front with per-program `perf/compile_ms/*`
  timings. With the persistent cache active, each warmup compile primes the
  cache entry the loop's live dispatch then deserializes, so first-dispatch
  cost is bounded IO, not compile — which is what lets the trainer's
  watchdog arm from warmup PROOF (mesh_warm + the `compiled_ks` exemption
  set) instead of waiting for first live steps.

Plan-row naming: the launch surface's rows carry plain program names;
variant surfaces suffix theirs so one plan can warm several compiled
surfaces without name collisions — `@lr_backoff` (the rollback rebuild,
this module), `@r<res>` (progressive phases, progressive/phases.py),
`@t<data>x<model>` (live-elasticity topologies, elastic/live.py). The
semantic tier's coverage rows (analysis/semantic.py, DCG009) pin the
suffixed names, so a renamed row is a lock diff, not a silent miss.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from dcgan_tpu.config import is_token_arch
from dcgan_tpu.utils import profiling

#: jax's own variable: where it is set the cache is kept there, and the
#: only thing that overrides it is an explicit --compile_cache_dir
CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the entry points' default when neither flag nor variable names a
#: directory. Fixed and inside the checkout (git-ignored): the path is
#: part of the cache key, so a directory that moves never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir(cfg_dir: str = "", *, entry_point: bool = False,
                      env=None) -> str:
    """Where the persistent compile cache lives — the one decision the
    trainer, the server, bench.py, chip_smoke.py and the tools share: the
    config/CLI value, else JAX_COMPILATION_CACHE_DIR, else
    CHECKOUT_CACHE_DIR for an entry point (the CLI mains, bench.py,
    chip_smoke.py) and "" for a library caller — which
    `configure_compile_cache` reads as "leave the process's setting
    alone"."""
    env = os.environ if env is None else env
    return (cfg_dir or env.get(CACHE_ENV_VAR, "")
            or (CHECKOUT_CACHE_DIR if entry_point else ""))


def configure_compile_cache(cache_dir: str, *,
                            per_process: bool = False) -> Optional[str]:
    """Point JAX's persistent compilation cache at `cache_dir`; returns the
    effective directory (per-process subdir under `per_process`) or None
    when the process has no cache. An empty `cache_dir` leaves the
    process's setting alone — whatever JAX_COMPILATION_CACHE_DIR or an
    entry point put in force stays in force, and is what gets returned.
    Must run before the first compile — the trainer calls it right after
    `initialize_multihost()` (the per-process keying needs the real
    process index), before any program is built.
    """
    cache_dir = cache_dir or jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return None
    if per_process and jax.process_count() > 1:
        # no shared filesystem: disjoint per-process stores under whichever
        # root is in force. Keys are process-independent, so this trades
        # dedup for zero cross-host filesystem assumptions. JAX only WRITES
        # cache entries from process 0, so non-chief stores stay empty
        # (reads are harmless) — the trainer excludes this mode from
        # watchdog warm proof and warns, rather than arming deadlines over
        # peers that will in fact recompile.
        sub = f"proc{jax.process_index()}"
        if os.path.basename(cache_dir) != sub:  # a repeat train() call
            cache_dir = os.path.join(cache_dir, sub)
    changed = jax.config.jax_compilation_cache_dir != cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache EVERY program: this trainer compiles a handful of long-lived
    # programs per run, all re-lowered on every restart — the "skip cheap
    # compiles" defaults exist for jit-churn workloads, not this shape
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if changed:
        # jax memoizes the cache OBJECT on first use; without this a
        # process that re-points the dir keeps reading/writing the old one
        _reset_cache_object()
    return cache_dir


def _reset_cache_object() -> None:
    """Drop jax's memoized persistent-cache object so the current
    `jax_compilation_cache_dir` value takes effect (jax initializes the
    object lazily ONCE and never re-reads the config)."""
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def cache_serves_all_processes(per_process: bool) -> bool:
    """Whether a warm restart can expect cache HITS on every process —
    the condition watchdog warm proof rides on. True for single-process
    and for the shared-dir multi-host mode (the chief writes during its
    AOT compiles, the warmup barrier orders those writes before any peer's
    live dispatch reads them). False for per-process dirs under multi-host:
    only process 0's store is ever written, so every other process
    recompiles at first live dispatch no matter how warm its warmup
    looked."""
    return jax.process_count() == 1 or not per_process


class CompileCacheMonitor:
    """Counts persistent-cache adoption from the `compile/backend` records
    (utils/profiling.py) that start after its construction: a record whose
    `count` is set asked the cache (`requests`); its `count` is 1 on a miss
    and 0 on a hit. It registers nothing with JAX.

    The counters are process-local and monotonic from construction;
    `counters()` snapshots them, `delta(since)` diffs two snapshots (the
    trainer brackets phases with it). `close()` freezes them. The store
    keeps the newest `profiling.SPAN_RING` compile records, so a monitor
    counts at most that many compiles.
    """

    def __init__(self) -> None:
        self._since = time.perf_counter()
        self._closed: Optional[Dict[str, float]] = None

    def counters(self) -> Dict[str, float]:
        if self._closed is not None:
            return dict(self._closed)
        asked = [r.count for r in profiling.spans("compile/backend")
                 if r.start >= self._since and r.count is not None]
        return {"requests": len(asked), "hits": asked.count(0),
                "misses": sum(asked)}

    @staticmethod
    def delta(now: Dict[str, float],
              since: Dict[str, float]) -> Dict[str, float]:
        return {k: now[k] - since.get(k, 0) for k in now}

    def close(self) -> None:
        if self._closed is None:
            self._closed = self.counters()


def backoff_config(cfg, scale: float):
    """The rollback LR-backoff TrainConfig variant — ONE construction shared
    by the trainer's rollback executor and the warmup plan, so the program
    the warmup pre-compiles is bit-identical (same HLO constants, same cache
    key) to the one a live rollback rebuilds."""
    import dataclasses

    def _bk(lr):
        return None if lr is None else lr * scale

    return dataclasses.replace(
        cfg, learning_rate=cfg.learning_rate * scale,
        d_learning_rate=_bk(cfg.d_learning_rate),
        g_learning_rate=_bk(cfg.g_learning_rate))


def _identity_copy():
    """A jit of the SAME identity lambda rollback.device_copy and the
    checkpoint rebase compile — byte-identical HLO, so one persistent-cache
    entry serves all three jit objects. Deliberately a FRESH jit per call:
    a memoized object would serve repeat warmups from its in-memory AOT
    cache and skip the persistent-cache write a newly-pointed cache dir
    needs (multi-`train()` processes — tests, drills)."""
    return jax.jit(lambda t: jax.tree_util.tree_map(lambda a: a + 0, t))


def state_example(pt):
    """The train-state example argument for `.lower()`ing `pt`'s programs
    WITHOUT allocating it: sharded ShapeDtypeStructs from `eval_shape` over
    `pt.init` + `pt.shardings`. The warmup plan itself receives the live
    state from the trainer; the semantic analyzer (ISSUE 11) lowers the
    same plan pre-allocation, so the derivation lives here where the plan
    is built and the two callers cannot shape-drift. The lambda matters:
    under the armed tripwire pt.init is a _GuardedFn, which eval_shape
    cannot weakref — a plain closure can."""
    shapes = jax.eval_shape(lambda k: pt.init(k), jax.random.key(0))
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, pt.shardings)


def _program_args(cfg, pt, state, *, sample_z=None, sample_labels=None,
                  eval_z=None) -> List[Tuple[str, Callable, tuple]]:
    """(name, jitted fn, example args) for every program `pt` can dispatch
    this run, with the trainer's exact live shapes/shardings: images as
    sharded ShapeDtypeStructs (never allocated), z/labels/state as the
    concrete arrays the loop itself feeds."""
    import jax.numpy as jnp

    from dcgan_tpu.parallel import batch_sharding

    mesh = pt.mesh
    if is_token_arch(cfg.model.arch):
        # a token arch's batch: int32 ids [batch, seq_len]
        img = jax.ShapeDtypeStruct(
            (cfg.batch_size, cfg.model.seq_len), jnp.int32,
            sharding=batch_sharding(mesh, 2))
    else:
        size = cfg.model.output_size
        img = jax.ShapeDtypeStruct(
            (cfg.batch_size, size, size, cfg.model.c_dim), jnp.float32,
            sharding=batch_sharding(mesh, 4, spatial=cfg.mesh.spatial))
    conditional = cfg.model.num_classes > 0
    key = jax.random.key(0)
    lbls = (jax.ShapeDtypeStruct((cfg.batch_size,), jnp.int32,
                                 sharding=batch_sharding(mesh, 1)),) \
        if conditional else ()

    def _scan_sds(sds, k):
        return jax.ShapeDtypeStruct(
            (k,) + sds.shape, sds.dtype,
            sharding=jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(None, *sds.sharding.spec)))

    if cfg.pipeline_gd:
        # pipelined dispatch (ISSUE 7): the loop runs the three stage
        # programs, never the fused step — warm exactly what it dispatches.
        # The fake stack example arg is a ShapeDtypeStruct with the
        # slot-axis-in-front scan sharding (batch on axis 1), the shape
        # gen_fakes/g_update produce and d_update consumes.
        fakes = _scan_sds(img, cfg.n_critic)
        step_programs: List[Tuple[str, Callable, tuple]] = [
            ("gen_fakes", pt.programs["gen_fakes"], (state, key)),
            ("d_update", pt.programs["d_update"],
             (state, img, fakes, key)),
            ("g_update", pt.programs["g_update"], (state, key)),
        ]
    else:
        step_programs = [("train_step", pt.programs["train_step"],
                          (state, img, key) + lbls)]
    programs: List[Tuple[str, Callable, tuple]] = step_programs + [
        # the state-tree identity copy: the program behind BOTH the
        # checkpoint restore's buffer rebase (utils/checkpoint.py) and the
        # rollback device-resident snapshot (train/rollback.device_copy) —
        # same lambda, same HLO, one cache entry serves all three jit
        # objects, so a warm restart's restore-time rebase deserializes
        # instead of being the one cold compile left on the restart path
        ("state_copy", _identity_copy(), (state,)),
    ]
    k = cfg.steps_per_call
    if k > 1:
        scan_img = _scan_sds(img, k)
        keys = jax.vmap(jax.random.fold_in, (None, 0))(key, jnp.arange(k))
        scan_lbls = (_scan_sds(lbls[0], k),) if conditional else ()
        programs.append((f"multi_step@k{k}", pt.programs["multi_step"],
                         (state, scan_img, keys) + scan_lbls))
    if sample_z is not None:
        s_lbls = (sample_labels,) if sample_labels is not None else ()
        programs.append(("sampler", pt.programs["sampler"],
                         (state, sample_z) + s_lbls))
    if eval_z is not None:
        programs.append(("eval_losses", pt.programs["eval_losses"],
                         (state, img, eval_z) + lbls))
    if cfg.activation_summary_steps:
        programs.append(("summarize", pt.programs["summarize"],
                         (state, img, key) + lbls))
    return programs


def build_warmup_plan(cfg, pt, state, *, sample_z=None, sample_labels=None,
                      eval_z=None, make_backoff_pt: Optional[Callable] = None
                      ) -> Tuple[List[Tuple[str, Callable, tuple]],
                                 Optional[Any]]:
    """Every (name, program, args) this run can dispatch, plus — when the
    run arms `rollback_lr_backoff` — a fully-built ParallelTrain for the
    FIRST rollback's LR scale whose step programs join the plan, so a live
    rollback swaps in a pre-warmed surface instead of recompiling mid-
    recovery. `make_backoff_pt` maps the backoff TrainConfig to that
    surface (the trainer passes make_parallel_train pinned to its mesh)."""
    plan = _program_args(cfg, pt, state, sample_z=sample_z,
                         sample_labels=sample_labels, eval_z=eval_z)
    pt_backoff = None
    if (cfg.nan_policy == "rollback" and cfg.rollback_lr_backoff < 1.0
            and make_backoff_pt is not None):
        pt_backoff = make_backoff_pt(
            backoff_config(cfg, cfg.rollback_lr_backoff))
        for name, fn, args in _program_args(
                cfg, pt_backoff, state, sample_z=sample_z,
                sample_labels=sample_labels, eval_z=eval_z):
            # only the step programs rebuild on rollback; sampler/probe/
            # summarize are LR-independent (identical HLO, already planned).
            # Under --pipeline_gd the step programs are the d_update/
            # g_update stages (optimizer constants bake the LR in);
            # gen_fakes is LR-independent like the sampler
            if name.startswith(("train_step", "multi_step",
                                "d_update", "g_update")):
                plan.append((f"{name}@lr_backoff", fn, args))
    return plan, pt_backoff


def aot_compile(plan: List[Tuple[str, Callable, tuple]],
                ) -> Dict[str, float]:
    """`.lower().compile()` every planned program; {name: compile_ms}.

    Each compile lands in the persistent cache (when configured), so the
    loop's live dispatch of the same program deserializes instead of
    compiling — warmup converts unbounded compile time into bounded IO at
    a point where nothing is blocked on it.
    """
    timings: Dict[str, float] = {}
    for name, fn, args in plan:
        t0 = time.perf_counter()
        fn.lower(*args).compile()
        timings[name] = (time.perf_counter() - t0) * 1e3
    return timings
