"""Per-component chip profile of a train step (the MFU numerator).

VERDICT r3 #1 (weak #2): the headline's "~64 TFLOP/s" effective rate had no
in-repo breakdown — no per-op split of the 2.91 ms step and no reproducible
FLOP count. VERDICT r4 #5 extended the same question to the families below
the 4x north star (dcgan128, wgan-gp, sagan64-attn): are they at THEIR
roofs, or leaving throughput on the table? This tool measures both — for
the headline config by default, or any preset/knob combo via the same
BENCH_PRESET / BENCH_ATTN / BENCH_SN / BENCH_PALLAS / BENCH_SIZE env vars
bench.py reads (so a profile always describes exactly the config of a
captured bench row) — standalone or under capture_all (section
"roofline"):

- `compiled.cost_analysis()` on the exact headline train-step program gives
  the XLA FLOP count (the numerator of every TFLOP/s claim in DESIGN.md).
- Component timings through the same scanned-dispatch + value-readback
  harness bench.py uses (each component is scanned K times inside ONE
  compiled program so the per-dispatch host cost cannot pollute a
  ~ms-scale component):
    train_step      full D-then-G step (2 fwd passes + 2 bwd + 2 Adam + BN)
    fwd_losses      forward only: G fwd, D fwd on real and fake (eval_losses)
    g_forward       generator forward alone (the sampler path)
    adam_applies    both optax Adam chains applied to synthetic grads
  The scan body varies its inputs from the scanned-over axis so XLA cannot
  hoist loop-invariant work out and time an empty loop.

The decomposition is arithmetic, not a trace: bwd+opt = step - fwd_losses is
reported as the derived residual (fusion blurs any finer split — XLA fuses
elementwise/BN work into the convs, which is the design, DESIGN.md §1).

Prints one JSON line per component and a summary:
  {"component": "train_step", "ms": t, "images_per_sec": r}
  {"label": "step-profile", "step_ms": t, "flops_per_step": F,
   "tflops_effective": F/t, ...}

PIPELINE_GD=1 additionally emits per-stage FLOP rows for the pipelined
G/D stage programs (ISSUE 7) — {"component": "stage/d_update", ...} for
gen_fakes / d_update / g_update, with the same scan_trips stamp — so cost
attribution under --pipeline_gd describes the programs that run, not only
the fused one.

PALLAS_FUSED=1 / PRECISION={bf16,fp8} (ISSUE 17) profile the knobbed
program (the fused Pallas conv⊕BN⊕act blocks / the reduced-precision
policy), and PALLAS_FUSED=1 additionally emits one
{"component": "fused_kernel/gen/deconv1", ...} row per fused launch —
analytic flops/bytes/peak_temp_mib from ops/pallas_fused.kernel_cost —
plus a fused-conservation summary pinning the analytic count against the
XLA-counted unfused im2col parts.

Per-program rows additionally carry a `collectives` column (ISSUE 20):
op counts by kind plus total collective bytes from the traced jaxpr's
census walk (the same CENSUS_PRIMS mapping the semantic tier uses). The
single-device programs honestly census zero; ZERO_STAGE={2,3} (devices
permitting) appends census-only rows for the SHARDED shard_map step at
that stage — {"component": "census/train_step@zero2@off", ...} vs the
COMM_OVERLAP={bucket,prefetch} arm — so bucket coalescing is visible
per program: the @bucket arm's op count collapses from one collective
per leaf to one per dtype bucket while its bytes stay equal.

Workload anchor: the hot loop being replaced, image_train.py:147-194.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

BATCH = int(os.environ.get("BENCH_BATCH", 64))
SCAN = int(os.environ.get("BENCH_SCAN", 50))
WINDOWS = int(os.environ.get("BENCH_WINDOWS", 3))
# calls per window: one value-readback sync per window, amortized over
# CALLS dispatches (bench.py's policy — a per-call sync puts a queue
# drain inside every measurement)
CALLS = max(1, int(os.environ.get("BENCH_STEPS", 400)) // SCAN)


def main() -> None:
    import jax

    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    import jax.numpy as jnp
    from jax import lax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import dataclasses

    from dcgan_tpu.config import TrainConfig
    from dcgan_tpu.train.steps import make_optimizer, make_train_step

    # same config knobs as bench.py — one shared parser
    # (dcgan_tpu/utils/bench_env.py), so every profile row decomposes
    # exactly a captured bench config (VERDICT r4 #5)
    from dcgan_tpu.utils.bench_env import (
        apply_attn_res_override,
        bench_model_config,
    )

    preset_name = os.environ.get("BENCH_PRESET", "")
    if preset_name:
        from dcgan_tpu.presets import get_preset

        cfg = dataclasses.replace(get_preset(preset_name),
                                  batch_size=BATCH)
        profile_of = preset_name
    else:
        mcfg, profile_of = bench_model_config()
        cfg = TrainConfig(model=mcfg, batch_size=BATCH)
    cfg = apply_attn_res_override(cfg)
    if preset_name and os.environ.get("BENCH_ATTN_RES"):
        # non-preset labels already carry the attn/flash/dense naming from
        # bench_model_config (computed post-override, ADVICE r5 #2); preset
        # labels only need the attn_res marker appended
        profile_of += f"-attn{os.environ['BENCH_ATTN_RES']}"
    # PRECISION / PALLAS_FUSED compose like bench.py's A/B knobs (ISSUE 17):
    # the profiled train step IS the knobbed program, and PALLAS_FUSED=1
    # additionally emits the per-fused-kernel rows below
    fused = os.environ.get("PALLAS_FUSED") == "1"
    if fused:
        cfg = dataclasses.replace(cfg, model=dataclasses.replace(
            cfg.model, use_pallas=True, pallas_fused=True))
        profile_of += "-fused"
    if os.environ.get("PRECISION"):
        cfg = dataclasses.replace(cfg, precision=os.environ["PRECISION"])
        profile_of += f"-{cfg.precision}"
    if cfg.model.num_classes:
        raise SystemExit(
            "step_profile does not thread class labels; profile the "
            "unconditional families")
    fns = make_train_step(cfg)

    state = jax.jit(fns.init)(jax.random.key(0))
    size = cfg.model.output_size
    images = jnp.asarray(np.random.default_rng(0).uniform(
        -1, 1, size=(BATCH, size, size, cfg.model.c_dim)).astype(np.float32))
    base = jax.random.key(1)
    keys = jax.random.split(base, SCAN)
    zs = jax.random.uniform(base, (SCAN, BATCH, cfg.model.z_dim),
                            minval=-1.0, maxval=1.0)
    # per-iteration input scale ~1.0: defeats loop-invariant hoisting of the
    # real-image branch without changing the work's shape or magnitude
    scales = 1.0 + 1e-6 * jnp.arange(SCAN, dtype=jnp.float32)

    def _sync(out):
        float(jax.tree_util.tree_leaves(out)[0].ravel()[0])

    def _timed(call, carry):
        """Best-of-WINDOWS ms/step; each window is CALLS dispatches with
        ONE value-readback sync at the end (the per-dispatch RTT amortizes
        like bench.py's windows). `call(carry) -> (carry, syncable)`."""
        carry, out = call(carry)      # compile + warmup
        _sync(out)
        dt = float("inf")
        for _ in range(WINDOWS):
            t0 = time.perf_counter()
            for _ in range(CALLS):
                carry, out = call(carry)
            _sync(out)
            dt = min(dt, time.perf_counter() - t0)
        return dt / (CALLS * SCAN) * 1e3

    # --- collective census of a traced program (ISSUE 20) -----------------
    # The same primitive mapping the semantic tier's manifest census uses
    # (analysis/semantic.py::CENSUS_PRIMS), plus output bytes per
    # collective eqn — op COUNT is what bucketing shrinks, BYTES is what
    # it must conserve.
    from dcgan_tpu.analysis.semantic import CENSUS_PRIMS, _walk_jaxpr

    def _census(closed_jaxpr):
        ops, nbytes = {}, 0

        def visit(eqn):
            nonlocal nbytes
            kind = CENSUS_PRIMS.get(eqn.primitive.name)
            if kind is None:
                return
            ops[kind] = ops.get(kind, 0) + 1
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                if aval is None or not hasattr(aval, "dtype"):
                    continue
                n = 1
                for d in getattr(aval, "shape", ()):
                    n *= int(d)
                nbytes += n * np.dtype(aval.dtype).itemsize
        _walk_jaxpr(closed_jaxpr.jaxpr, visit)
        return {"ops": dict(sorted(ops.items())), "bytes": int(nbytes)}

    # --- XLA cost analysis of the single-step program (lowered up front:
    # the donated train-step timing below consumes `state`'s buffers;
    # traced first so the census walk sees the jaxpr) ----------------------
    traced_step = jax.jit(fns.train_step, donate_argnums=(0,)).trace(
        state, images, base)
    step_census = _census(traced_step.jaxpr)
    lowered = traced_step.lower()
    compiled = lowered.compile()

    # --- per-program resident-bytes split (ISSUE 13) ----------------------
    # What a program keeps LIVE in HBM across dispatches is exactly its
    # donated state — read from the lowering's donation map (args_info),
    # grouped by top-level state key — plus the f32 gradient tree its
    # backward materializes transiently (mirrors the differentiated param
    # subtree). Under --zero_stage these are the buffers the data axis
    # splits; this column is the per-program form of bench.py's
    # peak_state_mib.
    def _grads_mib(*trees):
        """Transient f32 gradient peak: the LARGEST single net's tree —
        the D backward's gradients are consumed (Adam applied, buffers
        free) before the G backward materializes its own, so the fused
        step's peak is max(gen, disc), never the sum."""
        return round(max(
            sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(t))
            for t in trees) * 4 / 2**20, 2)

    def _resident_split(low, grads_mib=None):
        import jax.tree_util as jtu

        groups = {}
        for path, info in jtu.tree_flatten_with_path(low.args_info)[0]:
            if not getattr(info, "donated", False):
                continue
            group = "other"
            for k in path[1:]:
                if hasattr(k, "key"):
                    group = str(k.key)
                    break
            n = 1
            for d in info.shape:
                n *= int(d)
            groups[group] = groups.get(group, 0) \
                + n * np.dtype(info.dtype).itemsize
        row = {f"{k}_mib": round(v / 2**20, 2)
               for k, v in sorted(groups.items())}
        row["state_total_mib"] = round(sum(groups.values()) / 2**20, 2)
        if grads_mib is not None:
            row["grads_mib"] = grads_mib
        return row

    print(json.dumps({"component": "resident/train_step",
                      **_resident_split(
                          lowered,
                          _grads_mib(state["params"]["gen"],
                                     state["params"]["disc"]))}),
          flush=True)

    # --- per-fused-kernel rows (ISSUE 17, PALLAS_FUSED=1) ------------------
    # One row per fused conv⊕BN⊕act launch of a train forward (G + D), from
    # the analytic model in ops/pallas_fused.py (XLA's cost_analysis cannot
    # see inside a pallas_call on TPU, and the CPU interpreter lowers the
    # grid as a loop it counts once). The conservation check is the
    # independent cross-check: the analytic fused count must equal the
    # XLA-counted flops of the SAME im2col formulation unfused — the
    # patches @ w2d GEMM program plus the BN(+act) program the block
    # replaces. (Not lax.conv's own count: XLA skips multiplies against
    # padding/dilation zeros, which the materialized patch GEMM — and the
    # MXU — pay; at small resolutions that bookkeeping difference is >4x,
    # so it would be the wrong denominator for kernel time. Patch
    # extraction itself is excluded for the dual reason — it is 0-flop
    # data movement, but XLA prices its identity-kernel conv lowering as
    # real multiplies.) GEMM dominates, 2% tolerance covers the
    # moment/EMA accounting tails on both sides.
    if fused:
        from dcgan_tpu.ops.norm import batch_norm_apply, batch_norm_init
        from dcgan_tpu.ops.pallas_fused import fused_sites, kernel_cost

        def _xla_flops(fn, *args):
            c = jax.jit(fn).lower(*args).compile()
            ca = c.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            return ca.get("flops")

        cdt = jnp.dtype(cfg.model.compute_dtype)
        totals = {"fused": 0, "parts": 0}
        conserved_all = True
        for s in fused_sites(cfg.model, BATCH):
            cost = kernel_cost(s["m"], s["k"], s["c"], train=True,
                               compute_dtype=cdt)
            parts_flops = None
            try:
                p2d = jax.ShapeDtypeStruct((s["m"], s["k"]), cdt)
                w2d = jax.ShapeDtypeStruct((s["k"], s["c"]), cdt)
                bias = jax.ShapeDtypeStruct((s["c"],), cdt)
                bn_p, bn_s = batch_norm_init(jax.random.key(0), s["c"])
                u = jax.ShapeDtypeStruct(
                    (BATCH, s["out_res"], s["out_res"], s["c"]), cdt)
                parts_flops = _xla_flops(
                    lambda p, w2, bb: jnp.dot(p, w2) + bb, p2d, w2d, bias) \
                    + _xla_flops(functools.partial(
                        batch_norm_apply, train=True, act=s["act"],
                        leak=cfg.model.leak), bn_p, bn_s, u)
            except Exception as e:  # platform may not expose cost analysis
                print(f"{s['name']} unfused cost_analysis unavailable: {e}",
                      file=sys.stderr)
            row = {"component": f"fused_kernel/{s['name']}",
                   "gemm_m": s["m"], "gemm_k": s["k"], "gemm_c": s["c"],
                   "flops": cost["flops"],
                   "flops_parts": cost["flops_parts"],
                   "bytes_accessed": cost["bytes"],
                   "peak_temp_mib": cost["peak_temp_mib"]}
            if parts_flops:
                totals["fused"] += cost["flops"]
                totals["parts"] += int(parts_flops)
                row["xla_unfused_parts_flops"] = int(parts_flops)
                row["conserved"] = bool(
                    abs(cost["flops"] - parts_flops) <= 0.02 * parts_flops)
                conserved_all &= row["conserved"]
            print(json.dumps(row), flush=True)
        if totals["parts"]:
            print(json.dumps({
                "label": "fused-conservation",
                "fused_flops_total": totals["fused"],
                "xla_unfused_parts_total": totals["parts"],
                "ratio": round(totals["fused"] / totals["parts"], 4),
                "conserved": conserved_all}), flush=True)

    # VERDICT Weak #6: XLA's cost model counts a lax.scan (while-loop) body
    # ONCE regardless of trip count, so any in-step scan — the n_critic
    # critic loop (wgan-gp: 5), the grad_accum microbatch loops — under-
    # counts the step's true FLOP/bytes by ~(trips-1) bodies. When the
    # config scans, lower a SECOND, fully-unrolled variant purely for cost
    # analysis (scan with unroll=length emits the body `length` times, so
    # the per-op accounting is exact; verified flops(unroll=k) == k*body on
    # this backend). Timing always uses the real rolled program.
    scan_trips = {}
    if cfg.n_critic > 1:
        scan_trips["n_critic"] = cfg.n_critic
    if cfg.grad_accum > 1:
        scan_trips["grad_accum"] = cfg.grad_accum
    compiled_for_cost = compiled
    if scan_trips:
        orig_scan = lax.scan

        def _unrolled_scan(f, init, xs=None, length=None, **kw):
            n = length if length is not None else \
                jax.tree_util.tree_leaves(xs)[0].shape[0]
            kw["unroll"] = max(1, int(n))
            return orig_scan(f, init, xs, length=length, **kw)

        # contained monkeypatch: steps.py references the same jax.lax
        # module object, so every in-step scan unrolls for this one lowering
        lax.scan = _unrolled_scan
        try:
            cost_fns = make_train_step(cfg)
            compiled_for_cost = jax.jit(
                cost_fns.train_step, donate_argnums=(0,)).lower(
                    state, images, base).compile()
        finally:
            lax.scan = orig_scan

    # --- pipelined stage programs (ISSUE 7, PIPELINE_GD=1) ----------------
    # Under --pipeline_gd the trainer dispatches gen_fakes / d_update /
    # g_update instead of the fused program; without these rows the cost
    # attribution would silently keep describing a program the pipelined
    # run never executes. Same unrolled-scan discipline as the fused count
    # (the d_update critic loop and the microbatch scans under-count by
    # ~(trips-1) bodies otherwise), same scan_trips stamp on each row.
    if os.environ.get("PIPELINE_GD") == "1":
        def _stage_cost(fn, *args, donate=()):
            traced = jax.jit(fn, donate_argnums=donate).trace(*args)
            low = traced.lower()
            c = low.compile()
            ca = c.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            try:
                peak = getattr(c.memory_analysis(), "temp_size_in_bytes",
                               None)
            except Exception:
                peak = None
            return (ca.get("flops"), ca.get("bytes accessed"), peak, low,
                    _census(traced.jaxpr))

        stage_fns = cost_fns if scan_trips else fns
        fakes = jnp.zeros((cfg.n_critic, BATCH, size, size,
                           cfg.model.c_dim), jnp.float32)
        # donation mirrors the backends' (state-only — parallel/api.py);
        # the donated-leaf walk is the resident column's source. Each
        # stage's transient grad tree is the net it differentiates.
        stage_args = {
            "gen_fakes": (stage_fns.gen_fakes, (), None, state, base),
            "d_update": (stage_fns.d_update, (0,), state["params"]["disc"],
                         state, images, fakes, base),
            "g_update": (stage_fns.g_update, (0,), state["params"]["gen"],
                         state, base),
        }
        if scan_trips:
            # the unrolled lowering for exact counts (see above): re-enter
            # the contained monkeypatch for the stage programs' own scans
            lax.scan = _unrolled_scan
        try:
            for name, (fn, donate, grads_tree, *args) in stage_args.items():
                try:
                    s_flops, s_bytes, s_peak, s_low, s_census = \
                        _stage_cost(fn, *args, donate=donate)
                except Exception as e:  # platform may not expose it
                    print(f"{name} cost_analysis unavailable: {e}",
                          file=sys.stderr)
                    continue
                row = {"component": f"stage/{name}", "flops": s_flops,
                       "bytes_accessed": s_bytes,
                       "collectives": s_census}
                if donate:
                    row.update(_resident_split(s_low,
                                               _grads_mib(grads_tree)))
                if s_peak is not None:
                    # the pipelined mode's honest single-device win: the
                    # largest stage program's peak temp is below the fused
                    # program's (measured -15% at the flagship config) —
                    # per-step flops are conservation-equal (d+g == fused;
                    # the fused program's shared-z generator forward is
                    # already CSE'd by XLA)
                    row["peak_temp_mib"] = round(s_peak / 2**20, 1)
                if scan_trips:
                    row["scan_trips"] = scan_trips
                print(json.dumps(row), flush=True)
        finally:
            if scan_trips:
                lax.scan = orig_scan

    # --- sharded-program census rows (ISSUE 20, ZERO_STAGE={2,3}) ---------
    # make_train_step's single-device program censuses zero collectives by
    # construction, so bucket coalescing can't show up in the rows above.
    # These rows trace (never compile) the SHARDED shard_map step at the
    # requested stage, off vs the COMM_OVERLAP arm, purely for the census:
    # the arm's op count collapses to one collective per dtype bucket
    # while its bytes stay conserved.
    zero_env = int(os.environ.get("ZERO_STAGE", "0") or 0)
    if zero_env >= 2:
        if len(jax.devices()) < 2:
            print("ZERO_STAGE census rows need >= 2 devices; skipping",
                  file=sys.stderr)
        else:
            from dcgan_tpu.config import MeshConfig
            from dcgan_tpu.parallel import make_mesh, make_parallel_train
            from dcgan_tpu.train import warmup

            overlap = os.environ.get("COMM_OVERLAP", "")
            if overlap in ("", "1"):
                overlap = "bucket"
            mesh_cfg = MeshConfig(data=2, zero_stage=zero_env)
            mesh = make_mesh(mesh_cfg, jax.devices()[:2])
            for mode in ("off", overlap):
                cfg_s = dataclasses.replace(
                    cfg, backend="shard_map", mesh=mesh_cfg,
                    comm_overlap=mode)
                pt_s = make_parallel_train(cfg_s, mesh)
                st_s = warmup.state_example(pt_s)
                img_s = jax.ShapeDtypeStruct(
                    (BATCH, size, size, cfg.model.c_dim), jnp.float32)
                tr = jax.jit(pt_s.step).trace(st_s, img_s, base)
                print(json.dumps(
                    {"component":
                         f"census/train_step@zero{zero_env}@{mode}",
                     "collectives": _census(tr.jaxpr)}), flush=True)

    # --- forward only: G fwd + D fwd on real and fake (no grads, no Adam) --
    @jax.jit
    def many_fwd(state, images, zs, scales):
        def body(acc, xs):
            z, s = xs
            m = fns.eval_losses(state, images * s, z)
            return acc + m["d_loss"], None
        acc, _ = lax.scan(body, jnp.float32(0), (zs, scales))
        return acc

    fwd_ms = _timed(lambda c: (c, many_fwd(state, images, zs, scales)),
                    None)
    print(json.dumps({"component": "fwd_losses", "ms": round(fwd_ms, 4)}),
          flush=True)

    # --- generator forward alone (the sampler path) ------------------------
    @jax.jit
    def many_gen(state, zs):
        def body(acc, z):
            return acc + fns.sample(state, z).sum(), None
        acc, _ = lax.scan(body, jnp.float32(0), zs)
        return acc

    gen_ms = _timed(lambda c: (c, many_gen(state, zs)), None)
    print(json.dumps({"component": "g_forward", "ms": round(gen_ms, 4)}),
          flush=True)

    # --- both Adam applies alone -------------------------------------------
    import optax

    opt_g = make_optimizer(cfg, cfg.g_learning_rate)
    opt_d = make_optimizer(cfg, cfg.d_learning_rate,
                           updates_per_step=cfg.n_critic)

    @jax.jit
    def many_adam(params, opt_state, _keys):
        def body(carry, _):
            params, opt_state = carry
            # grads derived from the carry: cannot be hoisted, stays O(1)
            gg = jax.tree_util.tree_map(lambda p: p * 1e-8, params["gen"])
            gd = jax.tree_util.tree_map(lambda p: p * 1e-8, params["disc"])
            ug, og = opt_g.update(gg, opt_state["gen"], params["gen"])
            ud, od = opt_d.update(gd, opt_state["disc"], params["disc"])
            params = {"gen": optax.apply_updates(params["gen"], ug),
                      "disc": optax.apply_updates(params["disc"], ud)}
            return (params, {"gen": og, "disc": od}), None
        (params, opt_state), _ = lax.scan(body, (params, opt_state), _keys)
        return params

    adam_ms = _timed(
        lambda c: (c, many_adam(state["params"], state["opt"], keys)), None)
    print(json.dumps({"component": "adam_applies", "ms": round(adam_ms, 4)}),
          flush=True)

    # --- full train step LAST (donation consumes the state buffers) --------
    # donated like the real consumers (trainer/bench): without donation the
    # same program measures ~0.8 ms/step slower on the chip
    @functools.partial(jax.jit, donate_argnums=(0,))
    def many_steps(state, images, keys):
        def body(s, k):
            s, m = fns.train_step(s, images, k)
            return s, m["d_loss"]
        return lax.scan(body, state, keys)

    step_ms = _timed(lambda s: many_steps(s, images, keys), state)
    print(json.dumps({"component": "train_step", "ms": round(step_ms, 4),
                      "images_per_sec": round(BATCH / step_ms * 1e3, 1),
                      "collectives": step_census}),
          flush=True)

    flops = bytes_accessed = None
    try:
        ca = compiled_for_cost.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        flops = ca.get("flops")
        bytes_accessed = ca.get("bytes accessed")
    except Exception as e:  # platform may not expose cost analysis
        print(f"cost_analysis unavailable: {e}", file=sys.stderr)
    peak_hbm = None
    try:
        ma = compiled.memory_analysis()
        peak_hbm = getattr(ma, "temp_size_in_bytes", None)
    except Exception as e:
        print(f"memory_analysis unavailable: {e}", file=sys.stderr)

    summary = {
        "label": "step-profile",
        "preset": profile_of,
        "batch": BATCH, "scan": SCAN,
        "step_ms": round(step_ms, 4),
        "fwd_ms": round(fwd_ms, 4),
        "bwd_opt_ms_derived": round(step_ms - fwd_ms, 4),
        "g_forward_ms": round(gen_ms, 4),
        "adam_ms": round(adam_ms, 4),
    }
    if scan_trips:
        # stamp the rows so capture_all's tables can distinguish trip-exact
        # counts (this build onward) from pre-fix counted-once captures
        summary["scan_trips"] = scan_trips
    if flops:
        summary["flops_per_step"] = flops
        summary["tflops_effective"] = round(flops / (step_ms * 1e-3) / 1e12,
                                            2)
    if bytes_accessed:
        summary["bytes_accessed"] = bytes_accessed
        summary["hbm_gbps_effective"] = round(
            bytes_accessed / (step_ms * 1e-3) / 1e9, 1)
    if peak_hbm is not None:
        summary["peak_temp_hbm_mib"] = round(peak_hbm / 2**20, 1)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
