"""Semantic tier (ISSUE 11): contracts checked in the LOWERED programs.

The AST tier (core.py + the DCG001-006 checkers) polices source without
importing it; this tier deliberately does the opposite — it imports,
builds, and `.lower()`s every program the repo can dispatch, on CPU at a
small preset, and checks the contracts that only exist after tracing:

    DCG007  donation realized as aliasing     check_donation
    DCG008  collective census vs the manifest check_manifest/check_transports
    DCG009  retrace hazards + warmup coverage check_warmup_coverage/check_retrace
    DCG010  traced-body hygiene               check_hygiene
    DCG011  sharding-rule spec coverage       check_spec_coverage

The enumeration is the repo's real dispatch surface: both ParallelTrain
backends' `programs` dicts through the AOT warmup plan (train/warmup.py —
including the k=1 tail, the `steps_per_call` scan, and the LR-backoff
rebuild variants), the `--pipeline_gd` stage programs, and the serving
plane's bucket-ladder sampler rungs (serve/buckets.py). Host-side
coordination transports (`process_allgather` is opaque to `.lower()`)
join the manifest as declared rows from
train/coordination.py::TRANSPORT_CENSUS.

Everything is computed on one canonical topology — CPU, 2 virtual
devices, a 2-way "data" mesh, partitionable threefry — because the
committed manifest (analysis/programs.lock.jsonl) is byte-reproducible by
contract. Two devices, not one: collectives over a size-1 axis are elided
at trace time, so a 1-device census would be structurally empty. The CLI
(`python -m dcgan_tpu.analysis --semantic`) arranges the topology before
jax initializes; in-process callers must already satisfy it
(tests/conftest.py's 8-virtual-device env does — the mesh only takes the
first two devices, and the jaxprs are identical).
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from dcgan_tpu.analysis import manifest as manifest_lib
from dcgan_tpu.analysis.core import Finding

SEMANTIC_CHECKS = ("DCG007", "DCG008", "DCG009", "DCG010", "DCG011")

#: devices the canonical topology forces / the enumeration's mesh uses
CANONICAL_DEVICES = 2

#: serve bucket ladder top rung for the enumeration (granule = the data
#: axis, so the default doubling ladder is 2, 4, 8 — three compiled rungs,
#: the shape set `serve.buckets.build_ladder` produces for this preset)
SERVE_MAX_BATCH = 8

#: jaxpr primitive -> canonical census op. `psum_invariant` /
#: `all_gather_invariant` are what a user-written psum / all_gather traces
#: to inside a shard_map that checks replication (`check_vma`) — same ops,
#: typed for the varying-manual-axes system.
CENSUS_PRIMS = {
    "psum": "psum", "psum_invariant": "psum",
    "all_gather": "all_gather", "all_gather_invariant": "all_gather",
    "reduce_scatter": "reduce_scatter",
    "ppermute": "ppermute", "all_to_all": "all_to_all",
    "pmax": "pmax", "pmin": "pmin",
}

#: DCG010: host-callback primitives — a callback inside a dispatched
#: program re-enters Python from the runtime (ordering hazards against the
#: async dispatch stream, catastrophic on real meshes)
CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                  "debug_print"}

#: DCG010: explicit transfer primitives inside traced code
TRANSFER_PRIMS = {"device_put"}

#: DCG009: closure-captured consts above this element count are flagged —
#: an array baked into the program bloats every retrace and defeats the
#: persistent-cache key (the array's VALUE is in the HLO)
CONST_SIZE_LIMIT = 64

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")
#: a shard_map equation prints `manual_axes=frozenset({'data', 'model'})`,
#: whose element order follows the process's string-hash seed
_FROZENSET_RE = re.compile(r"frozenset\(\{([^{}]*)\}\)")


def _sanitized(jaxpr_text: str) -> str:
    """The jaxpr text with what varies from process to process taken out:
    object addresses, and the element order of printed frozensets."""
    text = _ADDR_RE.sub("0x", jaxpr_text)
    return _FROZENSET_RE.sub(
        lambda m: "frozenset({" + ", ".join(sorted(
            e.strip() for e in m.group(1).split(","))) + "})", text)

#: the ParallelTrain backends `enumerate_audits` lowers, each a share of
#: the manifest's program rows; the serving plane's rows (`serve::`) lower
#: gspmd's sampler and ride with its share
BACKENDS = ("gspmd", "shard_map")

#: where findings for each enumeration group anchor
GROUP_PATHS = {
    "gspmd": "dcgan_tpu/parallel/api.py",
    "shard_map": "dcgan_tpu/parallel/shard_map_backend.py",
    "serve": "dcgan_tpu/serve/buckets.py",
    "coordination": "dcgan_tpu/train/coordination.py",
    "elastic": "dcgan_tpu/elastic/rules.py",
}


def ensure_semantic_platform() -> None:
    """Arrange the canonical topology. Must run before jax is imported
    (JAX_PLATFORMS is read then; `_require_platform` refuses a process
    where it came too late) — the CLI calls it first; tools embedding the
    tier should too."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None or int(m.group(1)) < CANONICAL_DEVICES:
        # no ambient count, or one too small for the census (an ambient
        # `=1` is common in CPU dev shells and would elide every
        # collective at trace time) — rewrite it; a LARGER ambient count
        # (the 8-device test env) is left alone, the mesh only takes the
        # first CANONICAL_DEVICES devices either way
        if m is not None:
            flags = flags.replace(m.group(0), "")
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
            f"{CANONICAL_DEVICES}").strip()
    import jax

    jax.config.update("jax_threefry_partitionable", True)


def _require_platform() -> None:
    """The enumeration refuses to run on a non-canonical topology rather
    than produce fingerprints that can never match the manifest."""
    import jax

    devs = jax.devices()
    problems = []
    if devs[0].platform != "cpu":
        problems.append(f"platform is {devs[0].platform!r}, need cpu")
    if len(devs) < CANONICAL_DEVICES:
        problems.append(f"{len(devs)} device(s), need >= "
                        f"{CANONICAL_DEVICES} (collectives over a size-1 "
                        "axis are elided at trace time)")
    if not jax.config.jax_threefry_partitionable:
        problems.append("jax_threefry_partitionable is off (RNG lowering "
                        "differs, fingerprints cannot match)")
    if problems:
        raise RuntimeError(
            "semantic tier needs the canonical topology — "
            + "; ".join(problems)
            + ". Run via `python -m dcgan_tpu.analysis --semantic` (it "
            "arranges the environment before jax initializes).")


def small_config(backend: str = "gspmd", pipeline: bool = False,
                 zero: int = 1, precision: str = "", overlap: str = "off"):
    """The small CPU preset every program is lowered at: tiny dcgan16
    model, global batch 8 over the 2-way data mesh, every optional
    program's knob armed (sampler / probe / summarize / rollback with LR
    backoff) so the warmup plan enumerates the full dispatch surface.
    `zero` selects the ZeRO stage (ISSUE 13) — the 2-way data mesh is
    exactly the canonical topology stages >= 2 need. `precision` selects
    the reduced-precision policy (ISSUE 17). `overlap` selects the collective overlap plane (ISSUE 20) for the
    `@overlap`/`@prefetch` variant rows."""
    from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig

    return TrainConfig(
        model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                          compute_dtype="float32"),
        mesh=MeshConfig(data=CANONICAL_DEVICES, zero_stage=zero),
        batch_size=8,
        backend=backend,
        precision=precision,
        comm_overlap=overlap,
        # pipeline_gd is config-validated to steps_per_call=1; the plain
        # variant scans k=2 so the multi_step program joins the manifest
        steps_per_call=1 if pipeline else 2,
        pipeline_gd=pipeline,
        sample_every_steps=100,
        activation_summary_steps=100,
        nan_check_steps=100,
        nan_policy="rollback",
        rollback_snapshot_steps=100,
        rollback_lr_backoff=0.5,
        tensorboard=False)


def progressive_config(backend: str = "gspmd"):
    """The canonical progressive schedule the semantic tier enumerates
    (ISSUE 15): the headline 64 -> 128 -> 256 ladder at the small feature
    dims, fade armed so the per-phase blend programs join the audit.
    Every phase's step program is lowered and fingerprinted (`@r64` /
    `@r128` / `@r256` rows), so the donation audit (DCG007) holds for the
    grown conv stacks and the warmup-coverage check (DCG009) proves the
    switch dispatches only planned programs."""
    from dcgan_tpu.config import MeshConfig, ModelConfig, TrainConfig

    return TrainConfig(
        model=ModelConfig(output_size=256, gf_dim=8, df_dim=8,
                          compute_dtype="float32"),
        mesh=MeshConfig(data=CANONICAL_DEVICES),
        batch_size=8,
        backend=backend,
        progressive="64:4,128:4,256:*",
        progressive_fade_steps=2,
        sample_every_steps=0,
        activation_summary_steps=0,
        nan_check_steps=100,
        tensorboard=False)


@dataclasses.dataclass(frozen=True)
class ProgramAudit:
    """Everything the checkers need about one lowered program."""

    name: str              # "gspmd::train_step", "serve::sampler@b4", ...
    path: str              # repo-relative path findings anchor to
    args: Tuple[str, ...]  # short per-argument signatures
    fingerprint: str       # sha256[:16] of the sanitized jaxpr text
    collectives: Dict[str, int]
    donation: Optional[Dict[str, object]]   # None when nothing is donated
    expect_donation: bool
    consts: Tuple[Tuple[str, int, str, bool], ...]  # (label, size, dtype,
                                                    #  weak_type)
    callbacks: Tuple[str, ...]   # callback primitive names found
    transfers: Tuple[str, ...]   # transfer primitive names found
    f64_prims: Tuple[str, ...]   # primitives with float64/complex128 out
    cadence: str = ""

    @property
    def base(self) -> str:
        """Program name without the group / @shape qualifiers."""
        return self.name.split("::", 1)[-1].split("@", 1)[0]


def _walk_jaxpr(jaxpr, visit) -> None:
    """visit(eqn) over every equation, recursing into sub-jaxprs (scan
    bodies, pjit calls, shard_map bodies, cond branches, custom-vjp
    closures — anything whose params carry a Jaxpr/ClosedJaxpr)."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(j, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _walk_jaxpr(inner, visit)
                elif hasattr(j, "eqns"):
                    _walk_jaxpr(j, visit)


def _arg_sig(x) -> str:
    import jax

    leaves = jax.tree_util.tree_leaves(x)
    if len(leaves) != 1 or leaves[0] is not x:
        return f"tree({len(leaves)} leaves)"
    return jax.typeof(leaves[0]).str_short()


def _alias_param_numbers(hlo_text: str) -> Set[int]:
    """Entry-parameter numbers in the compiled module's
    `input_output_alias={ {out}: (param, {index}, kind), ... }` map."""
    i = hlo_text.find("input_output_alias={")
    if i < 0:
        return set()
    j = i + len("input_output_alias=")
    depth = 0
    end = None
    for k in range(j, len(hlo_text)):
        if hlo_text[k] == "{":
            depth += 1
        elif hlo_text[k] == "}":
            depth -= 1
            if depth == 0:
                end = k + 1
                break
    if end is None:
        return set()
    return {int(m.group(1)) for m in
            re.finditer(r":\s*\(\s*(\d+)\s*,", hlo_text[j:end])}


def audit_callable(name: str, fn, args: tuple, *, path: str,
                   expect_donation: bool = False,
                   cadence: str = "") -> ProgramAudit:
    """Trace + lower (+ compile, iff anything is donated) one program and
    extract the audited facts. `fn` is a jitted callable (tripwire
    wrappers forward `.trace`/`.lower`); `args` are example arguments —
    ShapeDtypeStructs are fine, nothing is executed."""
    import jax.tree_util as jtu

    traced = fn.trace(*args)
    closed = traced.jaxpr

    census: Dict[str, int] = {}
    callbacks: List[str] = []
    transfers: List[str] = []
    f64: List[str] = []

    def visit(eqn):
        prim = eqn.primitive.name
        op = CENSUS_PRIMS.get(prim)
        if op is not None:
            census[op] = census.get(op, 0) + 1
        if prim in CALLBACK_PRIMS or (prim not in CENSUS_PRIMS
                                      and "callback" in prim):
            callbacks.append(prim)
        if prim in TRANSFER_PRIMS:
            transfers.append(prim)
        for ov in eqn.outvars:
            dt = getattr(getattr(ov, "aval", None), "dtype", None)
            if dt is not None and str(dt) in ("float64", "complex128"):
                f64.append(prim)
                break

    _walk_jaxpr(closed.jaxpr, visit)

    consts: List[Tuple[str, int, str, bool]] = []
    for i, c in enumerate(closed.consts):
        aval = getattr(c, "aval", None)
        shape = tuple(getattr(c, "shape", ()))
        size = 1
        for d in shape:
            size *= int(d)
        dtype = str(getattr(c, "dtype", "?"))
        weak = bool(getattr(aval, "weak_type", False))
        label = f"const{i}:{dtype}{list(shape)}"
        consts.append((label, size, dtype, weak))

    fingerprint = hashlib.sha256(
        _sanitized(str(closed)).encode()).hexdigest()[:16]

    import warnings

    with warnings.catch_warnings():
        # the audit below IS the actionable form of this lowering warning
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        # lower the Traced we already have — fn.lower(*args) would re-trace
        # every program from scratch (tracing dominates enumeration cost)
        lowered = traced.lower()
    flat_info, _ = jtu.tree_flatten(lowered.args_info)
    donated = [i for i, a in enumerate(flat_info) if a.donated]
    donation: Optional[Dict[str, object]] = None
    if donated:
        labels = [jtu.keystr(p) for p, _ in
                  jtu.tree_flatten_with_path(lowered.args_info)[0]]
        try:
            kept = sorted(lowered._lowering.compile_args["kept_var_idx"])
        except Exception:  # internals moved: assume nothing was pruned
            kept = list(range(len(flat_info)))
        compiled = lowered.compile()
        aliased_flat = {kept[p] for p in
                        _alias_param_numbers(compiled.as_text())
                        if p < len(kept)}
        kept_set = set(kept)
        donation = {
            "donated": len(donated),
            "aliased": len(aliased_flat & set(donated)),
            "pruned": sum(1 for i in donated if i not in kept_set),
            "unaliased": sorted(labels[i] for i in donated
                                if i in kept_set
                                and i not in aliased_flat),
        }

    return ProgramAudit(
        name=name, path=path, args=tuple(_arg_sig(a) for a in args),
        fingerprint=fingerprint,
        collectives=dict(sorted(census.items())), donation=donation,
        expect_donation=expect_donation, consts=tuple(consts),
        callbacks=tuple(sorted(set(callbacks))),
        transfers=tuple(sorted(set(transfers))),
        f64_prims=tuple(sorted(set(f64))), cadence=cadence)


@dataclasses.dataclass(frozen=True)
class CoverageRow:
    """One config variant's dispatch surface vs its warmup plan (DCG009):
    `programs` is the ParallelTrain programs-dict key set, `plan` the
    warmup plan's row names, `must_cover` the names the trainer loop
    dispatches at THAT config (so the plan must contain them)."""

    variant: str
    path: str
    programs: frozenset
    plan: Tuple[str, ...]
    must_cover: frozenset


def _base(name: str) -> str:
    return name.split("@", 1)[0]


def enumerate_audits(backends: Sequence[str] = BACKENDS
                     ) -> Tuple[List[ProgramAudit], List[CoverageRow]]:
    """Lower the full dispatch surface at the small preset, or the rows
    of `backends` alone (a share of the manifest). Order is
    deterministic; the returned audits are the manifest's program rows."""
    _require_platform()
    import jax
    import jax.numpy as jnp

    from dcgan_tpu.parallel import make_mesh, make_parallel_train
    from dcgan_tpu.parallel.api import DONATED_PROGRAMS
    from dcgan_tpu.serve.buckets import build_ladder, sampler_plan
    from dcgan_tpu.train import warmup

    devices = jax.devices()[:CANONICAL_DEVICES]
    audits: List[ProgramAudit] = []
    coverage: List[CoverageRow] = []
    serve_rows: List[Tuple[str, object, tuple]] = []

    for backend in backends:
        path = GROUP_PATHS[backend]
        cfg = small_config(backend)
        mesh = make_mesh(cfg.mesh, devices)
        pt = make_parallel_train(cfg, mesh)
        state = warmup.state_example(pt)
        z = jax.ShapeDtypeStruct((cfg.batch_size, cfg.model.z_dim),
                                 jnp.float32)
        plan, _pt_backoff = warmup.build_warmup_plan(
            cfg, pt, state, sample_z=z, eval_z=z,
            make_backoff_pt=lambda c, _m=mesh: make_parallel_train(c, _m))
        rows = [("init", pt.programs["init"], (jax.random.key(0),))]
        rows += [(n, f, a) for n, f, a in plan]

        cfg_p = small_config(backend, pipeline=True)
        pt_p = make_parallel_train(cfg_p, mesh)
        plan_p, _bk = warmup.build_warmup_plan(
            cfg_p, pt_p, state, sample_z=None, eval_z=None,
            make_backoff_pt=lambda c, _m=mesh: make_parallel_train(c, _m))
        stages = ("gen_fakes", "d_update", "g_update")
        rows += [(n, f, a) for n, f, a in plan_p if _base(n) in stages]

        coverage.append(CoverageRow(
            variant=backend, path=path,
            programs=frozenset(pt.programs),
            plan=tuple(n for n, _, _ in plan),
            must_cover=frozenset(
                {"train_step", f"multi_step@k{cfg.steps_per_call}",
                 "sampler", "eval_losses", "summarize", "state_copy"})))
        coverage.append(CoverageRow(
            variant=f"{backend}+pipeline_gd", path=path,
            programs=frozenset(pt_p.programs),
            plan=tuple(n for n, _, _ in plan_p),
            must_cover=frozenset(stages)))

        # ZeRO-2/3 variants (ISSUE 13): the state-sharded step programs —
        # the census intentionally changes (shard_map gains explicit
        # psum_scatter/all_gather rows; gspmd rows stay "0 explicit", the
        # partitioner inserts theirs) and the donation audit must hold for
        # every data-SHARDED donated leaf in both backends, including the
        # LR-backoff rebuild variants. Only the step-family rows are
        # traced (sampler/probe/summarize differ from the stage-1 rows
        # only by the state gathers, which the stage rows already cover);
        # the coverage rows still see the FULL warmup plan.
        step_bases = {"train_step", "multi_step"}
        for stage in (2, 3):
            cfg_z = small_config(backend, zero=stage)
            pt_z = make_parallel_train(cfg_z, mesh)
            state_z = warmup.state_example(pt_z)
            plan_z, _bkz = warmup.build_warmup_plan(
                cfg_z, pt_z, state_z, sample_z=z, eval_z=z,
                make_backoff_pt=lambda c, _m=mesh: make_parallel_train(
                    c, _m))
            cfg_zp = small_config(backend, pipeline=True, zero=stage)
            pt_zp = make_parallel_train(cfg_zp, mesh)
            plan_zp, _bkzp = warmup.build_warmup_plan(
                cfg_zp, pt_zp, warmup.state_example(pt_zp), sample_z=None,
                eval_z=None,
                make_backoff_pt=lambda c, _m=mesh: make_parallel_train(
                    c, _m))
            zrows = [(n, f, a) for n, f, a in plan_z
                     if _base(n) in step_bases]
            zrows += [(n, f, a) for n, f, a in plan_zp
                      if _base(n) in stages]
            coverage.append(CoverageRow(
                variant=f"{backend}+zero{stage}", path=path,
                programs=frozenset(pt_z.programs),
                plan=tuple(n for n, _, _ in plan_z),
                must_cover=frozenset(
                    {"train_step", f"multi_step@k{cfg_z.steps_per_call}",
                     "sampler", "eval_losses", "summarize",
                     "state_copy"})))
            coverage.append(CoverageRow(
                variant=f"{backend}+pipeline_gd+zero{stage}", path=path,
                programs=frozenset(pt_zp.programs),
                plan=tuple(n for n, _, _ in plan_zp),
                must_cover=frozenset(stages)))
            for n, f, a in zrows:
                cadence = ""
                if n == "train_step":
                    cadence = (
                        f"every step when `--zero_stage {stage}` "
                        + ("(grads reduce-scatter onto the data axis, one "
                           "fused all-gather rebuilds params per update)"
                           if stage == 2 else
                           "(stage 2's pattern + params resident sharded; "
                           "just-in-time all-gather per forward)"))
                audits.append(audit_callable(
                    f"{backend}::{n}@zero{stage}", f, a, path=path,
                    expect_donation=_base(n) in DONATED_PROGRAMS,
                    cadence=cadence))

        # Collective-overlap variants (ISSUE 20, DESIGN §6n): shard_map
        # only — the bucket/prefetch restructuring changes the lowered
        # program only where collectives are hand-placed (gspmd's half
        # of the overlap plane is async-scheduler XLA flags; its
        # constraint-hook program is unchanged and already audited by
        # the @zero rows above). The SHRUNKEN census on the @overlap
        # rows is the tentpole's headline proof — one collective per
        # dtype bucket instead of one per leaf — and the @prefetch rows
        # pin the staged-gather structure (same all-gather count as
        # "off": the barrier chain moves gathers, it does not merge
        # them). Donation must hold for every variant, and the coverage
        # rows extend the DCG009 warmup-coverage check to the new
        # plans (the zero-recompile contract under `--comm_overlap`).
        if backend == "shard_map":
            for o_stage, o_mode in ((2, "bucket"), (3, "bucket"),
                                    (3, "prefetch")):
                o_tag = "overlap" if o_mode == "bucket" else "prefetch"
                cfg_o = small_config(backend, zero=o_stage,
                                     overlap=o_mode)
                pt_o = make_parallel_train(cfg_o, mesh)
                plan_o, _bko = warmup.build_warmup_plan(
                    cfg_o, pt_o, warmup.state_example(pt_o), sample_z=z,
                    eval_z=z,
                    make_backoff_pt=lambda c, _m=mesh:
                        make_parallel_train(c, _m))
                cfg_op = small_config(backend, pipeline=True,
                                      zero=o_stage, overlap=o_mode)
                pt_op = make_parallel_train(cfg_op, mesh)
                plan_op, _bkop = warmup.build_warmup_plan(
                    cfg_op, pt_op, warmup.state_example(pt_op),
                    sample_z=None, eval_z=None,
                    make_backoff_pt=lambda c, _m=mesh:
                        make_parallel_train(c, _m))
                coverage.append(CoverageRow(
                    variant=f"{backend}+zero{o_stage}+{o_mode}",
                    path=path, programs=frozenset(pt_o.programs),
                    plan=tuple(n for n, _, _ in plan_o),
                    must_cover=frozenset(
                        {"train_step",
                         f"multi_step@k{cfg_o.steps_per_call}",
                         "sampler", "eval_losses", "summarize",
                         "state_copy"})))
                coverage.append(CoverageRow(
                    variant=(f"{backend}+pipeline_gd+zero{o_stage}"
                             f"+{o_mode}"),
                    path=path, programs=frozenset(pt_op.programs),
                    plan=tuple(n for n, _, _ in plan_op),
                    must_cover=frozenset(stages)))
                orows = [(n, f, a) for n, f, a in plan_o
                         if _base(n) in step_bases]
                orows += [(n, f, a) for n, f, a in plan_op
                          if _base(n) in stages]
                for n, f, a in orows:
                    cadence = ""
                    if n == "train_step":
                        cadence = (
                            f"every step when `--comm_overlap bucket` "
                            f"at `--zero_stage {o_stage}` (per-leaf "
                            "reduce-scatter/all-gather packed into ONE "
                            "collective per dtype bucket; each bucket's "
                            "reduce-scatter issues as its cotangents "
                            "complete)"
                            if o_mode == "bucket" else
                            "every step when `--comm_overlap prefetch` "
                            "(bucket's grad plan + layer-ahead staged "
                            "param gathers: gather i+1 overlaps "
                            "compute i via an optimization_barrier "
                            "chain)")
                    audits.append(audit_callable(
                        f"{backend}::{n}@zero{o_stage}@{o_tag}", f, a,
                        path=path,
                        expect_donation=_base(n) in DONATED_PROGRAMS,
                        cadence=cadence))

        # Reduced-precision variant (ISSUE 17): the @bf16 rows lower the
        # reduced-precision policy (bf16 params/compute, f32 master Adam
        # mu). Only the step-family rows are traced (sampler/probe/
        # summarize differ only by dtype, which the step rows already
        # fingerprint). The donation audit must hold: the bf16 lowering
        # emits a conservative "donated buffers were not usable" warning
        # for the small (C,)-shaped bf16 leaves, but the compiled alias
        # map realizes every donation (unaliased=[]) — the structured
        # audit below, not the warning, is the gate.
        cfg_v = small_config(backend, precision="bf16")
        pt_v = make_parallel_train(cfg_v, mesh)
        plan_v, _bkv = warmup.build_warmup_plan(
            cfg_v, pt_v, warmup.state_example(pt_v), sample_z=z, eval_z=z,
            make_backoff_pt=lambda c, _m=mesh: make_parallel_train(c, _m))
        coverage.append(CoverageRow(
            variant=f"{backend}+bf16", path=path,
            programs=frozenset(pt_v.programs),
            plan=tuple(n for n, _, _ in plan_v),
            must_cover=frozenset(
                {"train_step", f"multi_step@k{cfg_v.steps_per_call}",
                 "sampler", "eval_losses", "summarize", "state_copy"})))
        for n, f, a in plan_v:
            if _base(n) not in step_bases:
                continue
            cadence = ""
            if n == "train_step":
                cadence = ("every step when `--precision bf16` (bf16 "
                           "params+compute, f32 master Adam mu)")
            audits.append(audit_callable(
                f"{backend}::{n}@bf16", f, a, path=path,
                expect_donation=_base(n) in DONATED_PROGRAMS,
                cadence=cadence))

        for n, f, a in rows:
            cadence = ""
            if n == "train_step":
                cadence = ("every step (default `steps_per_call`=1; a "
                           "scanned run dispatches `multi_step`, census "
                           "identical ×k)")
            audits.append(audit_callable(
                f"{backend}::{n}", f, a, path=path,
                expect_donation=_base(n) in DONATED_PROGRAMS,
                cadence=cadence))

        # Progressive-resolution variants (ISSUE 15): the canonical
        # 64->128->256 schedule's per-phase step programs, named @r<res>
        # (EVERY phase suffixed — the base rows above are a different
        # model config, so the plain names must not collide). The plan
        # comes from the same PhaseRuntime the trainer warms, so the
        # coverage row proves a mid-run switch dispatches only planned
        # programs; the fade blends (phase > 0, non-donating) are audited
        # once under gspmd (the program is backend-agnostic).
        from dcgan_tpu.progressive import PhaseRuntime, parse_schedule

        cfg_pr = progressive_config(backend)
        rt = PhaseRuntime(
            cfg_pr, mesh,
            parse_schedule(cfg_pr.progressive, model=cfg_pr.model,
                           batch_size=cfg_pr.batch_size,
                           max_steps=cfg_pr.max_steps,
                           fade_steps=cfg_pr.progressive_fade_steps),
            cfg_pr.max_steps,
            make_pt=lambda c, m: make_parallel_train(c, m))
        plan_pr = rt.build_warmup_plan(warmup.state_example(rt.pt))
        coverage.append(CoverageRow(
            variant=f"{backend}+progressive", path=path,
            programs=frozenset(rt.pt.programs),
            plan=tuple(n for n, _, _ in plan_pr),
            must_cover=frozenset(
                {"train_step", "init@r128", "train_step@r128",
                 "state_copy@r128", "fade@r128", "init@r256",
                 "train_step@r256", "state_copy@r256", "fade@r256"})))
        res0 = rt.schedule.phases[0].resolution
        for n, f, a in plan_pr:
            base_n = _base(n)
            if base_n not in ("train_step", "fade"):
                continue
            if base_n == "fade" and backend != "gspmd":
                continue
            nm = n if "@" in n else f"{n}@r{res0}"
            audits.append(audit_callable(
                f"{backend}::{nm}", f, a, path=path,
                expect_donation=base_n in DONATED_PROGRAMS,
                cadence=f"every step of its phase under `--progressive "
                        f"\"64:N,128:N,256:*\"`" if base_n == "train_step"
                        else "per-step inside a fade window "
                             "(`--progressive_fade_steps`)"))

        # Live-elasticity variants (ISSUE 18): the target-submesh step
        # programs a preemption-notice-driven switch lands on, named
        # @t<data>x<model> by the same LiveTopologyRuntime the trainer
        # warms — so the coverage row proves a live shrink dispatches only
        # planned programs (the AOT-warm-both-topologies contract behind
        # compile_requests_delta == 0 across a switch). The launch
        # topology's rows keep their plain names and are NOT re-audited
        # (same programs as the base rows above); only the @t1x1 step row
        # is traced — sampler/probe rows differ from the base ones only by
        # mesh extent, which the step row already fingerprints.
        from dcgan_tpu.elastic.live import LiveTopologyRuntime

        cfg_le = dataclasses.replace(cfg, elastic_target_devices=1,
                                     sample_every_steps=0)
        rt_le = LiveTopologyRuntime(
            cfg_le, mesh, make_pt=lambda c, m: make_parallel_train(c, m),
            launch_pt=pt)
        plan_le = rt_le.build_warmup_plan(warmup.state_example(rt_le.pt))
        sub_tag = rt_le.tag(1)
        coverage.append(CoverageRow(
            variant=f"{backend}+live_elastic", path=path,
            programs=frozenset(rt_le.surface(1)[2].programs),
            plan=tuple(n for n, _, _ in plan_le),
            must_cover=frozenset(
                {"train_step", f"init@{sub_tag}",
                 f"train_step@{sub_tag}",
                 f"multi_step@k{cfg_le.steps_per_call}@{sub_tag}",
                 f"state_copy@{sub_tag}"})))
        for n, f, a in plan_le:
            if _base(n) != "train_step" or not n.endswith(f"@{sub_tag}"):
                continue
            audits.append(audit_callable(
                f"{backend}::{n}", f, a, path=path,
                expect_donation=_base(n) in DONATED_PROGRAMS,
                cadence=f"every step after a notice-driven live shrink "
                        f"onto `--elastic_target_devices 1` (grow-back "
                        f"returns to the plain rows)"))

        if backend == "gspmd":
            # The one-network token archs (models/mla_moe.py, named @lm;
            # models/loop_lm.py, named @loop_lm; models/sambay.py, named
            # @sambay): the likelihood step at each tiny preset over the
            # same 2-way data mesh (loss and gradient per shard inside a
            # shard_map, the looped and the hybrid arch's kernels in
            # interpret mode). A token arch's surface is "init"
            # and "train_step" alone, and the warmup plan covers it from
            # the same `_program_args` the trainer warms.
            from dcgan_tpu.presets import get_preset

            for preset, tag in (("mla_moe_tiny", "lm"),
                                ("loop_lm_tiny", "loop_lm"),
                                ("sambay_tiny", "sambay")):
                cfg_lm = get_preset(preset, mesh=cfg.mesh)
                pt_lm = make_parallel_train(cfg_lm, mesh)
                plan_lm, _bk_lm = warmup.build_warmup_plan(
                    cfg_lm, pt_lm, warmup.state_example(pt_lm),
                    sample_z=None, eval_z=None,
                    make_backoff_pt=lambda c, _m=mesh: make_parallel_train(
                        c, _m))
                coverage.append(CoverageRow(
                    variant=f"gspmd+{tag}", path=path,
                    programs=frozenset(pt_lm.programs),
                    plan=tuple(n for n, _, _ in plan_lm),
                    must_cover=frozenset({"train_step", "state_copy"})))
                for n, f, a in plan_lm:
                    if _base(n) == "train_step":
                        audits.append(audit_callable(
                            f"gspmd::{n}@{tag}", f, a, path=path,
                            expect_donation=True,
                            cadence="every step of a token-family run "
                                    f"(`arch={cfg_lm.model.arch}`, "
                                    "`loss=lm`)"))

            # the serving plane's rungs: the checkpoint-source sampler at
            # every bucket of the default doubling ladder (granule = the
            # data-axis size, the BucketLadder contract)
            ladder = build_ladder(SERVE_MAX_BATCH, mesh.shape["data"])
            serve_rows = sampler_plan(pt.sample, ladder, cfg.model.z_dim,
                                      state=state)

    for n, f, a in serve_rows:
        audits.append(audit_callable(
            f"serve::{n}", f, a, path=GROUP_PATHS["serve"],
            expect_donation=False))
    return audits, coverage


# -- checkers ----------------------------------------------------------------

def check_donation(audits: Sequence[ProgramAudit]) -> List[Finding]:
    """DCG007: donation realized as aliasing, in both directions."""
    findings: List[Finding] = []
    for a in audits:
        if a.donation is None:
            if a.expect_donation:
                findings.append(Finding(
                    check="DCG007", path=a.path, line=0, symbol=a.name,
                    key=f"undonated:{a.name}",
                    message=f"{a.name} is declared a donating program "
                            "(parallel/api.py::DONATED_PROGRAMS) but its "
                            "compiled form donates nothing — the state "
                            "update silently stopped being in-place"))
            continue
        if not a.expect_donation:
            findings.append(Finding(
                check="DCG007", path=a.path, line=0, symbol=a.name,
                key=f"undeclared-donor:{a.name}",
                message=f"{a.name} donates buffers but is not declared in "
                        "parallel/api.py::DONATED_PROGRAMS — an undeclared "
                        "donor invalidates buffers its callers may still "
                        "hold; declare it and regenerate the manifest"))
        for label in a.donation.get("unaliased", ()):
            findings.append(Finding(
                check="DCG007", path=a.path, line=0, symbol=a.name,
                key=f"unaliased:{a.name}:{label}",
                message=f"{a.name}: donated argument {label} is NOT "
                        "realized as an input_output_aliases pair in the "
                        "compiled executable — a silent copy every "
                        "dispatch"))
    return findings


def check_transports() -> List[Finding]:
    """DCG008 (registry half): every declared transport row must name a
    live callable in train/coordination.py that the runtime tripwire
    wraps — a renamed transport must fail here, not silently drop out of
    the manifest."""
    from dcgan_tpu.analysis import tripwire
    from dcgan_tpu.train import coordination

    findings: List[Finding] = []
    path = GROUP_PATHS["coordination"]
    for tname, (fn_name, census, _cadence) in sorted(
            coordination.TRANSPORT_CENSUS.items()):
        name = f"coordination::{tname}"
        if not callable(getattr(coordination, fn_name, None)):
            findings.append(Finding(
                check="DCG008", path=path, line=0, symbol=name,
                key=f"transport:{tname}",
                message=f"TRANSPORT_CENSUS entry {tname!r} names "
                        f"coordination.{fn_name}, which does not exist — "
                        "the declared census no longer describes a live "
                        "transport"))
        if fn_name not in tripwire.WRAPPED_TRANSPORTS:
            findings.append(Finding(
                check="DCG008", path=path, line=0, symbol=name,
                key=f"transport-unwrapped:{tname}",
                message=f"transport {fn_name!r} (census entry {tname!r}) "
                        "is not in the runtime tripwire's wrap list — a "
                        "declared collective transport must also be "
                        "thread-policed (analysis/tripwire.py)"))
    return findings


def transport_records() -> List[manifest_lib.ProgramRecord]:
    from dcgan_tpu.train import coordination

    return [manifest_lib.ProgramRecord(
        name=f"coordination::{tname}", kind="transport",
        path=GROUP_PATHS["coordination"], args=(fn_name,),
        fingerprint="-", collectives=dict(census), donation=None,
        cadence=cadence)
        for tname, (fn_name, census, cadence) in
        sorted(coordination.TRANSPORT_CENSUS.items())]


def records_from(audits: Sequence[ProgramAudit]
                 ) -> List[manifest_lib.ProgramRecord]:
    return [manifest_lib.ProgramRecord(
        name=a.name, kind="program", path=a.path, args=a.args,
        fingerprint=a.fingerprint, collectives=dict(a.collectives),
        donation=a.donation, cadence=a.cadence)
        for a in audits] + transport_records()


def check_warmup_coverage(coverage: Sequence[CoverageRow]) -> List[Finding]:
    """DCG009 (coverage half): the warmup plan must enumerate what the
    loop dispatches — per variant (`must_cover` rows present verbatim)
    and per backend (every `programs`-dict entry except the pre-warmup
    `init` planned by SOME variant). Generalizes PR 7's test-pinned
    stage-coverage check to every program and both backends."""
    findings: List[Finding] = []
    planned_by_backend: Dict[str, Set[str]] = {}
    programs_by_backend: Dict[str, Tuple[str, Set[str]]] = {}
    for row in coverage:
        backend = row.variant.split("+", 1)[0]
        planned_by_backend.setdefault(backend, set()).update(
            _base(n) for n in row.plan)
        # UNION across the backend's variants: a program registered by
        # only one variant's construction must still be planned somewhere
        programs_by_backend.setdefault(backend, (row.path, set()))[1] \
            .update(row.programs)
        for want in sorted(row.must_cover):
            if want not in row.plan:
                findings.append(Finding(
                    check="DCG009", path=row.path, line=0,
                    symbol=f"{row.variant}::warmup_plan",
                    key=f"warmup-gap:{row.variant}:{want}",
                    message=f"[{row.variant}] the trainer loop dispatches "
                            f"{want!r} at this config but the warmup plan "
                            "does not enumerate it — its first live "
                            "dispatch would compile under an armed "
                            "watchdog deadline (DESIGN §6d)"))
    for backend, (path, programs) in sorted(programs_by_backend.items()):
        for prog in sorted(programs - {"init"}
                           - planned_by_backend.get(backend, set())):
            findings.append(Finding(
                check="DCG009", path=path, line=0,
                symbol=f"{backend}::warmup_plan",
                key=f"warmup-unplanned:{backend}:{prog}",
                message=f"[{backend}] ParallelTrain.programs[{prog!r}] is "
                        "dispatchable but no warmup-plan variant ever "
                        "enumerates it — AOT warmup cannot pre-compile "
                        "what the plan does not name"))
    return findings


def check_retrace(audits: Sequence[ProgramAudit]) -> List[Finding]:
    """DCG009 (hazard half): closure-captured constvars and weak-typed
    (python-scalar) leakage in the traced programs."""
    findings: List[Finding] = []
    for a in audits:
        for label, size, _dtype, weak in a.consts:
            if size > CONST_SIZE_LIMIT:
                findings.append(Finding(
                    check="DCG009", path=a.path, line=0, symbol=a.name,
                    key=f"const:{a.name}:{label}",
                    message=f"{a.name} closes over {label} ({size} "
                            "elements) as a baked-in constant — its VALUE "
                            "is part of the HLO, so every change retraces "
                            "and re-keys the persistent compile cache; "
                            "pass it as an argument instead"))
            elif weak:
                findings.append(Finding(
                    check="DCG009", path=a.path, line=0, symbol=a.name,
                    key=f"weak-const:{a.name}:{label}",
                    message=f"{a.name} closes over weak-typed {label} — a "
                            "leaked python scalar whose promotion "
                            "semantics differ from committed arrays; bind "
                            "it with an explicit dtype"))
    return findings


def check_hygiene(audits: Sequence[ProgramAudit]) -> List[Finding]:
    """DCG010: host callbacks, implicit f64 promotion, and explicit
    transfers inside the traced bodies."""
    findings: List[Finding] = []
    for a in audits:
        for prim in a.callbacks:
            findings.append(Finding(
                check="DCG010", path=a.path, line=0, symbol=a.name,
                key=f"callback:{a.name}:{prim}",
                message=f"{a.name} contains host callback {prim!r} — a "
                        "dispatched program re-entering Python has no "
                        "ordering against the async dispatch stream "
                        "(DESIGN §6b) and stalls the device on the host"))
        for prim in a.f64_prims:
            findings.append(Finding(
                check="DCG010", path=a.path, line=0, symbol=a.name,
                key=f"f64:{a.name}:{prim}",
                message=f"{a.name} computes in float64/complex128 "
                        f"(first at {prim!r}) — an implicit promotion "
                        "slipped in; TPUs emulate f64 at ~100x cost"))
        for prim in a.transfers:
            findings.append(Finding(
                check="DCG010", path=a.path, line=0, symbol=a.name,
                key=f"transfer:{a.name}:{prim}",
                message=f"{a.name} embeds transfer primitive {prim!r} "
                        "inside traced code — placement belongs to the "
                        "caller (shardings/donation), not the program "
                        "body"))
    return findings


#: DCG011: the model-family variants whose FULL train state (params, both
#: optimizer states, BN/SN state, EMA, step) must be rule-covered — the
#: structural union of what the repo can train: plain dcgan, dcgan with
#: attention + spectral norm + conditioning, the resnet family with
#: attention + SN, and stylegan with SN (its norm-free critic is the
#: resnet one). eval_shape only — no arrays, no lowering.
def spec_coverage_variants():
    from dcgan_tpu.config import ModelConfig, TrainConfig

    return (
        ("dcgan", TrainConfig(model=ModelConfig(
            output_size=16, gf_dim=8, df_dim=8,
            compute_dtype="float32"), batch_size=8)),
        ("dcgan+attn+sn+cond", TrainConfig(model=ModelConfig(
            output_size=32, gf_dim=8, df_dim=8, compute_dtype="float32",
            attn_res=16, spectral_norm="gd", num_classes=10),
            batch_size=8)),
        ("resnet+attn+sn", TrainConfig(model=ModelConfig(
            arch="resnet", output_size=32, gf_dim=8, df_dim=8,
            compute_dtype="float32", attn_res=16, spectral_norm="d"),
            batch_size=8, loss="hinge")),
        ("stylegan+sn", TrainConfig(model=ModelConfig(
            arch="stylegan", output_size=32, gf_dim=8, df_dim=8,
            compute_dtype="float32", spectral_norm="d"),
            batch_size=8, loss="hinge")),
    )


def check_spec_coverage() -> List[Finding]:
    """DCG011: every leaf of every model family's train state must match
    EXACTLY ONE row of the sharding-rule table (elastic/rules.py). An
    unmatched leaf means a new layer has no classified placement (the
    engine raises at run time — this catches it at lint time, for every
    family at once); a multiply-matched leaf means two rows compete and
    first-match order silently decides a spec — the checkpoint sidecar
    and the cross-topology restore both resolve through this table, so
    ambiguity here is placement nondeterminism there."""
    import jax

    from dcgan_tpu.elastic import rules
    from dcgan_tpu.train.steps import init_train_state

    findings: List[Finding] = []
    path = GROUP_PATHS["elastic"]
    for variant, cfg in spec_coverage_variants():
        shapes = jax.eval_shape(lambda k, c=cfg: init_train_state(k, c),
                                jax.random.key(0))
        for leaf_path, leaf in jax.tree_util.tree_flatten_with_path(
                shapes)[0]:
            p = rules.path_str(leaf_path)
            ndim = len(getattr(leaf, "shape", ()))
            hits = rules.matching_rules(p, ndim)
            if len(hits) == 1:
                continue
            if not hits:
                findings.append(Finding(
                    check="DCG011", path=path, line=0,
                    symbol=f"{variant}::state",
                    key=f"spec-unmatched:{variant}:{p}",
                    message=f"[{variant}] state leaf {p!r} (rank {ndim}) "
                            "matches NO row of PARTITION_RULES — an "
                            "unclassified placement; the engine would "
                            "raise at the first state_shardings call at "
                            "this config"))
            else:
                pats = [rules.PARTITION_RULES[i][0] for i in hits]
                findings.append(Finding(
                    check="DCG011", path=path, line=0,
                    symbol=f"{variant}::state",
                    key=f"spec-ambiguous:{variant}:{p}",
                    message=f"[{variant}] state leaf {p!r} (rank {ndim}) "
                            f"matches {len(hits)} rules ({pats}) — "
                            "first-match order is silently deciding its "
                            "spec; make the patterns disjoint"))
        # grad-spec derivation (ISSUE 13): under ZeRO >= 2 a gradient leaf
        # must resolve to EXACTLY the spec of the mu moment that consumes
        # it — the reduce-scattered gradient is the shard-local Adam
        # update's input with zero re-layout. Gradients are addressed by
        # the bare param tail (rules.grad_shardings), moments by
        # their full "opt/<net>/.../mu/<tail>" path; a rule row that keys
        # on either prefix silently splits the two resolutions, so audit
        # them against each other on the canonical 2-way mesh.
        mesh_shape = {"data": CANONICAL_DEVICES, "model": 1}
        for net in ("gen", "disc"):
            for leaf_path, leaf in jax.tree_util.tree_flatten_with_path(
                    shapes["params"][net])[0]:
                tail = rules.path_str(leaf_path)
                shape = tuple(getattr(leaf, "shape", ()))
                try:
                    gspec = rules.resolve_spec(
                        rules.logical_spec(tail, len(shape)), shape,
                        mesh_shape, zero=True)
                    mspec = rules.resolve_spec(
                        rules.logical_spec(f"opt/{net}/1/0/mu/{tail}",
                                           len(shape)), shape,
                        mesh_shape, zero=True)
                except ValueError:
                    continue  # unmatched leaves are already flagged above
                if gspec != mspec:
                    findings.append(Finding(
                        check="DCG011", path=path, line=0,
                        symbol=f"{variant}::grads",
                        key=f"grad-spec-drift:{variant}:{net}/{tail}",
                        message=f"[{variant}] gradient leaf "
                                f"{net}/{tail!r} resolves to {gspec} but "
                                f"its mu moment resolves to {mspec} — a "
                                "rule row keys on the opt/ or params/ "
                                "prefix, so the reduce-scattered gradient "
                                "and the shard-local Adam state disagree "
                                "on layout under zero_stage >= 2"))
    return findings


def check_manifest(records: Sequence[manifest_lib.ProgramRecord],
                   manifest_path: str) -> List[Finding]:
    """DCG008 (drift half): live records vs the committed manifest."""
    if not os.path.exists(manifest_path):
        return [Finding(
            check="DCG008", path="dcgan_tpu/analysis/programs.lock.jsonl",
            line=0, symbol="<manifest>", key="manifest-missing",
            message=f"no committed program manifest at {manifest_path} — "
                    "generate one with `python -m dcgan_tpu.analysis "
                    "--semantic --write-manifest`")]
    return manifest_lib.diff(records, manifest_lib.load_path(manifest_path))


def run_semantic(checks: Optional[Sequence[str]] = None,
                 manifest_path: Optional[str] = None,
                 compare_manifest: bool = True,
                 backends: Sequence[str] = BACKENDS,
                 ) -> Tuple[List[Finding],
                            List[manifest_lib.ProgramRecord]]:
    """The full semantic tier: enumerate + audit + every requested checker
    (default: all five). Returns (findings, manifest records); the CLI
    applies the shared baseline on top, exactly like the AST tier.
    `backends` narrows the enumeration to a share of the manifest, which
    only a regeneration (`compare_manifest=False`) may ask for: drift
    against the whole committed manifest would call the other share
    missing."""
    if tuple(backends) != BACKENDS and compare_manifest:
        raise ValueError("a share of the manifest (backends "
                         f"{list(backends)}) can only be regenerated "
                         "(--write-manifest), not checked against the "
                         "whole committed manifest")
    if checks:
        checks = [c.upper() for c in checks]
        unknown = sorted(set(checks) - set(SEMANTIC_CHECKS))
        if unknown:
            raise ValueError(f"unknown semantic check ID(s) {unknown}; "
                             f"valid: {list(SEMANTIC_CHECKS)}")
    active = set(checks or SEMANTIC_CHECKS)
    # DCG011 is eval_shape-only — a `--checks DCG011` run (the command the
    # rule engine's unmatched-leaf error names) must not pay the full
    # trace+lower enumeration it never reads. Manifest regeneration
    # (compare_manifest=False is the CLI's --write-manifest mode) always
    # enumerates: the records ARE its output.
    if active - {"DCG011"} or not compare_manifest:
        audits, coverage = enumerate_audits(backends)
        records = records_from(audits)
    else:
        audits, coverage, records = [], [], []
    findings: List[Finding] = []
    if "DCG007" in active:
        findings += check_donation(audits)
    if "DCG008" in active:
        findings += check_transports()
        if compare_manifest:
            findings += check_manifest(
                records,
                manifest_path or manifest_lib.default_manifest_path())
    if "DCG009" in active:
        findings += check_warmup_coverage(coverage)
        findings += check_retrace(audits)
    if "DCG010" in active:
        findings += check_hygiene(audits)
    if "DCG011" in active:
        findings += check_spec_coverage()
    findings.sort(key=lambda f: (f.path, f.symbol, f.check, f.key))
    return findings, records
