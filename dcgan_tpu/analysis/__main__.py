"""CLI: `python -m dcgan_tpu.analysis [--semantic|--protocol|--all] ...`.

Three tiers behind one entry point and one exit contract (exit 1 on any
non-baselined finding — tests/test_tools_analysis_all*.py pin the
umbrella clean):

- default: the import-free AST tier (DCG001-006 + the DCG013 divergence
  lint) over the package or the given paths, milliseconds per run; a
  full run also audits stale `# dcg: disable` suppressions (DCG014) and
  stale baseline rows (DCG015);
- `--semantic`: the lowered-program tier (DCG007-011, ISSUE 11) — builds
  and `.lower()`s every dispatchable program on the canonical CPU
  topology and compares against the committed program manifest
  (analysis/programs.lock.jsonl);
- `--protocol`: the lockstep tier (DCG012, ISSUE 14) — N virtual
  processes through the REAL coordination decision code over the
  (knob x fault) lattice, audited for termination + lockstep and
  compared against the committed analysis/protocol.lock.jsonl;
- `--all`: the umbrella — AST + semantic + protocol in one invocation
  with per-tier timing and a single exit code (the consolidated tier-1
  pin). Also the full-strength home of `--prune-baseline`.

Lock workflows:
    python -m dcgan_tpu.analysis --semantic --write-manifest   # programs
    python -m dcgan_tpu.analysis --protocol --write-lock       # schedules

`--write-baseline FILE` drafts baseline entries for the current findings
(with `why` left as a TODO each entry must replace before review); the
baseline file is shared by all tiers. `--prune-baseline` rewrites it
minus rows whose fingerprint no longer matches any finding of the
check(s) that ran.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

from dcgan_tpu.analysis import core, semantic  # jax-free until they run


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m dcgan_tpu.analysis",
        description="invariant analyzer: concurrency/donation/parity "
                    "contract lint (AST tier), lowered-program contract "
                    "audit (--semantic), and coordination-protocol "
                    "lockstep audit (--protocol); --all runs the three "
                    "as one gate")
    p.add_argument("paths", nargs="*",
                   help="files/directories to scan (default: the "
                        "dcgan_tpu package; AST tier only)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="one JSON object per finding + a summary line")
    p.add_argument("--baseline", default=None,
                   help="baseline JSONL of accepted findings (default: "
                        "dcgan_tpu/analysis/baseline.jsonl; pass '' to "
                        "ignore the baseline)")
    p.add_argument("--checks", nargs="+", default=None,
                   metavar="DCGXXX", help="run only these checker IDs")
    p.add_argument("--write-baseline", default=None, metavar="FILE",
                   help="write the current findings as draft baseline "
                        "entries to FILE and exit 0")
    p.add_argument("--prune-baseline", action="store_true",
                   help="rewrite the baseline file minus rows whose "
                        "fingerprint matches no current finding of the "
                        "check(s) that ran (full strength under --all)")
    p.add_argument("--semantic", action="store_true",
                   help="run the lowered-program tier (DCG007-011) "
                        "instead of the AST tier")
    p.add_argument("--protocol", action="store_true",
                   help="run the coordination-protocol lockstep tier "
                        "(DCG012) instead of the AST tier")
    p.add_argument("--all", action="store_true", dest="run_all",
                   help="run AST + semantic + protocol tiers in one "
                        "invocation (per-tier timing, one exit code)")
    p.add_argument("--manifest", default=None, metavar="FILE",
                   help="program manifest to check against (default: "
                        "dcgan_tpu/analysis/programs.lock.jsonl)")
    p.add_argument("--write-manifest", nargs="?", const="", default=None,
                   metavar="FILE",
                   help="with --semantic/--all: (re)write the program "
                        "manifest (default: the committed "
                        "analysis/programs.lock.jsonl) — drift findings "
                        "are moot while regenerating, every other "
                        "finding still gates the exit code")
    p.add_argument("--lock", default=None, metavar="FILE",
                   help="protocol lock to check against (default: "
                        "dcgan_tpu/analysis/protocol.lock.jsonl)")
    p.add_argument("--write-lock", nargs="?", const="", default=None,
                   metavar="FILE",
                   help="with --protocol/--all: (re)write the protocol "
                        "lock (default: the committed "
                        "analysis/protocol.lock.jsonl) — drift findings "
                        "are moot while regenerating, termination/"
                        "lockstep findings still gate the exit code")
    p.add_argument("--backend", default=None, choices=semantic.BACKENDS,
                   help="with --write-manifest: lower and write only this "
                        "backend's share of the program rows (gspmd's "
                        "share carries the serving rows, both carry the "
                        "transports)")
    p.add_argument("--stream-table", action="store_true",
                   help="with --semantic: print DESIGN §6c.1's generated "
                        "dispatch-stream table from the live census and "
                        "exit")
    args = p.parse_args(argv)

    tiers = sum((args.semantic, args.protocol, args.run_all))
    if tiers > 1:
        p.error("--semantic / --protocol / --all are mutually exclusive "
                "(--all already includes the other two)")
    if (args.write_manifest is not None or args.stream_table
            or args.manifest or args.backend) \
            and not (args.semantic or args.run_all):
        p.error("--write-manifest/--manifest/--backend require --semantic "
                "or --all; --stream-table requires --semantic")
    if args.stream_table and args.run_all:
        p.error("--stream-table is a pure printer — run it under "
                "--semantic, separately from the --all gate")
    if (args.write_lock is not None or args.lock) \
            and not (args.protocol or args.run_all):
        p.error("--write-lock/--lock require --protocol or --all")
    if args.stream_table and args.write_manifest is not None:
        # --stream-table is a pure printer (its stdout is pasted into
        # DESIGN §6c.1) and returns 0 unconditionally; silently swallowing
        # --write-manifest's finding-gated exit under it would let a
        # DCG007-011 regression ship — run the two steps separately
        p.error("--stream-table and --write-manifest cannot be combined "
                "(the table printer exits 0 regardless of findings); run "
                "--write-manifest first, then --stream-table")
    if (args.semantic or args.protocol or args.run_all) and args.paths:
        p.error("--semantic/--protocol/--all audit fixed enumerations, "
                "not source paths")
    if args.run_all and (args.checks or args.write_baseline is not None):
        p.error("--all runs every tier's full check set; use the "
                "per-tier flags for --checks/--write-baseline")

    if args.run_all:
        return _run_all(p, args)
    if args.semantic:
        return _run_semantic(p, args)
    if args.protocol:
        return _run_protocol(p, args)
    return _run_ast(p, args)


# -- tier executors (findings + tier metadata; baseline applied by caller) ----

def _ast_tier(p, args, full_registry: bool):
    root = core.default_root()
    paths = args.paths or [os.path.join(root, "dcgan_tpu")]
    suppressed: List[core.Finding] = []
    sources = core.collect_sources(paths, root)
    findings = core.run_checks(sources, core.Config(), checks=args.checks,
                               suppressed_out=suppressed)
    if full_registry:
        # only a full-registry run can prove a suppression dead
        findings = findings + core.audit_stale_suppressions(sources,
                                                            suppressed)
        findings.sort(key=lambda f: (f.path, f.line, f.check))
    ran = tuple(c.upper() for c in args.checks) if args.checks else (
        core.AST_CHECK_IDS + (core.STALE_SUPPRESSION_CHECK,))
    # a path-scoped run cannot prove a baseline row dead: rows anchor on
    # files that may simply not have been scanned — only full-package
    # runs feed the DCG015 audit (and --prune-baseline)
    return findings, {"files": len(sources), "ran_checks": ran,
                      "audit_baseline": not args.paths}


def _semantic_tier(p, args):
    from dcgan_tpu.analysis import manifest as manifest_lib

    writing = args.write_manifest is not None
    findings, records = semantic.run_semantic(
        checks=None if args.run_all else args.checks,
        manifest_path=args.manifest,
        # drift against the old manifest is moot while regenerating it
        compare_manifest=not writing,
        backends=(args.backend,) if args.backend else semantic.BACKENDS)
    if writing:
        path = args.write_manifest or manifest_lib.default_manifest_path()
        with open(path, "w", encoding="utf-8") as f:
            f.write(manifest_lib.dumps(records))
        # stderr: stdout is the findings/summary JSON stream under --json
        print(f"wrote {len(records)} manifest row(s) to {path}",
              file=sys.stderr)
    ran = tuple(c.upper() for c in args.checks) \
        if (args.checks and not args.run_all) \
        else tuple(semantic.SEMANTIC_CHECKS)
    if writing:
        # drift findings are muted while regenerating — a baselined
        # DCG008 drift exemption must not be called stale by the very
        # run that rewrites the manifest
        ran = tuple(c for c in ran if c != "DCG008")
    if args.backend:
        # one share cannot prove a baseline row dead: its finding may
        # belong to the other share's programs
        ran = ()
    return findings, records, {"programs": len(records), "ran_checks": ran}


def _protocol_tier(p, args):
    from dcgan_tpu.analysis import protocol

    writing = args.write_lock is not None
    findings, rows, stats = protocol.run_protocol(
        checks=None if args.run_all else args.checks,
        lock_path=args.lock,
        # drift against the old lock is moot while regenerating it
        compare_lock=not writing)
    if writing:
        path = args.write_lock or protocol.default_lock_path()
        with open(path, "w", encoding="utf-8") as f:
            f.write(protocol.dumps(rows))
        # stderr: stdout is the findings/summary JSON stream under --json
        print(f"wrote {len(rows)} protocol lock row(s) to {path}",
              file=sys.stderr)
    # explored-interleaving counts, ALWAYS printed: silent lattice
    # shrinkage must be visible in CI logs (the committed lock catches
    # it as missing-row findings; this line makes the scale auditable
    # at a glance)
    per = ", ".join(f"{k}={v}" for k, v in stats["per_config"].items())
    print(f"[dcgan_tpu.analysis --protocol] explored "
          f"{stats['interleavings']} interleaving(s) across "
          f"{stats['configs']} knob config(s): {per}", file=sys.stderr)
    ran = tuple(c.upper() for c in args.checks) \
        if (args.checks and not args.run_all) \
        else tuple(protocol.PROTOCOL_CHECKS)
    if writing:
        # lock-drift findings are muted while regenerating (as above for
        # the manifest) — DCG012 baseline rows stay un-audited this run
        ran = tuple(c for c in ran if c != "DCG012")
    return findings, rows, stats, {"ran_checks": ran}


# -- single-tier drivers ------------------------------------------------------

def _run_ast(p: argparse.ArgumentParser, args) -> int:
    try:  # bad path / unknown --checks ID: usage error, not a traceback
        findings, meta = _ast_tier(p, args, full_registry=not args.checks)
    except ValueError as e:
        p.error(str(e))

    if args.write_baseline is not None:
        return _write_baseline(args.write_baseline, findings)

    new, old, n_stale = _gate(p, args, findings, meta["ran_checks"],
                              audit_baseline=meta["audit_baseline"])
    return _emit(args, "dcgan-analysis", "",
                 {"files": meta["files"]}, f"{meta['files']} file(s)",
                 len(findings), new, old, n_stale)


def _run_semantic(p: argparse.ArgumentParser, args) -> int:
    # topology first, BEFORE anything can initialize jax: the census needs
    # >= 2 CPU devices (collectives over a size-1 axis trace away) and the
    # committed fingerprints assume partitionable threefry
    semantic.ensure_semantic_platform()
    from dcgan_tpu.analysis import manifest as manifest_lib

    try:
        findings, records, meta = _semantic_tier(p, args)
    except (ValueError, RuntimeError) as e:
        p.error(str(e))

    if args.stream_table:  # pure printer (mutually exclusive with writing)
        print(manifest_lib.render_stream_table(records))
        return 0
    if args.write_baseline is not None:
        return _write_baseline(args.write_baseline, findings)

    new, old, n_stale = _gate(p, args, findings, meta["ran_checks"])
    return _emit(args, "dcgan-analysis-semantic", " --semantic",
                 {"programs": meta["programs"]},
                 f"{meta['programs']} program(s)",
                 len(findings), new, old, n_stale)


def _run_protocol(p: argparse.ArgumentParser, args) -> int:
    try:
        findings, rows, stats, meta = _protocol_tier(p, args)
    except (ValueError, RuntimeError) as e:
        p.error(str(e))

    if args.write_baseline is not None:
        return _write_baseline(args.write_baseline, findings)

    new, old, n_stale = _gate(p, args, findings, meta["ran_checks"])
    return _emit(args, "dcgan-analysis-protocol", " --protocol",
                 {"configs": stats["configs"],
                  "interleavings": stats["interleavings"]},
                 f"{stats['interleavings']} interleaving(s) / "
                 f"{stats['configs']} config(s)",
                 len(findings), new, old, n_stale)


# -- the umbrella -------------------------------------------------------------

def _run_all(p: argparse.ArgumentParser, args) -> int:
    # the semantic tier's canonical topology must be arranged before the
    # FIRST jax import in this process — the AST tier never imports jax
    # and the protocol tier only patches process identity, so one
    # arrangement up front serves all three
    semantic.ensure_semantic_platform()

    tier_meta = {}
    findings: List[core.Finding] = []
    ran_checks: List[str] = []
    try:
        t0 = time.monotonic()
        ast_findings, meta = _ast_tier(p, args, full_registry=True)
        tier_meta["ast"] = {"files": meta["files"],
                            "findings": len(ast_findings),
                            "ms": round((time.monotonic() - t0) * 1e3, 1)}
        findings += ast_findings
        ran_checks += list(meta["ran_checks"])

        t0 = time.monotonic()
        sem_findings, records, meta = _semantic_tier(p, args)
        tier_meta["semantic"] = {
            "programs": meta["programs"], "findings": len(sem_findings),
            "ms": round((time.monotonic() - t0) * 1e3, 1)}
        findings += sem_findings
        ran_checks += list(meta["ran_checks"])

        t0 = time.monotonic()
        proto_findings, rows, stats, meta = _protocol_tier(p, args)
        tier_meta["protocol"] = {
            "configs": stats["configs"],
            "interleavings": stats["interleavings"],
            "findings": len(proto_findings),
            "ms": round((time.monotonic() - t0) * 1e3, 1)}
        findings += proto_findings
        ran_checks += list(meta["ran_checks"])
    except (ValueError, RuntimeError) as e:
        p.error(str(e))

    new, old, n_stale = _gate(p, args, findings, ran_checks)
    timing = ", ".join(f"{t} {m['ms']:.0f} ms ({m['findings']} "
                       f"finding(s))" for t, m in tier_meta.items())
    return _emit(args, "dcgan-analysis-all", " --all",
                 {"tiers": tier_meta}, timing,
                 len(findings), new, old, n_stale)


# -- shared plumbing ----------------------------------------------------------

def _emit(args, label: str, flag: str, extra: dict, human_stats: str,
          n_findings: int, new, old, n_stale: int) -> int:
    """ONE output/exit contract for every tier: finding rows + a summary
    (JSON object stream under --json — nothing else may print to stdout
    there), `:line` suffix only when a finding has a source line, exit 1
    on any new finding."""
    if args.as_json:
        for finding in new:
            print(json.dumps(finding.to_json()))
        print(json.dumps({
            "label": label, **extra, "findings": n_findings,
            "baselined": len(old), "stale_baseline_rows": n_stale,
            "new_findings": len(new)}))
    else:
        for finding in new:
            where = f":{finding.line}" if finding.line else ""
            print(f"{finding.path}{where}: {finding.check} "
                  f"[{finding.symbol}] {finding.message}")
        print(f"[dcgan_tpu.analysis{flag}] {human_stats}, "
              f"{len(new)} new finding(s), {len(old)} baselined"
              + ("" if new else " — clean"))
    return 1 if new else 0


def _write_baseline(path: str, findings) -> int:
    with open(path, "w", encoding="utf-8") as f:
        for finding in findings:
            f.write(json.dumps(finding.baseline_entry()) + "\n")
    print(f"wrote {len(findings)} draft baseline entr"
          f"{'y' if len(findings) == 1 else 'ies'} to "
          f"{path} (fill in each 'why')")
    return 0


def _gate(p: argparse.ArgumentParser, args, findings, ran_checks,
          audit_baseline: bool = True):
    """Apply the baseline, then the stale-row audit over the checks that
    ran (DCG015); `--prune-baseline` resolves stale rows by rewriting the
    file instead of reporting them. Returns (new, baselined, n_stale).
    `audit_baseline=False` (path-scoped AST runs) skips the stale audit
    entirely: a row anchored on an unscanned file is not dead, just out
    of view."""
    baseline_path = args.baseline if args.baseline is not None \
        else core.default_baseline_path()
    try:  # malformed entry / draft TODO why: a clean error, not a dump
        entries = core.load_baseline(baseline_path) if baseline_path \
            else []
    except ValueError as e:
        p.error(str(e))
    new, old = core.split_baselined(findings, entries)
    rel = os.path.relpath(baseline_path, core.default_root()).replace(
        os.sep, "/") if baseline_path else "<none>"
    if not audit_baseline:
        return new, old, 0
    stale_findings, stale_rows = core.audit_stale_baseline(
        entries, old, ran_checks, rel)
    if args.prune_baseline and stale_rows:
        dropped = core.prune_baseline_file(baseline_path, stale_rows)
        # stderr: stdout is the findings/summary JSON stream under --json
        print(f"pruned {dropped} stale baseline row(s) from "
              f"{baseline_path}", file=sys.stderr)
    elif stale_findings:
        # stale-audit findings never pass through the baseline: the fix
        # is deleting the dead row, not exempting the exemption
        new = list(new) + stale_findings
    return new, old, len(stale_rows)


if __name__ == "__main__":
    sys.exit(main())
