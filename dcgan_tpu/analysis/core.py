"""Analyzer plumbing: findings, suppressions, baselines, the check registry.

The invariant analyzer (ISSUE 8) is a plain-AST pass — no imports of the
code under analysis, no jax — so it runs in milliseconds on every tier-1
pass and cannot be broken by a module that fails to import. Each checker
is a function `(sources, config) -> [Finding]` registered in `CHECKS`
under its stable ID; this module owns everything the checkers share:

- `SourceFile`: one parsed file (AST + parent links + the per-line
  `# dcg: disable=DCGxxx` suppression map). Paths are repo-relative
  POSIX strings — the stable coordinate findings and baselines key on.
- `Finding.fingerprint()` deliberately EXCLUDES the line number: a
  baseline must survive unrelated edits above the finding, so identity is
  (check, file, enclosing symbol, detail key), not a line.
- Baselines are JSONL (one object per line) because JSON has no comments
  and every baselined finding must carry a one-line `why` justification —
  the file is the reviewed list of intentional exemptions, not a dumping
  ground (`python -m dcgan_tpu.analysis --write-baseline` drafts entries
  with `why` left as TODO).
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize
from typing import Dict, List, Optional, Sequence, Tuple

_SUPPRESS_RE = re.compile(r"#\s*dcg:\s*disable=([A-Za-z0-9_,\s]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One invariant violation at a concrete site."""

    check: str      # "DCG001".."DCG006"
    path: str       # repo-relative POSIX path
    line: int       # 1-based line of the offending node
    symbol: str     # enclosing function/class qualname, or "<module>"
    key: str        # stable detail (sink name, key literal, call name...)
    message: str

    def fingerprint(self) -> Tuple[str, str, str, str]:
        """Line-free identity — what suppression baselines match on."""
        return (self.check, self.path, self.symbol, self.key)

    def to_json(self) -> Dict[str, object]:
        return dataclasses.asdict(self)

    def baseline_entry(self, why: str = "TODO: justify") -> Dict[str, str]:
        return {"check": self.check, "path": self.path,
                "symbol": self.symbol, "key": self.key, "why": why}


class SourceFile:
    """One parsed python file plus the lookup structure checkers need."""

    def __init__(self, path: str, source: str):
        self.path = path.replace(os.sep, "/")
        self.source = source
        self.tree = ast.parse(source)
        # module dotted name ("dcgan_tpu.train.services") — the call-graph
        # checker resolves cross-module imports through it
        self.module = self.path[:-3].replace("/", ".") \
            if self.path.endswith(".py") else self.path.replace("/", ".")
        # suppressions come from real COMMENT tokens only (ISSUE 14): the
        # old per-line regex also matched `# dcg: disable=...` mentions
        # inside docstrings, which both created phantom suppressions and
        # would have made the stale-suppression audit (DCG014) flag prose
        self.suppressed: Dict[int, set] = {}
        try:
            tokens = list(tokenize.generate_tokens(
                io.StringIO(source).readline))
        except (tokenize.TokenError, IndentationError):  # pragma: no cover
            tokens = []
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if m:
                ids = {t.strip().upper() for t in m.group(1).split(",")
                       if t.strip()}
                self.suppressed[tok.start[0]] = ids
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        # local alias -> (module, original name) for `from X import y` —
        # checkers use it to see through un-qualified calls
        # (`from time import time; time()` is still time.time)
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for alias in node.names:
                    self.from_imports[alias.asname or alias.name] = \
                        (node.module, alias.name)

    @classmethod
    def from_source(cls, source: str, path: str) -> "SourceFile":
        return cls(path, source)

    def is_suppressed(self, finding: Finding) -> bool:
        return finding.check in self.suppressed.get(finding.line, ())

    def enclosing_symbol(self, node: ast.AST) -> str:
        """Dotted qualname of the innermost enclosing def/class chain."""
        parts: List[str] = []
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = self.parents.get(cur)
        return ".".join(reversed(parts)) or "<module>"

    def enclosing_class(self, node: ast.AST) -> Optional[ast.ClassDef]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur
            cur = self.parents.get(cur)
        return None


@dataclasses.dataclass
class Config:
    """Checker knobs. The defaults describe THIS repo; fixture suites pass
    synthetic paths that land inside (or outside) the scopes below."""

    # DCG004: modules whose metric-key literals must appear in the
    # inventory (dcgan_tpu/train/event_keys.py unless overridden here)
    inventory: Optional[Dict[str, str]] = None
    parity_modules: Tuple[str, ...] = (
        "dcgan_tpu/train/trainer.py",
        "dcgan_tpu/train/coordination.py",
        "dcgan_tpu/serve/server.py",
        "dcgan_tpu/serve/__main__.py",
        # fleet report rows (ISSUE 19): serve/fleet_* and the drop split
        "dcgan_tpu/serve/fleet.py",
        "dcgan_tpu/serve/router.py",
        # emits the progressive/* scalar-row extras (ISSUE 15)
        "dcgan_tpu/progressive/phases.py",
    )
    # DCG001: thread targets that ARE a dispatch thread by design — a
    # subsystem whose single worker owns every collective/program dispatch
    # (the serving plane's ServeWorker). Collectives reachable from these
    # roots are on the right thread by definition; the runtime tripwire
    # still polices them (the worker enters dispatch_scope), so the
    # exemption is declared, not assumed. Format: "path::QualName".
    dispatch_thread_targets: Tuple[str, ...] = (
        "dcgan_tpu/serve/worker.py::ServeWorker._run",
        # each protocol-simulator thread IS the dispatch thread of its
        # virtual process (ISSUE 14) — it drives the real coordination
        # transports through rendezvous shims by design
        "dcgan_tpu/analysis/simulate.py::_virtual_process_main",
    )
    # DCG006: modules whose mutating filesystem calls must be retried
    # (utils/retry.retry_io) or explicitly fenced with try/except OSError
    io_modules: Tuple[str, ...] = (
        "dcgan_tpu/train/services.py",
        "dcgan_tpu/utils/checkpoint.py",
        "dcgan_tpu/utils/metrics.py",
    )
    # DCG003: the one file allowed to name jax's shard_map directly
    shard_map_exempt: Tuple[str, ...] = ("dcgan_tpu/utils/backend.py",)
    # DCG013: modules that participate in the multi-host lockstep
    # protocol — the divergence lint only makes sense where N processes
    # must issue identical collective streams (the serving plane is a
    # single-process surface by design and stays out)
    protocol_modules: Tuple[str, ...] = (
        "dcgan_tpu/train/",
        "dcgan_tpu/utils/checkpoint.py",
        "dcgan_tpu/elastic/",
        "dcgan_tpu/parallel/",
        "dcgan_tpu/evals/",
        # the progressive switch dispatches mesh programs (per-phase init,
        # the state-carry copies) at a step-keyed boundary — its decision
        # code must stay free of host-local-state branches (ISSUE 15)
        "dcgan_tpu/progressive/",
    )

    def load_inventory(self) -> Dict[str, str]:
        if self.inventory is not None:
            return self.inventory
        from dcgan_tpu.train.event_keys import EVENT_KEYS

        return EVENT_KEYS


def collect_sources(paths: Sequence[str], root: str) -> List[SourceFile]:
    """Every .py file under `paths`, parsed, with repo-relative names."""
    out: List[SourceFile] = []
    seen = set()
    for p in paths:
        p = os.path.abspath(p)
        files: List[str] = []
        if os.path.isdir(p):
            for dirpath, dirnames, names in os.walk(p):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                files.extend(os.path.join(dirpath, n)
                             for n in sorted(names) if n.endswith(".py"))
        elif p.endswith(".py") and os.path.isfile(p):
            files.append(p)
        else:
            raise ValueError(
                f"path {p!r} is not a directory or an existing .py file")
        for f in files:
            rel = os.path.relpath(f, root).replace(os.sep, "/")
            if rel in seen:
                continue
            seen.add(rel)
            with open(f, encoding="utf-8") as fh:
                out.append(SourceFile(rel, fh.read()))
    return out


# -- AST helpers shared by the checkers --------------------------------------

def dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.psum' for a pure Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> Tuple[Optional[str], str]:
    """(terminal callee name, dotted receiver or '') for a Call node."""
    func = call.func
    if isinstance(func, ast.Name):
        return func.id, ""
    if isinstance(func, ast.Attribute):
        return func.attr, dotted(func.value) or ""
    return None, ""


def iter_calls(node: ast.AST):
    """Every Call in `node`'s subtree (nested defs and lambdas included —
    the conservative read: code textually inside a function is attributed
    to it, which is exactly right for worker closures and retry thunks)."""
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            yield n


def lexical_def(sf: SourceFile, site: ast.AST,
                name: str) -> Optional[ast.AST]:
    """The def named `name` visible from `site`: innermost enclosing
    function scopes first, then module level — how thread-target
    closures, retry thunks, and jitted local bodies are resolved. Shared
    by the thread and hygiene checkers so their resolution semantics
    cannot drift."""
    cur: Optional[ast.AST] = site
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Module)):
            for child in ast.walk(cur):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)) \
                        and child.name == name:
                    return child
        cur = sf.parents.get(cur)
    return None


# -- baseline ----------------------------------------------------------------

def load_baseline(path: str) -> List[Dict[str, str]]:
    entries: List[Dict[str, str]] = []
    if not path or not os.path.exists(path):
        return entries
    with open(path, encoding="utf-8") as f:
        for i, line in enumerate(f, start=1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                obj = json.loads(line)
            except ValueError as e:
                raise ValueError(
                    f"{path}:{i}: unparseable baseline line: {e}") from e
            missing = [k for k in ("check", "path", "symbol", "key", "why")
                       if k not in obj]
            if missing:
                raise ValueError(
                    f"{path}:{i}: baseline entry missing {missing} "
                    f"(every exemption needs a 'why' justification)")
            if str(obj["why"]).strip().upper().startswith("TODO"):
                # reject the --write-baseline draft placeholder: an entry
                # is an exemption only once a human wrote its reason
                raise ValueError(
                    f"{path}:{i}: baseline entry for {obj['key']!r} still "
                    "carries the draft 'TODO' justification — replace it "
                    "with the real reason before committing")
            obj["_line"] = i  # stale-audit/prune anchor (never written)
            entries.append(obj)
    return entries


def split_baselined(findings: Sequence[Finding],
                    baseline: Sequence[Dict[str, str]]
                    ) -> Tuple[List[Finding], List[Finding]]:
    """(new findings, baselined findings). Matching is MULTISET-wise:
    each baseline entry absorbs at most one finding, so a second
    violation landing on an already-exempted fingerprint (another bare
    write in the same function, say) still fails the run instead of
    hiding behind the reviewed entry."""
    import collections

    budget = collections.Counter(
        (e["check"], e["path"], e["symbol"], e["key"]) for e in baseline)
    new: List[Finding] = []
    old: List[Finding] = []
    for f in findings:
        fp = f.fingerprint()
        if budget[fp] > 0:
            budget[fp] -= 1
            old.append(f)
        else:
            new.append(f)
    return new, old


# -- driver ------------------------------------------------------------------

def run_checks(sources: Sequence[SourceFile], config: Optional[Config] = None,
               checks: Optional[Sequence[str]] = None,
               suppressed_out: Optional[List[Finding]] = None
               ) -> List[Finding]:
    """Run the requested checkers (default: all) over the parsed sources;
    per-line `# dcg: disable=` suppressions are already applied. Pass
    `suppressed_out` to receive the findings a suppression absorbed —
    the stale-suppression audit (DCG014) needs them to tell a working
    suppression from a dead one."""
    from dcgan_tpu.analysis import hygiene, parity, protocol, threads

    registry = {
        "DCG001": threads.check_collectives_off_dispatch,
        "DCG003": hygiene.check_raw_shard_map,
        "DCG004": parity.check_key_inventory,
        "DCG005": hygiene.check_traced_body_hygiene,
        "DCG006": hygiene.check_bare_io,
        "DCG013": protocol.check_divergent_branch,
    }
    config = config or Config()
    if checks:
        checks = [c.upper() for c in checks]
        unknown = sorted(set(checks) - set(registry))
        if unknown:
            from dcgan_tpu.analysis.protocol import PROTOCOL_CHECKS
            from dcgan_tpu.analysis.semantic import SEMANTIC_CHECKS

            if set(unknown) <= set(SEMANTIC_CHECKS):
                raise ValueError(
                    f"{unknown} are semantic-tier check ID(s) — run "
                    "`python -m dcgan_tpu.analysis --semantic --checks "
                    + " ".join(unknown) + "`")
            if set(unknown) <= set(PROTOCOL_CHECKS):
                raise ValueError(
                    f"{unknown} are protocol-tier check ID(s) — run "
                    "`python -m dcgan_tpu.analysis --protocol`")
            raise ValueError(
                f"unknown check ID(s) {unknown}; valid: {sorted(registry)}"
                f" (AST tier) + {list(SEMANTIC_CHECKS)} (--semantic) + "
                f"{list(PROTOCOL_CHECKS)} (--protocol)")
    by_path = {sf.path: sf for sf in sources}
    findings: List[Finding] = []
    for check_id in checks or sorted(registry):
        for f in registry[check_id](list(sources), config):
            sf = by_path.get(f.path)
            if sf is not None and sf.is_suppressed(f):
                if suppressed_out is not None:
                    suppressed_out.append(f)
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.check))
    return findings


AST_CHECK_IDS = ("DCG001", "DCG003", "DCG004", "DCG005", "DCG006",
                 "DCG013")

STALE_SUPPRESSION_CHECK = "DCG014"
STALE_BASELINE_CHECK = "DCG015"


def audit_stale_suppressions(sources: Sequence[SourceFile],
                             suppressed: Sequence[Finding]
                             ) -> List[Finding]:
    """DCG014: `# dcg: disable=DCGxxx` comments that suppress no current
    finding are findings themselves — a dead suppression is an exemption
    with no exempted violation, and it would silently swallow the NEXT
    real finding landing on its line. Only sound after a FULL AST run
    (the drivers skip it under `--checks` subsets); IDs belonging to the
    semantic/protocol tiers can never match a line suppression (those
    findings have no source line) and are therefore always stale."""
    used = {(f.path, f.line, f.check) for f in suppressed}
    findings: List[Finding] = []
    for sf in sources:
        for line, ids in sorted(sf.suppressed.items()):
            for check_id in sorted(ids):
                if (sf.path, line, check_id) in used:
                    continue
                findings.append(Finding(
                    check=STALE_SUPPRESSION_CHECK, path=sf.path, line=line,
                    symbol="<suppression>", key=check_id,
                    message=(f"suppression `# dcg: disable={check_id}` "
                             "matches no current finding on this line — "
                             "delete it (a dead suppression would "
                             "silently swallow the next real finding "
                             "here)")))
    return findings


def audit_stale_baseline(entries: Sequence[Dict[str, str]],
                         consumed: Sequence[Finding],
                         ran_checks: Sequence[str],
                         baseline_rel_path: str
                         ) -> Tuple[List[Finding], List[Dict[str, str]]]:
    """DCG015: baseline rows whose fingerprint no longer matches any
    finding of a check that RAN this invocation. Returns (findings,
    stale entries) — `--prune-baseline` rewrites the file minus the
    latter. Rows of tiers that did not run are left alone (a per-tier
    invocation must not call another tier's exemptions dead). Stale-audit
    findings are deliberately NOT baselinable — the fix is deleting the
    row, never exempting the exemption."""
    import collections

    ran = set(ran_checks)
    budget = collections.Counter(f.fingerprint() for f in consumed)
    findings: List[Finding] = []
    stale: List[Dict[str, str]] = []
    for e in entries:
        if e["check"] not in ran:
            continue
        fp = (e["check"], e["path"], e["symbol"], e["key"])
        if budget[fp] > 0:
            budget[fp] -= 1
            continue
        stale.append(e)
        findings.append(Finding(
            check=STALE_BASELINE_CHECK, path=baseline_rel_path,
            line=int(e.get("_line", 0)), symbol=e["symbol"],
            key=f"{e['check']}:{e['key']}",
            message=(f"baseline row ({e['check']}, {e['path']}, "
                     f"{e['symbol']}, {e['key']}) matches no current "
                     "finding — the exemption is dead; delete the row "
                     "or run --prune-baseline")))
    return findings, stale


def prune_baseline_file(path: str,
                        stale: Sequence[Dict[str, str]]) -> int:
    """Rewrite the baseline minus the given stale rows (matched by their
    load-time line numbers); comment/header lines survive. Returns the
    number of rows dropped."""
    dead_lines = {int(e["_line"]) for e in stale if "_line" in e}
    if not dead_lines:
        return 0
    with open(path, encoding="utf-8") as f:
        lines = f.readlines()
    kept = [line for i, line in enumerate(lines, start=1)
            if i not in dead_lines]
    with open(path, "w", encoding="utf-8") as f:
        f.writelines(kept)
    return len(lines) - len(kept)


def default_root() -> str:
    """The repo root (parent of the dcgan_tpu package directory)."""
    import dcgan_tpu

    return os.path.dirname(os.path.dirname(
        os.path.abspath(dcgan_tpu.__file__)))


def default_baseline_path() -> str:
    return os.path.join(default_root(), "dcgan_tpu", "analysis",
                        "baseline.jsonl")
