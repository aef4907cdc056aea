"""DCG003/005/006: the smaller mechanical contracts.

- **DCG003** — raw shard_map references (the `shard_map` attribute on
  `jax` or `jax.experimental`, or imports of the experimental module)
  outside `utils/backend.py`. Every call site routes through
  `utils/backend.shard_map`, so the replication check is switched in one
  place under one keyword (`check`). Docstrings are checked too (for the
  literal raw-API name) — a doc that names the raw API is how the next
  call site gets written against it.

- **DCG005** — traced-body hygiene: wall-clock (`time.time`,
  `datetime.now`, ...) and host RNG (`random.*`, `np.random.*`) calls
  inside functions that are jitted / shard_mapped / pallas_called. Traced
  code runs ONCE at trace time; a clock or host-RNG call bakes one
  arbitrary value into the compiled program — and with the persistent
  compile cache it also poisons reproducibility across restarts.
  Detection covers decorator jits and functions passed by name (or as
  lambdas) to `jax.jit` / `shard_map` / `smap` / `pallas_call`; values
  jitted through intermediate namespaces (e.g. `jax.jit(fns.train_step)`)
  are out of static reach and covered by the parity suites.

- **DCG006** — mutating filesystem IO in the retry-scoped modules
  (services/checkpoint/metrics paths) that is neither wrapped in
  `utils/retry.retry_io` nor explicitly fenced by a `try/except OSError`.
  One transient NFS hiccup must not kill a multi-hour run (DESIGN.md
  §6c); reads are exempt (they are either retried by their callers or
  best-effort by design), as is anything lexically inside a callable
  handed to `retry_io`.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Sequence, Set

from dcgan_tpu.analysis.core import (
    Config,
    Finding,
    SourceFile,
    call_name,
    dotted,
    iter_calls,
    lexical_def,
)


# -- DCG003 ------------------------------------------------------------------

def check_raw_shard_map(sources: Sequence[SourceFile],
                        config: Config) -> List[Finding]:
    findings: List[Finding] = []
    for sf in sources:
        if sf.path in config.shard_map_exempt:
            continue
        for node in ast.walk(sf.tree):
            chain = None
            if isinstance(node, ast.Attribute):
                chain = dotted(node)
            if chain in ("jax.shard_map", "jax.experimental.shard_map") or \
                    (chain or "").endswith("experimental.shard_map"):
                findings.append(_sm_finding(sf, node, chain))
            elif isinstance(node, ast.ImportFrom) and node.module and (
                    node.module == "jax.experimental.shard_map"
                    or (node.module == "jax.experimental"
                        and any(a.name == "shard_map"
                                for a in node.names))):
                findings.append(_sm_finding(sf, node, node.module))
            elif isinstance(node, ast.Import) and any(
                    a.name.startswith("jax.experimental.shard_map")
                    for a in node.names):
                findings.append(_sm_finding(
                    sf, node, "jax.experimental.shard_map"))
            elif isinstance(node, (ast.Module, ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.ClassDef)):
                doc = ast.get_docstring(node, clean=False)
                if doc and "jax.shard_map" in doc:
                    line = node.body[0].lineno if node.body else 1
                    findings.append(Finding(
                        check="DCG003", path=sf.path, line=line,
                        symbol=sf.enclosing_symbol(node.body[0])
                        if node.body else "<module>",
                        key="docstring:jax.shard_map",
                        message=(
                            "docstring names raw `jax.shard_map` — name "
                            "utils/backend.shard_map, the one call site, "
                            "so the next caller is written against "
                            "it")))
    return findings


def _sm_finding(sf: SourceFile, node: ast.AST, chain: Optional[str]
                ) -> Finding:
    return Finding(
        check="DCG003", path=sf.path, line=node.lineno,
        symbol=sf.enclosing_symbol(node), key=chain or "shard_map",
        message=(f"raw {chain!r} reference outside utils/backend.py — "
                 "route through utils/backend.shard_map, the one call "
                 "site that switches the replication check"))


# -- DCG005 ------------------------------------------------------------------

_JIT_CALLERS = frozenset({"jit", "shard_map", "smap", "pallas_call"})

_TIME_ATTRS = frozenset({"time", "monotonic", "perf_counter", "time_ns",
                         "process_time", "monotonic_ns"})
_RANDOM_ATTRS = frozenset({"random", "randint", "uniform", "randrange",
                           "choice", "choices", "shuffle", "sample",
                           "gauss", "normalvariate", "getrandbits",
                           "Random", "rand", "randn", "normal",
                           "permutation", "default_rng", "seed"})


def _banned_call(call: ast.Call, sf: Optional[SourceFile] = None
                 ) -> Optional[str]:
    name, receiver = call_name(call)
    if name is None:
        return None
    if receiver == "" and sf is not None and name in sf.from_imports:
        # `from time import time; time()` is still time.time
        receiver, name = sf.from_imports[name]
    segments = receiver.split(".") if receiver else []
    if receiver == "time" and name in _TIME_ATTRS:
        return f"time.{name}"
    if name in ("now", "utcnow", "today") and segments and \
            segments[-1] in ("datetime", "date"):
        return f"{receiver}.{name}"
    if segments and segments[0] in ("np", "numpy") and \
            segments[-1] == "random":
        return f"{receiver}.{name}"
    if receiver == "random" and name in _RANDOM_ATTRS:
        return f"random.{name}"
    return None


def _is_jit_decorator(dec: ast.AST) -> bool:
    chain = dotted(dec)
    if chain in ("jit", "jax.jit"):
        return True
    if isinstance(dec, ast.Call):
        name, receiver = call_name(dec)
        if name == "jit":
            return True
        if name == "partial" and dec.args:
            return dotted(dec.args[0]) in ("jit", "jax.jit")
    return False


def _traced_nodes(sf: SourceFile) -> List[ast.AST]:
    """Function/lambda nodes whose bodies run under a trace."""
    traced: List[ast.AST] = []
    # decorator form
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                any(_is_jit_decorator(d) for d in node.decorator_list):
            traced.append(node)
    # passed-by-name / inline-lambda form
    for call in iter_calls(sf.tree):
        name, _ = call_name(call)
        if name not in _JIT_CALLERS or not call.args:
            continue
        arg = call.args[0]
        if isinstance(arg, ast.Lambda):
            traced.append(arg)
        elif isinstance(arg, ast.Name):
            node = lexical_def(sf, call, arg.id)
            if node is not None:
                traced.append(node)
    return traced




def check_traced_body_hygiene(sources: Sequence[SourceFile],
                              config: Config) -> List[Finding]:
    findings: List[Finding] = []
    for sf in sources:
        seen: Set[int] = set()
        for fn in _traced_nodes(sf):
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            fn_name = getattr(fn, "name", "<lambda>")
            for call in iter_calls(fn):
                banned = _banned_call(call, sf)
                if banned is None:
                    continue
                findings.append(Finding(
                    check="DCG005", path=sf.path, line=call.lineno,
                    symbol=sf.enclosing_symbol(call),
                    key=f"{fn_name}:{banned}",
                    message=(
                        f"{banned}() inside traced body {fn_name!r}: "
                        "traced code runs once at trace time, so the "
                        "value is baked into the compiled program (and "
                        "cached across restarts by the persistent "
                        "compile cache) — pass timestamps in as "
                        "arguments and draw randomness from the jax PRNG "
                        "key stream")))
    return findings


# -- DCG006 ------------------------------------------------------------------

_FS_MUTATORS = {
    ("replace", "os"), ("rename", "os"), ("remove", "os"),
    ("unlink", "os"), ("makedirs", "os"), ("mkdir", "os"),
    ("rmtree", "shutil"), ("truncate", "os"),
}
_CATCHING = frozenset({"OSError", "IOError", "EnvironmentError",
                       "FileNotFoundError", "PermissionError",
                       "Exception", "BaseException"})


def _is_write_open(call: ast.Call) -> bool:
    name, receiver = call_name(call)
    if name != "open" or receiver not in ("", "io"):
        return False
    mode = None
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
        mode = call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = kw.value.value
    if not isinstance(mode, str):
        return False  # default "r": a read
    return any(c in mode for c in "wax+")


def _mutator(call: ast.Call, sf: Optional[SourceFile] = None
             ) -> Optional[str]:
    name, receiver = call_name(call)
    if name is None:
        return None
    if receiver == "" and sf is not None and name in sf.from_imports:
        # `from os import replace; replace(...)` is still os.replace
        receiver, name = sf.from_imports[name]
    head = receiver.split(".")[0] if receiver else ""
    for mname, mmod in _FS_MUTATORS:
        if name == mname and head == mmod:
            return f"{receiver}.{name}"
    if _is_write_open(call):
        return "open(w)"
    return None


def _retry_protected_nodes(sf: SourceFile) -> Set[int]:
    """ids of def/lambda nodes passed (by name or inline) to retry_io."""
    protected: Set[int] = set()
    for call in iter_calls(sf.tree):
        name, _ = call_name(call)
        if name != "retry_io" or not call.args:
            continue
        arg = call.args[0]
        if isinstance(arg, ast.Lambda):
            protected.add(id(arg))
        elif isinstance(arg, ast.Name):
            node = lexical_def(sf, call, arg.id)
            if node is not None:
                protected.add(id(node))
    return protected


def _fenced(sf: SourceFile, node: ast.AST, protected: Set[int]) -> bool:
    """Inside a retry_io-protected callable, or a try/except that catches
    OSError (or broader)?"""
    cur: Optional[ast.AST] = node
    prev = node
    while cur is not None:
        if id(cur) in protected:
            return True
        if isinstance(cur, ast.Try) and prev in cur.body:
            for handler in cur.handlers:
                if handler.type is None:
                    return True
                types = handler.type.elts \
                    if isinstance(handler.type, ast.Tuple) \
                    else [handler.type]
                for t in types:
                    chain = dotted(t) or ""
                    if chain.split(".")[-1] in _CATCHING:
                        return True
        if isinstance(cur, ast.stmt) or isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            prev = cur
        cur = sf.parents.get(cur)
    return False


def check_bare_io(sources: Sequence[SourceFile],
                  config: Config) -> List[Finding]:
    findings: List[Finding] = []
    for sf in sources:
        if sf.path not in config.io_modules:
            continue
        protected = _retry_protected_nodes(sf)
        for call in iter_calls(sf.tree):
            what = _mutator(call, sf)
            if what is None:
                continue
            if _fenced(sf, call, protected):
                continue
            findings.append(Finding(
                check="DCG006", path=sf.path, line=call.lineno,
                symbol=sf.enclosing_symbol(call), key=what,
                message=(
                    f"bare {what} in a retry-scoped module: one "
                    "transient OSError here kills the run — wrap the "
                    "operation in utils/retry.retry_io (bounded jittered "
                    "backoff) or fence it with an explicit try/except "
                    "OSError if best-effort is the intent")))
    return findings
