"""Invariant analyzer: AST lint, runtime tripwire, semantic + protocol tiers.

The contracts that keep a dozen PRs of concurrency, donation, parity,
and coordination machinery correct live here as executable checks
instead of docstring folklore. Three static tiers plus a runtime
tripwire:

AST tier (ISSUE 8 — no imports of the code under analysis, milliseconds):

    DCG001  collectives only on the dispatch thread   analysis/threads.py
    DCG003  shard_map only via utils/backend          analysis/hygiene.py
    DCG004  event keys declared + gated (parity)      analysis/parity.py
    DCG005  no wall-clock/host-RNG in traced bodies   analysis/hygiene.py
    DCG006  retry-wrapped IO in services/checkpoint   analysis/hygiene.py
    DCG013  no host-local branch into a collective    analysis/protocol.py
    DCG014  stale `# dcg: disable` suppressions       analysis/core.py
    DCG015  stale baseline rows (--prune-baseline)    analysis/core.py

Semantic tier (ISSUE 11 — imports, builds, and `.lower()`s every program
the repo can dispatch on a canonical CPU topology; `--semantic`):

    DCG007  donation realized as input_output_aliases analysis/semantic.py
    DCG008  collective census + program manifest      analysis/semantic.py
    DCG009  retrace hazards + warmup-plan coverage    analysis/semantic.py
    DCG010  traced-body hygiene (callbacks/f64/...)   analysis/semantic.py
    DCG011  sharding-rule coverage + grad-spec parity analysis/semantic.py

Protocol tier (ISSUE 14 — N virtual processes through the REAL
coordination decision code over the knob x one-shot-fault lattice;
`--protocol`):

    DCG012  lockstep audit: termination + identical   analysis/protocol.py
            per-process collective schedules vs the   analysis/simulate.py
            committed analysis/protocol.lock.jsonl

Surface: `python -m dcgan_tpu.analysis [--semantic|--protocol|--all]
[--json] [--baseline FILE] [--prune-baseline] [paths...]` — exit 1 on
any non-baselined finding; `--all` runs the three tiers with per-tier
timing under one exit code (the consolidated tier-1 pin). Per-line
suppression (AST tier, real comment tokens only): `# dcg:
disable=DCG005`. Committed exemptions (all tiers):
analysis/baseline.jsonl (every entry carries a `why`). Committed
contracts: analysis/programs.lock.jsonl (`--semantic --write-manifest`)
and analysis/protocol.lock.jsonl (`--protocol --write-lock`) — any
unexplained drift is a DCG008/DCG012 finding. The runtime halves are
analysis/tripwire.py (`DCGAN_THREAD_CHECKS=1`, armed across tier-1 by
tests/conftest.py) and the chaos drill's protocol replay
(`DCGAN_PROTOCOL_LOG`: the live mh-sigterm-stop collective sequence must
equal the committed simulator schedule). See docs/DESIGN.md §7b/§7c/§7d
for the invariant catalog.
"""

from dcgan_tpu.analysis.core import (  # noqa: F401
    Config,
    Finding,
    SourceFile,
    collect_sources,
    default_baseline_path,
    default_root,
    load_baseline,
    run_checks,
    split_baselined,
)
