"""The committed program manifest: programs.lock.jsonl (ISSUE 11).

The semantic tier's output is a *contract*, not a report: one JSONL row
per program the repo can dispatch — name, call-shape signature, jaxpr
fingerprint, explicit-collective census, donation map — committed next to
the baseline and regenerated only deliberately
(`python -m dcgan_tpu.analysis --semantic --write-manifest`). A check run
recomputes every row on the canonical CPU topology and reports any
difference as findings (DCG008), so the §6c.1 dispatch-stream table, the
donation-aliasing story, and the program inventory can no longer drift
from the code without failing tier-1.

Byte-identity is part of the contract (tests/test_tools_analysis_all*.py
pin it): rows are sorted by name, keys are sorted, floats never appear,
and the header carries no timestamps — regenerating an unchanged repo
reproduces the file exactly.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Sequence

from dcgan_tpu.analysis.core import Finding

#: manifest rows are either lowered jit programs ("program") or the
#: host-side coordination transports ("transport") declared in
#: train/coordination.py::TRANSPORT_CENSUS — process_allgather is opaque
#: to `.lower()`, so its census is declared next to the transport code and
#: cross-checked against the live module by the semantic tier.
KINDS = ("program", "transport")

_HEADER = (
    "# Program manifest (ISSUE 11): every program the repo can dispatch,",
    "# lowered on the canonical topology (CPU, 2-device 'data' mesh, small",
    "# preset, partitionable threefry) — name -> call shapes -> jaxpr",
    "# fingerprint -> explicit-collective census -> donation map. DO NOT",
    "# EDIT BY HAND: regenerate with",
    "#   python -m dcgan_tpu.analysis --semantic --write-manifest",
    "# A check run (`--semantic`) recomputes every row and reports any",
    "# difference as DCG008 findings; unexplained drift fails tier-1.",
)


@dataclasses.dataclass(frozen=True)
class ProgramRecord:
    """One manifest row. `collectives` counts explicit jaxpr collective
    primitives only — GSPMD-backend programs legitimately census 0 because
    the partitioner inserts their collectives at compile time (the census
    is the *hand-written* collective stream, which is exactly the part
    that can silently drift). `donation` is None for non-donating
    programs, else {donated, aliased, pruned, unaliased:[leaf labels]}
    from the compiled executable's input_output_alias map. `cadence` is
    non-empty only for rows that appear in DESIGN §6c.1's dispatch-stream
    table (when this program/transport runs at default knobs)."""

    name: str
    kind: str
    path: str
    args: tuple            # per-argument short signature strings
    fingerprint: str       # sha256[:16] of the traced jaxpr text
    collectives: Dict[str, int]
    donation: Optional[Dict[str, object]] = None
    cadence: str = ""

    def to_json(self) -> Dict[str, object]:
        return {
            "name": self.name, "kind": self.kind, "path": self.path,
            "args": list(self.args), "fingerprint": self.fingerprint,
            "collectives": dict(sorted(self.collectives.items())),
            "donation": self.donation, "cadence": self.cadence,
        }

    @classmethod
    def from_json(cls, obj: Dict[str, object]) -> "ProgramRecord":
        return cls(name=str(obj["name"]), kind=str(obj["kind"]),
                   path=str(obj["path"]), args=tuple(obj["args"]),
                   fingerprint=str(obj["fingerprint"]),
                   collectives={str(k): int(v) for k, v in
                                dict(obj["collectives"]).items()},
                   donation=obj.get("donation"),
                   cadence=str(obj.get("cadence", "")))


def dumps(records: Sequence[ProgramRecord]) -> str:
    """Serialize to the committed JSONL form — deterministic by
    construction (sorted rows, sorted keys, no timestamps)."""
    lines = list(_HEADER)
    for rec in sorted(records, key=lambda r: r.name):
        lines.append(json.dumps(rec.to_json(), sort_keys=True,
                                separators=(",", ":")))
    return "\n".join(lines) + "\n"


def loads(text: str, origin: str = "<manifest>") -> List[ProgramRecord]:
    records: List[ProgramRecord] = []
    for i, line in enumerate(text.splitlines(), start=1):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            records.append(ProgramRecord.from_json(json.loads(line)))
        except (ValueError, KeyError, TypeError) as e:
            raise ValueError(
                f"{origin}:{i}: unparseable manifest row: {e}") from e
    return records


def load_path(path: str) -> List[ProgramRecord]:
    with open(path, encoding="utf-8") as f:
        return loads(f.read(), origin=path)


def default_manifest_path() -> str:
    from dcgan_tpu.analysis.core import default_root

    return os.path.join(default_root(), "dcgan_tpu", "analysis",
                        "programs.lock.jsonl")


def _census_str(collectives: Dict[str, int]) -> str:
    if not collectives:
        return "0 explicit"
    return ", ".join(f"{op} ×{n}"
                     for op, n in sorted(collectives.items()))


def diff(live: Sequence[ProgramRecord],
         committed: Sequence[ProgramRecord]) -> List[Finding]:
    """Live recomputation vs the committed manifest -> DCG008 findings.

    Every difference is a finding: a vanished or new program, a changed
    jaxpr fingerprint, a changed collective census, a changed donation
    map or call signature. The message always names the escape hatch —
    regenerate the manifest if the drift is intentional — because the
    point is *unexplained* drift, not frozen code.
    """
    regen = ("regenerate with `python -m dcgan_tpu.analysis --semantic "
             "--write-manifest` if intentional")
    by_live = {r.name: r for r in live}
    by_committed = {r.name: r for r in committed}
    findings: List[Finding] = []

    def _f(rec: ProgramRecord, key: str, message: str) -> None:
        findings.append(Finding(
            check="DCG008", path=rec.path, line=0, symbol=rec.name,
            key=key, message=message))

    for name in sorted(set(by_committed) - set(by_live)):
        rec = by_committed[name]
        _f(rec, f"missing:{name}",
           f"program {name!r} is in the committed manifest but the live "
           f"enumeration no longer produces it — {regen}")
    for name in sorted(set(by_live) - set(by_committed)):
        rec = by_live[name]
        _f(rec, f"uncommitted:{name}",
           f"program {name!r} is dispatchable but absent from the "
           f"committed manifest — {regen}")
    for name in sorted(set(by_live) & set(by_committed)):
        a, b = by_live[name], by_committed[name]
        if a.collectives != b.collectives:
            _f(a, f"census:{name}",
               f"collective census of {name!r} drifted: live "
               f"[{_census_str(a.collectives)}] vs committed "
               f"[{_census_str(b.collectives)}] — the §6c.1 dispatch "
               f"stream is a contract; {regen}")
        if a.donation != b.donation:
            _f(a, f"donation:{name}",
               f"donation map of {name!r} drifted: live {a.donation} vs "
               f"committed {b.donation} — {regen}")
        if a.args != b.args:
            _f(a, f"shapes:{name}",
               f"call shapes of {name!r} drifted: live {list(a.args)} vs "
               f"committed {list(b.args)} — {regen}")
        if a.fingerprint != b.fingerprint:
            _f(a, f"fingerprint:{name}",
               f"jaxpr fingerprint of {name!r} drifted "
               f"({b.fingerprint} -> {a.fingerprint}) — the traced "
               f"program changed; {regen}")
    return findings


#: markers delimiting the generated dispatch-stream table in DESIGN §6c.1;
#: tests/test_analysis.py pins the block between them to
#: `render_stream_table(load_path(default_manifest_path()))`, so the doc
#: cannot drift from the committed census.
STREAM_TABLE_BEGIN = "<!-- DCG008:stream-table:begin (generated) -->"
STREAM_TABLE_END = "<!-- DCG008:stream-table:end -->"


def render_stream_table(records: Sequence[ProgramRecord]) -> str:
    """The §6c.1 default-knob collective dispatch stream as a markdown
    table, generated from manifest rows that carry a cadence. Regenerate
    via `python -m dcgan_tpu.analysis --semantic --stream-table`."""
    rows = sorted((r for r in records if r.cadence),
                  key=lambda r: (r.kind != "transport", r.name))
    lines = [
        "| program | explicit collectives (jaxpr census) | dispatched |",
        "|---------|-------------------------------------|------------|",
    ]
    for r in rows:
        census = _census_str(r.collectives)
        if r.kind == "transport":
            census += " (host transport)"
        lines.append(f"| `{r.name}` | {census} | {r.cadence} |")
    return "\n".join(lines)
