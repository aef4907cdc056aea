"""THE consolidated analyzer pin, in two cases, one a file so that two
workers share it: `python -m dcgan_tpu.analysis --all --backend
<b>` must run every tier CLEAN — zero non-baselined findings across
DCG001-015 — AND regenerate its backend's share of the committed program
manifest (analysis/programs.lock.jsonl) and the whole protocol lock
(analysis/protocol.lock.jsonl) byte-identically. This file holds the
gspmd case (with the serving rows) and the shared body;
tests/test_tools_analysis_all_shard_map.py the shard_map case. Between
them they regenerate every manifest row, and the byte compares against
the committed files ARE the drift checks at full strength.

`--write-manifest/--write-lock <tmp>` recompute the rows (exit code still
gated on the non-drift findings). The CLI arranges its own canonical
topology (CPU, 2 virtual devices) before jax initializes, so the pin is
environment-stable. Per-tier flags keep working and are covered
in-process (tests/test_analysis.py, tests/test_protocol.py) plus the
dedicated --protocol subprocess pin below. The gspmd case is the first
of this file's three tests: with two more queued behind it, its xdist
worker (`--dist loadfile`) takes no other file until it ends, so the
shard_map case cannot queue behind it.
"""

import json
import os
import re

import pytest

from tool_smoke import REPO, json_rows, run_tool

LOCKS = os.path.join(REPO, "dcgan_tpu", "analysis")

#: the manifest rows each `--backend` share regenerates, by name prefix
SHARE_ROWS = {"gspmd": ("gspmd::", "serve::", "coordination::"),
              "shard_map": ("shard_map::", "coordination::")}


def committed_manifest():
    """The committed programs.lock.jsonl: (header lines, {name: row line})."""
    with open(os.path.join(LOCKS, "programs.lock.jsonl"), "rb") as f:
        lines = f.readlines()
    return ([line for line in lines if line.startswith(b"#")],
            {json.loads(line)["name"]: line for line in lines
             if not line.startswith(b"#")})


def committed_share(backend):
    """The committed programs.lock.jsonl as the `backend` share writes it:
    the header and that share's rows, in the file's order."""
    header, rows = committed_manifest()
    return b"".join(header + [line for name, line in rows.items()
                              if name.startswith(SHARE_ROWS[backend])])


def check_all_tiers(backend, tmp_path, *, timeout):
    expected = committed_share(backend)
    out_manifest = str(tmp_path / "programs.lock.jsonl")
    out_lock = str(tmp_path / "protocol.lock.jsonl")
    res = run_tool(["-m", "dcgan_tpu.analysis", "--all", "--json",
                    "--backend", backend, "--write-manifest", out_manifest,
                    "--write-lock", out_lock], timeout=timeout)
    assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-800:])
    summary = json_rows(res.stdout)[-1]
    assert summary["label"] == "dcgan-analysis-all"
    assert summary["new_findings"] == 0
    tiers = summary["tiers"]
    # per-tier timing is part of the contract: a tier that silently
    # stopped running would report no timing row
    assert set(tiers) == {"ast", "semantic", "protocol"}
    assert all(t["ms"] > 0 for t in tiers.values())
    assert tiers["ast"]["files"] > 50
    assert tiers["semantic"]["programs"] == sum(
        not line.startswith(b"#") for line in expected.splitlines())
    # the protocol lattice really explored (>= 4 configs x >= 6
    # interleavings); the stderr line makes silent shrinkage visible in
    # CI logs
    assert tiers["protocol"]["configs"] >= 4
    assert tiers["protocol"]["interleavings"] >= 24
    assert "explored" in res.stderr and "interleaving" in res.stderr
    with open(os.path.join(LOCKS, "protocol.lock.jsonl"), "rb") as f:
        committed_lock = f.read()
    for out, committed, what in (
            (out_manifest, expected, f"{backend} rows of programs.lock.jsonl"),
            (out_lock, committed_lock, "protocol.lock.jsonl")):
        with open(out, "rb") as f_new:
            assert f_new.read() == committed, (
                f"regenerated {what} differs from the committed file "
                "— either the contract drifted (regenerate "
                "deliberately and review the diff) or determinism "
                "broke")


class TestAnalysisAllSmoke:
    @pytest.mark.parametrize("backend", ["gspmd"])
    def test_all_tiers_clean_and_locks_reproducible_within_budget(
            self, backend, tmp_path):
        # 123.7 s alone, 154.4-238.0 s under six workers
        check_all_tiers(backend, tmp_path, timeout=480)


class TestProtocolAnalysisSmoke:
    """The protocol tier's dedicated pin: `--protocol --json` alone must
    run clean (the simulator is pure host code), and must PRINT the
    explored-interleaving counts so lattice shrinkage can never be silent
    in logs."""

    def test_protocol_clean_within_budget(self):
        # 6.1 s alone, 6.7-8.3 s under six workers
        res = run_tool(["-m", "dcgan_tpu.analysis", "--protocol", "--json"],
                       timeout=60)
        assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-800:])
        summary = json_rows(res.stdout)[-1]
        assert summary["label"] == "dcgan-analysis-protocol"
        assert summary["new_findings"] == 0
        assert summary["configs"] >= 4
        assert summary["interleavings"] >= 24
        m = re.search(r"explored (\d+) interleaving\(s\) across (\d+) "
                      r"knob config\(s\)", res.stderr)
        assert m, f"no interleaving-count line in stderr: {res.stderr}"
        assert int(m.group(1)) == summary["interleavings"]


class TestAnalysisShares:
    def test_every_committed_row_is_in_a_share(self):
        """The two `--backend` cases regenerate every manifest row
        between them: a row no share holds would go unchecked."""
        every_share = tuple(p for ps in SHARE_ROWS.values() for p in ps)
        _, rows = committed_manifest()
        assert rows
        unshared = [n for n in rows if not n.startswith(every_share)]
        assert not unshared, f"rows no --backend share regenerates: {unshared}"
