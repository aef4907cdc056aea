"""chip_smoke.py's no-fallback rule, as far as a machine without a chip can
show it: on the CPU backend the script refuses to run a single phase. (That
it passes on the chip is proven by running it there — CHANGES.md records
the runs.)"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*argv, cwd):
    return subprocess.run(
        [sys.executable, SMOKE, *argv], cwd=cwd, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_refuses_the_cpu_backend(tmp_path):
    res = _run("--out", str(tmp_path / "out"), cwd=str(tmp_path))
    assert res.returncode != 0
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    # refused before any phase: nothing was built, written or compiled
    assert not (tmp_path / "out").exists()
    assert '"phase"' not in res.stdout


def test_help_lists_the_options(tmp_path):
    res = _run("--help", cwd=str(tmp_path))
    assert res.returncode == 0
    assert "--chips" in res.stdout and "--out" in res.stdout
