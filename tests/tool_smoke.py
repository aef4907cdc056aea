"""Shared plumbing of the subprocess smokes (tests/test_tools_*.py): run one
repo tool from the repo root on the CPU backend and read the JSON rows it
prints.

Each smoke has ONE time limit, the `timeout` it passes here. The child
leads a process group of its own, and a timeout kills the whole group —
the trainer or serve processes a drill started included — so a hung child
fails its own test and leaves nothing running behind it.
"""

import json
import os
import signal
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_tool(args, *, timeout, **env):
    """`python <args>` from the repo root with JAX_PLATFORMS=cpu (plus any
    `env` overrides); a CompletedProcess with text stdout/stderr."""
    proc = subprocess.Popen(
        [sys.executable, *args], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:  # the whole group ended meanwhile
            pass
        proc.communicate()
        raise
    return subprocess.CompletedProcess(proc.args, proc.returncode, out, err)


def json_rows(stdout):
    """Every stdout line that is a JSON object, in order."""
    return [json.loads(line) for line in stdout.splitlines()
            if line.startswith("{")]
