"""Summarize a jax.profiler trace: device program durations per step.

The tracing subsystem (utils/profiling.py::TraceCapture, wired into the
trainer as --profile_dir/--profile_start_step/--profile_num_steps, plus the
on-demand --profile_trigger file) captures a Chrome-trace timeline of the
training loop. This tool reads the `*.trace.json.gz` it writes and reports,
for each device-track program, the execution count and per-execution
duration — the device's OWN measurement of step time, independent of every
host-side wall-clock harness (bench.py and StepTimer
sync through the transport; the trace does not).

    python -m dcgan_tpu.train --synthetic --profile_dir /tmp/tr ...
    python tools/trace_summary.py /tmp/tr
    python tools/trace_summary.py docs/assets/trace_train_step_v5e.json.gz

The parser lives in dcgan_tpu/utils/trace.py (ISSUE 6) — the same code the
trainer uses to digest trigger-file captures in-process — so this tool and
the live perf/device/* events can never disagree about what a trace says.
CPU captures have no TPU-named process; the shared parser falls back to
the busiest XLA-executor (or non-python) thread track and this tool says
so on stderr instead of silently printing nothing (the pre-ISSUE-6
behavior). A trace with no duration events at all exits nonzero with a
usage hint.

The committed artifact docs/assets/trace_train_step_v5e.json.gz is a real
v5e capture of 5 per-step train_step dispatches: 2.8441-2.8458 ms each
(±0.06%), the cleanest confirmation of the headline step time
(DESIGN.md §1b). Note: that capture, taken on the previous machine, holds
PROGRAM-level device events only — no per-XLA-op rows; the per-operation
breakdown is `benchmark/run.py --trace 1`'s (PERF.md section 5).

Prints one JSON line per device program.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dcgan_tpu.utils.trace import find_trace  # noqa: E402
from dcgan_tpu.utils.trace import summarize as _summarize  # noqa: E402


def summarize(trace_path: str) -> list:
    """Per-program rows (back-compat shim over the shared parser)."""
    rows, _ = _summarize(trace_path)
    return rows


def main(argv=None) -> None:
    args = argv if argv is not None else sys.argv[1:]
    if len(args) != 1:
        print("usage: trace_summary.py <trace.json.gz | profile_dir>",
              file=sys.stderr)
        sys.exit(2)
    try:
        path = find_trace(args[0])
        rows, source = _summarize(path)
        if not rows:
            print(f"no duration events in {path} — capture one with "
                  "`python -m dcgan_tpu.train --profile_dir <dir>` (or "
                  "touch a --profile_trigger file mid-run) and point this "
                  "tool at the dir or the *.trace.json.gz",
                  file=sys.stderr)
            sys.exit(1)
        if source != "tpu":
            print(f"note: no TPU-named process in {path}; reporting the "
                  f"{source} track (CPU captures time host-side execution "
                  "— device numbers need a chip capture)", file=sys.stderr)
        for row in rows:
            print(json.dumps(row))
    except BrokenPipeError:  # e.g. piped into head
        sys.stderr.close()


if __name__ == "__main__":
    main()
