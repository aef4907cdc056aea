"""The comparison that decides `correct`.

A training cell's first three steps run through the window's own call and
feed; the plain reference follows the first two from the same weights,
batches and keys. A run compares the numbers that `limits/<cell>.json`
gives a limit, each against its own (how each limit was set, and why the
loss gaps have none: PERF.md section 2):

- `grad_err`: the relative error of the first step's gradient VECTOR as the
  optimizer got it (from Adam's first moment after one step), by the median
  leaf: the norm of the difference between the program's leaf and the
  reference's, against the reference's norm of that leaf or of the median
  leaf, whichever is larger. Rounding errors of a lower operand precision
  all but cancel in a leaf's norm and show here in full: this is the number
  that the control (the reference computed in fp8) has to fail.
  `grad_err_worst` is the same by the worst leaf;
- `stat_err`: the same relative error, by the worst leaf, of the first
  step's change of the model state beside the weights: the batch-norm
  moving moments of every normalized layer of G and D (and the spectral
  norm's vectors). These are forward quantities, averaged over batch and
  space, so operand rounding moves them in proportion and nothing of the
  backward pass's cancellation amplifies it; `stat_err_med` by the median
  leaf;
- `grad_gap`: the first step's gradient as the optimizer got it (from Adam's
  second moment after one step), by the worst leaf: the gap between the
  program's norm and the reference's, against the reference's norm of that
  leaf or of the median leaf, whichever is larger;
- `delta_gap`: the parameters' change over the two steps, by the worst leaf
  in the same measure; leaves whose reference gradient is under a
  thousandth of the median leaf's move under Adam by round-off alone and
  are left out, by that rule and not by name;
- `loss_gap`: the first step's d and g loss, |program - reference| as a
  share of max(|reference|, 1); `loss2_gap`: the same of the later step;
- `feed_gap` (fed cells): the widest absolute gap between a delivered row
  and the record it claims to be, over the batches compared;
- `replica_gap` (cells over several chips): the widest relative gap between
  the chips' copies of the parameters after the window, by fingerprints.

A limit whose number is missing or not finite fails. `training_numbers`
works out all of them for `benchmark/readings.py`; a number with no limit in
the cell's file takes no part in a run.
"""

from __future__ import annotations

import json
import math
import statistics
import sys
from typing import Dict, Iterable, List

NOUGHT_GRAD = 1e-3   # of the median leaf's gradient norm
GRADIENT_NUMBERS = {"grad_err", "grad_err_worst"}   # need the gradient itself


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leave_out: Iterable[str] = ()) -> float:
    skip = set(leave_out)
    names = [n for n in ref if n not in skip]
    if not names or set(names) - set(prog):
        return math.inf
    median = statistics.median(ref[n] for n in names)
    worst = 0.0
    for n in names:
        denom = max(ref[n], median)
        gap = abs(prog[n] - ref[n]) / denom if denom > 0 else math.inf
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def leaf_errors(diff: Dict[str, float], ref: Dict[str, float]
                ) -> List[float]:
    """Per leaf: the norm of (program - reference) against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    if not ref or set(ref) - set(diff):
        return [math.inf]
    median = statistics.median(ref.values())
    errs = [diff[n] / max(ref[n], median) if max(ref[n], median) > 0
            else math.inf for n in ref]
    return [e if math.isfinite(e) else math.inf for e in errs]


def nought_leaves(ref_grad: Dict[str, float]) -> List[str]:
    median = statistics.median(ref_grad.values())
    return [n for n, g in ref_grad.items() if g < NOUGHT_GRAD * median]


def loss_gap(prog: List[Dict[str, float]], ref: List[Dict[str, float]],
             steps: slice = slice(None)) -> float:
    worst = 0.0
    for p, r in zip(prog[steps], ref[steps]):
        for name in ("d_loss", "g_loss"):
            gap = abs(p[name] - r[name]) / max(abs(r[name]), 1.0)
            if not math.isfinite(gap):
                return math.inf
            worst = max(worst, gap)
    return worst if len(prog) == len(ref) and ref[steps] else math.inf


def training_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """`prog` and `ref`: {"losses": [per step], "grad": {leaf: norm},
    "delta": {leaf: norm}, "stat": {leaf: norm}} as the driver and the
    reference read them; `prog` also {"grad_diff", "stat_diff": {leaf: norm
    of its gradient (state change) less the reference's}}, the gradient's
    only where it was kept."""
    stat_errs = leaf_errors(prog["stat_diff"], ref["stat"])
    out = {"stat_err": max(stat_errs),
           "stat_err_med": statistics.median(stat_errs)}
    if "grad_diff" in prog:
        errs = leaf_errors(prog["grad_diff"], ref["grad"])
        out.update(grad_err=statistics.median(errs), grad_err_worst=max(errs))
    return {
        **out,
        "loss_gap": loss_gap(prog["losses"], ref["losses"], slice(0, 1)),
        "loss2_gap": loss_gap(prog["losses"], ref["losses"], slice(1, None)),
        "grad_gap": worst_leaf_gap(prog["grad"], ref["grad"]),
        "delta_gap": worst_leaf_gap(prog["delta"], ref["delta"],
                                    leave_out=nought_leaves(ref["grad"])),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """{"correct": bool, "compared": {name: {"value", "limit"}}} over the
    numbers that have a limit; every limit must find its number."""
    compared = {}
    ok = True
    for name, limit in sorted(limits.items()):
        value = numbers.get(name)
        good = value is not None and math.isfinite(value)
        ok = ok and good and value <= limit
        compared[name] = {"value": value if good else None, "limit": limit}
    return {"correct": bool(ok and limits), "compared": compared}


def print_compared(verdict: dict, file=sys.stderr) -> None:
    """Each number compared beside its limit, as the run's last lines on
    standard error."""
    for name, c in verdict["compared"].items():
        print(f"check {name} value={json.dumps(c['value'])} "
              f"limit={json.dumps(c['limit'])}", file=file)
    print(f"check correct={json.dumps(verdict['correct'])}", file=file,
          flush=True)
