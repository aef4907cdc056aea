"""The `loop_lm` family: looped causal language models, one stack of layers
run `total_ut_steps` times on shared weights, an exit gate after each pass
and an expected loss over the exits, trained by a likelihood step
(`ouro-2.6b`).

Under the names `manifest.FAMILY_API` fixes: the yardstick (operations one
step needs, from the configuration's shapes, APPLICATIONS of a weight
counted, not parameters), the draw of a batch of ids and of each leaf, what
is read from the program's state after its first steps, the plain reference
that follows the same steps (`families/loop_lm_reference.py`, loaded by path
from beside this file), the numbers worked out from the two, and the
variants `readings.py` sets limits from. The readings that any token
family takes from Adam's moments, and the tree arithmetic, are the `mla_moe`
family's (loaded through `manifest.family`).

The numbers compared (PERF.md section 2):

- `loss_gap`: the first step's objective and each exit's cross-entropy,
  |program - reference| as a share of max(|reference|, 1): the forward
  pass of every pass;
- `exit_gap`: the per-exit mass `sum_i p_t(i)` the step accumulates in its
  state (`state["exit_mass"]`), after the first step: the sum over the
  exits of |program - reference| over the positions scored: the gate and
  the exit distribution;
- `grad_gap` (leaf norms of the first gradient, from Adam's `nu`),
  `delta_gap` (the parameters' change over two steps) and `grad_err` (the
  first gradient VECTOR, from `mu`, on a fixed strided sample of each
  leaf's coordinates, median leaf): the backward pass through four uses of
  every weight, in `check.py`'s measures.
"""

from __future__ import annotations

import gc
import json
import math
import os
import statistics
from typing import Any, Callable, Dict

import numpy as np

from benchmark import check, manifest

Tree = Dict[str, Any]

_HERE = os.path.dirname(os.path.abspath(__file__))
# the plain reference lies beside this file and is loaded by path, like
# every module of the benchmark (a temporary root loads its own copy)
reference = manifest._load_module(
    os.path.join(_HERE, "loop_lm_reference.py"),
    "bench_family_loop_lm_reference")
# the token family whose state readings and tree arithmetic this one shares
token = manifest.family(os.path.dirname(os.path.dirname(_HERE)),
                        {"family": "mla_moe"})


# --- the yardstick -------------------------------------------------------------

def _forward_ops(m: dict, seq_len: int) -> Dict[str, float]:
    """Operations of ONE sequence's forward pass (2 per multiply-add), by
    part: every layer applied `total_ut_steps` times, the head and the gate
    once per pass; causal scores as the lower triangle."""
    h, nh, d = m["hidden_size"], m["num_attention_heads"], m["head_dim"]
    uses = m["num_hidden_layers"] * m["total_ut_steps"]
    s = seq_len
    return {
        "qkvo": 2.0 * uses * s * 4 * h * nh * d,
        "scores": 2.0 * uses * nh * (s * (s + 1) // 2) * 2 * d,
        "ffn": 2.0 * uses * s * 3 * h * m["intermediate_size"],
        "heads": 2.0 * m["total_ut_steps"] * s * h * m["vocab_size"],
        "gate": 2.0 * m["total_ut_steps"] * s * h,
    }


def step_ops(config: dict, global_batch: int) -> Dict[str, float]:
    """Operations one train step needs: forward and backward (3 x forward),
    nothing recomputed counted (the per-block recomputation and the flash
    backward's rebuilt tiles are the program's choice). `total` is what
    `step_mfu` divides by the peak."""
    parts = {k: 3.0 * global_batch * v for k, v in
             _forward_ops(config["model"], config["seq_len"]).items()}
    return {**parts, "total": sum(parts.values())}


def kernel_costs(config: dict, batch: int) -> Dict[str, Dict[str, float]]:
    """{kernel: {"ops", "bytes"}}: `causal_flash`, read by
    `loop_flash_roofline`: the lower triangle of scores and `P v` of every
    layer-pass, forward and backward; q, k, v, o and their gradients cross
    HBM once each in the compute type."""
    m = config["model"]
    if not m.get("use_pallas"):
        return {}
    uses = m["num_hidden_layers"] * m["total_ut_steps"]
    itemsize = 2 if m["compute_dtype"] == "bfloat16" else 4
    width = 4 * m["head_dim"]                       # q, k, v, o per head
    return {"causal_flash": {
        "ops": step_ops(config, batch)["scores"],
        "bytes": 2.0 * uses * batch * m["num_attention_heads"]
        * config["seq_len"] * width * itemsize}}


# --- the inputs ------------------------------------------------------------------

# a batch is the token family's: int32 ids [batch, seq_len], uniform over
# the configuration's `vocab_size` (here the whole vocabulary)
batch_shape = token.batch_shape
draw_batch = token.draw_batch

#: the gate's bias: lambda about 0.3 everywhere, so that the four exits
#: hold about 0.30, 0.21, 0.15, 0.34 of the mass and every exit's loss
#: reaches the gradient
GATE_BIAS = math.log(0.3 / 0.7)


def draw_leaf(path: str, shape, k):
    """Fan-in scaled normals, so that every matmul keeps the scale of its
    input and the logits spread over about one unit (the loss sits
    measurably above ln(vocabulary)); norm gains away from 1; the gate's
    weights small and its bias at GATE_BIAS, so that lambda stays within
    about 0.2..0.4 and no exit holds less than a tenth of the mass."""
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    normal = lambda std: std * jax.random.normal(k, shape, jnp.float32)
    if path.endswith("exit_gate/b"):
        return GATE_BIAS + normal(0.05)
    if path.endswith("exit_gate/w"):
        return normal(0.3 * float(shape[-2]) ** -0.5)
    if name == "scale":
        return 1.0 + normal(0.1)
    if name == "table":
        return normal(1.0)
    if name == "w":
        return normal(float(shape[-2]) ** -0.5)
    raise ValueError(f"no rule to draw leaf {path!r}")


def drawn(shapes: Tree) -> Tree:
    return {"params": shapes["params"]}


def initial_state(state: Tree, model_state: Tree) -> Tree:
    """The drawn weights laid over the program's own init, which keeps its
    optimizer state and counters."""
    return {**state, "params": model_state["params"]}


# --- the readings ------------------------------------------------------------------

_STEP: Dict[tuple, Any] = {}


def _reference_step(config: dict, operand: str):
    """The reference's compiled pieces, ONE set kept at a time (the seeds
    and the planted faults of one operand type share it)."""
    key = (json.dumps([config["model"], config["train"]], sort_keys=True),
           operand)
    if key not in _STEP:
        _STEP.clear()
        gc.collect()
        _STEP[key] = reference.make_step(dict(config["model"]),
                                         dict(config["train"]), operand)
    return _STEP[key]


def program_readings(config: dict, wanted=None
                     ) -> Dict[str, Dict[str, Callable]]:
    """The token family's readings of Adam's moments and of the parameters'
    change, with the per-exit mass in the place of its pair counts."""
    reads = token.program_readings(config, wanted)
    del reads["first"]["counts"]
    reads["first"]["exit_mass"] = lambda state, start: state["exit_mass"]
    return reads


def reference_readings(config: dict, mesh, draw: Callable, key0, base,
                       batches, steps: int, *, operand: str = "float32",
                       passes: int = 0, last_pass_only: bool = False,
                       gate_grad: bool = True, causal: bool = True) -> dict:
    """The plain reference through the same first `steps` steps from the
    state `draw(key0)` gives (float32) and the same batches (the step keys
    are unused: the step draws nothing). `operand` is the control's and the
    witness's knob; the others plant the faults."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    del base
    ref = token.reference
    rep = NamedSharding(mesh, P())
    step = _reference_step(config, operand)
    sw = reference.switches(dict(config["model"]), causal=causal,
                            gate_grad=gate_grad, passes=passes,
                            last_pass_only=last_pass_only)
    state = reference.init_state(jax.jit(draw, out_shardings=rep)(key0))

    @jax.jit
    def first_reading(grads):
        flat = ref.leaves(grads)
        return {"grad": {n: ref.norm(g) for n, g in flat.items()},
                "gvec": {n: ref.sample(g) for n, g in flat.items()}}

    delta = jax.jit(lambda p, k: {
        n: ref.norm(a - b) for (n, a), b in
        zip(ref.leaves(p).items(), ref.leaves(draw(k)["params"]).values())})
    losses, first, mass = [], None, None
    for i in range(steps):
        state, loss, m, read = step(
            state, jax.device_put(batches[i], rep), sw,
            read=first_reading if i == 0 else None, last=i == steps - 1)
        losses.append(loss)
        if i == 0:
            first, mass = read, m
    got = jax.device_get({"losses": losses, "first": first, "mass": mass,
                          "delta": delta(state["params"], key0)})
    del state
    return {"losses": [{k: float(v) for k, v in l.items()}
                       for l in got["losses"]],
            "grad": {k: float(v) for k, v in got["first"]["grad"].items()},
            "gvec": got["first"]["gvec"], "exit_mass": got["mass"],
            "delta": {k: float(v) for k, v in got["delta"].items()}}


def _loss_gap(prog: dict, ref: dict) -> float:
    """Over the objective and every exit's cross-entropy the reference
    reports; one the program lacks fails."""
    names = ["loss"] + sorted(n for n in ref if n.startswith("loss_ut"))
    if any(n not in prog for n in names):
        return math.inf
    return max(abs(prog[n] - ref[n]) / max(abs(ref[n]), 1.0) for n in names)


def numbers(read: dict, ref: dict, mesh) -> Dict[str, float]:
    """The numbers of `read` (the program's readings, or those of the
    reference put in its place) against the reference's `ref`."""
    del mesh
    norm = lambda x: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    want = np.asarray(ref["exit_mass"], np.float64)
    out = {
        "loss_gap": _loss_gap(read["losses"][0], ref["losses"][0]),
        "loss2_gap": max(_loss_gap(p, r) for p, r in
                         zip(read["losses"][1:], ref["losses"][1:])),
        "exit_gap": float(np.sum(np.abs(
            np.asarray(read["exit_mass"], np.float64) - want))
            / max(np.sum(want), 1.0)),
        "grad_gap": check.worst_leaf_gap(read["grad"], ref["grad"]),
        "delta_gap": check.worst_leaf_gap(
            read["delta"], ref["delta"],
            leave_out=check.nought_leaves(ref["grad"])),
    }
    if read.get("gvec") is not None:
        errs = check.leaf_errors(
            {n: norm(np.asarray(read["gvec"][n]) - np.asarray(g))
             for n, g in ref["gvec"].items()},
            {n: norm(g) for n, g in ref["gvec"].items()})
        out.update(grad_err=statistics.median(errs), grad_err_worst=max(errs))
    return out


def variants(config: dict, global_batch: int, chips: int) -> Dict[str, dict]:
    """What `readings.py` puts in the program's place: the control (every
    matmul operand rounded to fp8 e4m3: the configuration states bfloat16),
    the witness in bfloat16, and the planted faults: three passes for four,
    the gradient of the last pass only (the truncated backward), the gate
    detached (the exit distribution constant in the backward), the causal
    mask left out."""
    return {
        "reference_fp8": {"must_pass": False, "kwargs": {"operand": "fp8"}},
        "reference_bf16": {"must_pass": True,
                           "kwargs": {"operand": "bfloat16"}},
        "three_passes": {"must_pass": False,
                         "kwargs": {"passes":
                                    config["model"]["total_ut_steps"] - 1}},
        "last_pass_grad": {"must_pass": False,
                           "kwargs": {"last_pass_only": True}},
        "gate_detached": {"must_pass": False, "kwargs": {"gate_grad": False}},
        "no_causal_mask": {"must_pass": False, "kwargs": {"causal": False}},
    }
