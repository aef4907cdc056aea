"""The `sambay` family: decoder-hybrid-decoder causal language models (Mamba
scans, window / full / cross differential attention, gated memory units,
one key/value set and one memory shared across layers, a tied head),
trained by a likelihood step (`phi-4-mini-flash`).

Under the names `manifest.FAMILY_API` fixes: the yardstick (operations one
step needs, from the configuration's shapes), the draw of a batch of ids and
of each leaf, what is read from the program's state after its first steps,
the plain reference that follows the same steps
(`families/sambay_reference.py`, loaded by path from beside this file), the
numbers worked out from the two, and the variants `readings.py` sets limits
from. The readings that any token family takes from Adam's moments, the
batches and the tree arithmetic are the `mla_moe` family's (loaded through
`manifest.family`).

The numbers compared (PERF.md section 2):

- `loss_gap`: the first step's loss, |program - reference| as a share of
  max(|reference|, 1): the forward pass of every kind of layer;
  `loss2_gap` the second step's;
- `mem_gap`: the per-channel mean of `|m|`, the memory layer's scan output,
  which the step accumulates in its state (`state["mem_abs"]`), after the
  first step: the sum over the channels of |program - reference| over the
  reference's sum: the convolution, `dt` and the scan, two layers deep;
- `grad_gap` (leaf norms of the first gradient, from Adam's `nu`),
  `delta_gap` (the parameters' change over two steps) and `grad_err` (the
  first gradient VECTOR, from `mu`, on a fixed strided sample of each
  leaf's coordinates, median leaf): the backward pass, the sums over the
  readers of `m` and of the shared keys and values and the tied leaf's two
  uses among it, in `check.py`'s measures. The bias of `W_qkv` is read as
  its three parts (`..qkv_proj/b:q`, `:k`, `:v`) in `grad_gap` and
  `delta_gap`: the keys' bias has NO gradient (a shift of every key alike
  leaves the softmax as it was), Adam turns its round-off into steps of
  either sign, and read as a part it falls under `check.nought_leaves`'
  rule where inside the whole leaf it would be a fifth of its change.
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
    os.path.join(_HERE, "sambay_reference.py"),
    "bench_family_sambay_reference")
# the token family whose batches, state readings and tree arithmetic this
# one shares
token = manifest.family(os.path.dirname(os.path.dirname(_HERE)),
                        {"family": "mla_moe"})

#: elementwise operations of one (step, channel, state) of the scan's
#: forward (dt A, exp, times s, times B, add, times C, add) and of one
#: (step, channel) beside them (dt u, D u and its add)
SCAN_OPS_PER_STATE = 7
SCAN_OPS_PER_CHANNEL = 3


# --- the yardstick -------------------------------------------------------------

def _sizes(m: dict):
    h = m["hidden_size"]
    d = h // m["num_attention_heads"]
    return (h, d, m["num_key_value_heads"] * d, m["mamba_expand"] * h,
            m["mamba_d_state"], m["mamba_dt_rank"] or math.ceil(h / 16))


def _seen(s: int, window: int = 0) -> int:
    """(query, key) pairs of one causal map over `s` positions, each query
    seeing its last `window` keys (0: all of them)."""
    if not window or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def _forward_ops(m: dict, s: int) -> Dict[str, float]:
    """Operations of ONE sequence's forward pass (2 per multiply-add), by
    part: what the algorithm needs. A differential layer runs two maps per
    query pair, each `q k^T` one head wide and `P v` two heads wide."""
    h, d, kv, di, n, r = _sizes(m)
    kinds = list(m["layer_types"])
    count = lambda *names: sum(kinds.count(k) for k in names)
    pairs = m["num_attention_heads"] // 2
    per_pair = 2.0 * 2 * (d + 2 * d)               # two maps; q k^T and P v
    return {
        "mlp": 2.0 * len(kinds) * s * 3 * h * m["intermediate_size"],
        "mamba_proj": 2.0 * count("mamba") * s * (
            h * 2 * di + di * (r + 2 * n) + r * di + di * h),
        "mamba_conv": 2.0 * count("mamba") * s * di * m["mamba_d_conv"],
        "scan": 1.0 * count("mamba") * s * di * (
            SCAN_OPS_PER_STATE * n + SCAN_OPS_PER_CHANNEL),
        "attn_proj": 2.0 * s * h * (
            count("attn_win", "attn_full") * (h + 2 * kv + h)
            + count("attn_cross") * 2 * h),
        "scores_full": count("attn_full", "attn_cross") * pairs * per_pair
        * _seen(s),
        "scores_window": count("attn_win") * pairs * per_pair
        * _seen(s, m["sliding_window"]),
        "gmu": 2.0 * count("gmu") * s * 2 * h * di,
        "head": 2.0 * s * h * m["vocab_size"],
    }


def step_ops(config: dict, global_batch: int) -> Dict[str, float]:
    """Operations one train step needs: forward and backward (3 x forward),
    nothing recomputed counted (the per-block recomputation, the flash
    backward's rebuilt tiles and the scan backward's rebuilt states are the
    program's choice). `total` is what `step_mfu` divides by the peak."""
    parts = {k: 3.0 * global_batch * v for k, v in
             _forward_ops(config["model"], config["seq_len"]).items()}
    return {**parts, "total": sum(parts.values())}


def kernel_costs(config: dict, batch: int) -> Dict[str, Dict[str, float]]:
    """{kernel: {"ops", "bytes"}}, by the stem of the roofline metric that
    reads it. `causal_flash` (read by `hybrid_flash_roofline`): every map
    of every attention layer, triangles and bands, forward and backward; q,
    k, v, o of the folded rows and their gradients cross HBM once each in
    the compute type. `window_flash`: the window layers' part of that.
    `ssm_scan`: the scans' elementwise operations, and u, dt, B, C, y and
    their gradients crossing HBM once each in float32."""
    m, s = config["model"], config["seq_len"]
    h, d, _, di, n, _ = _sizes(m)
    ops = step_ops(config, batch)
    kinds = list(m["layer_types"])
    itemsize = 2 if m["compute_dtype"] == "bfloat16" else 4
    rows = 2 * (m["num_attention_heads"] // 2)     # [q1; q2] of every pair
    layer_bytes = 2.0 * batch * rows * s * (d + d + 2 * d + 2 * d) * itemsize
    win = kinds.count("attn_win")
    attn = win + kinds.count("attn_full") + kinds.count("attn_cross")
    return {
        "causal_flash": {"ops": ops["scores_full"] + ops["scores_window"],
                         "bytes": attn * layer_bytes},
        "window_flash": {"ops": ops["scores_window"],
                         "bytes": win * layer_bytes},
        "ssm_scan": {"ops": ops["scan"],
                     "bytes": 2.0 * kinds.count("mamba") * batch * s
                     * (3 * di + 2 * n) * 4},
    }


# --- the inputs ------------------------------------------------------------------

# a batch is the token family's: int32 ids [batch, seq_len], uniform over
# the configuration's `vocab_size` (the slice held)
batch_shape = token.batch_shape
draw_batch = token.draw_batch


def draw_leaf(path: str, shape, k):
    """Fan-in scaled normals, so that every matmul keeps the scale of its
    input; norm gains away from 1 and biases away from 0. The tied table at
    1/sqrt(hidden), so that the logits of the normed state spread over about
    one unit (at 1 they would spread over sqrt(hidden) and the loss would
    read the largest logit alone). The scan's leaves as the family draws
    them: `A_log` the log of 1..N, `D` ones, `dt`'s bias the inverse
    softplus of a step log-uniform in 0.001..0.1; the lambda vectors normal
    at 0.1, so that lambda stays near its `lambda_init`."""
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    normal = lambda std: std * jax.random.normal(k, shape, jnp.float32)
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if path.endswith("dt_proj/b"):
        step = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                          math.log(1e-3), math.log(1e-1)))
        return step + jnp.log(-jnp.expm1(-step))
    if name.startswith("lambda_"):
        return normal(0.1)
    if name == "scale":
        return 1.0 + normal(0.1)
    if name in ("bias", "b"):
        return normal(0.1)
    if name == "table":
        return normal(float(shape[-1]) ** -0.5)
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


def _by_part(flat: Dict[str, Any], m: dict) -> Dict[str, Any]:
    """`flat` ({leaf name: array}) with the bias of every `W_qkv` as its
    query, key and value parts (the module's docstring says why)."""
    h, _, kv, _, _, _ = _sizes(m)
    out = {}
    for name, x in flat.items():
        if name.endswith("qkv_proj/b"):
            out.update({name + ":q": x[:h], name + ":k": x[h:h + kv],
                        name + ":v": x[h + kv:]})
        else:
            out[name] = x
    return out


def program_readings(config: dict, wanted=None
                     ) -> Dict[str, Dict[str, Callable]]:
    """The token family's readings of Adam's moments and of the parameters'
    change, the leaf norms by part (`_by_part`), with the memory's
    per-channel mean in the place of its pair counts."""
    import jax.numpy as jnp

    m, beta2 = config["model"], config["train"]["beta2"]
    ref = token.reference
    reads = token.program_readings(config, wanted)
    del reads["first"]["counts"]
    reads["first"]["mem_abs"] = lambda state, start: state["mem_abs"]
    reads["first"]["grad"] = lambda state, start: {
        n: jnp.sqrt(jnp.sum(v.astype(jnp.float32)) / (1.0 - beta2))
        for n, v in _by_part(token._moment_leaves(state["opt"], "nu"),
                             m).items()}
    reads["last"]["delta"] = lambda state, start: {
        n: ref.norm(d) for n, d in _by_part(
            {n: a - b for (n, a), b in
             zip(ref.leaves(state["params"]).items(),
                 ref.leaves(start["params"]).values())}, m).items()}
    return reads


def reference_readings(config: dict, mesh, draw: Callable, key0, base,
                       batches, steps: int, *, operand: str = "float32",
                       **faults) -> dict:
    """The plain reference through the same first `steps` steps from the
    state `draw(key0)` gives (float32) and the same batches (the step keys
    are unused: the step draws nothing). `operand` is the control's and the
    witness's knob; `faults` are `reference.switches`' (the planted
    faults)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    del base
    ref = token.reference
    rep = NamedSharding(mesh, P())
    step = _reference_step(config, operand)
    sw = reference.switches(**faults)
    state = reference.init_state(jax.jit(draw, out_shardings=rep)(key0))

    m = config["model"]

    @jax.jit
    def first_reading(grads):
        flat = ref.leaves(grads)
        return {"grad": {n: ref.norm(g)
                         for n, g in _by_part(flat, m).items()},
                "gvec": {n: ref.sample(g) for n, g in flat.items()}}

    delta = jax.jit(lambda p, k: {
        n: ref.norm(d) for n, d in _by_part(
            {n: a - b for (n, a), b in
             zip(ref.leaves(p).items(),
                 ref.leaves(draw(k)["params"]).values())}, m).items()})
    losses, first, mem = [], None, None
    for i in range(steps):
        state, loss, readings, read = step(
            state, jax.device_put(batches[i], rep), sw,
            read=first_reading if i == 0 else None, last=i == steps - 1)
        losses.append(loss)
        if i == 0:
            first, mem = read, readings["mem_abs"]
    got = jax.device_get({"losses": losses, "first": first, "mem": mem,
                          "delta": delta(state["params"], key0)})
    del state
    return {"losses": [{k: float(v) for k, v in l.items()}
                       for l in got["losses"]],
            "grad": {k: float(v) for k, v in got["first"]["grad"].items()},
            "gvec": got["first"]["gvec"], "mem_abs": got["mem"],
            "delta": {k: float(v) for k, v in got["delta"].items()}}


def _loss_gap(prog: dict, ref: dict) -> float:
    gap = abs(prog["loss"] - ref["loss"]) / max(abs(ref["loss"]), 1.0)
    return gap if math.isfinite(gap) else math.inf


def numbers(read: dict, ref: dict, mesh) -> Dict[str, float]:
    """The numbers of `read` (the program's readings, or those of the
    reference put in its place) against the reference's `ref`."""
    del mesh
    norm = lambda x: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    want = np.asarray(ref["mem_abs"], np.float64)
    out = {
        "loss_gap": _loss_gap(read["losses"][0], ref["losses"][0]),
        "loss2_gap": max(_loss_gap(p, r) for p, r in
                         zip(read["losses"][1:], ref["losses"][1:])),
        "mem_gap": float(np.sum(np.abs(
            np.asarray(read["mem_abs"], np.float64) - want))
            / max(np.sum(want), 1e-30)),
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
    the witness in bfloat16, and the planted faults: no window (the window
    layer sees the whole triangle), the second map dropped (lambda = 0),
    `m` detached from the gated memory units, the shared keys and values
    detached from the cross layers, `dt` without its softplus."""
    fault = lambda name: {"must_pass": False, "kwargs": {name: False}}
    return {
        "reference_fp8": {"must_pass": False, "kwargs": {"operand": "fp8"}},
        "reference_bf16": {"must_pass": True,
                           "kwargs": {"operand": "bfloat16"}},
        "no_window": fault("window"),
        "second_map_dropped": fault("second_map"),
        "m_detached": fault("m_grad"),
        "kv_detached": fault("kv_grad"),
        "dt_without_softplus": fault("softplus"),
    }
