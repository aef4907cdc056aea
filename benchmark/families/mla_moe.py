"""The `mla_moe` family: one-network causal token models with latent
attention, routed experts of which a chip holds a stated share, and a
multi-token head, trained by a likelihood step (`joyai-llm-flash`).

Under the names `manifest.FAMILY_API` fixes: the yardstick (operations one
step needs, from the configuration's shapes), the draw of a batch of ids and
of each leaf, what is read from the program's state after its first steps,
the plain reference that follows the same steps
(`families/mla_moe_reference.py`, loaded by path from beside this file), the
numbers worked out from the two, and the variants `readings.py` sets limits
from.

The numbers compared (PERF.md section 2):

- `loss_gap`: the first step's two losses (trunk, multi-token module),
  |program - reference| as a share of max(|reference|, 1): the forward
  pass; `loss2_gap` the second step's;
- `route_diff`: the per-expert pair counts the step accumulates in its
  state, after the first step: the sum over layers and held experts of
  |program - reference| over the reference's pairs here. A pair routed to
  another expert moves two counts (one, if the other expert is absent);
- `grad_gap` (leaf norms of the first gradient, from Adam's `nu`),
  `delta_gap` (the parameters' change over two steps) and `grad_err` /
  `grad_err_worst` (the first gradient VECTOR, from `mu`, on a fixed
  strided sample of each leaf's coordinates): the backward pass, in
  `check.py`'s measures.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
from typing import Any, Callable, Dict

import numpy as np

from benchmark import check, manifest

Tree = Dict[str, Any]


# the plain reference lies beside this file and is loaded by path, like
# every module of the benchmark (a temporary root loads its own copy)
reference = manifest._load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "mla_moe_reference.py"), "bench_family_mla_moe_reference")


# --- the yardstick -------------------------------------------------------------

def _layers(m: dict):
    """(attention layers, expert layers): the multi-token module is one of
    each."""
    mtp = m["num_nextn_predict_layers"]
    return (m["num_hidden_layers"] + mtp,
            m["num_hidden_layers"] - m["first_k_dense_replace"] + mtp)


def _forward_ops(m: dict, seq_len: int) -> Dict[str, float]:
    """Operations of ONE sequence's forward pass (2 per multiply-add), by
    part: what the algorithm needs, causal scores as the lower triangle,
    routed pairs at their expectation under even routing (tokens x
    experts per token x held / routed)."""
    h, nh = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    attn, moe = _layers(m)
    s = seq_len
    proj = (h * m["q_lora_rank"] + m["q_lora_rank"] * nh * (dn + dr)
            + h * (m["kv_lora_rank"] + dr)
            + m["kv_lora_rank"] * nh * (dn + dv) + nh * dv * h)
    expert = 3 * h * m["moe_intermediate_size"]
    pairs = s * m["num_experts_per_tok"] * m["experts_held"] \
        / m["n_routed_experts"]
    return {
        "mla_proj": 2.0 * attn * s * proj,
        "mla_scores": 2.0 * attn * nh * (s * (s + 1) // 2) * (dn + dr + dv),
        "dense_ffn": 2.0 * m["first_k_dense_replace"] * s * 3 * h
        * m["intermediate_size"],
        "shared": 2.0 * moe * s * expert * m["n_shared_experts"],
        "routed": 2.0 * moe * pairs * expert,
        "router": 2.0 * moe * s * h * m["n_routed_experts"],
        "heads": 2.0 * (1 + m["num_nextn_predict_layers"]) * s * h
        * m["vocab_size"],
        "eh_proj": 2.0 * m["num_nextn_predict_layers"] * s * 2 * h * h,
    }


def step_ops(config: dict, global_batch: int) -> Dict[str, float]:
    """Operations one train step needs: forward and backward (3 x forward),
    nothing recomputed counted (the per-block recomputation and the flash
    backward's rebuilt tiles are the program's choice). `total` is what
    `step_mfu` divides by the peak."""
    parts = {k: 3.0 * global_batch * v
             for k, v in _forward_ops(config["model"], config["seq_len"]).items()}
    return {**parts, "total": sum(parts.values())}


def kernel_costs(config: dict, batch: int) -> Dict[str, Dict[str, float]]:
    """{kernel: {"ops", "bytes"}}: the least one step's kernels must do, by
    the stem of the roofline metric that reads it. `causal_flash`: the
    lower triangle of scores and `P v` of every attention layer, forward
    and backward; q, k, v, o and their gradients cross HBM once each in the
    compute type. `moe_gmm`: the grouped products over the expected pairs
    here; every held expert's three matrices read in forward and backward
    and their gradients written, the rows in and out."""
    m, s = config["model"], config["seq_len"]
    if not m.get("use_pallas"):
        return {}
    ops = step_ops(config, batch)
    attn, moe = _layers(m)
    nh, h = m["num_attention_heads"], m["hidden_size"]
    width = 2 * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) \
        + 2 * m["v_head_dim"]                       # q, k | v, o per head
    itemsize = 2 if m["compute_dtype"] == "bfloat16" else 4
    pairs = batch * s * m["num_experts_per_tok"] * m["experts_held"] \
        / m["n_routed_experts"]
    expert = 3 * h * m["moe_intermediate_size"]
    return {
        "causal_flash": {
            "ops": ops["mla_scores"],
            "bytes": 2.0 * attn * batch * nh * s * width * itemsize},
        "moe_gmm": {
            "ops": ops["routed"],
            "bytes": moe * itemsize * (
                3.0 * m["experts_held"] * expert
                + 2.0 * pairs * 2 * (h + 3 * m["moe_intermediate_size"]))},
    }


# --- the inputs ------------------------------------------------------------------

# `draw_batch(key, shape)` sees no configuration: the vocabulary of a batch
# shape is what the `batch_shape` call that made the shape said it was
_VOCAB: Dict[tuple, int] = {}


def batch_shape(config: dict, global_batch: int):
    shape = (global_batch, config["seq_len"])
    _VOCAB[shape] = int(config["model"]["vocab_size"])
    return shape


def draw_batch(key, shape):
    """One batch of int32 ids, uniform over the slice of the vocabulary
    held, every row different (8,192 draws from 16,160 ids never repeat a
    row)."""
    import jax
    import jax.numpy as jnp

    return jax.random.randint(key, shape, 0, _VOCAB[tuple(shape)], jnp.int32)


def draw_leaf(path: str, shape, k):
    """Fan-in scaled normals, so that every matmul keeps the scale of its
    input, scores and logits spread over about one unit and the loss sits
    measurably away from ln(vocabulary) (a loss AT ln V, from logits near
    0, reads nothing; with ids drawn independently of everything it lies
    above, by about half the logits' variance); norm gains away from 1;
    router columns alike in scale, so that selection is near even; the
    selection bias non-zero, so that selection differs from plain top-k."""
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    normal = lambda std: std * jax.random.normal(k, shape, jnp.float32)
    if path.startswith("moe_bias/"):
        return normal(0.05)
    if name == "scale":
        return 1.0 + normal(0.1)
    if name == "table":
        return normal(1.0)
    if name == "w" or "/experts/" in path:
        return normal(float(shape[-2]) ** -0.5)
    raise ValueError(f"no rule to draw leaf {path!r}")


def drawn(shapes: Tree) -> Tree:
    return {"params": shapes["params"], "moe_bias": shapes["moe_bias"]}


def initial_state(state: Tree, model_state: Tree) -> Tree:
    """The drawn weights and selection biases laid over the program's own
    init, which keeps its optimizer state and counters."""
    return {**state, "params": model_state["params"],
            "moe_bias": model_state["moe_bias"]}


# --- the readings ------------------------------------------------------------------

_STEP: Dict[tuple, Any] = {}


def _reference_step(config: dict, operand: str):
    """The reference's compiled step, ONE kept at a time: the seeds and the
    planted faults (run-time switches) of one operand type share it, and a
    change of operand type drops the last one first. A loaded program keeps
    its temporaries reserved on the device (5.2 GB for the float32 gradient
    of the shipped configuration, 8.3 GB with fp8 operands; compile, PR
    27), so two of them beside parameters and gradient do not fit."""
    key = (json.dumps([config["model"], config["train"]], sort_keys=True),
           operand)
    if key not in _STEP:
        _STEP.clear()
        gc.collect()
        _STEP[key] = reference.make_step(dict(config["model"]),
                                         dict(config["train"]), operand)
    return _STEP[key]


def _moment_leaves(opt_state, moment: str) -> Dict[str, Any]:
    """{"block0/mla/q_a/w": leaf} out of the optimizer state: the leaves
    under Adam's `mu` or `nu`, named by the dict keys that follow it."""
    import jax

    out = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(opt_state)
    for path, leaf in flat:
        keys = [getattr(k, "name", getattr(k, "key", None)) for k in path]
        if moment in keys:
            out["/".join(str(k) for k in keys[keys.index(moment) + 1:])] = leaf
    return out


def program_readings(config: dict, wanted=None
                     ) -> Dict[str, Dict[str, Callable]]:
    """`first`, after the first step: the first gradient's leaf norms
    (Adam's `nu`), a sample of the gradient itself (`mu`) where a number
    in `wanted` needs it, the per-expert pair counts. `last`: the
    parameters' change."""
    import jax.numpy as jnp

    beta1, beta2 = config["train"]["beta1"], config["train"]["beta2"]

    def grad(state, start):
        return {n: jnp.sqrt(jnp.sum(v.astype(jnp.float32)) / (1.0 - beta2))
                for n, v in _moment_leaves(state["opt"], "nu").items()}

    def gvec(state, start):
        return {n: reference.sample(m) / (1.0 - beta1)
                for n, m in _moment_leaves(state["opt"], "mu").items()}

    def counts(state, start):
        return dict(state["moe_counts"])

    def delta(state, start):
        return {n: reference.norm(a - start_leaf) for (n, a), start_leaf in
                zip(reference.leaves(state["params"]).items(),
                    reference.leaves(start["params"]).values())}

    first = {"grad": grad, "gvec": gvec, "counts": counts}
    if wanted is not None and not check.GRADIENT_NUMBERS & set(wanted):
        del first["gvec"]
    return {"first": first, "last": {"delta": delta}}


def reference_readings(config: dict, mesh, draw: Callable, key0, base,
                       batches, steps: int, *, operand: str = "float32",
                       mtp: bool = True, held_norm: bool = False,
                       causal: bool = True) -> dict:
    """The plain reference through the same first `steps` steps from the
    state `draw(key0)` gives (float32) and the same batches (the step keys
    are unused: the step draws nothing). `operand` is the control's and the
    witness's knob; `mtp`, `held_norm`, `causal` plant the faults."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    del base
    m = dict(config["model"])
    rep = NamedSharding(mesh, P())
    step = _reference_step(config, operand)
    sw = reference.switches(m, mtp=mtp, held_norm=held_norm, causal=causal)
    state = reference.init_state(
        jax.jit(draw, out_shardings=rep)(key0))

    @jax.jit
    def first_reading(grads):
        flat = reference.leaves(grads)
        return {"grad": {n: reference.norm(g) for n, g in flat.items()},
                "gvec": {n: reference.sample(g) for n, g in flat.items()}}

    delta = jax.jit(lambda p, k: {
        n: reference.norm(a - b) for (n, a), b in
        zip(reference.leaves(p).items(),
            reference.leaves(draw(k)["params"]).values())})
    losses, first, counts = [], None, None
    for i in range(steps):
        state, loss, c, read = step(
            state, jax.device_put(batches[i], rep), sw,
            read=first_reading if i == 0 else None, last=i == steps - 1)
        losses.append(loss)
        if i == 0:
            first, counts = read, c
    got = jax.device_get({"losses": losses, "first": first, "counts": counts,
                          "delta": delta(state["params"], key0)})
    del state
    return {"losses": [{k: float(v) for k, v in l.items()}
                       for l in got["losses"]],
            "grad": {k: float(v) for k, v in got["first"]["grad"].items()},
            "gvec": got["first"]["gvec"], "counts": got["counts"],
            "delta": {k: float(v) for k, v in got["delta"].items()}}


def _loss_gap(prog, ref) -> float:
    return max(abs(prog[n] - ref[n]) / max(abs(ref[n]), 1.0)
               for n in ("loss", "loss_mtp"))


def numbers(read: dict, ref: dict, mesh) -> Dict[str, float]:
    """The numbers of `read` (the program's readings, or those of the
    reference put in its place) against the reference's `ref`."""
    del mesh
    norm = lambda x: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    moved = sum(float(np.sum(np.abs(np.asarray(read["counts"][n], np.int64)
                                    - np.asarray(c, np.int64))))
                for n, c in ref["counts"].items())
    here = sum(float(np.sum(c)) for c in ref["counts"].values())
    out = {
        "loss_gap": _loss_gap(read["losses"][0], ref["losses"][0]),
        "loss2_gap": max(_loss_gap(p, r) for p, r in
                         zip(read["losses"][1:], ref["losses"][1:])),
        "route_diff": moved / max(here, 1.0),
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
    the witness in bfloat16, and the planted faults: the multi-token loss
    left out, the routing weights normalized over the HELD selected experts
    only, the causal mask left out."""
    return {
        "reference_fp8": {"must_pass": False, "kwargs": {"operand": "fp8"}},
        "reference_bf16": {"must_pass": True,
                           "kwargs": {"operand": "bfloat16"}},
        "no_mtp_loss": {"must_pass": False, "kwargs": {"mtp": False}},
        "held_norm": {"must_pass": False, "kwargs": {"held_norm": True}},
        "no_causal_mask": {"must_pass": False, "kwargs": {"causal": False}},
    }
