"""The `gan` family: two-player image GANs on the DCGAN stacks, with the
optional SAGAN attention block and spectral norm (`sagan128`, `dcgan128`).

Everything of the benchmark that depends on what kind of model a
configuration is, under the names `manifest.FAMILY_API` fixes
(benchmark/README.md, "Add a family"): the yardstick (`benchmark/flops.py`),
the draw of a batch and of each leaf of the state, what is read from the
program's state after its first steps, the plain reference that follows the
same steps (`benchmark/reference.py`), the numbers worked out from the two
(`check.training_numbers`), and the variants `readings.py` sets limits from.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import numpy as np

from benchmark import check, flops, reference, traffic

Tree = Dict[str, Any]


# --- the yardstick -------------------------------------------------------------

def _model(config: dict) -> dict:
    return dict(config["model"], attn_qk_div=config["attn_qk_div"],
                attn_v_div=config["attn_v_div"])


def step_ops(config: dict, global_batch: int) -> Dict[str, float]:
    """Operations one train step needs; `total` is what `step_mfu` divides
    by the peak."""
    return flops.step_ops(_model(config), global_batch)


def kernel_costs(config: dict, batch: int) -> Dict[str, Dict[str, float]]:
    """{kernel: {"ops", "bytes"}}: the least one step's kernels must do at
    batch `batch`, by the stem of the roofline metric that reads it. The
    flash kernels run where the configuration has attention on Pallas."""
    model = config["model"]
    if not model.get("attn_res") or not model.get("use_pallas"):
        return {}
    return {"flash_attn": flops.flash_step_cost(_model(config), batch)}


# --- the inputs ------------------------------------------------------------------

def batch_shape(config: dict, global_batch: int):
    m = config["model"]
    return (global_batch, m["output_size"], m["output_size"], m["c_dim"])


draw_batch = traffic.uniform_images     # float32 images in the tanh range


def draw_leaf(path: str, shape, k):
    """The program's own initializer leaves the attention gate `gamma` at 0
    (the block is then the identity and its kernels' results never reach
    the loss); here `gamma` is drawn from [0.5, 1), as in a trained SAGAN,
    so that the comparison that decides `correct` sees the flash kernels'
    forward and backward results."""
    import jax
    import jax.numpy as jnp

    name = path.rsplit("/", 1)[-1]
    if "/sn_" in path:                      # power-iteration start vector
        u = jax.random.normal(k, shape, jnp.float32)
        return u / (jnp.linalg.norm(u) + 1e-12)
    if name == "mean":
        return jnp.zeros(shape, jnp.float32)
    if name == "var":
        return jnp.ones(shape, jnp.float32)
    if name == "gamma":
        return jax.random.uniform(k, shape, jnp.float32, 0.5, 1.0)
    if name == "scale":
        return 1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
    if name in ("w", "b", "bias"):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)
    raise ValueError(f"no rule to draw leaf {path!r}")


def drawn(shapes: Tree) -> Tree:
    """The part of the program's state the benchmark draws: the weights and
    the model state beside them (BN moments, power-iteration vectors)."""
    return {"params": shapes["params"], "bn": shapes["bn"]}


def initial_state(state: Tree, model_state: Tree) -> Tree:
    """The drawn model state laid over the program's own init, which keeps
    its optimizer state and counters."""
    import jax
    import jax.numpy as jnp

    return {**state, "params": model_state["params"], "bn": model_state["bn"],
            "ema_gen": jax.tree.map(jnp.copy, model_state["params"]["gen"])}


# --- the readings ------------------------------------------------------------------

def _moment_leaves(opt_state, moment: str) -> Dict[str, Any]:
    """{"gen/deconv1/w": leaf} out of the optimizer state: the leaves under
    Adam's `mu` or `nu`, named by the dict keys that follow it."""
    import jax

    out = {}
    for net in ("gen", "disc"):
        flat, _ = jax.tree_util.tree_flatten_with_path(opt_state[net])
        for path, leaf in flat:
            keys = [getattr(k, "name", getattr(k, "key", None)) for k in path]
            if moment in keys:
                tail = [str(k) for k in keys[keys.index(moment) + 1:]]
                out["/".join([net] + tail)] = leaf
    return out


def program_readings(config: dict, wanted=None
                     ) -> Dict[str, Dict[str, Callable]]:
    """What is read from the program's state, as functions of (state, the
    model state it started from): `first` after the first step (the first
    gradient's leaf norms from Adam's `nu`, the gradient itself from `mu`
    where a number in `wanted` needs it, the change of the BN moments and
    power-iteration vectors), `last` after the steps the reference follows
    (the parameters' change)."""
    import jax.numpy as jnp

    beta1, beta2 = config["train"]["beta1"], config["train"]["beta2"]

    def grad(state, start):
        return {n: jnp.sqrt(jnp.sum(v.astype(jnp.float32)) / (1.0 - beta2))
                for n, v in _moment_leaves(state["opt"], "nu").items()}

    def gvec(state, start):
        # after one step from zero moments mu is (1 - beta1) x the gradient
        return {n: m.astype(jnp.float32) / (1.0 - beta1)
                for n, m in _moment_leaves(state["opt"], "mu").items()}

    def stats(state, start):
        return reference.stat_changes(state["bn"], start["bn"])

    def delta(state, start):
        return reference.delta_norms(state["params"], start["params"])

    first = {"grad": grad, "gvec": gvec, "stats": stats}
    if wanted is not None and not check.GRADIENT_NUMBERS & set(wanted):
        del first["gvec"]
    return {"first": first, "last": {"delta": delta}}


def reference_readings(config: dict, mesh, draw: Callable, key0, base,
                       batches, steps: int, *, operand: str = "float32",
                       rows: Optional[slice] = None) -> dict:
    """The plain reference through the same first `steps` steps: the model
    state `draw(key0)` gives (float32), the same batches and step keys.
    `operand` and `rows` are the control's and the faults' knobs (lower
    precision; a part of the batch only)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mcfg, tcfg = _model(config), dict(config["train"])
    rep = NamedSharding(mesh, P())
    make = jax.jit(lambda k: reference.init_state(draw(k)),
                   out_shardings=rep)
    params0 = jax.jit(lambda k: draw(k)["params"], out_shardings=rep)
    n_shards = 1 if rows is not None else mesh.shape["data"]
    step = reference.make_step(mcfg, tcfg, operand, n_shards)
    state = make(key0)
    first_gradient = jax.jit(
        lambda opt: reference.first_gradient(opt, tcfg), out_shardings=rep)
    stat_changes = jax.jit(
        lambda bn, k: reference.stat_changes(bn, draw(k)["bn"]),
        out_shardings=rep)
    losses, grad, gvec, stats = [], None, None, None
    for i in range(steps):
        images = batches[i] if rows is None else \
            jax.device_put(batches[i][rows], rep)
        state, loss, norms = step(state, images, jax.random.fold_in(base, i))
        losses.append(loss)
        if i == 0:
            grad, gvec = norms, first_gradient(state["opt"])
            stats = stat_changes(state["bn"], key0)
    delta = jax.jit(reference.delta_norms, out_shardings=rep)(
        state["params"], params0(key0))
    got = jax.device_get({"losses": losses, "grad": grad, "delta": delta,
                          "stats": stats})
    del state
    return {"losses": [{k: float(v) for k, v in m.items()}
                       for m in got["losses"]],
            "grad": {k: float(v) for k, v in got["grad"].items()},
            "delta": {k: float(v) for k, v in got["delta"].items()},
            "gvec": gvec, "stats": got["stats"]}


def numbers(read: dict, ref: dict, mesh) -> Dict[str, float]:
    """The training numbers of `read` (the program's readings, or those of
    the reference put in its place) against the reference's `ref`. The
    first gradients meet on the device here, leaf by leaf."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    norm = lambda x: float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    read = {**read, "stat_diff": {k: norm(read["stats"][k] - v)
                                  for k, v in ref["stats"].items()}}
    if read.get("gvec") is not None:
        diff = jax.device_get(jax.jit(
            reference.diff_norms, out_shardings=NamedSharding(mesh, P()))(
                read["gvec"], ref["gvec"]))
        read["grad_diff"] = {k: float(v) for k, v in diff.items()}
    return check.training_numbers(
        read, {**ref, "stat": {k: norm(v) for k, v in ref["stats"].items()}})


def variants(config: dict, global_batch: int, chips: int
             ) -> Dict[str, dict]:
    """What `readings.py` puts in the program's place, as keyword arguments
    of `reference_readings`, and whether each has to come out correct: the
    control (operands of every matmul and convolution rounded to fp8: the
    configurations state bfloat16), the witness in bfloat16, and the planted
    faults (half of the batch left out; on several chips the exchange left
    out, the first chip's rows alone)."""
    out = {
        "reference_fp8": {"must_pass": False, "kwargs": {"operand": "fp8"}},
        "reference_bf16": {"must_pass": True,
                           "kwargs": {"operand": "bfloat16"}},
        "half_batch": {"must_pass": False,
                       "kwargs": {"rows": slice(0, global_batch // 2)}}}
    if chips > 1:
        out["no_exchange"] = {
            "must_pass": False,
            "kwargs": {"rows": slice(0, global_batch // chips)}}
    return out
