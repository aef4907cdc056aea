"""What a token block keeps across its recomputation
(models/token_ops.py::recomputed and the names in
ops/pallas_attention.py::_flash_vjp_fwd): the flash forward's outputs stay,
so the gradient of either token arch holds one forward kernel per backward
kernel; the kept arrays are what the recomputation would have written, so
nothing changes but the work; the step counts what it keeps; and a step
that recomputes nothing (the image families) lowers as it did."""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.models import loop_lm, mla_moe, token_ops
from dcgan_tpu.ops import pallas_attention
from dcgan_tpu.presets import get_preset

# arch module, tiny preset, blocks a step's program holds (kernel call
# sites), attention outputs a step keeps: the looped arch's 2 layers, each
# run in 4 passes of one rolled loop; the routed arch's 3 layers + the
# multi-token module
ARCHS = {"loop_lm": (loop_lm, "loop_lm_tiny", 2, 8),
         "mla_moe": (mla_moe, "mla_moe_tiny", 4, 4)}


def _model(arch, use_pallas=True):
    return dataclasses.replace(get_preset(ARCHS[arch][1]).model,
                               use_pallas=use_pallas)


def _loss_and_grads(arch, cfg, run=True):
    """(loss, aux), gradients of the arch's `lm_loss` at the tiny preset's
    own parameters on one batch of ids; with `run` false, the jaxpr."""
    mod = ARCHS[arch][0]
    state = mod.lm_init(jax.random.key(0), cfg)
    rest = {n: state[n] for n in mod.LM_READS}
    ids = jax.random.randint(jax.random.key(1), (2, cfg.seq_len), 0,
                             cfg.vocab_size)
    fn = jax.value_and_grad(lambda p: mod.lm_loss(p, rest, ids, cfg),
                            has_aux=True)
    if not run:
        return jax.make_jaxpr(fn)(state["params"]).jaxpr
    return jax.jit(fn)(state["params"])


def _sub_jaxprs(jaxpr):
    """Every jaxpr nested in the equations of `jaxpr`, itself included;
    kernel bodies are not entered."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _sub_jaxprs(sub)


def _kernel_sites(jaxpr):
    """`pallas_call` equations by the kernel's name."""
    return collections.Counter(
        eqn.params["name"] for sub in _sub_jaxprs(jaxpr) for eqn in sub.eqns
        if eqn.primitive.name == "pallas_call")


def _bare_checkpoint(monkeypatch):
    """The wrapper replaced by what stood in its place: a bare
    `jax.checkpoint`, which keeps a block's input and nothing else."""
    for mod in (loop_lm, mla_moe):
        monkeypatch.setattr(mod, "recomputed", jax.checkpoint)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_one_forward_kernel_for_each_backward_kernel(arch):
    """Call sites in the gradient's jaxpr (the looped arch's scan holds its
    body once): as many `flash_fwd` as `flash_dq_dkv`, one per block."""
    sites = _kernel_sites(_loss_and_grads(arch, _model(arch), run=False))
    assert sites["flash_fwd"] == sites["flash_dq_dkv"] == ARCHS[arch][2]


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_a_bare_checkpoint_runs_every_forward_kernel_twice(
        arch, monkeypatch):
    """The count sees what the wrapper cures: with a bare `jax.checkpoint`
    the recomputation holds the forward kernel again."""
    _bare_checkpoint(monkeypatch)
    sites = _kernel_sites(_loss_and_grads(arch, _model(arch), run=False))
    assert sites["flash_fwd"] == 2 * sites["flash_dq_dkv"] > 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_the_names_alone_keep_nothing(arch, monkeypatch):
    """Names under a policy that saves nothing are identities: both halves
    (the names on the kernel's outputs, the policy in the wrapper) are
    needed."""
    monkeypatch.setattr(token_ops, "_KEEP",
                        jax.checkpoint_policies.nothing_saveable)
    sites = _kernel_sites(_loss_and_grads(arch, _model(arch), run=False))
    assert sites["flash_fwd"] == 2 * sites["flash_dq_dkv"] > 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_loss_and_gradients_are_what_a_bare_checkpoint_gives(
        arch, monkeypatch):
    """Bit for bit: the kept float32 arrays are the ones the recomputation
    would have written."""
    cfg = _model(arch)
    (loss, aux), grads = _loss_and_grads(arch, cfg)
    _bare_checkpoint(monkeypatch)
    (loss0, aux0), grads0 = _loss_and_grads(arch, cfg)
    assert np.array_equal(loss, loss0)
    assert np.array_equal(aux["loss"], aux0["loss"])
    flat, flat0 = (jax.tree_util.tree_leaves_with_path(g)
                   for g in (grads, grads0))
    assert len(flat) == len(flat0) > 10
    for (path, a), (_, b) in zip(flat, flat0):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)
        assert np.any(np.asarray(a) != 0), jax.tree_util.keystr(path)


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_the_step_counts_the_attention_outputs_it_keeps(arch, use_pallas):
    """`attn_outputs_kept`: layers (+ the multi-token module) x passes with
    the kernels, 0 with the dense fallback, which names nothing."""
    mod, _, _, kept = ARCHS[arch]
    cfg = _model(arch, use_pallas)
    state = mod.lm_init(jax.random.key(0), cfg)
    rest = {n: state[n] for n in mod.LM_READS}
    ids = jnp.zeros((2, cfg.seq_len), jnp.int32)
    # a constant of the program: everything else is dead code under jit
    got = jax.jit(lambda p: mod.lm_metrics(mod.lm_loss(p, rest, ids, cfg)[1])[
        "attn_outputs_kept"])(state["params"])
    assert got.dtype == jnp.float32 and got.shape == ()
    assert float(got) == (kept if use_pallas else 0)
    if not use_pallas:
        jaxpr = _loss_and_grads(arch, cfg, run=False)
        assert "flash_fwd" not in _kernel_sites(jaxpr)


def _sagan16_step():
    """The sagan128 preset's train step cut to 16 px (attention at 8 x 8)
    and batch 4, with the shapes of its arguments."""
    from dcgan_tpu.train import make_train_step

    cfg = get_preset("sagan128")
    cfg = dataclasses.replace(
        cfg, batch_size=4, model=dataclasses.replace(
            cfg.model, output_size=16, gf_dim=8, df_dim=8, attn_res=8))
    fns = make_train_step(cfg)
    args = (jax.eval_shape(fns.init, jax.random.key(0)),
            jax.ShapeDtypeStruct((4, 16, 16, 3), jnp.float32),
            jax.eval_shape(lambda: jax.random.key(1)))
    return fns.train_step, args


def test_a_step_that_recomputes_nothing_lowers_as_before(monkeypatch):
    """The image families call `flash_attention` under no `jax.checkpoint`:
    the step holds its four forward and four backward call sites (five
    forward ones before PR 34 ran G's forward once), and the names leave
    no operation behind. The lowered text is the text without
    the names, but for the numbers MLIR appends to the private functions'
    symbols (`@closed_call_285` / `@closed_call_284`)."""
    def lowered():
        step, args = _sagan16_step()
        return re.sub(r"@(\w+?)_\d+\b", r"@\1",
                      jax.jit(step).lower(*args).as_text())

    step, args = _sagan16_step()
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    assert _kernel_sites(jaxpr) == {"flash_fwd": 4, "flash_dq_dkv": 4}
    names = [eqn.params["name"] for sub in _sub_jaxprs(jaxpr)
             for eqn in sub.eqns if eqn.primitive.name == "name"]
    assert sorted(set(names)) == sorted(token_ops.KEPT_NAMES)
    assert len(names) == 2 * 4        # the four differentiated forwards
    with_names = lowered()
    monkeypatch.setattr(pallas_attention, "checkpoint_name",
                        lambda x, name: x)
    assert lowered() == with_names
