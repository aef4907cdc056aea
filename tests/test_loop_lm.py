"""The looped token family (models/loop_lm.py, the likelihood step): the
program against the plain reference computed in blocks, the gradient of a
weight used four times, the exit distribution, the one-pass model as a plain
decoder, the step over a data mesh, the trainer on the published preset cut
to a tiny size, and what the config refuses by name."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.config import (
    LM_LOSS,
    LOOP_ARCH,
    TOKEN_ARCHS,
    LoopModelConfig,
    MeshConfig,
    TrainConfig,
    config_from_dict,
    config_to_dict,
    is_token_arch,
    resolve_model_config,
    save_config,
)
from dcgan_tpu.models import loop_lm
from dcgan_tpu.presets import get_preset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = LoopModelConfig(compute_dtype="float32")      # the tiny preset's model
TINY = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
            intermediate_size=128, num_attention_heads=2,
            num_key_value_heads=2, head_dim=32, seq_len=32,
            compute_dtype="float32")


@pytest.fixture(scope="module")
def reference():
    """The benchmark's plain reference, loaded by path as the family does."""
    path = os.path.join(REPO, "benchmark", "families", "loop_lm_reference.py")
    spec = importlib.util.spec_from_file_location("loop_lm_reference_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _drawn(cfg, seed=0, gate_bias=-0.85):
    """Parameters with weights large enough that every part matters, norm
    gains away from 1 and the gate near 0.3, and a batch of ids."""
    kp, kn, ki = jax.random.split(jax.random.key(seed), 3)
    params = loop_lm.loop_init(kp, cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(kn, len(flat))
    out = []
    for (path, leaf), k in zip(flat, keys):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            leaf = 1.0 + 0.1 * jax.random.normal(k, leaf.shape)
        elif "exit_gate" in name and "'b'" in name:
            leaf = jnp.full(leaf.shape, gate_bias)
        elif "table" in name:
            leaf = jax.random.normal(k, leaf.shape)
        else:
            leaf = jax.random.normal(k, leaf.shape) * leaf.shape[0] ** -0.5
        out.append(leaf)
    ids = jax.random.randint(ki, (2, cfg.seq_len), 0, cfg.vocab_size)
    return jax.tree_util.tree_unflatten(tree, out), ids


def _as_dict(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def _close(got, want, rtol):
    """Every leaf to `rtol` of the leaf's own largest element."""
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=rtol * scale, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("use_pallas", [True, False])
def test_program_against_the_reference(reference, use_pallas):
    """Objective, each exit's loss, the exit mass to 1e-5 and every leaf of
    the gradient to 1e-4: the scan over four passes (kernels in interpret
    mode, or dense masked attention) against the reference's hand-written
    backward that adds a layer's gradient over its four uses."""
    cfg = dataclasses.replace(CFG, use_pallas=use_pallas)
    params, ids = _drawn(cfg)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: loop_lm.loop_loss(p, ids, cfg), has_aux=True))(params)
    m = _as_dict(cfg)
    ref_grads, ref_losses, ref_mass = reference.make_gradient(m, "float32")(
        params, ids, **reference.switches(m))
    np.testing.assert_allclose(loss, ref_losses["loss"], rtol=1e-5)
    for t in range(cfg.total_ut_steps):
        np.testing.assert_allclose(aux["loss_ut"][t],
                                   ref_losses[f"loss_ut{t + 1}"], rtol=1e-5)
    np.testing.assert_allclose(aux["exit_mass"], ref_mass, rtol=1e-5)
    np.testing.assert_allclose(aux["exit_entropy"],
                               ref_losses["exit_entropy"], rtol=1e-5)
    np.testing.assert_allclose(aux["exit_mean_step"],
                               ref_losses["exit_mean_step"], rtol=1e-5)
    _close(grads, ref_grads, 1e-4)
    assert float(jnp.min(jnp.abs(grads["exit_gate"]["w"]).max())) > 0


def test_a_shared_leaf_gets_the_sum_of_its_four_uses(reference):
    """The gradient the scan accumulates for a layer equals the sum of the
    four per-pass gradients of an UNROLLED copy in which every pass has
    parameters of its own (the reference's pieces, differentiated by JAX),
    and no single pass's gradient is the whole."""
    params, ids = _drawn(CFG, seed=3)
    m = _as_dict(CFG)
    steps, layers = CFG.total_ut_steps, [f"block{i}" for i in range(2)]

    def unshared(stacks):
        x = params["embed"]["table"][ids]
        left, total = jnp.ones(ids.shape), 0.0
        for t, stack in enumerate(stacks):
            for name in layers:
                x = reference.block(stack[name], x, jnp.bool_(True), m=m,
                                    operand="float32")
            x = reference.rms_norm(x, params["final_norm"]["scale"],
                                   CFG.rms_norm_eps)
            (share, left), _ = reference.exit_step(
                params, x, left, ids, jnp.bool_(t == steps - 1),
                jnp.bool_(True), m=m, operand="float32")
            total = total + share
        return total

    stack = {n: params[n] for n in layers}
    per_pass = jax.jit(jax.grad(unshared))([stack] * steps)
    grads = jax.jit(jax.grad(
        lambda p: loop_lm.loop_loss(p, ids, CFG)[0]))(params)
    summed = jax.tree.map(lambda *g: sum(g), *per_pass)
    _close({n: grads[n] for n in layers}, summed, 1e-4)
    w = lambda g: g["block0"]["q_proj"]["w"]
    for g in per_pass:
        assert float(jnp.linalg.norm(w(g) - w(summed))) \
            > 0.2 * float(jnp.linalg.norm(w(summed)))


@pytest.mark.parametrize("bias, exit_", [(-30.0, 3), (30.0, 0)])
def test_the_exit_distribution_sums_to_one(bias, exit_):
    """The masses of the exits add up to the positions scored at any gate;
    a gate that never opens leaves everything to the last exit, one that
    always opens gives everything to the first."""
    params, ids = _drawn(CFG)
    scored = 2 * (CFG.seq_len - 1)
    _, aux = jax.jit(lambda p: loop_lm.loop_loss(p, ids, CFG))(params)
    np.testing.assert_allclose(jnp.sum(aux["exit_mass"]), scored, rtol=1e-6)
    assert float(jnp.min(aux["exit_mass"])) > 0.1 * scored
    shut = {**params, "exit_gate": {
        "w": jnp.zeros_like(params["exit_gate"]["w"]),
        "b": jnp.full((1,), bias)}}
    loss, aux = jax.jit(lambda p: loop_lm.loop_loss(p, ids, CFG))(shut)
    np.testing.assert_allclose(aux["exit_mass"][exit_], scored, rtol=1e-6)
    np.testing.assert_allclose(aux["exit_mean_step"], exit_ + 1, rtol=1e-6)
    np.testing.assert_allclose(aux["exit_entropy"], 0.0, atol=1e-6)
    # the objective is then that exit's cross-entropy
    np.testing.assert_allclose(loss, aux["loss_ut"][exit_], rtol=1e-5)


def test_one_pass_without_the_entropy_term_is_a_plain_decoder(reference):
    """`total_ut_steps=1`, `loss_beta=0`: the loss is the mean next-token
    cross-entropy of a plain 2-layer decoder with a final norm."""
    cfg = dataclasses.replace(CFG, total_ut_steps=1, loss_beta=0.0)
    params, ids = _drawn(cfg)
    m = _as_dict(cfg)
    x = params["embed"]["table"][ids]
    for name in ("block0", "block1"):
        x = reference.block(params[name], x, jnp.bool_(True), m=m,
                            operand="float32")
    x = reference.rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
    ce = reference.cross_entropy(x, params["lm_head"]["w"],
                                 jnp.roll(ids, -1, axis=1),
                                 "float32").reshape(ids.shape)
    loss, aux = jax.jit(lambda p: loop_lm.loop_loss(p, ids, cfg))(params)
    np.testing.assert_allclose(loss, jnp.mean(ce[:, :-1]), rtol=1e-5)
    np.testing.assert_allclose(aux["loss_ut"][0], loss, rtol=1e-6)


def test_the_step_is_the_same_on_one_device_and_over_a_data_mesh():
    """Counters, the per-exit mass and the parameters after two steps agree
    between one device and a 2-way data mesh (loss and gradient per shard
    inside a shard_map, averaged), with the kernels in interpret mode; the
    state and the metrics hold nothing of the routed arch."""
    from dcgan_tpu.parallel import make_mesh, make_parallel_train

    cfg = get_preset("loop_lm_tiny")
    ids = jax.random.randint(jax.random.key(1), (8, 32), 0, 256)
    out = []
    for n in (1, 2):
        c = dataclasses.replace(cfg, mesh=MeshConfig(data=n))
        pt = make_parallel_train(c, make_mesh(c.mesh, jax.devices()[:n]))
        state = pt.init(jax.random.key(0))
        for i in range(2):
            state, m = pt.step(state, ids, jax.random.key(i))
        assert all(np.ndim(v) == 0 for v in m.values())
        assert sorted(pt.programs) == ["init", "train_step"]
        out.append((jax.device_get(m), jax.device_get(state)))
    (m1, s1), (m2, s2) = out
    assert sorted(m1) == ["attn_outputs_kept", "exit_entropy",
                          "exit_mean_step", "loss", "loss_ut1", "loss_ut2",
                          "loss_ut3", "loss_ut4"]
    assert m1["attn_outputs_kept"] == m2["attn_outputs_kept"] == 2 * 4
    assert sorted(s1) == ["exit_mass", "opt", "params", "step"]
    for k in m1:
        np.testing.assert_allclose(m1[k], m2[k], rtol=1e-5)
    np.testing.assert_allclose(s1["exit_mass"], s2["exit_mass"], rtol=1e-5)
    np.testing.assert_allclose(np.sum(s1["exit_mass"]), 2 * 8 * 31, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    assert int(s1["step"]) == 2


def test_the_step_names_its_scopes():
    """Every scope PERF.md section 3 lists is in the lowered step, and no
    path holds `loop` twice (the readers sum the paths that END in a name,
    so a repeated scope would count its operations twice). The recomputed
    block runs its projections again and no forward kernel.

    Only scope paths are read: a named location with a `/` in it
    (`loop/block0/ffn/dot_general`). The text also holds file names and
    Python frames, and those are not the step's alone: jax keeps the
    jaxprs of its jitted library functions (`jax.nn.silu`, `logsumexp`)
    by shape, each with the frames of its FIRST trace, so after the routed
    arch's tests in one process this step's text names
    `models/mla_moe.py` and `moe_apply` as callers of `token_ops`."""
    import re

    from dcgan_tpu.train.steps import make_lm_train_step

    cfg = get_preset("loop_lm_tiny")
    fns = make_lm_train_step(cfg)
    state = jax.eval_shape(fns.init, jax.random.key(0))
    ids = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    text = jax.jit(fns.train_step).lower(
        state, ids, jax.random.key(0)).as_text(debug_info=True)
    paths = {p for p in re.findall(r'loc\("([^"]+)"\(', text) if "/" in p}
    parts = [[re.sub(r"^\w+\((.*)\)$", r"\1", q) for q in p.split("/")]
             for p in paths]
    named = {q for p in parts for q in p}
    for scope in ("embed", "loop", "block0", "block1", "attn_block",
                  "qkv_proj", "rope", "attn", "o_proj", "ffn", "exit", "head",
                  "loss", "adam"):
        assert scope in named, scope
    assert any(p.count("qkv_proj") and "checkpoint" in p for p in parts)
    assert any("flash_fwd" in p for p in parts)
    for p in parts:
        # the backward pass (`checkpoint/..`) holds the backward kernel
        # and, in its recomputation, no forward kernel
        if "flash_fwd" in p or "flash_dq_dkv" in p:
            assert ("checkpoint" in p) == ("flash_dq_dkv" in p), p
        assert p.count("loop") <= 1, p
        if "attn_block" in p or "ffn" in p:
            assert p.count("loop") == 1 and "head" not in p, p
        if "head" in p or "exit" in p:
            assert "loop" not in p, p
    assert not any("moe" in q for q in named)


@pytest.mark.parametrize("field, value", [
    ("sample_every_steps", 100), ("fid_every_steps", 1000),
    ("g_ema_decay", 0.999), ("precision", "bf16")])
def test_image_only_services_refuse_by_name(field, value):
    cfg = get_preset("loop_lm_tiny")
    with pytest.raises(ValueError, match=f"{field}.*'loop_lm' refuses"):
        dataclasses.replace(cfg, **{field: value})


def test_config_rules_and_round_trip():
    with pytest.raises(ValueError, match="go together"):
        TrainConfig(model=LoopModelConfig())
    with pytest.raises(ValueError, match="num_key_value_heads"):
        LoopModelConfig(num_key_value_heads=1)
    with pytest.raises(ValueError, match="LoopModelConfig.arch"):
        LoopModelConfig(arch="mla_moe")
    # no inference path reads the threshold: only "never exit early" is true
    with pytest.raises(ValueError, match="early_exit_threshold must be 1"):
        LoopModelConfig(early_exit_threshold=0.9)
    cfg = get_preset("loop_lm_tiny")
    assert cfg.model.arch == LOOP_ARCH and cfg.loss == LM_LOSS
    assert config_from_dict(config_to_dict(cfg)) == cfg
    assert all(is_token_arch(a) for a in TOKEN_ARCHS)
    assert not is_token_arch("dcgan")
    big = get_preset("ouro_2_6b")
    assert (big.model.num_hidden_layers, big.model.total_ut_steps,
            big.model.vocab_size, big.model.seq_len, big.batch_size) \
        == (48, 4, 49152, 4096, 1)


@pytest.mark.parametrize("preset", ["loop_lm_tiny", "mla_moe_tiny"])
def test_checkpoint_consumers_refuse_a_token_arch(tmp_path, preset):
    """generate, evals, export and serve resolve their model through
    `resolve_model_config`, which refuses every token arch alike."""
    save_config(get_preset(preset), str(tmp_path))
    with pytest.raises(ValueError, match="one-network token family"):
        resolve_model_config(str(tmp_path))
    with pytest.raises(ValueError, match="one-network token family"):
        resolve_model_config(str(tmp_path / "none"), preset=preset)


def test_trainer_trains_the_published_preset_cut_to_a_tiny_size(tmp_path,
                                                                capsys):
    """`trainer.train` on preset `ouro_2_6b` overridden to the tiny sizes:
    id batches through the feed and `DevicePrefetcher`, the likelihood step
    through `make_parallel_train`, a checkpoint at the end; real data
    refused by name."""
    from dcgan_tpu.train.trainer import train

    big = get_preset("ouro_2_6b")
    cfg = dataclasses.replace(
        big, model=dataclasses.replace(big.model, **TINY), batch_size=8,
        checkpoint_dir=str(tmp_path / "ck"), sample_dir=str(tmp_path / "sm"),
        tensorboard=False)
    state = train(cfg, synthetic_data=True, max_steps=3)
    assert int(state["step"]) == 3
    out = capsys.readouterr().out
    assert "step 3" in out and "loss_ut4" in out and "exit_mean_step" in out
    assert "d_loss" not in out and "moe" not in out
    np.testing.assert_allclose(
        np.sum(jax.device_get(state["exit_mass"])), 3 * 8 * 31, rtol=1e-5)
    with pytest.raises(ValueError, match="synthetic ids only"):
        train(cfg, synthetic_data=False, max_steps=1)
