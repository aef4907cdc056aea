"""The decoder-hybrid-decoder token family (models/sambay.py, the likelihood
step): the program against the plain reference computed in blocks, the
gradients that cross blocks (the memory and the shared keys and values from
BOTH their uses, the tied leaf from both of its), the published layout and
its counts, the vocabulary slices against the uncut head, the step over a
data mesh, its scopes and kernels, the trainer on the published preset cut
to a tiny size, and what the config refuses by name."""

import collections
import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.config import (
    LM_LOSS,
    SAMBAY_ARCH,
    MeshConfig,
    SambaYModelConfig,
    TrainConfig,
    config_from_dict,
    config_to_dict,
    is_token_arch,
    resolve_model_config,
    sambay_layout,
    save_config,
)
from dcgan_tpu.models import sambay, token_ops
from dcgan_tpu.presets import get_preset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = SambaYModelConfig(compute_dtype="float32")    # the tiny preset's model
TINY = dict(vocab_size=256, hidden_size=64, num_hidden_layers=6,
            layer_types=CFG.layer_types, intermediate_size=256,
            num_attention_heads=4, num_key_value_heads=2, sliding_window=8,
            mamba_dt_rank=0, seq_len=32, compute_dtype="float32")


@pytest.fixture(scope="module")
def reference():
    """The benchmark's plain reference, loaded by path as the family does."""
    path = os.path.join(REPO, "benchmark", "families", "sambay_reference.py")
    spec = importlib.util.spec_from_file_location("sambay_reference_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _drawn(cfg, seed=0):
    """Parameters with weights large enough that every part matters, norm
    gains away from 1, biases away from 0 (the scan's own leaves as the init
    draws them), and a batch of ids."""
    kp, kn, ki = jax.random.split(jax.random.key(seed), 3)
    params = sambay.sambay_init(kp, cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(kn, len(flat))
    out = []
    for (path, leaf), k in zip(flat, keys):
        name = jax.tree_util.keystr(path)
        draw = lambda std: std * jax.random.normal(k, leaf.shape)
        if "A_log" in name or "'D'" in name or "dt_proj']['b'" in name:
            pass
        elif "scale" in name:
            leaf = 1.0 + draw(0.1)
        elif "bias" in name or "'b'" in name:
            leaf = draw(0.1)
        elif "lambda" in name:
            leaf = draw(0.3)
        elif "table" in name:
            leaf = draw(1.0)
        else:
            leaf = draw(leaf.shape[0] ** -0.5)
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


def _loss_and_grads(params, ids, cfg=CFG):
    return jax.jit(jax.value_and_grad(
        lambda p: sambay.sambay_loss(p, ids, cfg), has_aux=True))(params)


@pytest.fixture(scope="module")
def sound(reference):
    """The drawn parameters and ids, the program's loss and gradient, and
    the reference's compiled gradient."""
    params, ids = _drawn(CFG)
    return (params, ids, _loss_and_grads(params, ids),
            reference.make_gradient(_as_dict(CFG), "float32"))


def test_program_against_the_reference(reference, sound):
    """The loss and the memory's readings to 1e-5 and every leaf of the
    gradient to 1e-4, every kind of layer present: the kernels in interpret
    mode and the autodiff of the checkpointed blocks against a scan over
    time, dense masked maps written out four at a time and a backward pass
    that adds the crossing cotangents by hand."""
    params, ids, ((loss, aux), grads), gradient = sound
    assert sorted(set(CFG.layer_types)) == sorted(
        ["mamba", "attn_win", "attn_full", "gmu", "attn_cross"])
    ref_grads, ref_losses, ref = gradient(params, ids, reference.switches())
    np.testing.assert_allclose(loss, ref_losses["loss"], rtol=1e-5)
    np.testing.assert_allclose(aux["mem_abs"], ref["mem_abs"], rtol=1e-5)
    np.testing.assert_allclose(aux["mem_rms"], ref["mem_rms"], rtol=1e-5)
    np.testing.assert_allclose(aux["diff_lambda"], ref["diff_lambda"],
                               rtol=1e-5)
    assert 1e-3 < float(aux["dt_mean"]) < 0.3
    _close(grads, ref_grads, 1e-4)
    for leaf in jax.tree.leaves(grads):
        assert float(jnp.max(jnp.abs(leaf))) > 0


def test_two_steps_of_adam_against_the_reference(reference, sound):
    """The parameters' change over two steps of the program's step to 1e-4
    of the reference's (Adam with the moments parked on the host)."""
    from dcgan_tpu.train.steps import make_lm_train_step

    params, ids, _, _ = sound
    cfg = get_preset("sambay_tiny")
    fns = make_lm_train_step(cfg)
    state = {**fns.init(jax.random.key(0)), "params": params}
    step = reference.make_step(_as_dict(CFG), dict(
        beta1=cfg.beta1, beta2=0.999, adam_eps=1e-8,
        learning_rate=cfg.learning_rate), "float32")
    # the reference updates in place (donated): its own copy
    ref = reference.init_state({"params": jax.tree.map(jnp.copy, params)})
    train = jax.jit(fns.train_step)
    for i in range(2):
        state, _ = train(state, ids, jax.random.key(i))
        ref, _, _, _ = step(ref, ids, reference.switches(), last=i == 1)
    change = lambda new: jax.tree.map(lambda a, b: a - b, new, params)
    got, want = change(state["params"]), change(ref["params"])
    # the keys' bias has no gradient (a shift of every key alike leaves the
    # softmax as it was): Adam turns its round-off into steps of either
    # sign, and the comparison leaves that third of the leaf out, as
    # benchmark/families/sambay.py does by `check.nought_leaves`
    h, kv = CFG.hidden_size, CFG.num_key_value_heads * CFG.head_dim
    for tree in (got, want):
        for name in ("block1", "block3"):
            b = tree[name]["mixer"]["qkv_proj"]
            b["b"] = b["b"].at[h:h + kv].set(0.0)
    # every leaf's change by its norm to 1e-4; element by element Adam
    # turns any gradient near nought into a step of either sign, so the
    # difference's norm to a twentieth of the change's
    norms = lambda tree: jax.tree.map(jnp.linalg.norm, tree)
    _close(norms(got), norms(want), 1e-4)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert float(jnp.linalg.norm(a - b)) \
            <= 0.05 * float(jnp.linalg.norm(b)), jax.tree_util.keystr(path)


@pytest.mark.parametrize("fault,patched,leaves", [
    # the gated memory unit's use of m: without it the memory layer's scan
    # leaves keep the gradient of their own layer's use alone
    ("m_grad", "gmu_apply", ("block2", "mixer", "A_log")),
    # the cross layer's use of the shared keys and values
    ("kv_grad", "cross_attn_apply", ("block3", "mixer", "qkv_proj"))])
def test_a_producer_gets_the_gradient_of_both_uses(reference, sound,
                                                   monkeypatch, fault,
                                                   patched, leaves):
    """The program with the consumer's use detached is the reference with
    that cotangent dropped, and differs from the whole gradient on the
    producer's leaves: the whole one holds both uses."""
    params, ids, (_, grads), gradient = sound
    real = getattr(sambay, patched)

    def detached(p, x, crossing, *rest):
        return real(p, x, jax.lax.stop_gradient(crossing), *rest)

    monkeypatch.setattr(sambay, patched, detached)
    _, cut = _loss_and_grads(params, ids)
    ref_cut, _, _ = gradient(params, ids, reference.switches(**{fault: False}))
    _close(cut, ref_cut, 1e-4)
    pick = lambda tree: jax.tree.leaves(
        tree[leaves[0]][leaves[1]][leaves[2]])[-1]
    whole, part = pick(grads), pick(cut)
    assert float(jnp.max(jnp.abs(whole - part))) \
        > 1e-3 * float(jnp.max(jnp.abs(whole)))
    assert float(jnp.max(jnp.abs(part))) > 0


def test_the_tied_leaf_gets_the_gradient_of_both_uses(sound, monkeypatch):
    """One leaf, two uses: with the head's use detached what is left is the
    look-up's gradient, on the rows of the batch's ids alone (the last
    position's excepted: nothing scored reads it); the rest of the whole
    gradient, the head's, reaches every row."""
    params, ids, (_, grads), _ = sound
    real = sambay.head_loss
    monkeypatch.setattr(
        sambay, "head_loss", lambda h, scale, w, *a, **kw: real(
            h, scale, jax.lax.stop_gradient(w), *a, **kw))
    _, cut = _loss_and_grads(params, ids)
    lookup = np.asarray(cut["embed"]["table"])
    head = np.asarray(grads["embed"]["table"]) - lookup
    seen = np.zeros(CFG.vocab_size, bool)
    seen[np.asarray(ids)[:, :-1].ravel()] = True
    assert np.all(np.abs(lookup[~seen]).max(axis=1) == 0)
    assert np.all(np.abs(lookup[seen]).max(axis=1) > 0)
    assert np.all(np.abs(head).max(axis=1) > 0)
    assert sorted(params) == ["block0", "block1", "block2", "block3",
                              "block4", "block5", "embed", "final_norm"]


def test_the_published_layout_and_its_counts(reference):
    """32 layers: 9 Mamba (8 + the memory layer), 8 window + 1 full, 7
    gated memory units, 7 cross; 3,852,562,944 parameters as published and
    697,094,272 in the shipped cut, from the program's own shapes and from
    the reference's arithmetic alike."""
    kinds = sambay_layout(32)
    assert collections.Counter(kinds) == {
        "mamba": 9, "attn_win": 8, "attn_full": 1, "gmu": 7, "attn_cross": 7}
    assert kinds[:4] == ("mamba", "attn_win", "mamba", "attn_win")
    assert kinds[16:20] == ("mamba", "attn_full", "gmu", "attn_cross")
    assert kinds[30:] == ("gmu", "attn_cross")
    big = get_preset("phi_4_mini_flash").model
    assert big.layer_types == kinds and big.memory_layer == 16
    assert (big.head_dim, big.d_inner, big.dt_rank) == (64, 5120, 160)
    count = lambda cfg: sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda k: sambay.sambay_init(k, cfg), jax.random.key(0))))
    cut = dataclasses.replace(big, num_hidden_layers=6, vocab_size=25008,
                              layer_types=CFG.layer_types)
    assert count(big) == reference.parameter_count(_as_dict(big))["total"] \
        == 3852562944
    assert count(cut) == reference.parameter_count(_as_dict(cut))["total"] \
        == 697094272
    assert cut.memory_layer == 2
    assert [round(sambay.lambda_init(i), 3) for i in (1, 3, 5)] \
        == [0.356, 0.556, 0.666]


def test_the_vocabulary_slices_side_by_side_are_the_uncut_head():
    """Eight chips hold an eighth of the tied table each. On ids of its own
    slice a chip's trunk is the uncut model's (the look-up reads the same
    rows), and the eight slices' logits side by side are the uncut head's:
    a sliced vocabulary leaves nothing out but the other chips' columns."""
    params, _ = _drawn(CFG, seed=2)
    table = params["embed"]["table"]
    rows = CFG.vocab_size // 8
    small = dataclasses.replace(CFG, vocab_size=rows)
    local = jax.random.randint(jax.random.key(9), (1, CFG.seq_len), 0, rows)

    def logits(cfg, table, ids):
        p = {**params, "embed": {"table": table}}
        x, _, _ = sambay.trunk(p, ids, cfg)
        h = sambay.layer_norm(x, p["final_norm"], cfg.layer_norm_eps)
        return token_ops.mm(h, table.T, jnp.float32)

    whole, one = jax.jit(logits, static_argnums=0), \
        jax.jit(logits, static_argnums=0)
    for k in (0, 3, 7):
        mine = slice(k * rows, (k + 1) * rows)
        full = whole(CFG, table, local + k * rows)          # [1, S, V]
        parts = [one(small, table[j * rows:(j + 1) * rows],
                     local if j == k else jnp.zeros_like(local))
                 for j in range(8)]
        np.testing.assert_allclose(parts[k], full[..., mine], rtol=1e-5,
                                   atol=1e-5)
    # the head alone, any state: the columns are the slices'
    h = jax.random.normal(jax.random.key(4), (5, CFG.hidden_size))
    side_by_side = jnp.concatenate(
        [token_ops.mm(h, table[j * rows:(j + 1) * rows].T, jnp.float32)
         for j in range(8)], axis=-1)
    np.testing.assert_allclose(side_by_side,
                               token_ops.mm(h, table.T, jnp.float32),
                               rtol=1e-6, atol=1e-6)


def _sub_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _sub_jaxprs(sub)


def test_one_forward_kernel_for_each_backward_kernel(sound):
    """Call sites in the gradient's jaxpr: the recomputation holds no
    forward kernel, of attention or of the scan (both keep their outputs by
    name); the step counts the three attention outputs it keeps."""
    params, ids, ((_, aux), _), _ = sound
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: sambay.sambay_loss(p, ids, CFG)[0]))(params).jaxpr
    sites = collections.Counter(
        eqn.params["name"] for sub in _sub_jaxprs(jaxpr) for eqn in sub.eqns
        if eqn.primitive.name == "pallas_call")
    assert sites == {"flash_fwd": 2, "flash_dq_dkv": 2, "flash_fwd_win": 1,
                     "flash_dq_dkv_win": 1, "ssm_scan_fwd": 2,
                     "ssm_scan_bwd": 2}
    assert float(aux["attn_kept"]) == 3


def test_the_step_is_the_same_on_one_device_and_over_a_data_mesh():
    """Counters, the memory's per-channel mean and the parameters agree
    between one device and a 2-way data mesh (loss and gradient per shard
    inside a shard_map, averaged), the kernels in interpret mode: the first
    step to 1e-5; after the second to a few learning rates, since Adam
    turns a gradient near nought (the keys' bias, `A_log`) into a step of
    either sign and the two reductions round differently."""
    from dcgan_tpu.parallel import make_mesh, make_parallel_train

    cfg = get_preset("sambay_tiny")
    ids = jax.random.randint(jax.random.key(1), (4, 32), 0, 256)
    out = []
    for n in (1, 2):
        c = dataclasses.replace(cfg, mesh=MeshConfig(data=n))
        pt = make_parallel_train(c, make_mesh(c.mesh, jax.devices()[:n]))
        state = pt.init(jax.random.key(0))
        state, first = pt.step(state, ids, jax.random.key(0))
        first = jax.device_get((first, state["mem_abs"]))
        state, m = pt.step(state, ids, jax.random.key(1))
        assert all(np.ndim(v) == 0 for v in m.values())
        assert sorted(pt.programs) == ["init", "train_step"]
        out.append((first, jax.device_get(m), jax.device_get(state)))
    ((f1, mem1), m1, s1), ((f2, mem2), m2, s2) = out
    assert sorted(m1) == ["attn_outputs_kept", "diff_lambda", "dt_mean",
                          "loss", "mem_rms"]
    assert m1["attn_outputs_kept"] == m2["attn_outputs_kept"] == 3
    assert sorted(s1) == ["mem_abs", "opt", "params", "step"]
    for k in m1:
        np.testing.assert_allclose(f1[k], f2[k], rtol=1e-5)
        np.testing.assert_allclose(m1[k], m2[k], rtol=2e-3)
    np.testing.assert_allclose(mem1, mem2, rtol=1e-5)
    assert mem1.shape == (128,) and np.all(mem1 > 0)
    np.testing.assert_array_less(mem1, s1["mem_abs"])
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(a, b, rtol=0, atol=4 * cfg.learning_rate)
    assert int(s1["step"]) == 2


def test_the_step_names_its_scopes():
    """Every scope PERF.md section 3 lists is in the lowered step, and
    every scope path holds ONE `block<i>` at most (the readers sum the paths
    that END in a name, so a repeated scope would count its operations
    twice). The recomputed block runs its projections again and no forward
    kernel. Only scope paths are read (tests/test_loop_lm.py says why)."""
    from dcgan_tpu.train.steps import make_lm_train_step

    cfg = get_preset("sambay_tiny")
    fns = make_lm_train_step(cfg)
    state = jax.eval_shape(fns.init, jax.random.key(0))
    ids = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    text = jax.jit(fns.train_step).lower(
        state, ids, jax.random.key(0)).as_text(debug_info=True)
    paths = {p for p in re.findall(r'loc\("([^"]+)"\(', text) if "/" in p}
    parts = [[re.sub(r"^\w+\((.*)\)$", r"\1", q) for q in p.split("/")]
             for p in paths]
    named = {q for p in parts for q in p}
    for scope in ("embed", *(f"block{i}" for i in range(6)), "mamba",
                  "in_proj", "conv", "dt_proj", "scan", "out_proj",
                  "attn_win", "attn_full", "attn_cross", "qkv_proj", "attn",
                  "diff", "o_proj", "gmu", "mlp", "head", "loss", "adam"):
        assert scope in named, scope
    assert any(p.count("in_proj") and "checkpoint" in p for p in parts)
    kernels = {"flash_fwd", "flash_fwd_win", "ssm_scan_fwd"}
    backward = {"flash_dq_dkv", "flash_dq_dkv_win", "ssm_scan_bwd"}
    mixers = {"mamba", "attn_win", "attn_full", "attn_cross", "gmu"}
    for k in kernels | backward:
        assert any(k in p for p in parts), k
    for p in parts:
        blocks = [q for q in p if re.fullmatch(r"block\d", q)]
        assert len(blocks) <= 1, p
        if kernels & set(p) or backward & set(p):
            # the backward pass (`checkpoint/..`) holds the backward kernel
            # and, in its recomputation, no forward kernel
            assert ("checkpoint" in p) == bool(backward & set(p)), p
        if mixers & set(p) or "mlp" in p:
            assert len(blocks) == 1 and len(mixers & set(p)) <= 1, p
            assert "head" not in p, p
        if "head" in p:
            assert not blocks, p
    assert not any("moe" in q or q == "loop" for q in named)


@pytest.mark.parametrize("field, value", [
    ("sample_every_steps", 100), ("fid_every_steps", 1000),
    ("g_ema_decay", 0.999), ("precision", "bf16")])
def test_image_only_services_refuse_by_name(field, value):
    cfg = get_preset("sambay_tiny")
    with pytest.raises(ValueError, match=f"{field}.*'sambay' refuses"):
        dataclasses.replace(cfg, **{field: value})


def test_config_rules_and_round_trip():
    with pytest.raises(ValueError, match="go together"):
        TrainConfig(model=SambaYModelConfig())
    with pytest.raises(ValueError, match="SambaYModelConfig.arch"):
        SambaYModelConfig(arch="loop_lm")
    with pytest.raises(ValueError, match="must name num_hidden_layers"):
        SambaYModelConfig(num_hidden_layers=8)
    with pytest.raises(ValueError, match="ONE full-attention layer"):
        SambaYModelConfig(layer_types=("mamba", "attn_full") * 3)
    with pytest.raises(ValueError, match="puts a reader first"):
        SambaYModelConfig(layer_types=("mamba", "gmu", "mamba", "attn_full",
                                       "gmu", "attn_cross"))
    with pytest.raises(ValueError, match="pairs the heads"):
        SambaYModelConfig(num_key_value_heads=1)
    with pytest.raises(ValueError, match="ties embedding and head"):
        SambaYModelConfig(tie_word_embeddings=False)
    with pytest.raises(ValueError, match="mb_per_layer 2"):
        SambaYModelConfig(mb_per_layer=4)
    with pytest.raises(ValueError, match="even number of layers"):
        sambay_layout(7)
    cfg = get_preset("sambay_tiny")
    assert cfg.model.arch == SAMBAY_ARCH and cfg.loss == LM_LOSS
    assert is_token_arch(SAMBAY_ARCH)
    # JSON gives the layout back as a list: the config takes it as a tuple
    again = config_from_dict(config_to_dict(cfg))
    assert again == cfg and isinstance(again.model.layer_types, tuple)
    assert hash(again.model) == hash(cfg.model)
    big = get_preset("phi_4_mini_flash")
    assert (big.model.num_hidden_layers, big.model.vocab_size,
            big.model.seq_len, big.model.sliding_window, big.batch_size) \
        == (32, 200064, 8192, 512, 1)


def test_checkpoint_consumers_refuse_the_arch(tmp_path):
    """generate, evals, export and serve resolve their model through
    `resolve_model_config`, which refuses every token arch alike."""
    save_config(get_preset("sambay_tiny"), str(tmp_path))
    with pytest.raises(ValueError, match="one-network token family"):
        resolve_model_config(str(tmp_path))
    with pytest.raises(ValueError, match="one-network token family"):
        resolve_model_config(str(tmp_path / "none"), preset="sambay_tiny")


def test_trainer_trains_the_published_preset_cut_to_a_tiny_size(tmp_path,
                                                                capsys):
    """`trainer.train` on preset `phi_4_mini_flash` overridden to the tiny
    sizes: id batches through the feed and `DevicePrefetcher`, the
    likelihood step through `make_parallel_train`, a checkpoint at the end;
    real data refused by name."""
    from dcgan_tpu.train.trainer import train

    big = get_preset("phi_4_mini_flash")
    cfg = dataclasses.replace(
        big, model=dataclasses.replace(big.model, **TINY), batch_size=8,
        checkpoint_dir=str(tmp_path / "ck"), sample_dir=str(tmp_path / "sm"),
        tensorboard=False)
    state = train(cfg, synthetic_data=True, max_steps=3)
    assert int(state["step"]) == 3
    out = capsys.readouterr().out
    assert "step 3" in out and "loss" in out
    assert "d_loss" not in out and "moe" not in out and "loss_ut" not in out
    assert np.all(jax.device_get(state["mem_abs"]) > 0)
    with pytest.raises(ValueError, match="synthetic ids only"):
        train(cfg, synthetic_data=False, max_steps=1)
