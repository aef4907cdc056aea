"""The token family (models/mla_moe.py, the likelihood step): the expert
layer's share arithmetic, no token dropped at any imbalance, the trainer on
the tiny preset, and the image-only services refusing by name."""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.config import (
    LM_LOSS,
    TOKEN_ARCH,
    MeshConfig,
    ModelConfig,
    TokenModelConfig,
    TrainConfig,
    config_from_dict,
    config_to_dict,
)
from dcgan_tpu.models import mla_moe
from dcgan_tpu.presets import get_preset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def reference():
    """The benchmark's plain reference, loaded by path as the family does."""
    path = os.path.join(REPO, "benchmark", "families", "mla_moe_reference.py")
    spec = importlib.util.spec_from_file_location("mla_moe_reference_t", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CFG = TokenModelConfig(compute_dtype="float32", n_routed_experts=8,
                       num_experts_per_tok=3, experts_held=8)


def _layer(key, cfg, tokens=64):
    """One expert layer's parameters with all experts, a non-zero selection
    bias and `tokens` tokens."""
    kp, kb, kx = jax.random.split(key, 3)
    p = jax.tree.map(lambda w: 5.0 * w, mla_moe._moe_init(kp, cfg,
                                                          jnp.float32))
    bias = 0.1 * jax.random.normal(kb, (cfg.n_routed_experts,))
    x = jax.random.normal(kx, (tokens, cfg.hidden_size))
    return p, bias, x


def _share(p, cfg, first, held):
    cut = dataclasses.replace(cfg, experts_held=held, first_expert=first)
    experts = {n: w[first:first + held] for n, w in p["experts"].items()}
    return {**p, "experts": experts}, cut


@pytest.mark.parametrize("held", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(reference, held):
    """The routed parts that all shares give, with the shared expert (which
    every chip computes alike) counted once, add up to what the UNCUT
    reference gives for the whole layer; and the pair counts of the shares
    partition the uncut layer's."""
    p, bias, x = _layer(jax.random.key(0), CFG)
    m = dataclasses.asdict(CFG)
    whole, counts = reference.moe(p, bias, x, m, reference.switches(m),
                                  "float32")
    shared = mla_moe.swiglu_apply(p["shared"], x, jnp.float32)
    total, seen = shared, []
    for first in range(0, CFG.n_routed_experts, held):
        ps, cut = _share(p, CFG, first, held)
        y, c = mla_moe.moe_apply(ps, bias, x, cut)
        total = total + (y - shared)
        seen.append(np.asarray(c["counts"]))
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.concatenate(seen), np.asarray(counts))
    assert int(np.sum(counts)) == 64 * CFG.num_experts_per_tok


def test_no_token_is_dropped_when_every_token_selects_the_same_experts(
        reference):
    """The worst imbalance: a selection bias that sends EVERY token to the
    same three experts, all held here. Every pair is computed (counts 64
    each, the buffer's worst case filled to 3/8) and the result is the
    reference's."""
    p, _, x = _layer(jax.random.key(1), CFG)
    bias = jnp.zeros((8,)).at[jnp.array([1, 2, 3])].set(10.0)
    ps, cut = _share(p, CFG, 0, 4)
    y, c = mla_moe.moe_apply(ps, bias, x, cut)
    assert np.asarray(c["counts"]).tolist() == [0, 64, 64, 64]
    m = dataclasses.asdict(cut)
    want, _ = reference.moe(ps, bias, x, m, reference.switches(m), "float32")
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    # and none held: the shared expert alone, no NaN from the empty groups
    ps, cut = _share(p, CFG, 4, 4)
    y, c = mla_moe.moe_apply(ps, bias, x, cut)
    assert int(np.sum(c["counts"])) == 0 and int(c["rows"]) == 0
    np.testing.assert_allclose(
        y, mla_moe.swiglu_apply(p["shared"], x, jnp.float32), atol=1e-6)
    g = jax.grad(lambda q: jnp.sum(mla_moe.moe_apply(q, bias, x, cut)[0]))(ps)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(g))


# --- the sized buffer (C < m): 16 experts, 3 a token, 256 tokens -----------------

WIDE = TokenModelConfig(compute_dtype="float32", n_routed_experts=16,
                        num_experts_per_tok=3, experts_held=16)
TOKENS = 256


def _wide_layer(key, held, first, bias):
    """An expert layer's share at a size where the buffer is smaller than
    the worst case (768 pairs; 256, 384, 640 rows at 1, 2, 3 of 16 held).
    `bias`: "drawn", or a value laid on the held experts' selection bias
    (+10: every token selects them; -10: none does)."""
    p, drawn, x = _layer(key, WIDE, TOKENS)
    ps, cut = _share(p, WIDE, first, held)
    if bias != "drawn":
        drawn = jnp.zeros((16,)).at[first:first + held].set(bias)
    return ps, drawn, x, cut


def _value_and_grads(p, bias, x, cfg):
    """y, counters and the gradients of a weighted sum of y to every leaf
    and to x, through the recomputation the trunk wraps a block in."""
    r = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def loss(p, x):
        y, c = mla_moe.moe_apply(p, bias, x, cfg)
        return jnp.sum(y * r), (y, c)
    (_, (y, c)), g = jax.value_and_grad(
        jax.checkpoint(loss), argnums=(0, 1), has_aux=True)(p, x)
    return y, c, g


def _assert_same(got, want, tol):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        scale = float(jnp.max(jnp.abs(b))) + 1e-30
        np.testing.assert_allclose(a / scale, b / scale, atol=tol)


@pytest.mark.parametrize("held, first, bias, compact", [
    (1, 15, "drawn", 1.0), (2, 0, "drawn", 1.0), (3, 5, "drawn", 1.0),
    # every token selects both held experts: 512 pairs for 384 rows
    (2, 4, 10.0, 0.0),
    # no pair here at all: the shared expert alone
    (2, 4, -10.0, 1.0)])
def test_the_sized_buffer_gives_what_the_whole_buffer_gives(
        reference, monkeypatch, held, first, bias, compact):
    """Where the buffer is smaller than the worst case, the layer over it
    (or over the whole buffer, in a step where more pairs arrive than it
    has rows) gives the float32 reference's result and the whole-buffer
    body's result and gradients, with no pair dropped."""
    p, b, x, cut = _wide_layer(jax.random.key(4), held, first, bias)
    m = TOKENS * WIDE.num_experts_per_tok
    assert mla_moe.moe_buffer_rows(m, cut) < m
    y, c, g = _value_and_grads(p, b, x, cut)
    assert float(c["compact"]) == compact
    md = dataclasses.asdict(cut)
    want, counts = reference.moe(p, b, x, md, reference.switches(md),
                                 "float32")
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert np.array_equal(c["counts"], counts)
    if bias == 10.0:
        assert np.asarray(c["counts"]).tolist() == [TOKENS] * held
    if bias == -10.0:
        assert int(np.sum(c["counts"])) == 0 and int(c["rows"]) == 0
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(g))
    # the parent's body: the same function where the buffer is the worst case
    monkeypatch.setattr(mla_moe, "MOE_BUFFER_FACTOR", 16)
    assert mla_moe.moe_buffer_rows(m, cut) == m
    y0, c0, g0 = _value_and_grads(p, b, x, cut)
    _assert_same(y, y0, 1e-5)
    _assert_same(g, g0, 1e-4)
    assert np.array_equal(c["counts"], c0["counts"])
    assert int(c["rows"]) == int(c0["rows"]) and float(c0["compact"]) == 0.0


@pytest.mark.parametrize("extra, compact", [(0, 1.0), (1, 0.0)])
def test_the_buffer_is_taken_up_to_its_last_row(monkeypatch, extra, compact):
    """Exactly C pairs here run over the sized buffer, C + 1 over the whole
    one, and both give the whole-buffer body's result and gradients. The
    selection is laid down by hand (2 of 16 held, 384 rows: 128 tokens
    select both held experts, 128 + `extra` one of them)."""
    p, b, x, cut = _wide_layer(jax.random.key(5), 2, 4, "drawn")
    both = jnp.arange(TOKENS) < 128 + extra
    idx = jnp.stack([jnp.full((TOKENS,), 4), jnp.where(both, 5, 1),
                     jnp.zeros((TOKENS,), jnp.int32)], axis=1)
    real_route = mla_moe.route

    def route(router_w, bias, x, cfg):
        _, w = real_route(router_w, bias, x, cfg)
        return idx, w
    monkeypatch.setattr(mla_moe, "route", route)
    y, c, g = _value_and_grads(p, b, x, cut)
    assert int(np.sum(c["counts"])) == 384 + extra
    assert float(c["compact"]) == compact
    monkeypatch.setattr(mla_moe, "MOE_BUFFER_FACTOR", 16)
    y0, c0, g0 = _value_and_grads(p, b, x, cut)
    _assert_same(y, y0, 1e-5)
    _assert_same(g, g0, 1e-4)
    assert np.array_equal(c["counts"], c0["counts"])


def _sub_jaxprs(jaxpr, into_cond=True):
    """Every jaxpr nested in the equations of `jaxpr`, itself included;
    kernel bodies (which branch on their grid position) are left out."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" or (
                eqn.primitive.name == "cond" and not into_cond):
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _sub_jaxprs(sub, into_cond)


def _conds(jaxpr):
    """The program's own `cond` equations (those not inside another)."""
    return [e for j in _sub_jaxprs(jaxpr, into_cond=False) for e in j.eqns
            if e.primitive.name == "cond"]


def test_at_the_cells_shape_the_sized_branch_holds_no_worst_case_array():
    """Trace only, nothing executed: 8,192 tokens of width 2,048, 16 of 256
    experts held, 8 a token. Forward and gradient each branch once on the
    pairs that are here; the sized branch (buffer 16,384 rows) holds no
    array of 65,536 rows (flat or as [8,192, 8, .]) whose last axis is the
    hidden or the experts' width. With every expert held the buffer is the
    worst case and nothing branches."""
    cfg = TokenModelConfig(hidden_size=2048, moe_intermediate_size=768,
                           n_routed_experts=256, num_experts_per_tok=8,
                           experts_held=16)
    t, m = 8192, 8192 * 8
    assert mla_moe.moe_buffer_rows(m, cfg) == 16384

    def trace(cfg):
        p = jax.eval_shape(lambda k: mla_moe._moe_init(k, cfg, jnp.float32),
                           jax.random.key(0))
        x = jax.ShapeDtypeStruct((t, cfg.hidden_size), jnp.float32)
        bias = jax.ShapeDtypeStruct((cfg.n_routed_experts,), jnp.float32)
        return jax.make_jaxpr(jax.grad(
            lambda p, x, b: jnp.sum(mla_moe.moe_apply(p, b, x, cfg)[0]),
            argnums=(0, 1)))(p, x, bias).jaxpr

    conds = _conds(trace(cfg))
    assert len(conds) == 2                      # the layer and its cotangent
    for eqn in conds:
        whole, sized = eqn.params["branches"]   # index 1: the pairs fit
        def big(j):
            return [v.aval.shape for sub in _sub_jaxprs(j.jaxpr)
                    for e in sub.eqns for v in e.outvars
                    if v.aval.ndim >= 2 and v.aval.shape[-1] in (2048, 768)
                    and math.prod(v.aval.shape[:-1]) >= m]
        assert big(sized) == []
        assert big(whole)                       # the check sees what it bans
    assert _conds(trace(dataclasses.replace(cfg, experts_held=256))) == []


def test_routing_weights_are_normalized_over_all_selected(reference):
    """Weights are the sigmoid scores of the selected (without the bias),
    over their sum over ALL selected, times the scaling factor: the bias
    changes the selection and not the weights."""
    p, bias, x = _layer(jax.random.key(2), CFG)
    idx, w = mla_moe.route(p["router"]["w"], bias, x, CFG)
    np.testing.assert_allclose(jnp.sum(w, -1), CFG.routed_scaling_factor,
                               rtol=1e-6)
    plain, _ = mla_moe.route(p["router"]["w"], jnp.zeros_like(bias), x, CFG)
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain, -1))
    s = jax.nn.sigmoid(x @ p["router"]["w"])
    np.testing.assert_allclose(
        w, 2.5 * jnp.take_along_axis(s, idx, -1)
        / jnp.sum(jnp.take_along_axis(s, idx, -1), -1, keepdims=True),
        rtol=1e-5)


def test_rotary_de_interleaves_pairs(reference):
    """`rope_interleave`: the stored dimensions are pairs (2i, 2i+1)."""
    x = jax.random.normal(jax.random.key(3), (2, 16, 8))
    cos, sin = mla_moe.rotary_tables(16, 8, 10000.0)
    got = mla_moe.apply_rotary(x, cos, sin, True)
    np.testing.assert_allclose(got, reference.rotary(x, 10000.0, True),
                               rtol=1e-5, atol=1e-6)
    pairs = x.reshape(2, 16, 4, 2)
    halves = jnp.concatenate([pairs[..., 0], pairs[..., 1]], -1)
    np.testing.assert_allclose(
        got, mla_moe.apply_rotary(halves, cos, sin, False), rtol=1e-6)
    # position 0 is not rotated
    np.testing.assert_allclose(got[:, 0], halves[:, 0], rtol=1e-6)


def test_the_step_is_the_same_on_one_device_and_over_a_data_mesh():
    """Loss, counters and the parameters after two steps agree between one
    device and a 2-way data mesh (loss and gradient per shard inside a
    shard_map, averaged), with the kernels in interpret mode."""
    from dcgan_tpu.parallel import make_mesh, make_parallel_train

    cfg = get_preset("mla_moe_tiny")
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, use_pallas=True))
    ids = jax.random.randint(jax.random.key(1), (8, 32), 0, 256)
    out = []
    for n in (1, 2):
        c = dataclasses.replace(cfg, mesh=MeshConfig(data=n))
        pt = make_parallel_train(c, make_mesh(c.mesh, jax.devices()[:n]))
        state = pt.init(jax.random.key(0))
        for i in range(2):
            state, m = pt.step(state, ids, jax.random.key(i))
        assert all(np.ndim(v) == 0 for v in m.values())
        out.append((jax.device_get(m), jax.device_get(state)))
        assert sorted(pt.programs) == ["init", "train_step"]
        with pytest.raises(NotImplementedError, match="ParallelTrain.sample"):
            pt.sample(state, None)
    (m1, s1), (m2, s2) = out
    for k in ("loss", "loss_mtp", "moe_pairs_here", "moe_load_max"):
        np.testing.assert_allclose(m1[k], m2[k], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    assert int(s1["step"]) == 2
    held = cfg.model.experts_held
    assert all(v.shape == (held,) for v in s1["moe_counts"].values())
    # rows computed follow the pairs that are here: whole tiles, under 2x
    assert m1["moe_pairs_here"] <= m1["moe_rows_computed"]


def test_the_step_counts_the_layers_that_ran_over_the_sized_buffer():
    """`moe_compact_share` of a step with two expert layers (block1 and the
    multi-token module; 2 of 16 experts held, 384 rows for 768 pairs): 1.0
    as drawn, 0.0 where both layers' selection biases send every token to
    the held experts, 0.5 where one layer's does; the counts stay whole."""
    from dcgan_tpu.train.steps import make_lm_train_step

    cfg = get_preset("mla_moe_tiny")
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, num_hidden_layers=2, n_routed_experts=16,
        num_experts_per_tok=3, experts_held=2, first_expert=4))
    fns = make_lm_train_step(cfg)
    state = fns.init(jax.random.key(0))
    assert sorted(state["moe_bias"]) == ["block1", "mtp"]
    step = jax.jit(fns.train_step)
    ids = jax.random.randint(jax.random.key(1), (8, 32), 0, 256)
    to_held = jnp.zeros((16,)).at[4:6].set(10.0)
    for flooded, share in (((), 1.0), (("mtp",), 0.5),
                           (("block1", "mtp"), 0.0)):
        bias = {n: to_held if n in flooded else jnp.zeros((16,))
                for n in state["moe_bias"]}
        new, m = step({**state, "moe_bias": bias}, ids, jax.random.key(2))
        assert float(m["moe_compact_share"]) == share
        assert np.isfinite(float(m["loss"]))
        for n in flooded:
            assert np.asarray(new["moe_counts"][n]).tolist() == [256, 256]


@pytest.mark.parametrize("field, value", [
    ("sample_every_steps", 100), ("activation_summary_steps", 500),
    ("fid_every_steps", 1000), ("progressive", "16:10,32:*"),
    ("pipeline_gd", True), ("diffaug", "translation"),
    ("g_ema_decay", 0.999), ("precision", "bf16")])
def test_image_only_services_refuse_by_name(field, value):
    cfg = get_preset("mla_moe_tiny")
    with pytest.raises(ValueError, match=field):
        dataclasses.replace(cfg, **{field: value})


def test_config_rules_and_round_trip():
    with pytest.raises(ValueError, match="go together"):
        TrainConfig(model=TokenModelConfig())
    with pytest.raises(ValueError, match="go together"):
        TrainConfig(model=ModelConfig(), loss=LM_LOSS)
    with pytest.raises(ValueError, match="experts held"):
        TokenModelConfig(experts_held=8, first_expert=4)
    cfg = get_preset("mla_moe_tiny")
    assert cfg.model.arch == TOKEN_ARCH
    assert config_from_dict(config_to_dict(cfg)) == cfg
    big = get_preset("joyai_llm_flash").model
    assert (big.num_hidden_layers, big.experts_held, big.vocab_size,
            big.seq_len) == (40, 256, 129280, 8192)


def test_trainer_trains_the_tiny_preset_on_synthetic_ids(tmp_path, capsys):
    """`trainer.train` on the tiny preset: id batches through the feed and
    `DevicePrefetcher`, the likelihood step, a checkpoint at the end; real
    data refused by name."""
    from dcgan_tpu.train.trainer import train

    cfg = get_preset("mla_moe_tiny",
                     checkpoint_dir=str(tmp_path / "ck"),
                     sample_dir=str(tmp_path / "sm"), tensorboard=False)
    state = train(cfg, synthetic_data=True, max_steps=3)
    assert int(state["step"]) == 3
    out = capsys.readouterr().out
    assert "step 3" in out and "loss_mtp" in out and "d_loss" not in out
    assert int(sum(np.sum(c) for c in
                   jax.device_get(state["moe_counts"]).values())) > 0
    with pytest.raises(ValueError, match="synthetic ids only"):
        train(cfg, synthetic_data=False, max_steps=1)
