"""The fused step runs G's forward once (ISSUE 34).

With `n_critic == 1` and `grad_accum == 1` the fused `train_step` linearises
G's forward once: the D step takes its fake batch from it and the G step
pulls its loss gradient back through it. Here the step is held to a
two-forward formulation written out below (D's loss through its own G
forward, G's loss by `jax.value_and_grad` of the whole G loss through
`generator_apply`, as the step was before), and its traced program to one
flash forward and one `generator_apply` fewer.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dcgan_tpu.config import ModelConfig, TrainConfig
from dcgan_tpu.models.dcgan import discriminator_apply, generator_apply
from dcgan_tpu.ops.augment import diff_augment, parse_policy
from dcgan_tpu.train import losses as L
from dcgan_tpu.train import make_train_step
from dcgan_tpu.train import steps as steps_mod
from dcgan_tpu.train.steps import make_optimizer

B = 8
# attention at 8 x 8 on the flash kernels (interpret mode here), spectral
# norm on both nets, hinge and TTUR as the sagan presets; and plain dcgan
ATTN_SN = dict(
    model=ModelConfig(output_size=16, gf_dim=8, df_dim=8, attn_res=8,
                      spectral_norm="gd", use_pallas=True,
                      compute_dtype="float32"),
    loss="hinge", beta1=0.0, d_learning_rate=4e-4, g_learning_rate=1e-4,
    g_ema_decay=0.999)
DCGAN = dict(model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                               compute_dtype="float32"))
PRESETS = {"attn_sn": ATTN_SN, "dcgan": DCGAN}


def _cfg(preset, **kw):
    return TrainConfig(batch_size=B, **PRESETS[preset], **kw)


def _inputs():
    images = jnp.asarray(np.tanh(np.random.default_rng(0).normal(
        size=(B, 16, 16, 3))).astype(np.float32))
    return images, jax.random.key(7)


def two_forward_step(cfg: TrainConfig):
    """The fused n_critic = 1 step with G's forward run twice: once for the
    D step's fake batch (its new state thrown away), once inside the G
    loss's `value_and_grad`."""
    mcfg = cfg.model
    policy = parse_policy(cfg.diffaug)
    gan_losses = {"gan": functools.partial(
        L.bce_gan_losses, label_smoothing=cfg.label_smoothing),
        "hinge": L.hinge_losses}[cfg.loss]
    opt_g = make_optimizer(cfg, cfg.g_learning_rate)
    opt_d = make_optimizer(cfg, cfg.d_learning_rate)

    def aug(x, key, idx):
        if not policy:
            return x
        return diff_augment(x, jax.random.fold_in(key, idx), policy)

    def gen(gp, g_bn, z):
        return generator_apply(gp, g_bn, z, cfg=mcfg, train=True)

    def disc(dp, d_bn, x):
        return discriminator_apply(dp, d_bn, x, cfg=mcfg, train=True)

    def d_loss(dp, gp, bn, images, z, aug_key):
        fake, _ = gen(gp, bn["gen"], z)
        _, real_logits, bn1 = disc(dp, bn["disc"], aug(images, aug_key, 0))
        _, fake_logits, bn2 = disc(dp, bn1, aug(fake, aug_key, 1))
        loss, real, fk = gan_losses(real_logits, fake_logits)[:3]
        return loss, (bn2, real, fk)

    def g_loss(gp, dp, bn, z, aug_key):
        fake, g_bn = gen(gp, bn["gen"], z)
        _, fake_logits, _ = disc(dp, bn["disc"], aug(fake, aug_key, 2))
        return gan_losses(fake_logits, fake_logits)[3], g_bn

    def step(state, images, key):
        if policy:
            z_key, _, aug_key = jax.random.split(key, 3)
        else:
            z_key, _ = jax.random.split(key)
            aug_key = None
        z = jax.random.uniform(z_key, (images.shape[0], mcfg.z_dim),
                               minval=-1.0, maxval=1.0, dtype=jnp.float32)
        params, bn, opt = state["params"], state["bn"], state["opt"]
        (dl, (d_bn, real, fk)), dg = jax.value_and_grad(
            d_loss, has_aux=True)(params["disc"], params["gen"], bn,
                                  images, z, aug_key)
        upd, d_opt = opt_d.update(dg, opt["disc"], params["disc"])
        new_disc = jax.tree_util.tree_map(jnp.add, params["disc"], upd)
        if cfg.update_mode == "sequential":
            target, target_bn = new_disc, d_bn
        else:
            target, target_bn = params["disc"], bn["disc"]
        (gl, g_bn), gg = jax.value_and_grad(g_loss, has_aux=True)(
            params["gen"], target, {"gen": bn["gen"], "disc": target_bn},
            z, aug_key)
        upd, g_opt = opt_g.update(gg, opt["gen"], params["gen"])
        new_gen = jax.tree_util.tree_map(jnp.add, params["gen"], upd)
        e = cfg.g_ema_decay
        return {
            "params": {"gen": new_gen, "disc": new_disc},
            "bn": {"gen": g_bn, "disc": d_bn},
            "opt": {"gen": g_opt, "disc": d_opt},
            "ema_gen": jax.tree_util.tree_map(
                lambda a, p: e * a + (1.0 - e) * p, state["ema_gen"],
                new_gen),
            "step": state["step"] + 1,
        }, {"d_loss": dl, "d_loss_real": real, "d_loss_fake": fk,
            "g_loss": gl}

    return step


def _eqns(jaxpr):
    """Every equation of a closed jaxpr, through its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _flash_fwd_calls(fn, *args) -> int:
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return sum(1 for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"
               and e.params.get("name") == "flash_fwd")


class TestOneLinearisedForward:
    def test_four_flash_forwards_where_two_forwards_take_five(self):
        cfg = _cfg("attn_sn")
        fns = make_train_step(cfg)
        state = fns.init(jax.random.key(0))
        images, key = _inputs()
        assert _flash_fwd_calls(fns.train_step, state, images, key) == 4
        assert _flash_fwd_calls(two_forward_step(cfg), state, images,
                                key) == 5

    @pytest.mark.parametrize("kw, gens, fwds", [
        ({}, 1, 4),
        # the critic scan draws its own z and the microbatch scans run
        # their own forwards: both keep G's two forwards, as before
        ({"n_critic": 2}, 2, 5),
        ({"grad_accum": 2}, 2, 5),
    ])
    def test_generator_traced_once_only_in_the_plain_fused_step(
            self, monkeypatch, kw, gens, fwds):
        calls = []

        def counting(*a, **k):
            calls.append(1)
            return generator_apply(*a, **k)

        monkeypatch.setattr(steps_mod, "generator_apply", counting)
        fns = make_train_step(_cfg("attn_sn", **kw))
        state = fns.init(jax.random.key(0))
        images, key = _inputs()
        assert _flash_fwd_calls(fns.train_step, state, images, key) == fwds
        assert len(calls) == gens


def _rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("diffaug", ["", "color,translation"])
@pytest.mark.parametrize("update_mode", ["sequential", "fused"])
@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_matches_the_two_forward_step(preset, update_mode, diffaug):
    cfg = _cfg(preset, update_mode=update_mode, diffaug=diffaug)
    fns = make_train_step(cfg)
    state = fns.init(jax.random.key(0))
    images, key = _inputs()
    got_s, got_m = jax.jit(fns.train_step)(state, images, key)
    want_s, want_m = jax.jit(two_forward_step(cfg))(state, images, key)
    assert set(got_m) == set(want_m)
    got = jax.tree_util.tree_flatten_with_path((got_s, got_m))[0]
    want = jax.tree_util.tree_flatten_with_path((want_s, want_m))[0]
    assert [p for p, _ in got] == [p for p, _ in want]
    gaps = {jax.tree_util.keystr(p): _rel_gap(g, w)
            for (p, g), (_, w) in zip(got, want)}
    worst = max(gaps, key=gaps.get)
    assert gaps[worst] <= 1e-5, (worst, gaps[worst])
