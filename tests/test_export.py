"""Serving export (dcgan_tpu/export.py): checkpoint -> portable StableHLO
artifact with baked weights — the deployment surface the reference never had
(its sampler only exists inside the train graph, image_train.py:179-192)."""

import json
import os

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # trains tiny checkpoints; see pytest.ini

from dcgan_tpu.config import (
    MODEL_OVERRIDE_FLAGS,
    ModelConfig,
    TrainConfig,
)
from dcgan_tpu.export import build_parser, export_sampler, load_sampler, main
from dcgan_tpu.train.trainer import train


def _train_ckpt(root, **model_kw):
    cfg = TrainConfig(
        model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                          compute_dtype="float32", **model_kw),
        batch_size=8,
        checkpoint_dir=str(root / "ckpt"),
        sample_dir=str(root / "samples"),
        sample_every_steps=0, save_summaries_secs=1e9, save_model_secs=1e9,
        log_every_steps=0)
    train(cfg, synthetic_data=True, max_steps=1)
    return str(root / "ckpt")


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return _train_ckpt(tmp_path_factory.mktemp("export"))


class TestExportSampler:
    def test_artifact_matches_framework_sampler(self, ckpt, tmp_path):
        out = str(tmp_path / "sampler.jaxexport")
        meta = export_sampler(
            ckpt, out, overrides={"output_size": 16, "gf_dim": 8,
                                  "df_dim": 8},
            platforms=("cpu",))
        assert os.path.exists(out)
        sidecar = json.load(open(out + ".json"))
        assert sidecar["z_dim"] == meta["z_dim"] == 100
        assert sidecar["image_shape"] == [16, 16, 3]
        assert sidecar["step"] == 1

        exported = load_sampler(out)
        # batch 8 tiles the 8-virtual-device test mesh, so the same z can
        # feed the framework's sharded sample() below for the exact check
        z = np.random.default_rng(0).uniform(
            -1, 1, size=(8, 100)).astype(np.float32)
        imgs = np.asarray(exported.call(z))
        assert imgs.shape == (8, 16, 16, 3)
        assert np.abs(imgs).max() <= 1.0

        # the artifact must reproduce the framework's own sampler exactly
        # (same weights, same graph, just serialized)
        import jax

        from dcgan_tpu.parallel import make_mesh, make_parallel_train
        from dcgan_tpu.utils.checkpoint import Checkpointer

        cfg = TrainConfig(model=ModelConfig(output_size=16, gf_dim=8,
                                            df_dim=8,
                                            compute_dtype="float32"),
                          batch_size=8, checkpoint_dir=ckpt)
        pt = make_parallel_train(cfg, make_mesh(cfg.mesh))
        state = Checkpointer(ckpt).restore_latest(pt.init(jax.random.key(0)))
        ref = np.asarray(jax.device_get(pt.sample(state, jax.numpy.asarray(z))))
        np.testing.assert_allclose(imgs, ref, atol=1e-5)

    def test_symbolic_batch_serves_any_size(self, ckpt, tmp_path):
        out = str(tmp_path / "s.jaxexport")
        export_sampler(ckpt, out,
                       overrides={"output_size": 16, "gf_dim": 8,
                                  "df_dim": 8},
                       platforms=("cpu",))
        exported = load_sampler(out)
        for b in (1, 3, 8):
            z = np.zeros((b, 100), np.float32)
            assert np.asarray(exported.call(z)).shape == (b, 16, 16, 3)

    def test_conditional_artifact_takes_labels(self, tmp_path_factory,
                                               tmp_path):
        ckpt = _train_ckpt(tmp_path_factory.mktemp("export_cond"),
                           num_classes=4)
        out = str(tmp_path / "cond.jaxexport")
        meta = export_sampler(
            ckpt, out, overrides={"output_size": 16, "gf_dim": 8,
                                  "df_dim": 8, "num_classes": 4},
            platforms=("cpu",))
        assert meta["num_classes"] == 4
        exported = load_sampler(out)
        z = np.zeros((4, 100), np.float32)
        labels = np.arange(4, dtype=np.int32)
        imgs = np.asarray(exported.call(z, labels))
        assert imgs.shape == (4, 16, 16, 3)
        # conditioning must matter: different labels, different images
        other = np.asarray(exported.call(z, np.zeros(4, np.int32)))
        assert not np.allclose(imgs[1:], other[1:])

    def test_flash_trained_attention_checkpoint_exports_dense(
            self, tmp_path_factory, tmp_path):
        """The rev-2 sagan presets TRAIN with the flash kernels
        (use_pallas=True); their checkpoints must still export — the
        artifact forces the dense lowering for StableHLO portability
        (export.py's use_pallas=False replace), and attention parameters
        are execution-form-agnostic, so the flash-trained weights serve
        through the dense sampler unchanged."""
        ckpt = _train_ckpt(tmp_path_factory.mktemp("export_attn"),
                           attn_res=8, use_pallas=True)
        out = str(tmp_path / "attn.jaxexport")
        meta = export_sampler(
            ckpt, out, overrides={"output_size": 16, "gf_dim": 8,
                                  "df_dim": 8},
            platforms=("cpu",))
        assert meta["z_dim"] == 100
        exported = load_sampler(out)
        z = np.random.default_rng(1).uniform(
            -1, 1, size=(8, 100)).astype(np.float32)
        imgs = np.asarray(exported.call(z))
        assert imgs.shape == (8, 16, 16, 3)
        assert np.abs(imgs).max() <= 1.0
        assert np.isfinite(imgs).all()

        # exact check against the framework sampler running the FLASH form
        # (interpret kernels on CPU): both attention forms are exact, so
        # the dense-lowered artifact must reproduce the flash-path images
        # to f32 tolerance — this is what pins the restored attention
        # parameters to the right wiring
        import jax

        from dcgan_tpu.parallel import make_mesh, make_parallel_train
        from dcgan_tpu.utils.checkpoint import Checkpointer

        cfg = TrainConfig(model=ModelConfig(output_size=16, gf_dim=8,
                                            df_dim=8, attn_res=8,
                                            use_pallas=True,
                                            compute_dtype="float32"),
                          batch_size=8, checkpoint_dir=ckpt)
        pt = make_parallel_train(cfg, make_mesh(cfg.mesh))
        state = Checkpointer(ckpt).restore_latest(pt.init(jax.random.key(0)))
        ref = np.asarray(jax.device_get(
            pt.sample(state, jax.numpy.asarray(z))))
        np.testing.assert_allclose(imgs, ref, atol=1e-5)

    def test_resnet_checkpoint_exports_and_matches(self, tmp_path_factory,
                                                   tmp_path):
        """Round-trip for the second model family (VERDICT next-round #6):
        a spectral-norm resnet checkpoint — whose generator restore rides
        the same tree as the SN power-iteration state — must export to
        StableHLO and reproduce the framework sampler exactly."""
        import jax

        from dcgan_tpu.parallel import make_mesh, make_parallel_train
        from dcgan_tpu.utils.checkpoint import Checkpointer

        ckpt = _train_ckpt(tmp_path_factory.mktemp("export_resnet"),
                           arch="resnet", spectral_norm="d")
        ov = {"arch": "resnet", "output_size": 16, "gf_dim": 8, "df_dim": 8,
              "spectral_norm": "d"}
        out = str(tmp_path / "resnet.jaxexport")
        meta = export_sampler(ckpt, out, overrides=ov, platforms=("cpu",))
        assert meta["arch"] == "resnet"
        z = np.random.default_rng(2).uniform(
            -1, 1, size=(8, 100)).astype(np.float32)
        imgs = np.asarray(load_sampler(out).call(z))
        assert imgs.shape == (8, 16, 16, 3)
        assert np.isfinite(imgs).all()

        cfg = TrainConfig(model=ModelConfig(arch="resnet", output_size=16,
                                            gf_dim=8, df_dim=8,
                                            spectral_norm="d",
                                            compute_dtype="float32"),
                          batch_size=8, checkpoint_dir=ckpt)
        pt = make_parallel_train(cfg, make_mesh(cfg.mesh))
        state = Checkpointer(ckpt).restore_latest(pt.init(jax.random.key(0)))
        assert any(k.startswith("sn_") for k in state["bn"]["disc"])
        ref = np.asarray(jax.device_get(
            pt.sample(state, jax.numpy.asarray(z))))
        np.testing.assert_allclose(imgs, ref, atol=1e-5)

    def test_stylegan_ema_checkpoint_exports_and_matches(
            self, tmp_path_factory, tmp_path):
        """Third family: StyleGAN2-lite's per-sample weight modulation must
        survive both the symbolic-batch export (modulated convs reshape by
        the batch dim) and the EMA weight source."""
        import jax

        from dcgan_tpu.parallel import make_mesh, make_parallel_train
        from dcgan_tpu.utils.checkpoint import Checkpointer

        root = tmp_path_factory.mktemp("export_stylegan")
        cfg = TrainConfig(
            model=ModelConfig(arch="stylegan", output_size=16, gf_dim=8,
                              df_dim=8, compute_dtype="float32"),
            batch_size=8, g_ema_decay=0.5,
            checkpoint_dir=str(root / "ckpt"),
            sample_dir=str(root / "samples"),
            sample_every_steps=0, save_summaries_secs=1e9,
            save_model_secs=1e9, log_every_steps=0)
        train(cfg, synthetic_data=True, max_steps=2)
        ckpt = str(root / "ckpt")
        ov = {"arch": "stylegan", "output_size": 16, "gf_dim": 8,
              "df_dim": 8}
        out = str(tmp_path / "sg.jaxexport")
        meta = export_sampler(ckpt, out, overrides=ov, platforms=("cpu",),
                              use_ema=True)
        assert meta["arch"] == "stylegan" and meta["weights"] == "ema"
        exported = load_sampler(out)
        z = np.random.default_rng(3).uniform(
            -1, 1, size=(8, 100)).astype(np.float32)
        imgs = np.asarray(exported.call(z))
        assert imgs.shape == (8, 16, 16, 3)
        assert np.isfinite(imgs).all()
        # symbolic batch must serve odd sizes too — per-sample modulation
        # is the path most likely to have baked the trace batch
        assert np.asarray(exported.call(z[:3])).shape == (3, 16, 16, 3)
        np.testing.assert_allclose(np.asarray(exported.call(z[:3])),
                                   imgs[:3], atol=1e-5)

        # exact match against the framework's EMA sampler (pt.sample reads
        # ema_gen when g_ema_decay > 0)
        pt = make_parallel_train(cfg, make_mesh(cfg.mesh))
        state = Checkpointer(ckpt).restore_latest(pt.init(jax.random.key(0)))
        ref = np.asarray(jax.device_get(
            pt.sample(state, jax.numpy.asarray(z))))
        np.testing.assert_allclose(imgs, ref, atol=1e-5)

    def test_cli_and_flag_coverage(self, ckpt, tmp_path):
        parser = build_parser()
        args = parser.parse_args(["--checkpoint_dir", ckpt])
        for name in MODEL_OVERRIDE_FLAGS:
            assert hasattr(args, name), name
        out = str(tmp_path / "cli.jaxexport")
        main(["--checkpoint_dir", ckpt, "--out", out,
              "--output_size", "16", "--gf_dim", "8", "--df_dim", "8",
              "--platforms", "cpu", "--batch_size", "2"])
        exported = load_sampler(out)
        assert np.asarray(
            exported.call(np.zeros((2, 100), np.float32))).shape == \
            (2, 16, 16, 3)
        sidecar = json.load(open(out + ".json"))
        assert sidecar["batch"] == 2

    def test_ema_weights_differ_from_live(self, tmp_path_factory, tmp_path):
        root = tmp_path_factory.mktemp("export_ema")
        cfg = TrainConfig(
            model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                              compute_dtype="float32"),
            batch_size=8, g_ema_decay=0.5,
            checkpoint_dir=str(root / "ckpt"),
            sample_dir=str(root / "samples"),
            sample_every_steps=0, save_summaries_secs=1e9,
            save_model_secs=1e9, log_every_steps=0)
        train(cfg, synthetic_data=True, max_steps=2)
        ckpt = str(root / "ckpt")
        ov = {"output_size": 16, "gf_dim": 8, "df_dim": 8}
        live = str(tmp_path / "live.jaxexport")
        ema = str(tmp_path / "ema.jaxexport")
        export_sampler(ckpt, live, overrides=ov, platforms=("cpu",))
        export_sampler(ckpt, ema, overrides=ov, platforms=("cpu",),
                       use_ema=True)
        z = np.random.default_rng(1).uniform(
            -1, 1, size=(2, 100)).astype(np.float32)
        a = np.asarray(load_sampler(live).call(z))
        b = np.asarray(load_sampler(ema).call(z))
        assert not np.allclose(a, b)
        assert json.load(open(ema + ".json"))["weights"] == "ema"
