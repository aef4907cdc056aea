"""Preset configs (presets.py) — the BASELINE.json config matrix — and the
CLI --preset path with explicit-flag overrides."""

import pytest

from dcgan_tpu.presets import PRESETS, get_preset
from dcgan_tpu.train.cli import apply_overrides, explicit_flags


class TestPresets:
    def test_all_baseline_configs_named(self):
        # BASELINE.json lists exactly five configurations; sagan64 and
        # sngan-cifar10 are the beyond-BASELINE attention / resnet families
        # (presets.py docstrings); joyai_llm_flash and mla_moe_tiny are the
        # one-network token family (a published model as published, and the
        # size the tests train); ouro_2_6b and loop_lm_tiny the looped one;
        # phi_4_mini_flash and sambay_tiny the decoder-hybrid-decoder.
        assert set(PRESETS) == {
            "celeba64", "lsun64-dp8", "dcgan128", "cifar10-cond", "wgan-gp",
            "sagan64", "sagan128", "sagan256-lc", "sngan-cifar10",
            "stylegan64", "joyai_llm_flash", "mla_moe_tiny", "ouro_2_6b",
            "loop_lm_tiny", "phi_4_mini_flash", "sambay_tiny"}

    def test_celeba64_is_reference_headline(self):
        cfg = get_preset("celeba64")
        assert cfg.model.output_size == 64 and cfg.model.z_dim == 100
        assert cfg.batch_size == 64 and cfg.dataset == "celebA"
        assert cfg.learning_rate == 2e-4 and cfg.beta1 == 0.5

    def test_lsun_dp8_mesh_and_global_batch(self):
        cfg = get_preset("lsun64-dp8")
        assert cfg.mesh.data == 8
        assert cfg.batch_size == 64 * 8
        assert cfg.dataset == "lsun-bedroom"

    def test_dcgan128_deepens_stacks(self):
        cfg = get_preset("dcgan128")
        assert cfg.model.output_size == 128
        assert cfg.model.num_up_layers == 5

    def test_cifar10_conditional(self):
        cfg = get_preset("cifar10-cond")
        assert cfg.model.num_classes == 10
        assert cfg.model.output_size == 32
        assert cfg.dataset == "cifar10"

    def test_wgan_gp_loss_and_hparams(self):
        cfg = get_preset("wgan-gp")
        assert cfg.loss == "wgan-gp"
        assert cfg.learning_rate == 1e-4 and cfg.beta1 == 0.0
        assert cfg.n_critic == 5

    def test_sagan64_recipe(self):
        cfg = get_preset("sagan64")
        assert cfg.model.attn_res == 32
        assert cfg.model.spectral_norm == "gd"
        assert cfg.loss == "hinge" and cfg.beta1 == 0.0
        assert cfg.d_learning_rate == 4e-4 and cfg.g_learning_rate == 1e-4
        assert cfg.g_ema_decay == 0.999

    def test_sagan128_long_sequence_demo(self):
        cfg = get_preset("sagan128")
        assert cfg.model.output_size == 128 and cfg.model.attn_res == 64
        # attention stage sequence length = 64*64 = 4096 tokens
        assert cfg.model.attn_res ** 2 == 4096
        assert cfg.model.spectral_norm == "gd" and cfg.loss == "hinge"

    def test_sagan256_lc_is_flash_only_config(self):
        cfg = get_preset("sagan256-lc")
        assert cfg.model.output_size == 256 and cfg.model.attn_res == 128
        # attention stage sequence length = 128*128 = 16384 tokens — the
        # scale where dense attention cannot allocate at batch 64 and the
        # flash kernels are what makes the config trainable (DESIGN.md §8b)
        assert cfg.model.attn_res ** 2 == 16384
        assert cfg.model.use_pallas
        # shard_map backend: the one backend where use_pallas + attn_res
        # composes on multi-device data-parallel meshes (parallel/api.py
        # rejects the pair under multi-device gspmd)
        assert cfg.backend == "shard_map"
        assert cfg.model.spectral_norm == "d" and cfg.loss == "hinge"

    def test_sngan_cifar10_recipe(self):
        cfg = get_preset("sngan-cifar10")
        assert cfg.model.arch == "resnet" and cfg.model.output_size == 32
        assert cfg.model.spectral_norm == "d" and cfg.loss == "hinge"
        assert cfg.n_critic == 5 and cfg.beta1 == 0.0

    def test_factory_overrides(self):
        cfg = get_preset("celeba64", batch_size=128, seed=7)
        assert cfg.batch_size == 128 and cfg.seed == 7

    def test_unknown_preset_rejected(self):
        with pytest.raises(ValueError, match="unknown preset"):
            get_preset("biggan")


class TestCLIPreset:
    def test_preset_defaults_flow_through(self):
        argv = ["--preset", "wgan-gp"]
        cfg = apply_overrides(get_preset("wgan-gp"), explicit_flags(argv))
        assert cfg.loss == "wgan-gp" and cfg.learning_rate == 1e-4

    def test_explicit_flags_beat_preset(self):
        argv = ["--preset", "wgan-gp", "--learning_rate", "3e-4",
                "--batch_size", "32", "--no_normalize"]
        cfg = apply_overrides(get_preset("wgan-gp"), explicit_flags(argv))
        assert cfg.learning_rate == 3e-4
        assert cfg.batch_size == 32
        assert not cfg.normalize_inputs
        assert cfg.loss == "wgan-gp" and cfg.beta1 == 0.0  # preset survives

    def test_model_and_mesh_overrides(self):
        argv = ["--preset", "lsun64-dp8", "--gf_dim", "32", "--mesh_data", "4"]
        cfg = apply_overrides(get_preset("lsun64-dp8"), explicit_flags(argv))
        assert cfg.model.gf_dim == 32
        assert cfg.mesh.data == 4
        assert cfg.batch_size == 64 * 8  # untouched preset field

    def test_untouched_flags_do_not_leak(self):
        # Flags left at argparse defaults must not clobber preset values.
        argv = ["--preset", "cifar10-cond"]
        cfg = apply_overrides(get_preset("cifar10-cond"), explicit_flags(argv))
        assert cfg.model.num_classes == 10      # argparse default is 0
        assert cfg.model.output_size == 32      # argparse default is 64
