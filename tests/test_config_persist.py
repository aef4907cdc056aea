"""Checkpoints carry their config (VERDICT r1 #3, ADVICE r1 cBN finding).

The trainer writes config.json next to the Orbax step dirs;
generate/evals/resume read it back, so checkpoint consumers need zero
architecture flags and a mismatched resume fails with a readable error
instead of an Orbax tree/shape mismatch. (The reference's Saver had the
same silent-mismatch hazard — image_train.py:233-245.)
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from dcgan_tpu.config import (
    CONFIG_FILENAME,
    MeshConfig,
    ModelConfig,
    TrainConfig,
    config_from_dict,
    config_to_dict,
    load_config,
    resolve_model_config,
    save_config,
)
from dcgan_tpu.generate import build_parser, generate
from dcgan_tpu.train.trainer import train


def _tiny_cfg(tmp_path, **model_kw):
    return TrainConfig(
        model=ModelConfig(output_size=16, gf_dim=8, df_dim=8,
                          compute_dtype="float32", **model_kw),
        batch_size=8,
        checkpoint_dir=str(tmp_path / "ckpt"),
        sample_dir=str(tmp_path / "samples"),
        sample_every_steps=0, save_summaries_secs=1e9, save_model_secs=1e9,
        log_every_steps=0)


class TestSerialization:
    def test_round_trip_non_default(self):
        cfg = TrainConfig(
            model=ModelConfig(output_size=32, gf_dim=16, df_dim=24,
                              num_classes=10, conditional_bn=True,
                              attn_res=8, attn_heads=2, spectral_norm="gd",
                              compute_dtype="float32"),
            mesh=MeshConfig(model=2, shard_opt=True),
            batch_size=32, loss="hinge", r1_gamma=1.0, r1_interval=4,
            sample_grid=(4, 4), g_ema_decay=0.999)
        assert config_from_dict(config_to_dict(cfg)) == cfg

    def test_unknown_keys_warn_not_fail(self, capsys):
        d = config_to_dict(TrainConfig())
        d["model"]["future_knob"] = 7
        d["brand_new_field"] = "x"
        cfg = config_from_dict(d)
        assert cfg == TrainConfig()
        err = capsys.readouterr().err
        assert "future_knob" in err and "brand_new_field" in err

    def test_save_load_file(self, tmp_path):
        cfg = TrainConfig(model=ModelConfig(output_size=32,
                                            compute_dtype="float32"))
        path = save_config(cfg, str(tmp_path))
        assert os.path.basename(path) == CONFIG_FILENAME
        assert load_config(str(tmp_path)) == cfg
        # valid JSON on disk, not a pickle
        with open(path) as f:
            assert json.load(f)["model"]["output_size"] == 32

    def test_load_absent_returns_none(self, tmp_path):
        assert load_config(str(tmp_path)) is None


class TestRetiredKeysInSavedFiles:
    """Files written before PR 29 carry `bn_pallas`, `pallas_fused` and
    `quant` (config.json beside a checkpoint; benchmark/configs/*.json).
    Falsy, they load as the configuration that never named them; asking for
    deleted code is refused, so a run trained under the broken fp8 rung or
    on the deleted kernels does not resume silently as something else."""

    def _write(self, tmp_path, model=(), **top):
        d = config_to_dict(TrainConfig(model=ModelConfig(use_pallas=True)))
        d["model"].update(dict(model))
        d.update(top)
        with open(tmp_path / CONFIG_FILENAME, "w") as f:
            json.dump(d, f)
        return str(tmp_path)

    @pytest.mark.parametrize("model,top,match", [
        ({"bn_pallas": True}, {}, "removed in PR 29"),
        ({"pallas_fused": True}, {}, "removed in PR 29"),
        ({"quant": "fp8", "compute_dtype": "bfloat16",
          "param_dtype": "bfloat16"}, {"precision": "fp8"},
         "precision must be one of"),
    ], ids=["bn_pallas", "pallas_fused", "fp8_run"])
    def test_saved_config_asking_for_deleted_code_is_refused(
            self, tmp_path, model, top, match):
        with pytest.raises(ValueError, match=match):
            load_config(self._write(tmp_path, model, **top))

    @pytest.mark.parametrize("bn_pallas", [False, None])
    def test_falsy_retired_keys_load_as_if_absent(self, tmp_path, capsys,
                                                  bn_pallas):
        directory = self._write(tmp_path, {"bn_pallas": bn_pallas,
                                           "pallas_fused": False,
                                           "quant": ""})
        assert load_config(directory) == TrainConfig(
            model=ModelConfig(use_pallas=True))
        # `quant` is no field any more: dropped with the unknown-key warning
        assert "quant" in capsys.readouterr().err

    @pytest.mark.parametrize("name", ["sagan128", "dcgan128",
                                      "joyai-llm-flash"])
    def test_benchmark_configuration_files_give_the_preset_back(self, name):
        """The committed files still spell the retired keys out (a
        `benchmark` PR takes them out); applied to the preset as the
        benchmark applies them they change nothing but what the file says
        it cut."""
        from dcgan_tpu.presets import get_preset

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, "benchmark", "configs",
                               name + ".json")) as f:
            conf = json.load(f)
        preset = get_preset(conf["preset"]).model
        model = dataclasses.replace(preset, **conf["model"])
        cut = {k: getattr(preset, k) for k in conf["reduced"]}
        assert dataclasses.replace(model, **cut) == preset


class TestResolveModelConfig:
    def test_precedence_flag_over_saved(self, tmp_path):
        saved = TrainConfig(model=ModelConfig(output_size=32, gf_dim=16,
                                              compute_dtype="float32"))
        save_config(saved, str(tmp_path))
        m = resolve_model_config(str(tmp_path), overrides={
            "gf_dim": 8, "df_dim": None})  # None = not passed
        assert m.gf_dim == 8            # explicit flag wins
        assert m.output_size == 32      # from config.json
        assert m.df_dim == saved.model.df_dim

    def test_preset_replaces_saved_base(self, tmp_path):
        save_config(TrainConfig(model=ModelConfig(output_size=32,
                                                  compute_dtype="float32")),
                    str(tmp_path))
        m = resolve_model_config(str(tmp_path), preset="celeba64",
                                 overrides={})
        assert m.output_size == 64      # preset, not the saved 32

    def test_no_saved_no_preset_defaults(self, tmp_path):
        assert resolve_model_config(str(tmp_path)) == ModelConfig()


@pytest.mark.slow
class TestTrainerPersistence:
    def test_trainer_writes_and_generate_needs_no_flags(self, tmp_path):
        """The VERDICT's done-criterion: zero architecture flags on a
        non-default-architecture checkpoint."""
        cfg = _tiny_cfg(tmp_path)
        train(cfg, synthetic_data=True, max_steps=1)
        assert load_config(cfg.checkpoint_dir) == cfg

        args = build_parser().parse_args(
            ["--checkpoint_dir", cfg.checkpoint_dir,
             "--out_dir", str(tmp_path / "out"), "--num_images", "8",
             "--batch_size", "8", "--grid", "0",
             "--npz", str(tmp_path / "gen.npz")])
        result = generate(args)
        assert result["num_images"] == 8
        assert np.load(tmp_path / "gen.npz")["images"].shape == (8, 16, 16, 3)

    def test_resume_architecture_mismatch_fails_readably(self, tmp_path):
        cfg = _tiny_cfg(tmp_path)
        train(cfg, synthetic_data=True, max_steps=1)
        bad = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, gf_dim=16))
        with pytest.raises(ValueError, match="gf_dim.*(8, 16)"):
            train(bad, synthetic_data=True, max_steps=1)

    def test_cli_ignores_stale_config_without_checkpoint(self, tmp_path,
                                                         capsys):
        """ADVICE r2: a fresh CLI launch into a directory holding only a
        config.json (dead run, never saved) must NOT adopt it — unpassed
        flags keep their defaults instead of inheriting the dead run's."""
        from dcgan_tpu.train.cli import main as cli_main

        stale = _tiny_cfg(tmp_path, z_dim=77)
        save_config(stale, stale.checkpoint_dir)  # config, no checkpoint

        cli_main(["--checkpoint_dir", stale.checkpoint_dir, "--synthetic",
                  "--max_steps", "1", "--platform", "cpu",
                  "--output_size", "16", "--gf_dim", "8", "--df_dim", "8",
                  "--batch_size", "8",
                  "--sample_every_steps", "0", "--log_every_steps", "0"])
        out = capsys.readouterr().out
        assert "adopted config.json" not in out
        assert "ignoring config.json" in out
        # z_dim was not passed: must be the default, not the stale 77
        assert load_config(stale.checkpoint_dir).model.z_dim == \
            ModelConfig().z_dim

    def test_has_restorable_checkpoint(self, tmp_path):
        from dcgan_tpu.utils.checkpoint import has_restorable_checkpoint

        assert not has_restorable_checkpoint(str(tmp_path / "absent"))
        d = tmp_path / "ckpt"
        d.mkdir()
        assert not has_restorable_checkpoint(str(d))
        (d / "best").mkdir()           # retention subdir, not a step
        (d / "7.orbax-checkpoint-tmp-123").mkdir()  # in-flight temp
        assert not has_restorable_checkpoint(str(d))
        (d / "7").mkdir()              # completed step dir
        assert has_restorable_checkpoint(str(d))

    def test_cli_resume_adopts_config_zero_flags(self, tmp_path, capsys):
        """`dcgan_tpu.train --checkpoint_dir ckpt` with NO architecture
        flags resumes a non-default-architecture run: the CLI adopts the
        stored config.json (explicit flags would override)."""
        from dcgan_tpu.train.cli import main as cli_main

        cfg = _tiny_cfg(tmp_path)
        train(cfg, synthetic_data=True, max_steps=1)

        cli_main(["--checkpoint_dir", cfg.checkpoint_dir, "--synthetic",
                  "--max_steps", "2", "--platform", "cpu"])
        out = capsys.readouterr().out
        assert "adopted config.json" in out
        from dcgan_tpu.utils.checkpoint import Checkpointer
        assert Checkpointer(cfg.checkpoint_dir).latest_step() == 2

    def test_stale_config_without_checkpoint_not_binding(self, tmp_path):
        """A config.json left by a run that died before its first save must
        not claim the directory — a fresh run with a different architecture
        proceeds and overwrites it."""
        cfg = _tiny_cfg(tmp_path)
        save_config(cfg, cfg.checkpoint_dir)  # config written, no checkpoint
        fresh = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, gf_dim=16))
        train(fresh, synthetic_data=True, max_steps=1)
        assert load_config(cfg.checkpoint_dir) == fresh

    def test_resume_same_architecture_proceeds(self, tmp_path):
        cfg = _tiny_cfg(tmp_path)
        train(cfg, synthetic_data=True, max_steps=1)
        # run knobs may change between runs; only architecture is pinned
        resumed = dataclasses.replace(cfg, learning_rate=1e-4)
        state = train(resumed, synthetic_data=True, max_steps=2)
        assert int(np.asarray(state["step"])) == 2

    def test_conditional_bn_round_trip(self, tmp_path):
        """ADVICE r1 (medium): a cBN checkpoint must be samplable — its
        [K, C] BN tables restore only if the consumer reconstructs cBN."""
        cfg = _tiny_cfg(tmp_path, num_classes=4, conditional_bn=True)
        train(cfg, synthetic_data=True, max_steps=1)

        args = build_parser().parse_args(
            ["--checkpoint_dir", cfg.checkpoint_dir,
             "--out_dir", str(tmp_path / "out"), "--num_images", "8",
             "--batch_size", "8", "--grid", "0",
             "--npz", str(tmp_path / "gen.npz"), "--class_id", "1"])
        result = generate(args)
        assert result["num_images"] == 8
        assert (np.load(tmp_path / "gen.npz")["labels"] == 1).all()
