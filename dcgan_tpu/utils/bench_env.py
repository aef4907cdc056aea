"""The BENCH_* env knobs -> config, for bench.py (the driver's bench
contract) and the capture matrix that labels its rows.

Knobs handled here (model-shape only — batch/steps/scan/backends stay with
their owners, they don't change WHAT is measured, only how long):

  BENCH_PRESET     named preset (presets.py) instead of the flagship
  BENCH_SIZE       output resolution (default 64)
  BENCH_ATTN=1     self-attention at 32x32 (the sagan64-attn shape)
  BENCH_SN=1       spectral norm on both nets
  BENCH_PALLAS=1   use_pallas: attention on the flash kernels (a no-op
                   without attention — DESIGN.md §8b)
  BENCH_ATTN_RES=R attention at feature-map resolution R on top of
                   whatever config the knobs above built (the long-context
                   knob: R=128 at BENCH_SIZE=256 is S=16384)
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

from dcgan_tpu.config import ModelConfig, TrainConfig


def bench_model_config(env=None) -> Tuple[ModelConfig, str]:
    """(ModelConfig, label) from the non-preset BENCH_* model knobs."""
    env = os.environ if env is None else env
    mcfg = ModelConfig(
        output_size=int(env.get("BENCH_SIZE", 64)),
        use_pallas=env.get("BENCH_PALLAS", "") == "1",
        attn_res=32 if env.get("BENCH_ATTN", "") == "1" else 0,
        spectral_norm="gd" if env.get("BENCH_SN", "") == "1" else "none")
    # the label must be injective over the knobs above — capture renders
    # group by it, and two configs sharing a label would merge into one
    # published row (the never-mix-configs contract)
    size = mcfg.output_size
    if mcfg.attn_res:
        label = f"sagan{size}-attn"
    else:
        label = "headline" if size == 64 else f"dcgan{size}"
    # BENCH_ATTN_RES is applied to the CONFIG later (apply_attn_res_override
    # runs on the full TrainConfig), but the label must reflect it NOW
    # (ADVICE r5 #2): the flash suffix below keys off whether attention
    # actually runs. The bench matrix's long-context rows name these
    # '<family>-attn<R>-{flash,dense}' (tools/capture_all.py) — match that.
    attn_res_knob = int(env.get("BENCH_ATTN_RES", "0") or 0)
    if attn_res_knob:
        label += f"-attn{attn_res_knob}"
    effective_attn = mcfg.attn_res or attn_res_knob
    if mcfg.use_pallas and effective_attn:
        label += "-flash"
    elif attn_res_knob:
        label += "-dense"  # the bench matrix's explicit dense rows
    if mcfg.spectral_norm != "none":
        label += "-sn"
    return mcfg, label


def apply_attn_res_override(cfg: TrainConfig, env=None) -> TrainConfig:
    """BENCH_ATTN_RES on top of ANY built config (preset or default).

    Only overrides use_pallas when BENCH_PALLAS is explicitly set — a
    preset's own use_pallas must survive an attn_res-only override.
    """
    env = os.environ if env is None else env
    if not env.get("BENCH_ATTN_RES"):
        return cfg
    model_kw = {"attn_res": int(env["BENCH_ATTN_RES"])}
    if "BENCH_PALLAS" in env:
        model_kw["use_pallas"] = env["BENCH_PALLAS"] == "1"
    return dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **model_kw))
