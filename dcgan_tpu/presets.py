"""Named training presets — the BASELINE.json config matrix as one-call configs.

BASELINE.json lists five benchmark configurations for this framework; each is a
`TrainConfig` factory here so `python -m dcgan_tpu.train --preset <name>` (and
tests/bench code) can materialize them without repeating knob soup:

- ``celeba64``    — DCGAN 64x64 CelebA, single-host, z=100, batch 64: the
  reference's headline workload (image_train.py:42-48, distriubted_model.py:7-12).
- ``lsun64-dp8``  — DCGAN 64x64 LSUN-bedroom, data-parallel over 8 chips
  (v5e-8): global batch 64*8 sharded over the "data" mesh axis, grads psum'd
  over ICI — the sync replacement for the reference's async PS workers
  (SURVEY.md §2.5).
- ``dcgan128``    — 128x128: one extra stride-2 stage in both stacks
  (ModelConfig.num_up_layers == 5) with cross-replica synced BatchNorm.
- ``cifar10-cond`` — class-conditional DCGAN on CIFAR-10 (32x32, 10 classes):
  activates the reference's accepted-but-ignored `y` argument
  (distriubted_model.py:83, SURVEY.md §2.4 #7).
- ``wgan-gp``     — WGAN-GP loss variant: Wasserstein critic + gradient
  penalty (grad-of-grad), canonical lr 1e-4 / β1 0 hyperparameters.

Plus nine beyond-BASELINE presets across five further model/recipe
families (fourteen registered configs total — keep this count in sync with
``PRESETS`` below):

- ``sagan64``     — self-attention GAN (hinge + TTUR + EMA, attention at
  32x32), whose attention block is the framework's sequence-parallel
  (ring-attention) showcase under ``--mesh_spatial``.
- ``sagan128``    — the same recipe with attention at 64x64 (4096 tokens).
- ``sagan256-lc`` — the long-context configuration: attention over a
  128x128 feature map (16384 tokens) on the flash kernels, where the
  dense form cannot allocate at batch 64 (DESIGN.md §8b).
- ``sngan-cifar10`` / ``stylegan64`` — the resnet and stylegan families'
  canonical recipes (see their factory docstrings).
- ``joyai_llm_flash`` / ``mla_moe_tiny`` — the one-network token family
  (latent attention, routed experts, a multi-token head; likelihood step):
  a published 48B model as published, and the size the tests train.
- ``ouro_2_6b`` / ``loop_lm_tiny`` — the looped token family (one stack of
  layers run four times on shared weights, an exit gate, an expected loss
  over the exits; the same likelihood step): a published 2.6B model as
  published, and the size the tests train.
- ``phi_4_mini_flash`` / ``sambay_tiny`` — the decoder-hybrid-decoder token
  family (Mamba scans, window / full / cross differential attention, gated
  memory units, a tied head; the same likelihood step): a published 3.8B
  model as published, and the size the tests train.

Every preset factory takes overrides as keyword arguments forwarded to
`dataclasses.replace`-style reconstruction, so the CLI's explicit flags win
over preset defaults.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict

from dcgan_tpu.config import (
    LM_LOSS,
    LoopModelConfig,
    MeshConfig,
    ModelConfig,
    SambaYModelConfig,
    TokenModelConfig,
    TrainConfig,
)


def _build(model: ModelConfig, mesh: MeshConfig, **train_kw) -> TrainConfig:
    return TrainConfig(model=model, mesh=mesh, **train_kw)


def celeba64(**overrides) -> TrainConfig:
    """DCGAN 64x64 CelebA, single-host (the reference's headline workload)."""
    cfg = _build(ModelConfig(output_size=64), MeshConfig(),
                 batch_size=64, dataset="celebA")
    return dataclasses.replace(cfg, **overrides)


def lsun64_dp8(**overrides) -> TrainConfig:
    """DCGAN 64x64 LSUN-bedroom, data-parallel over an 8-chip mesh."""
    cfg = _build(ModelConfig(output_size=64), MeshConfig(data=8),
                 batch_size=64 * 8, dataset="lsun-bedroom")
    return dataclasses.replace(cfg, **overrides)


def dcgan128(**overrides) -> TrainConfig:
    """DCGAN 128x128: deeper G/D (5 up/down stages), synced BN across mesh."""
    cfg = _build(ModelConfig(output_size=128), MeshConfig(),
                 batch_size=64)
    return dataclasses.replace(cfg, **overrides)


def cifar10_cond(**overrides) -> TrainConfig:
    """Class-conditional DCGAN on CIFAR-10 (32x32 RGB, 10 classes)."""
    cfg = _build(ModelConfig(output_size=32, num_classes=10),
                 MeshConfig(), batch_size=64, dataset="cifar10")
    return dataclasses.replace(cfg, **overrides)


def wgan_gp(**overrides) -> TrainConfig:
    """WGAN-GP on 64x64: critic + gradient penalty, lr 1e-4, β1=0, n_critic=5.

    The BCE defaults (lr 2e-4, β1 0.5, image_train.py:11-13) destabilize a
    Wasserstein critic; these are the standard WGAN-GP settings (Gulrajani et
    al. 2017) — including 5 critic updates per generator update — and apply
    only when the flags are left at their defaults. One documented deviation
    from the paper's Algorithm 1: all 5 critic iterations see the *same* real
    batch (with fresh z each) rather than 5 fresh real minibatches, so the
    whole n_critic loop stays inside one compiled step on one incoming batch.
    """
    cfg = _build(ModelConfig(output_size=64), MeshConfig(),
                 batch_size=64, loss="wgan-gp",
                 learning_rate=1e-4, beta1=0.0, n_critic=5)
    return dataclasses.replace(cfg, **overrides)


def sagan64(**overrides) -> TrainConfig:
    """Self-attention GAN on 64x64: DCGAN stacks with attention at 32x32.

    The canonical SAGAN recipe (Zhang et al. 2018): hinge loss, spectral
    norm on both nets, TTUR (d_lr 4e-4 / g_lr 1e-4), beta1=0, generator
    weight EMA. Beyond-reference model family; under `--mesh_spatial` the
    attention runs as sequence-parallel ring attention (ops/attention.py).
    One documented divergence: G normalization is the reference's plain
    (synced) BatchNorm, not the paper's conditional BN.
    """
    cfg = _build(ModelConfig(output_size=64, attn_res=32,
                             spectral_norm="gd",
                             # attention on the flash kernels (DESIGN.md
                             # §8b). Composes with every mesh: per-shard
                             # nested shard_map on DP gspmd (attn_apply's
                             # pallas_mesh route), ring x flash under
                             # --mesh_spatial, per-shard under shard_map
                             use_pallas=True),
                 MeshConfig(),
                 batch_size=64, loss="hinge", beta1=0.0,
                 d_learning_rate=4e-4, g_learning_rate=1e-4,
                 g_ema_decay=0.999)
    return dataclasses.replace(cfg, **overrides)


def sagan128(**overrides) -> TrainConfig:
    """SAGAN at 128x128 — the long-sequence attention demonstrator
    (VERDICT r1 #7): attention at the 64x64 stage is a 4096-token sequence,
    the scale where the sequence-parallel machinery (ring/ulysses under
    --mesh_spatial) and the flash kernels (--use_pallas) earn their keep.
    Same recipe as sagan64 otherwise (hinge, SN both nets, TTUR, EMA)."""
    cfg = _build(ModelConfig(output_size=128, attn_res=64,
                             spectral_norm="gd", use_pallas=True),
                 MeshConfig(),
                 batch_size=64, loss="hinge", beta1=0.0,
                 d_learning_rate=4e-4, g_learning_rate=1e-4,
                 g_ema_decay=0.999)
    return dataclasses.replace(cfg, **overrides)


def sagan256_lc(**overrides) -> TrainConfig:
    """The long-context configuration: 256x256 DCGAN stacks with attention
    over the 128x128 feature map — a 16 384-token sequence — on the flash
    kernels (use_pallas). This config is flash-ONLY at the reference's
    batch 64: XLA's dense lowering needs a 64 GiB f32[64, 16384, 16384]
    score buffer and cannot allocate (DESIGN.md §8/8b; its rate on the
    flash path is not measured on the current machine). SAGAN recipe (hinge, SN on D, TTUR, EMA); SN
    is D-only here — G's 2048-channel early stages make G-side power
    iteration the dominant non-attention cost at this depth."""
    cfg = _build(ModelConfig(output_size=256, attn_res=128,
                             spectral_norm="d", use_pallas=True),
                 MeshConfig(),
                 # shard_map backend: each shard runs the flash kernels on
                 # its own batch rows. gspmd composes the same way since its
                 # nested shard_map (parallel/api.py; the benchmark's
                 # four-chip cell runs it), so the pin is a choice between
                 # two working backends that no cell has decided yet
                 # (ROADMAP Queue 3 item 2)
                 backend="shard_map",
                 batch_size=64, loss="hinge", beta1=0.0,
                 d_learning_rate=4e-4, g_learning_rate=1e-4,
                 g_ema_decay=0.999)
    return dataclasses.replace(cfg, **overrides)


def sngan_cifar10(**overrides) -> TrainConfig:
    """SNGAN on CIFAR-10 (32x32), after Miyato et al. 2018 (table 3):
    residual G/D, norm-free spectrally-normalized critic, hinge loss,
    Adam(2e-4, β1=0), 5 critic steps per G step. Two knowing deviations
    from the paper, so don't expect paper-exact FID: β2 stays at the repo
    default 0.999 (paper: 0.9), and the critic architecture differs —
    models/resnet.py doubles channel width per stage and downsamples in
    EVERY block (final 4x4 map), where the paper's CIFAR-10 D keeps
    constant 128-ch blocks with the last two blocks not downsampling
    (final 8x8 map). Beyond-reference model family (models/resnet.py)."""
    cfg = _build(ModelConfig(arch="resnet", output_size=32,
                             spectral_norm="d"),
                 MeshConfig(), batch_size=64, dataset="cifar10",
                 loss="hinge", learning_rate=2e-4, beta1=0.0, n_critic=5)
    return dataclasses.replace(cfg, **overrides)


def stylegan64(**overrides) -> TrainConfig:
    """StyleGAN2-lite at 64x64 (models/stylegan.py): mapping network +
    modulated convs + skip tRGB, paired with the norm-free residual critic
    and the paper's training regularizer — lazy R1 (gamma 10, every 16th
    step) — plus generator-weight EMA. Knowing deviations from the paper
    (documented in models/stylegan.py): no noise injection / style mixing /
    path-length regularization, Adam(2e-4, β1 0.5, β2 0.999) instead of
    (2.5e-3, 0, 0.99), tanh-range output. Beyond-reference model family."""
    cfg = _build(ModelConfig(arch="stylegan", output_size=64),
                 MeshConfig(), batch_size=64,
                 r1_gamma=10.0, r1_interval=16, g_ema_decay=0.999)
    return dataclasses.replace(cfg, **overrides)


def _lm(model, **train_kw) -> TrainConfig:
    """The token family's run knobs: the likelihood loss, the program's
    Adam at beta1 0.9, no decay, no clipping, and every image-only service
    (sample grids, activation summaries) off."""
    kw = dict(loss=LM_LOSS, beta1=0.9, learning_rate=2.2e-4,
              sample_every_steps=0, activation_summary_steps=0)
    kw.update(train_kw)
    return _build(model, MeshConfig(), **kw)


def joyai_llm_flash(**overrides) -> TrainConfig:
    """JoyAI-LLM-Flash (48B-A2.7B) AS PUBLISHED (jdopensource, config.json
    on huggingface.co/jdopensource/JoyAI-LLM-Flash; the layer equations
    are DeepSeek-V3's, arXiv:2412.19437): 40 layers of latent attention (32
    heads, q rank 1536, kv rank 512, 128 + 64 rotary | 128 value), one
    leading dense layer of width 7168, then 256 routed experts of width 768
    (8 per token, sigmoid scores, scale 2.5) beside one shared expert, one
    multi-token module, vocabulary 129,280, 8,192-token rows. No chip
    holds it whole (one expert layer is 19.8 GB of training state): a
    configuration that runs cuts depth, experts held and vocabulary to
    one chip's share of a stated deployment
    (benchmark/configs/joyai-llm-flash.json)."""
    model = TokenModelConfig(
        vocab_size=129280, hidden_size=2048, num_hidden_layers=40,
        first_k_dense_replace=1, intermediate_size=7168,
        moe_intermediate_size=768, n_routed_experts=256, n_shared_experts=1,
        num_experts_per_tok=8, norm_topk_prob=True,
        routed_scaling_factor=2.5, num_attention_heads=32,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_theta=32000000.0,
        rope_interleave=True, rms_norm_eps=1e-6, num_nextn_predict_layers=1,
        experts_held=256, first_expert=0, seq_len=8192)
    return dataclasses.replace(_lm(model, batch_size=1), **overrides)


def mla_moe_tiny(**overrides) -> TrainConfig:
    """The token family at a size the CPU tests and a smoke run train:
    hidden 64, 2 heads, 8 experts of which 4 held, vocabulary 256, rows of
    32 tokens, float32, dense masked attention in place of the kernels."""
    model = TokenModelConfig(experts_held=4, compute_dtype="float32",
                             use_pallas=False)
    return dataclasses.replace(_lm(model, batch_size=8), **overrides)


def ouro_2_6b(**overrides) -> TrainConfig:
    """Ouro-2.6B AS PUBLISHED (ByteDance, config.json on
    huggingface.co/ByteDance/Ouro-2.6B; "Scaling Latent Reasoning via Looped
    Language Models", arXiv:2510.25741): ONE stack of 48 layers run 4 times
    on shared weights (`total_ut_steps`), hidden 2048, 16 heads of 128
    (plain multi-head), SwiGLU 5632 wide, sandwich RMSNorm, rotary theta
    1e6 over the whole head, vocabulary 49,152 with embedding and head
    untied, an exit gate after every pass; 4,096-token rows (the
    pre-training length). 2,667,974,657 parameters are 42.7 GB of training
    state, so no chip holds it: a configuration that runs cuts the depth to
    one pipeline stage's layers (benchmark/configs/ouro-2.6b.json)."""
    model = LoopModelConfig(
        vocab_size=49152, hidden_size=2048, num_hidden_layers=48,
        intermediate_size=5632, num_attention_heads=16,
        num_key_value_heads=16, head_dim=128, rope_theta=1000000.0,
        rms_norm_eps=1e-6, total_ut_steps=4, early_exit_threshold=1.0,
        seq_len=4096, loss_beta=0.1)
    return dataclasses.replace(
        _lm(model, batch_size=1, learning_rate=3e-4), **overrides)


def loop_lm_tiny(**overrides) -> TrainConfig:
    """The looped family at a size the CPU tests and a smoke run train:
    hidden 64, 2 layers run 4 times, 2 heads of 32, vocabulary 256, rows of
    32 tokens, float32, the causal kernels in interpret mode."""
    model = LoopModelConfig(compute_dtype="float32")
    return dataclasses.replace(
        _lm(model, batch_size=8, learning_rate=3e-4), **overrides)


def phi_4_mini_flash(**overrides) -> TrainConfig:
    """Phi-4-mini-flash-reasoning AS PUBLISHED (microsoft, config.json on
    huggingface.co/microsoft/Phi-4-mini-flash-reasoning; SambaY with
    differential attention, arXiv:2507.06607): 32 layers of hidden 2560 in
    the published layout (9 Mamba-1 layers of d_inner 5120 and 16 states, 8
    layers of 512-token window attention, ONE full-attention layer whose
    keys and values 7 cross-attention layers share, 7 gated memory units
    reading layer 16's scan output), 40 heads of 64 over 20 key/value heads
    in differential pairs, SwiGLU 10240 wide, LayerNorm, no positional
    encoding, a tied vocabulary of 200,064; 8,192-token rows.
    3,852,562,944 parameters are 61.6 GB of training state, so no chip
    holds it: a configuration that runs cuts depth and vocabulary to one
    chip's share (benchmark/configs/phi-4-mini-flash.json)."""
    model = SambaYModelConfig(
        vocab_size=200064, hidden_size=2560, num_hidden_layers=32,
        layer_types=(), intermediate_size=10240, num_attention_heads=40,
        num_key_value_heads=20, sliding_window=512, mb_per_layer=2,
        layer_norm_eps=1e-5, mamba_d_state=16, mamba_d_conv=4,
        mamba_expand=2, mamba_dt_rank=160, seq_len=8192)
    return dataclasses.replace(
        _lm(model, batch_size=1, learning_rate=3e-4), **overrides)


def sambay_tiny(**overrides) -> TrainConfig:
    """The decoder-hybrid-decoder family at a size the CPU tests and a
    smoke run train: hidden 64, one layer of each kind in the published
    order (Mamba, window attention, Mamba as memory, full attention, gated
    memory unit, cross-attention), 4 heads of 16 in two pairs over one
    key/value pair, d_inner 128, a window of 8, vocabulary 256, rows of 32
    tokens, float32, every kernel in interpret mode."""
    model = SambaYModelConfig(compute_dtype="float32")
    return dataclasses.replace(
        _lm(model, batch_size=8, learning_rate=3e-4), **overrides)


PRESETS: Dict[str, Callable[..., TrainConfig]] = {
    "celeba64": celeba64,
    "lsun64-dp8": lsun64_dp8,
    "dcgan128": dcgan128,
    "cifar10-cond": cifar10_cond,
    "wgan-gp": wgan_gp,
    "sagan64": sagan64,
    "sagan128": sagan128,
    "sagan256-lc": sagan256_lc,
    "sngan-cifar10": sngan_cifar10,
    "stylegan64": stylegan64,
    "joyai_llm_flash": joyai_llm_flash,
    "mla_moe_tiny": mla_moe_tiny,
    "ouro_2_6b": ouro_2_6b,
    "loop_lm_tiny": loop_lm_tiny,
    "phi_4_mini_flash": phi_4_mini_flash,
    "sambay_tiny": sambay_tiny,
}

# Preset revisions: bump when a preset's PERF-RELEVANT config changes
# (execution form, backend, batch policy — anything that moves its bench
# row). bench.py stamps the revision into each preset capture and
# tools/capture_all.py publishes best/spread over the highest revision
# only, so a row's spread never mixes configs that no longer exist —
# the same contract ops/pallas_attention.py::ATTN_GEN gives kernel
# changes. Unlisted presets are revision 1.
# rev 2 (r5): sagan64/sagan128/sagan256-lc run attention on the flash
# kernels and BN on XLA (chip-measured +46% on the sagan64-shape step).
PRESET_REVS: Dict[str, int] = {
    "sagan64": 2,
    "sagan128": 2,
    "sagan256-lc": 2,
}


def get_preset(name: str, **overrides) -> TrainConfig:
    try:
        factory = PRESETS[name]
    except KeyError:
        raise ValueError(
            f"unknown preset {name!r}; available: {sorted(PRESETS)}") from None
    return factory(**overrides)
