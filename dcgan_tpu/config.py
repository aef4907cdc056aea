"""Configuration dataclasses — the single flat knob namespace of the reference
(`image_train.py:10-38` tf.app.flags) re-expressed as typed, validated dataclasses.

Unlike the reference, model hyperparameters here are *wired*: changing
`ModelConfig.output_size`/`c_dim` or `TrainConfig.batch_size` actually changes
the built model/step (the
reference's flags of the same names were disconnected from the module constants
actually used — SURVEY.md §2.4 #8, distriubted_model.py:7-12 vs image_train.py:15-18).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """DCGAN architecture knobs (reference: distriubted_model.py:7-12, image_train.py:42).

    The reference hard-codes output_size=64, gf_dim=df_dim=64, c_dim=3, z_dim=100.
    Here output_size may be any power of two >= 8; the G/D stacks deepen
    automatically (128x128 config from BASELINE.json uses output_size=128).
    """

    arch: str = "dcgan"            # model family: "dcgan" (the reference's
                                   # stride-2 5x5 stacks) | "resnet" (the
                                   # WGAN-GP/SNGAN residual blocks,
                                   # models/resnet.py — BN-free critic,
                                   # upsample-conv G) | "stylegan"
                                   # (StyleGAN2-lite: mapping network +
                                   # modulated convs + skip tRGB,
                                   # models/stylegan.py, paired with the
                                   # resnet critic). All scale by
                                   # base_size*2^k; dcgan/resnet compose
                                   # with conditioning/cBN/attention/SN/
                                   # pallas, stylegan with conditioning and
                                   # spectral_norm="d" (no BN to condition,
                                   # no attention site wired)
    output_size: int = 64          # spatial size of generated images (H == W)
    gf_dim: int = 64               # generator base feature maps
    df_dim: int = 64               # discriminator base feature maps
    c_dim: int = 3                 # image channels
    z_dim: int = 100               # latent dimension (image_train.py:42)
    num_classes: int = 0           # >0 activates class-conditional G/D (the
                                   # reference's dead `y` arg, distriubted_model.py:83)
    conditional_bn: bool = False   # conditional models only: the generator's
                                   # BN affine becomes per-class [K, C] tables
                                   # (SAGAN/BigGAN cBN) instead of the z-concat
                                   # conditioning alone; moments stay shared
    base_size: int = 4             # spatial size of the first feature map
    bn_momentum: float = 0.9       # EMA decay (distriubted_model.py:18,23)
    bn_eps: float = 1e-5           # (distriubted_model.py:18)
    leak: float = 0.2              # lrelu slope (distriubted_model.py:156)
    kernel_size: int = 5           # conv / deconv kernel (distriubted_model.py:176,190)
    compute_dtype: str = "bfloat16"  # MXU-native compute precision
    param_dtype: str = "float32"     # parameter / BN-stat storage precision
    use_pallas: bool = False       # the ONE kernel decision of the image
                                   # models: attention runs on the flash
                                   # kernels (ops/pallas_attention.py) where
                                   # the model has attention (attn_res > 0),
                                   # and nothing else changes — convolution,
                                   # BN, activations and Adam are XLA's
                                   # (DESIGN.md §8b). A no-op at attn_res=0
    # RETIRED names (PR 29): the Pallas BN kernels and the fused conv blocks
    # lost to XLA on the chip and are gone. Read by nothing; accepted while
    # falsy (configs saved beside old checkpoints and benchmark/configs/*.json
    # carry them), normalized to these defaults, refused when truthy. They go
    # when those files drop the keys (ROADMAP.md Queue 3 item 1)
    bn_pallas: Optional[bool] = None
    pallas_fused: bool = False
    attn_res: int = 0              # >0 inserts a SAGAN-style self-attention
                                   # block (ops/attention.py) into both stacks
                                   # at the stage whose feature maps are
                                   # attn_res x attn_res (e.g. 32 for the
                                   # SAGAN-64 recipe). Under a spatial mesh the
                                   # block executes as sequence-parallel ring
                                   # attention. 0 = off (reference parity: the
                                   # reference is pure conv)
    attn_heads: int = 1            # heads for the attention block (1 = the
                                   # SAGAN paper's single head). Apply-time
                                   # split of the same projections — param
                                   # shapes and checkpoints are head-count
                                   # independent (ops/attention.py)
    attn_seq_strategy: str = "ring"  # sequence-parallel execution under a
                                     # spatial mesh: "ring" (ppermute k/v,
                                     # any head count) | "ulysses" (two
                                     # all_to_alls; attn_heads must be
                                     # divisible by the model-axis size —
                                     # arXiv:2309.14509). Exact either way;
                                     # a pure execution knob
    spectral_norm: str = "none"    # "d": spectral-normalize every
                                   # discriminator weight (SN-GAN,
                                   # arXiv:1802.05957); "gd": both nets (the
                                   # SAGAN recipe); "none" = reference parity.
                                   # Power-iteration state is explicit, like
                                   # BN moments (ops/spectral.py)

    def __post_init__(self):
        if self.arch not in ("dcgan", "resnet", "stylegan"):
            raise ValueError(
                f"arch must be 'dcgan', 'resnet', or 'stylegan', got "
                f"{self.arch!r}")
        if self.bn_pallas or self.pallas_fused:
            raise ValueError(
                "bn_pallas / pallas_fused were removed in PR 29: BN and "
                "convolution run on XLA")
        object.__setattr__(self, "bn_pallas", None)
        object.__setattr__(self, "pallas_fused", False)
        if self.arch == "stylegan":
            if self.conditional_bn:
                raise ValueError(
                    "arch='stylegan' has no BatchNorm to condition "
                    "(styles carry conditioning); drop conditional_bn")
            if self.attn_res:
                raise ValueError(
                    "arch='stylegan' has no attention site wired; use "
                    "arch='dcgan'/'resnet' for attn_res")
            if self.spectral_norm == "gd":
                raise ValueError(
                    "arch='stylegan' supports spectral_norm='d' (critic "
                    "only) — SN on a style-modulated generator is not "
                    "wired")
        n = self.num_up_layers
        if n < 1 or self.base_size * (2 ** n) != self.output_size:
            raise ValueError(
                f"output_size={self.output_size} must be base_size*2^k with "
                f"k >= 1 (base_size={self.base_size})")
        if self.attn_res:
            sites = {self.base_size * (2 ** j) for j in range(n)}
            if self.attn_res not in sites:
                raise ValueError(
                    f"attn_res={self.attn_res} is not a feature-map "
                    f"resolution of this stack; choose one of {sorted(sites)}")
        if self.spectral_norm not in ("none", "d", "gd"):
            raise ValueError(
                f"spectral_norm must be 'none', 'd', or 'gd', got "
                f"{self.spectral_norm!r}")
        if self.attn_heads < 1:
            raise ValueError(
                f"attn_heads must be >= 1, got {self.attn_heads}")
        if self.attn_seq_strategy not in ("ring", "ulysses"):
            raise ValueError(
                f"attn_seq_strategy must be 'ring' or 'ulysses', got "
                f"{self.attn_seq_strategy!r}")
        if self.conditional_bn and not self.num_classes:
            raise ValueError(
                "conditional_bn requires a conditional model "
                "(num_classes > 0)")

    @property
    def num_up_layers(self) -> int:
        """Number of stride-2 deconv (G) / conv (D) stages.

        output_size 64 -> 4 stages (matching the reference's fixed 4-deconv stack,
        distriubted_model.py:93-109); 128 -> 5 stages.
        """
        return int(round(math.log2(self.output_size / self.base_size)))


#: the one-network token family's `arch` (models/mla_moe.py): a causal
#: language model with latent attention, routed experts and a multi-token
#: head, trained by a likelihood step (TrainConfig.loss == LM_LOSS)
TOKEN_ARCH = "mla_moe"
#: the looped token family's `arch` (models/loop_lm.py): one stack of
#: layers run `total_ut_steps` times on shared weights, an exit gate and
#: an expected loss over the exits; the same likelihood step
LOOP_ARCH = "loop_lm"
#: the decoder-hybrid-decoder family's `arch` (models/sambay.py): Mamba
#: scans, window, full and cross differential attention and gated memory
#: units in one stack, a tied head; the same likelihood step
SAMBAY_ARCH = "sambay"
LM_LOSS = "lm"


@dataclasses.dataclass(frozen=True)
class TokenModelConfig:
    """A causal token model: latent attention (MLA), one leading dense
    SwiGLU layer, then layers of routed + shared experts, and multi-token
    prediction modules (DeepSeek-V3, arXiv:2412.19437). Fields carry the
    names of the public `config.json` of that family where it has one; the
    defaults are a small model, the presets hold published ones.

    What a chip holds of a layer is stated, never inferred: `experts_held`
    experts starting at `first_expert` (the router still scores all
    `n_routed_experts` and selects `num_experts_per_tok` of them wherever
    they live), and `vocab_size` rows of the embedding and the head.
    """

    arch: str = TOKEN_ARCH
    vocab_size: int = 256          # rows of embedding and head held here
    hidden_size: int = 64
    num_hidden_layers: int = 3     # trunk layers, the leading dense included
    first_k_dense_replace: int = 1  # leading layers with a dense FFN
    intermediate_size: int = 128   # the dense FFN's width
    moe_intermediate_size: int = 32  # one expert's width
    n_routed_experts: int = 8      # the router's outputs
    n_shared_experts: int = 1
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True    # weights divided by their sum over ALL
                                   # selected experts, held here or not
    routed_scaling_factor: float = 2.5
    num_attention_heads: int = 2
    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 10000.0
    rope_interleave: bool = True   # stored rotary dims are pairs (2i, 2i+1)
    rms_norm_eps: float = 1e-6
    num_nextn_predict_layers: int = 1  # multi-token modules (0 or 1)
    experts_held: int = 8          # of n_routed_experts, on this chip
    first_expert: int = 0          # index of the first one held
    seq_len: int = 32              # tokens of one row of the batch
    mtp_loss_weight: float = 0.3   # the multi-token loss's share
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    use_pallas: bool = True        # causal flash kernels; False = dense
                                   # masked attention (short sequences)

    # what the shared trainer and config code asks of any model: this
    # family has no classes
    num_classes = property(lambda self: 0)

    def __post_init__(self):
        if self.arch != TOKEN_ARCH:
            raise ValueError(
                f"TokenModelConfig.arch must be {TOKEN_ARCH!r}, got "
                f"{self.arch!r}")
        if not 0 < self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                "first_k_dense_replace must lie in 1..num_hidden_layers")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers must be 0 or 1")
        if not (0 < self.experts_held <= self.n_routed_experts
                and 0 <= self.first_expert
                and self.first_expert + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts held [{self.first_expert}, {self.first_expert} + "
                f"{self.experts_held}) must lie within the "
                f"{self.n_routed_experts} routed experts")
        if not 0 < self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError("num_experts_per_tok out of range")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if self.seq_len < 3:
            raise ValueError("seq_len must be >= 3 (the multi-token loss "
                             "needs a target two ahead)")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class LoopModelConfig:
    """A looped causal language model (Ouro, arXiv:2510.25741): ONE stack
    of `num_hidden_layers` layers applied `total_ut_steps` times on shared
    weights, the final norm and an exit gate after each pass, one head for
    every exit, trained on the expected loss over the exits less `loss_beta`
    times the entropy of the exit distribution. Each layer: multi-head
    attention with rotary over the whole head and a sandwich RMSNorm (a
    norm before AND after each branch), then SwiGLU likewise. Fields carry
    the names of the model's public `config.json`; the defaults are a
    small model, the presets hold the published one.
    """

    arch: str = LOOP_ARCH
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 2     # layers of the one stack
    intermediate_size: int = 128   # SwiGLU width
    num_attention_heads: int = 2
    num_key_value_heads: int = 2   # plain multi-head: equal to the heads
    head_dim: int = 32
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4        # passes over the stack = exits
    early_exit_threshold: float = 1.0  # inference's; 1 (never exit early)
                                       # is all the training step can mean
    seq_len: int = 32              # tokens of one row of the batch
    loss_beta: float = 0.1         # weight of the exit distribution's entropy
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    use_pallas: bool = True        # causal flash kernels; False = dense
                                   # masked attention (short sequences)

    num_classes = property(lambda self: 0)

    def __post_init__(self):
        if self.arch != LOOP_ARCH:
            raise ValueError(
                f"LoopModelConfig.arch must be {LOOP_ARCH!r}, got "
                f"{self.arch!r}")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "arch 'loop_lm' is plain multi-head attention: "
                "num_key_value_heads must equal num_attention_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary pairs)")
        if self.total_ut_steps < 1 or self.num_hidden_layers < 1:
            raise ValueError(
                "total_ut_steps and num_hidden_layers must be >= 1")
        if self.early_exit_threshold != 1:
            raise ValueError(
                "arch 'loop_lm' trains every pass and has no inference "
                "path: early_exit_threshold must be 1 (never exit early), "
                f"got {self.early_exit_threshold}")
        if self.seq_len < 2:
            raise ValueError("seq_len must be >= 2 (a next token to score)")


#: the kinds of layer of the decoder-hybrid-decoder family, by the name of
#: the scope each mixer runs under
SAMBAY_KINDS = ("mamba", "attn_win", "attn_full", "gmu", "attn_cross")


def sambay_layout(n: int) -> tuple:
    """The published layout of `n` layers (Phi-4-mini-flash-reasoning, 32):
    the self-decoder alternates Mamba and window attention over the first
    half, layer n/2 is a Mamba layer (its scan output is the memory), layer
    n/2 + 1 the one full-attention layer (its keys and values are the
    shared set), and the cross-decoder after it alternates gated memory
    units (even layers) and cross-attention (odd)."""
    if n < 4 or n % 2:
        raise ValueError(
            f"the published layout needs an even number of layers >= 4 "
            f"(two decoders around the bridge pair), got {n}")
    half = n // 2
    return tuple(
        ("mamba" if i % 2 == 0 else "attn_win") if i < half else
        "mamba" if i == half else "attn_full" if i == half + 1 else
        ("gmu" if i % 2 == 0 else "attn_cross") for i in range(n))


@dataclasses.dataclass(frozen=True)
class SambaYModelConfig:
    """A decoder-hybrid-decoder causal language model (SambaY with
    differential attention: "Decoder-Hybrid-Decoder Architecture for
    Efficient Reasoning with Long Generation", arXiv:2507.06607;
    Phi-4-mini-flash-reasoning): every layer is `x += Mixer(LN(x)); x +=
    SwiGLU(LN(x))` with one of five mixers: a Mamba-1 selective scan, window
    / full / cross differential attention (two softmax maps over one value
    set, subtracted), a gated memory unit. The last Mamba layer before the
    cross-decoder hands its scan output to every gated memory unit, the one
    full-attention layer its keys and values to every cross-attention
    layer. LayerNorm with bias, no positional encoding, the head is the
    embedding transposed. Fields carry the names of the model's public
    `config.json`; what it does not give (the Mamba sizes, the layout) is
    the family's convention. The defaults are a small model, the presets
    hold the published one.
    """

    arch: str = SAMBAY_ARCH
    vocab_size: int = 256          # rows of the tied embedding held here
    hidden_size: int = 64
    num_hidden_layers: int = 6
    #: the kind of every layer, of SAMBAY_KINDS; () is the published layout
    #: of `num_hidden_layers` layers (`sambay_layout`)
    layer_types: tuple = ("mamba", "attn_win", "mamba", "attn_full", "gmu",
                          "attn_cross")
    intermediate_size: int = 256   # SwiGLU width
    num_attention_heads: int = 4   # 64-wide at the published size; a
                                   # differential pair is two of them
    num_key_value_heads: int = 2
    sliding_window: int = 8        # keys a window layer's query sees,
                                   # itself counted
    mb_per_layer: int = 2          # every second layer of the self-decoder
                                   # is Mamba: all `sambay_layout` lays out
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0         # 0: ceil(hidden_size / 16)
    seq_len: int = 32              # tokens of one row of the batch
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # no `use_pallas`: one scan and one attention path, the kernels
    # (interpreted off the TPU)

    num_classes = property(lambda self: 0)

    def __post_init__(self):
        if self.arch != SAMBAY_ARCH:
            raise ValueError(
                f"SambaYModelConfig.arch must be {SAMBAY_ARCH!r}, got "
                f"{self.arch!r}")
        kinds = tuple(self.layer_types) or sambay_layout(
            self.num_hidden_layers)
        object.__setattr__(self, "layer_types", kinds)   # JSON gives a list
        if len(kinds) != self.num_hidden_layers \
                or any(k not in SAMBAY_KINDS for k in kinds):
            raise ValueError(
                f"layer_types must name num_hidden_layers="
                f"{self.num_hidden_layers} kinds of {SAMBAY_KINDS}, got "
                f"{kinds}")
        if kinds.count("attn_full") != 1:
            raise ValueError(
                "arch 'sambay' has ONE full-attention layer (the shared "
                f"keys and values); layer_types holds "
                f"{kinds.count('attn_full')}")
        full = kinds.index("attn_full")
        late = [i for i, k in enumerate(kinds) if k in ("gmu", "attn_cross")]
        if any(i < full for i in late) or "mamba" not in kinds[:full]:
            raise ValueError(
                "gated memory units and cross-attention read the last "
                "Mamba layer and the full-attention layer before them: "
                f"layer_types {kinds} puts a reader first")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2 \
                or self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError(
                "differential attention pairs the heads: "
                "num_attention_heads and num_key_value_heads must be even, "
                "the first a multiple of the second and a divisor of "
                "hidden_size")
        if not (self.tie_word_embeddings and not self.mlp_bias
                and not self.lm_head_bias and self.mb_per_layer == 2):
            raise ValueError(
                "arch 'sambay' ties embedding and head, has no bias in the "
                "SwiGLU or on the head, and alternates Mamba and attention "
                "(mb_per_layer 2)")
        if self.sliding_window < 1 or self.mamba_d_conv < 1:
            raise ValueError("sliding_window and mamba_d_conv must be >= 1")
        if self.seq_len < 2:
            raise ValueError("seq_len must be >= 2 (a next token to score)")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def memory_layer(self) -> int:
        """The Mamba layer whose scan output the gated memory units read:
        the last one before the full-attention layer."""
        full = self.layer_types.index("attn_full")
        return max(i for i in range(full) if self.layer_types[i] == "mamba")


#: the archs that train one network on id batches by `LM_LOSS`, each with
#: the dataclass that holds its model config; an arch is the name of its
#: module under models/ (train/steps.py takes init, loss and counters from
#: it)
TOKEN_MODEL_CONFIGS = {TOKEN_ARCH: TokenModelConfig,
                       LOOP_ARCH: LoopModelConfig,
                       SAMBAY_ARCH: SambaYModelConfig}
TOKEN_ARCHS = tuple(TOKEN_MODEL_CONFIGS)


def is_token_arch(arch: str) -> bool:
    """The one test of "a one-network token family": no sampler, no
    critic, int32 id batches, the likelihood step."""
    return arch in TOKEN_MODEL_CONFIGS


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh topology. Replaces ClusterSpec/Server/ps-role entirely
    (reference: image_train.py:52-67) — there is no parameter-server process;
    parameters are replicated (or model-sharded) per the sharding rules and
    gradients all-reduce over ICI.
    """

    data: int = -1                 # data-parallel axis size; -1 = all devices
    model: int = 1                 # second mesh axis size (1 = off)
    spatial: bool = False          # repurpose the "model" axis for spatial
                                   # partitioning: activations shard over image
                                   # height (GSPMD inserts conv halo exchanges)
                                   # and weights stay replicated — the image-
                                   # domain analogue of sequence/context
                                   # parallelism (SURVEY.md §2.5). False =
                                   # tensor parallelism (wide weights shard)
    shard_opt: bool = False        # ZeRO-1: shard Adam moments over the data
                                   # axis (each replica owns 1/N and updates
                                   # its slice; reduce-scatter/all-gather
                                   # inserted by GSPMD — arXiv:2004.13336).
                                   # gspmd backend only
    zero_stage: int = 1            # state-sharding stage (arXiv:2004.13336
                                   # generalized): 1 = today's behavior
                                   # (parity; shard_opt alone still gives
                                   # ZeRO-1 on the gspmd backend). 2 =
                                   # ZeRO-2: optimizer state AND gradients
                                   # shard over the data axis — the full-
                                   # gradient psum becomes a reduce-scatter,
                                   # the Adam update runs shard-local, and
                                   # one fused all-gather rebuilds the
                                   # replicated params per update (same
                                   # bytes on the wire as the all-reduce it
                                   # replaces). 3 = ZeRO-3: params and the
                                   # EMA copy additionally stay RESIDENT
                                   # sharded between steps, all-gathered
                                   # just in time inside each forward — the
                                   # per-chip memory floor for params+grads+
                                   # Adam state drops ~Nx on an N-way data
                                   # axis. Both backends (gspmd via sharding
                                   # constraints, shard_map via explicit
                                   # psum_scatter/all_gather); stages >= 2
                                   # need a data axis of size > 1 and reject
                                   # spatial meshes (DESIGN.md §6i)

    def __post_init__(self):
        if self.zero_stage not in (1, 2, 3):
            raise ValueError(
                f"zero_stage must be 1, 2, or 3, got {self.zero_stage}")
        if self.zero_stage >= 2 and self.spatial:
            raise ValueError(
                "zero_stage >= 2 does not compose with spatial meshes "
                "(spatial mode replicates all weights by policy — there is "
                "no per-leaf dim left for the data-axis state shards); use "
                "zero_stage=1 with spatial=True")
        if self.spatial and self.model <= 1:
            raise ValueError(
                "spatial=True repurposes the 'model' mesh axis to shard image "
                f"height, which needs model > 1 (got model={self.model}); "
                "with model=1 the run would silently be plain data "
                "parallelism")

    def axis_sizes(self, n_devices: int) -> Tuple[int, int]:
        if self.model < 1:
            raise ValueError(f"model axis must be >= 1, got {self.model}")
        model = self.model
        if self.data > 0:
            data = self.data
        else:
            if n_devices % model != 0:
                raise ValueError(
                    f"model axis {model} does not divide {n_devices} devices")
            data = n_devices // model
        if data * model != n_devices:
            raise ValueError(
                f"mesh {data}x{model} does not cover {n_devices} devices")
        return data, model


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Run knobs — same set as the reference's flags (image_train.py:10-38) plus
    the defect-fix gates from SURVEY.md §2.4.
    """

    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)

    # Optimization (image_train.py:11-13,109-112)
    learning_rate: float = 2e-4
    d_learning_rate: Optional[float] = None  # TTUR: per-net learning rates
    g_learning_rate: Optional[float] = None  # (None = learning_rate; the
                                             # reference uses one lr for both)
    lr_schedule: str = "constant"  # "constant" (reference) | "linear" decay
                                   # to 0 over max_steps | "cosine" to 0
    warmup_steps: int = 0          # linear warmup from 0 before the schedule
    beta1: float = 0.5
    batch_size: int = 64           # global batch (sharded over the data axis)
    max_steps: int = 1_200_000     # (image_train.py:150)
    loss: str = "gan"              # "gan" (BCE, image_train.py:91-96) |
                                   # "wgan-gp" | "hinge" (SAGAN-style)
    gp_weight: float = 10.0        # WGAN-GP gradient-penalty coefficient
    r1_gamma: float = 0.0          # >0 adds (gamma/2)*E[||grad_x D(x)||^2]
                                   # on real images to the D loss (R1,
                                   # arXiv:1801.04406) — composes with the
                                   # "gan"/"hinge" families; 0 = off
                                   # (reference parity)
    r1_interval: int = 1           # lazy regularization (StyleGAN2,
                                   # arXiv:1912.04958 §appendix B): compute
                                   # R1 only every k-th step with gamma
                                   # scaled by k — same regularization
                                   # pressure, ~1/k of the extra D cost.
                                   # 1 = every step (the R1 paper's form)
    n_critic: int = 1              # D updates per G update. 1 = the reference's
                                   # one-D-one-G step (image_train.py:156-158);
                                   # WGAN-GP canonically uses 5 (each critic
                                   # iteration draws fresh z against the same
                                   # real batch, scanned in-program)
    update_mode: str = "sequential"  # "sequential": D step then G step (intended
                                     # semantics); "fused": both grads from the same
                                     # params, applied together (reference parity,
                                     # SURVEY.md §2.4 #2, image_train.py:156-158)
    grad_accum: int = 1            # microbatches per optimizer update (beyond
                                   # reference). K>1 scans K microbatches of
                                   # batch_size/K through each loss at fixed
                                   # params, accumulating gradients, then
                                   # applies each Adam once — the full-batch
                                   # mean gradient at ~1/K the activation
                                   # memory. BN statistics are per-microbatch
                                   # with state chained (standard large-batch
                                   # emulation semantics, not bitwise equal to
                                   # one full-batch BN pass). With n_critic>1
                                   # each scanned critic iteration applies one
                                   # Adam update from its own K-microbatch
                                   # accumulation.
    diffaug: str = ""              # differentiable augmentation policy for
                                   # every D input (DiffAugment,
                                   # arXiv:2006.10738): comma-joined subset
                                   # of {color, translation, cutout}, e.g.
                                   # "color,translation,cutout" for small
                                   # datasets. "" = off (reference parity)
    grad_clip: float = 0.0         # >0 clips both nets' gradients by global
                                   # norm before Adam (optax chain); 0 = off
                                   # (reference parity: no clipping)
    label_smoothing: float = 0.0   # one-sided label smoothing (Salimans et
                                   # al. 2016): D's real target becomes
                                   # 1 - eps ("gan" loss family only);
                                   # 0 = off (reference parity)
    g_ema_decay: float = 0.0       # >0 keeps an EMA copy of generator weights
                                   # updated per step and samples from it —
                                   # a beyond-reference FID improvement
                                   # (typical 0.999); 0 = off (strict parity:
                                   # the reference samples live weights)

    # Data (image_input.py:11-16, image_train.py:19-26)
    data_dir: str = "train"
    sample_image_dir: str = "sample_data"
    dataset: str = "celebA"
    shuffle_buffer: int = 10_776   # 10% of epoch (image_input.py:134-136)
    num_loader_threads: int = 16   # (image_input.py:77)
    normalize_inputs: bool = True  # map reals to [-1,1]; the reference never does
                                   # (SURVEY.md §2.4 #1) — set False for strict parity
    record_dtype: str = "float64"  # on-disk pixel dtype (image_input.py:48)
    label_feature: str = "label"   # int64 per-example class feature, read when
                                   # model.num_classes > 0 (the schema the
                                   # reference comments out, image_input.py:44)
    prefetch_device_batches: int = 2  # depth of the background device-feed
                                   # queue (data/pipeline.DevicePrefetcher):
                                   # a transfer thread keeps this many
                                   # already-sharded device batches ready
                                   # ahead of the dispatch thread, so batch
                                   # assembly + H2D transfer overlap device
                                   # compute. 0 = legacy consumer-thread
                                   # double buffer (feed alternates with
                                   # dispatch)
    synthetic_device_cache: int = 0  # >0 (synthetic data only): pre-stage
                                   # this many sharded batches ON DEVICE and
                                   # cycle them — removes host->device feed
                                   # from the loop so the trainer's own hot-
                                   # loop machinery can be measured at chip
                                   # rate over transports that cannot sustain
                                   # the feed (tools/bench_trainer_loop.py)
    synthetic_global_stream: bool = False  # with synthetic data: every
                                   # process generates the FULL global batch
                                   # from one seed and cuts its own block, so
                                   # the global batch sequence is IDENTICAL
                                   # for every process layout over the same
                                   # mesh (2 proc x 1 dev == 1 proc x 2 dev,
                                   # bit-for-bit). The layout-invariance the
                                   # elastic shrink/grow drills replay losses
                                   # across (tools/chaos_drill.py); default
                                   # off — the block-seeded stream pays 1/P
                                   # of the host cost and stays byte-exact
                                   # with prior builds

    # Observability (image_train.py:37,129,179)
    async_services: bool = True    # run host-side observability (deferred
                                   # metric materialization, param/activation
                                   # histogram capture, sample-grid PNG
                                   # encode, JSONL/TB writes) on a background
                                   # single-worker executor with drop-oldest
                                   # backpressure (train/services.py), and
                                   # log step N's scalars while step N+1 runs
                                   # (lag-by-one). False = every service runs
                                   # inline on the dispatch thread at its
                                   # original call site — the pre-async loop
                                   # structure; the metrics JSONL matches the
                                   # pre-async trainer's up to the two new
                                   # perf/host_ms_mean + perf/
                                   # dispatch_occupancy timing keys (emitted
                                   # in both modes)
    checkpoint_dir: str = "checkpoint"
    sample_dir: str = "samples"
    tensorboard: bool = True       # mirror metrics into TensorBoard-native
                                   # event files (utils/tb_events.py) next to
                                   # the JSONL stream — the reference's
                                   # summary-file channel (image_train.py:118)
    save_summaries_secs: float = 10.0
    save_model_secs: float = 600.0   # single-process checkpoint cadence
    save_model_steps: int = 1000     # multi-host cadence (collective save
                                     # needs a clock-independent trigger)
    max_checkpoints: int = 5         # retained checkpoints (Orbax
                                     # max_to_keep; the reference's Saver
                                     # default was also 5)
    sample_every_steps: int = 100
    sample_grid: Tuple[int, int] = (8, 8)   # 8x8 grid (image_train.py:205)
    fid_every_steps: int = 0       # >0: periodic in-training surrogate
                                   # FID/KID probe (evals/ rig) against the
                                   # held-out sample pipeline — written as
                                   # eval/fid + eval/kid scalars. Single-process runs
                                   # only (multi-host scores offline via
                                   # `evals --multihost`); 0 = off
                                   # (reference parity: its only eval was
                                   # the human eyeballing grids)
    fid_num_samples: int = 2048    # samples per side for the probe (small
                                   # by design: KID is unbiased at small n,
                                   # and the probe's job is trend, not the
                                   # FID-50k headline)
    log_every_steps: int = 1
    nan_check_steps: int = 100     # every N steps all processes verify the
                                   # loss metrics are finite and abort with
                                   # step context if not (0 = off) — the
                                   # numerical-health hook SURVEY.md §5 names
                                   # as this design's sanitizer equivalent
    nan_policy: str = "abort"      # what a tripped NaN gate does: "abort"
                                   # (reference parity: raise with step
                                   # context) | "rollback" (fail-operational:
                                   # restore the last-good snapshot, skip
                                   # the offending batch window, keep
                                   # training — train/rollback.py. Multi-
                                   # host: gate verdicts are allgathered so
                                   # every process takes the same branch,
                                   # and the snapshot is a sharded device-
                                   # resident copy restored collectively —
                                   # train/coordination.py)
    coord_stop: bool = True        # multi-host: SIGTERM/SIGINT on ANY host
                                   # sets a local flag that is allgathered
                                   # at each step boundary, so the whole
                                   # job breaks together and runs the
                                   # collective final save (a preemption
                                   # notice becomes a resumable stop). One
                                   # tiny int32 allgather per step boundary
                                   # is the cost. False restores PR 3
                                   # semantics: default signal handling,
                                   # restart from the last periodic save.
                                   # Single-process stop handling is always
                                   # on and collective-free either way
    collective_timeout_secs: float = 0.0  # >0 arms the hung-collective
                                   # watchdog (train/coordination.py): a
                                   # daemon thread deadlines each dispatch/
                                   # save/consensus section and, on expiry,
                                   # dumps per-process stacks and exits
                                   # nonzero (43) so the launcher restarts
                                   # the job instead of hanging forever.
                                   # Set comfortably above the slowest
                                   # legitimate section (collective save
                                   # included; the first step's compile is
                                   # exempted). 0 = off
    rollback_snapshot_steps: int = 100  # nan_policy="rollback": keep a host-
                                   # side copy of the last gate-verified
                                   # state every K steps (the restore point;
                                   # one device_get of the full state per K
                                   # steps)
    max_rollbacks: int = 3         # rollbacks allowed per run before the
                                   # gate aborts anyway — persistent
                                   # divergence must still fail loudly, not
                                   # loop forever
    rollback_lr_backoff: float = 1.0  # <1.0: multiply both nets' base
                                   # learning rates by this on every
                                   # rollback (rebuilds the compiled step —
                                   # a recompile per rollback, acceptable
                                   # for a rare recovery event); 1.0 = off
    max_corrupt_records: int = 0   # >0: data-pipeline CRC/parse failures
                                   # quarantine the record (skip + log
                                   # file/offset + data/corrupt_records
                                   # counter) up to this many before hard-
                                   # failing; 0 = first corrupt record is
                                   # fatal (reference parity)
    activation_summary_steps: int = 500  # per-layer activation histogram +
                                         # sparsity cadence (0 = off). Step-
                                         # gated, not time-gated: the summary
                                         # program is a mesh collective, so
                                         # every process must agree on when it
                                         # runs (a per-process clock gate would
                                         # deadlock multi-host)

    # Warm start (DESIGN.md §6d): restart goodput — PRs 3-4 made restarts
    # the normal response to faults, so time-to-first-step is throughput
    # infrastructure, not a one-off cost
    compile_cache_dir: str = ""    # non-empty wires JAX's persistent
                                   # compilation cache at this directory: a
                                   # restart deserializes every already-
                                   # seen program instead of recompiling
                                   # it. Multi-host safe by construction —
                                   # JAX writes entries from the chief
                                   # only, every process reads. Cache
                                   # adoption is surfaced as
                                   # perf/compile_cache_* counters. "" =
                                   # the process's own setting stays in
                                   # force: JAX_COMPILATION_CACHE_DIR, or
                                   # the fixed in-checkout directory the
                                   # entry points fall back to
                                   # (train/warmup.resolve_cache_dir); a
                                   # bare library call has neither, so no
                                   # cache (reference parity)
    compile_cache_per_process: bool = False  # multi-host without a shared
                                   # filesystem: give each process its own
                                   # proc<i>/ subdirectory of
                                   # compile_cache_dir instead of the
                                   # chief-writes/all-read shared store
    aot_warmup: bool = False       # explicit AOT warmup phase before the
                                   # loop: .lower().compile() every program
                                   # and every known future call shape (the
                                   # k=1 n_critic tail, the steps_per_call
                                   # scan, sampler/probe/summarize, the
                                   # rollback LR-backoff rebuild variant)
                                   # with per-program perf/compile_ms
                                   # timings; with compile_cache_dir set the
                                   # loop's first dispatches deserialize
                                   # instead of compiling, and the hung-
                                   # collective watchdog arms from warmup
                                   # proof instead of waiting for first
                                   # live steps. False = compile lazily on
                                   # first dispatch (reference parity)

    # Profiling (SURVEY.md §5 — the reference has none; jax.profiler + step
    # timing is the named TPU-native equivalent)
    profile_dir: str = ""          # non-empty enables the scheduled trace
                                   # capture window
    profile_start_step: int = 10   # skip compile + warmup steps
    profile_num_steps: int = 5
    profile_trigger: str = ""      # non-empty: on-demand tracing (ISSUE 6)
                                   # — touch this file mid-run to capture
                                   # the next profile_num_steps steps, no
                                   # restart needed; the file is deleted as
                                   # the ack (touch again for another
                                   # capture). Each capture is digested
                                   # in-process on the services worker into
                                   # perf/device/* events (compute ms,
                                   # collective ms, idle-gap ms, devstep).
                                   # Traces land in profile_dir, or
                                   # checkpoint_dir/trace when unset
    timing_window: int = 50        # sliding window for step-time stats
    flight_recorder_steps: int = 64  # crash flight recorder (ISSUE 6):
                                   # ring of the last K per-step telemetry
                                   # records (step/host ms, losses, services
                                   # queue + drops, gate verdicts, recovery
                                   # counters), dumped as a standalone
                                   # JSONL file on watchdog trip, NaN
                                   # abort, coordinated stop, or uncaught
                                   # exception. Crash-path-only IO — the
                                   # default event stream is untouched.
                                   # 0 = off
    fleet_health_steps: int = 0    # >0: every N steps allgather a compact
                                   # per-host health vector on the dispatch
                                   # thread (collective-thread rule) and
                                   # chief-materialize fleet/* metrics —
                                   # straggler skew (max/min step_ms),
                                   # slowest host, queue/drop/recovery
                                   # totals; the slowest host is also named
                                   # in a watchdog trip header. One small
                                   # collective per N steps. 0 = off
                                   # (parity)

    # Misc
    seed: int = 0
    sample_size: int = 64          # fixed-z sample batch (image_train.py:43)
    steps_per_call: int = 1        # >1: dispatch K steps as one compiled
                                   # lax.scan program (ParallelTrain.
                                   # multi_step) — sheds per-dispatch host
                                   # overhead. Observability cadences
                                   # must be 0 or multiples of K; per-step
                                   # stdout logging (the reference's
                                   # every-step line) only reports each
                                   # call's last step
    progressive: str = ""          # progressive-resolution schedule
                                   # (ISSUE 15, ROADMAP item 5): a phase
                                   # table "RES:STEPS[,...],RES:*" — e.g.
                                   # "64:2000,128:2000,256:*" — making
                                   # resolution a scheduled training
                                   # dimension. Resolutions must be
                                   # ascending model-stack sites ending at
                                   # model.output_size (the base config
                                   # describes the FINAL model); the last
                                   # phase's '*' runs to max_steps. A
                                   # third ":BATCH" field per phase
                                   # shrinks the batch at high res. Phase
                                   # switches are zero-recompile after
                                   # --aot_warmup (every phase's programs
                                   # are pre-lowered AND primed at
                                   # startup), carry state across the
                                   # model-surface growth (new-at-phase
                                   # leaves init fresh, carried leaves
                                   # transfer), re-open the data pipeline
                                   # at the new decode resolution, and
                                   # persist a phase tag in the elastic
                                   # sidecar so restores resume into the
                                   # right phase. "" = off (parity)
    progressive_fade_steps: int = 0  # >0 with --progressive: a linear
                                   # fade-in over the first N steps of
                                   # each phase after the first — real
                                   # images blend alpha*x +
                                   # (1-alpha)*up(down(x)) through a tiny
                                   # jitted program (alpha is a traced
                                   # f32 scalar; one compile per phase),
                                   # ramping D's real distribution from
                                   # previous-resolution content to full
                                   # detail. 0 = hard switches
    elastic_target_devices: int = 0  # live in-run elasticity (ISSUE 18):
                                   # >0 arms a second pre-built topology
                                   # surface over the first N devices (N
                                   # divisible by mesh.model) and the
                                   # preemption-notice boundary poll. A
                                   # shrink notice (SIGUSR1, the notice
                                   # file, or a chaos plan) moves the LIVE
                                   # state onto the smaller mesh without a
                                   # restart — drain, reshard, resume from
                                   # pre-warmed executables (compile-
                                   # request delta 0 under --aot_warmup);
                                   # a grow notice moves back. Global
                                   # batch and model are unchanged (the
                                   # math is layout-invariant). Single-
                                   # controller runs only. 0 = off
                                   # (parity: no poll, no extra surface)
    elastic_notice_file: str = ""  # with elastic_target_devices: a file
                                   # path polled (retry_io-guarded) at
                                   # each step boundary — `touch <file>`
                                   # is a shrink notice, content "grow"
                                   # the grow-back; consumed notices are
                                   # renamed *.consumed and acked to
                                   # *.ack with the switch record. "" =
                                   # signal/chaos sources only
    pipeline_gd: bool = False      # software-pipelined G/D dispatch
                                   # (ISSUE 7, ParaGAN's separable-stage
                                   # framing): the fused train step is
                                   # dispatched as three stage programs —
                                   # gen_fakes (fill), d_update (consumes
                                   # the fake stack produced during the
                                   # PREVIOUS step, staleness 1), g_update
                                   # (returns the next stack). Per-step
                                   # FLOPs are conservation-equal to the
                                   # fused program (every consumed fake is
                                   # produced once; XLA already CSEs the
                                   # fused step's shared-z G forward) —
                                   # the wins are the largest program's
                                   # peak temp memory (~15% below fused at
                                   # the flagship config: batch headroom)
                                   # and the stage separation itself (the
                                   # substrate for cross-stage placement/
                                   # overlap, DESIGN.md §6f). The stack is
                                   # double-buffered on device and lives
                                   # OUTSIDE the checkpoint pytree (both
                                   # modes save/restore the identical
                                   # state tree); fill/drain at run start,
                                   # checkpoint boundaries, rollback, and
                                   # coordinated stop. Sequential
                                   # update_mode + unconditional models +
                                   # steps_per_call=1 only. False = the
                                   # fused step (reference parity)
    precision: str = ""            # reduced-precision ladder (ISSUE 17,
                                   # ROADMAP item 3). "" = leave the model's
                                   # compute_dtype/param_dtype alone (parity
                                   # with every prior build). "f32": force
                                   # float32 compute+params (the A/B
                                   # reference arm). "bf16": bfloat16 params
                                   # AND compute end-to-end, with f32 master
                                   # Adam first moments (make_optimizer sets
                                   # mu_dtype=float32; nu is a variance —
                                   # bf16's ~3 significant digits suffice —
                                   # and BN running stats follow param dtype
                                   # through batch_norm_init while the
                                   # moment REDUCTIONS are always f32). The
                                   # policy is applied by normalizing
                                   # model.{compute,param}_dtype in
                                   # __post_init__, so every downstream
                                   # consumer (init, steps, serve, analysis)
                                   # sees ordinary model dtypes
    backend: str = "gspmd"         # "gspmd": jit + sharding annotations, the
                                   # partitioner inserts collectives
                                   # (parallel/api.py) | "shard_map": explicit
                                   # per-device programs with hand-written
                                   # psum/pmean (parallel/shard_map_backend.py;
                                   # DP-only, composes with use_pallas)
    comm_overlap: str = "off"      # collective overlap plane (ISSUE 20,
                                   # DESIGN §6n). "off": the per-leaf ZeRO
                                   # collectives, byte-identical to every
                                   # prior build (parity-pinned). "bucket":
                                   # reduce_grads/gather_updates pack leaves
                                   # into dtype-grouped flat buffers — one
                                   # large collective per bucket instead of
                                   # one per leaf, bit-exact by construction.
                                   # "prefetch" (zero_stage=3 only): bucket's
                                   # plan PLUS gather_params restructured
                                   # into layer-ahead staged gathers so XLA
                                   # overlaps layer i+1's gather with layer
                                   # i's compute
    comm_bucket_mb: int = 4        # bucket size cap in MiB for
                                   # comm_overlap != "off" (per dtype group;
                                   # a single leaf above the cap gets its
                                   # own bucket)

    def _refuse_image_services(self):
        """A one-network token family has no sampler, critic or image
        pipeline: every option that needs one is refused here, by name, at
        config time, instead of failing inside a trace."""
        image_only = {
            "sample_every_steps": "sample grids need a sampler program",
            "activation_summary_steps": "activation summaries walk the "
                                        "G/D stacks",
            "fid_every_steps": "FID/KID score images",
            "progressive": "progressive resolution is an image schedule",
            "pipeline_gd": "the G/D stage pipeline needs two players",
            "diffaug": "augmentation acts on images",
            "r1_gamma": "R1 regularizes a critic",
            "label_smoothing": "label smoothing belongs to the GAN loss",
            "g_ema_decay": "the weight average is the generator's",
            "elastic_target_devices": "live resharding covers the GAN "
                                      "state tree only",
            "precision": "the precision ladder rewrites the image "
                         "families' dtypes",
        }
        arch = self.model.arch
        for name, why in image_only.items():
            if getattr(self, name):
                raise ValueError(
                    f"{name}={getattr(self, name)!r} is an image-family "
                    f"service ({why}); arch={arch!r} refuses it")
        if self.steps_per_call != 1 or self.grad_accum != 1 \
                or self.n_critic != 1:
            raise ValueError(
                f"arch={arch!r} runs one likelihood step per call: "
                "steps_per_call, grad_accum and n_critic must be 1")
        if self.backend != "gspmd" or self.mesh.zero_stage != 1 \
                or self.mesh.spatial or self.mesh.model != 1:
            raise ValueError(
                f"arch={arch!r} runs on the gspmd backend over a "
                "data-parallel mesh (no expert axis, no exchange yet)")

    def __post_init__(self):
        token = is_token_arch(self.model.arch)
        if token != (self.loss == LM_LOSS):
            raise ValueError(
                f"loss={LM_LOSS!r} (next-token likelihood) and a token arch "
                f"({', '.join(TOKEN_ARCHS)}) go together: got "
                f"loss={self.loss!r} with arch={self.model.arch!r}")
        if token:
            self._refuse_image_services()
        if self.precision not in ("", "f32", "bf16"):
            raise ValueError(
                f"precision must be one of '', 'f32', 'bf16', got "
                f"{self.precision!r}")
        if self.precision:
            # Normalize the policy into the model dtypes up front (frozen
            # dataclass: object.__setattr__ is the sanctioned escape hatch,
            # and the rewrite is idempotent so config round-trips through
            # config_from_dict reproduce the same model). precision OVERRIDES
            # any explicit model dtype flags — one knob, one meaning.
            dt = {"f32": "float32", "bf16": "bfloat16"}[self.precision]
            if (self.model.compute_dtype, self.model.param_dtype) != (dt, dt):
                object.__setattr__(
                    self, "model",
                    dataclasses.replace(self.model, compute_dtype=dt,
                                        param_dtype=dt))
        if self.backend not in ("gspmd", "shard_map"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.backend == "shard_map" and (self.mesh.model != 1
                                            or self.mesh.spatial
                                            or self.mesh.shard_opt):
            raise ValueError(
                "backend='shard_map' is data-parallel only (mesh.model must "
                "be 1, spatial/shard_opt False — tensor/spatial/ZeRO-1 "
                f"optimizer-state sharding live in the gspmd backend; "
                f"ZeRO-2/3 is mesh.zero_stage, supported here); got "
                f"mesh={self.mesh}")
        if self.backend == "shard_map" and self.mesh.zero_stage >= 2 \
                and self.grad_clip > 0:
            raise ValueError(
                "zero_stage >= 2 under backend='shard_map' does not compose "
                "with grad_clip: the clip's global norm would be computed "
                "over each replica's gradient SHARD (the explicit reduce-"
                "scatter hands optax local slices) — use the gspmd backend, "
                "where the partitioner computes the true global norm")
        if self.comm_overlap not in ("off", "bucket", "prefetch"):
            raise ValueError(
                f"comm_overlap must be one of 'off', 'bucket', 'prefetch', "
                f"got {self.comm_overlap!r}")
        if self.comm_overlap == "prefetch" and self.mesh.zero_stage != 3:
            raise ValueError(
                "comm_overlap='prefetch' restructures the ZeRO-3 "
                "just-in-time param gathers — it requires "
                f"mesh.zero_stage=3 (got {self.mesh.zero_stage}); use "
                "comm_overlap='bucket' at lower stages")
        if self.comm_bucket_mb <= 0:
            raise ValueError(
                f"comm_bucket_mb must be > 0, got {self.comm_bucket_mb}")
        if self.loss not in ("gan", "wgan-gp", "hinge", LM_LOSS):
            raise ValueError(f"unknown loss {self.loss!r}")
        if self.update_mode not in ("sequential", "fused"):
            raise ValueError(f"unknown update_mode {self.update_mode!r}")
        if self.n_critic < 1:
            raise ValueError(f"n_critic must be >= 1, got {self.n_critic}")
        if self.r1_gamma < 0:
            raise ValueError(f"r1_gamma must be >= 0, got {self.r1_gamma}")
        if self.r1_gamma and self.loss == "wgan-gp":
            raise ValueError(
                "r1_gamma composes with the 'gan'/'hinge' families; "
                "'wgan-gp' already carries its own gradient penalty")
        if self.r1_interval < 1:
            raise ValueError(
                f"r1_interval must be >= 1, got {self.r1_interval}")
        if self.r1_interval > 1 and not self.r1_gamma:
            raise ValueError(
                "r1_interval > 1 without r1_gamma is a silent no-op — set "
                "r1_gamma > 0 to enable R1")
        if self.grad_clip < 0:
            raise ValueError(f"grad_clip must be >= 0, got {self.grad_clip}")
        from dcgan_tpu.ops.augment import parse_policy
        parse_policy(self.diffaug)  # raises on unknown policy names
        if not 0.0 <= self.label_smoothing < 0.5:
            raise ValueError(
                f"label_smoothing must be in [0, 0.5), got "
                f"{self.label_smoothing}")
        if self.label_smoothing and self.loss != "gan":
            raise ValueError(
                "label_smoothing targets BCE labels and applies only to "
                f"loss='gan', got loss={self.loss!r}")
        if not 0.0 <= self.g_ema_decay < 1.0:
            raise ValueError(
                f"g_ema_decay must be in [0, 1), got {self.g_ema_decay}")
        if self.fid_every_steps < 0:
            raise ValueError(
                f"fid_every_steps must be >= 0, got {self.fid_every_steps}")
        if self.fid_every_steps and self.fid_num_samples < 64:
            raise ValueError(
                f"fid_num_samples must be >= 64 for a meaningful probe, "
                f"got {self.fid_num_samples}")
        if self.lr_schedule not in ("constant", "linear", "cosine"):
            raise ValueError(f"unknown lr_schedule {self.lr_schedule!r}")
        if self.warmup_steps < 0:
            raise ValueError(f"warmup_steps must be >= 0, got "
                             f"{self.warmup_steps}")
        if self.warmup_steps >= self.max_steps:
            raise ValueError(
                f"warmup_steps ({self.warmup_steps}) must be < max_steps "
                f"({self.max_steps}) — the whole run would be warmup and the "
                "decay schedule would never engage")
        if self.nan_policy not in ("abort", "rollback"):
            raise ValueError(
                f"nan_policy must be 'abort' or 'rollback', got "
                f"{self.nan_policy!r}")
        if self.nan_policy == "rollback" and not self.nan_check_steps:
            raise ValueError(
                "nan_policy='rollback' needs the NaN gate enabled "
                "(nan_check_steps > 0) — with the gate off nothing ever "
                "trips, so the snapshot cost buys no protection")
        if self.rollback_snapshot_steps < 1:
            raise ValueError(
                f"rollback_snapshot_steps must be >= 1, got "
                f"{self.rollback_snapshot_steps}")
        if self.max_rollbacks < 1:
            raise ValueError(
                f"max_rollbacks must be >= 1, got {self.max_rollbacks}")
        if not 0.0 < self.rollback_lr_backoff <= 1.0:
            raise ValueError(
                f"rollback_lr_backoff must be in (0, 1], got "
                f"{self.rollback_lr_backoff}")
        if self.collective_timeout_secs < 0:
            raise ValueError(
                f"collective_timeout_secs must be >= 0, got "
                f"{self.collective_timeout_secs}")
        if self.max_corrupt_records < 0:
            raise ValueError(
                f"max_corrupt_records must be >= 0, got "
                f"{self.max_corrupt_records}")
        if self.flight_recorder_steps < 0:
            raise ValueError(
                f"flight_recorder_steps must be >= 0, got "
                f"{self.flight_recorder_steps}")
        if self.fleet_health_steps < 0:
            raise ValueError(
                f"fleet_health_steps must be >= 0, got "
                f"{self.fleet_health_steps}")
        if self.steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {self.steps_per_call}")
        if self.steps_per_call > 1:
            cadences = {
                "log_every_steps": self.log_every_steps,
                "sample_every_steps": self.sample_every_steps,
                "activation_summary_steps": self.activation_summary_steps,
                "nan_check_steps": self.nan_check_steps,
                "save_model_steps": self.save_model_steps,
                "fid_every_steps": self.fid_every_steps,
                # the health gather is a per-cadence COLLECTIVE — a skewed
                # firing subset would deadlock multi-host, same as the
                # activation-summary reasoning
                "fleet_health_steps": self.fleet_health_steps,
            }
            if self.nan_policy == "rollback":
                # the snapshot cadence is inert under the default policy —
                # its (default 100) value must not constrain steps_per_call
                # for runs that never arm rollback
                cadences["rollback_snapshot_steps"] = \
                    self.rollback_snapshot_steps
            # A cadence that is a multiple of K fires exactly on schedule; a
            # cadence that divides K fires at every call boundary (e.g. the
            # default per-step log becomes one line per call, reporting the
            # call's last step). Anything else would fire on a skewed subset
            # of its steps — reject that.
            spc = self.steps_per_call
            bad = {k: v for k, v in cadences.items()
                   if v and v % spc != 0 and spc % v != 0}
            if bad:
                raise ValueError(
                    f"with steps_per_call={spc} every step cadence must be "
                    "0, a multiple of it (fires on schedule), or a divisor "
                    "of it (fires each call boundary); offending: "
                    f"{bad}")
        if self.n_critic > 1 and self.update_mode == "fused":
            raise ValueError(
                "update_mode='fused' (reference-parity single fused step) is "
                "defined only for n_critic=1")
        if self.pipeline_gd:
            if self.update_mode != "sequential":
                raise ValueError(
                    "pipeline_gd dispatches g_update AFTER d_update "
                    "(sequential semantics by construction); "
                    "update_mode='fused' has no pipelined equivalent")
            if self.model.num_classes:
                raise ValueError(
                    "pipeline_gd supports unconditional models only — the "
                    "stage programs do not thread class labels through the "
                    "fake stack")
            if self.steps_per_call != 1:
                raise ValueError(
                    f"pipeline_gd dispatches per-step stage programs; it "
                    f"does not compose with the scanned multi-step path "
                    f"(steps_per_call={self.steps_per_call} — set it to 1)")
        if self.progressive_fade_steps < 0:
            raise ValueError(
                f"progressive_fade_steps must be >= 0, got "
                f"{self.progressive_fade_steps}")
        if self.progressive_fade_steps and not self.progressive:
            raise ValueError(
                "progressive_fade_steps > 0 without --progressive is a "
                "silent no-op — set a --progressive schedule to fade into")
        if self.progressive:
            if self.model.attn_res:
                raise ValueError(
                    "--progressive does not compose with attn_res: the "
                    "attention site is anchored to one feature-map "
                    "resolution, which earlier phases may not contain "
                    "(and carrying attention projections across a stage "
                    "shift is undefined)")
            if self.fid_every_steps:
                raise ValueError(
                    "--progressive does not compose with fid_every_steps: "
                    "the probe's feature extractor and real-side "
                    "statistics are fixed-resolution; score offline per "
                    "phase via the evals CLI instead")
            if self.nan_policy == "rollback" \
                    and self.rollback_lr_backoff < 1.0:
                raise ValueError(
                    "--progressive does not compose with "
                    "rollback_lr_backoff < 1.0: the pre-warmed backoff "
                    "surface is per-phase and a mid-schedule rebuild "
                    "would recompile under the zero-recompile contract; "
                    "use rollback without LR backoff")
            # parse (and thereby validate) the schedule at construction —
            # the trainer re-parses against the live mesh for granule
            # checks; lazy import mirrors the parse_policy pattern above
            from dcgan_tpu.progressive.schedule import parse_schedule
            parse_schedule(self.progressive, model=self.model,
                           batch_size=self.batch_size,
                           max_steps=self.max_steps,
                           steps_per_call=self.steps_per_call,
                           grad_accum=self.grad_accum,
                           fade_steps=self.progressive_fade_steps)
        if self.elastic_target_devices < 0:
            raise ValueError(
                f"elastic_target_devices must be >= 0, got "
                f"{self.elastic_target_devices}")
        if self.elastic_target_devices:
            if self.progressive:
                raise ValueError(
                    "--elastic_target_devices does not compose with "
                    "--progressive: both own the phase-boundary switch "
                    "sequence and the warmed-surface table, and a notice "
                    "landing mid-schedule would have to re-warm every "
                    "remaining phase on the new mesh under the "
                    "zero-recompile contract; run fixed-resolution, or "
                    "take the restart-based elastic path between phases")
            if self.mesh.model > 0 \
                    and self.elastic_target_devices % self.mesh.model:
                raise ValueError(
                    f"elastic_target_devices="
                    f"{self.elastic_target_devices} must be divisible by "
                    f"the model axis (mesh.model={self.mesh.model}) — the "
                    "live switch resizes the data axis only")
        if self.elastic_notice_file and not self.elastic_target_devices:
            raise ValueError(
                "--elastic_notice_file without --elastic_target_devices "
                "is a silent no-op — arm a target topology to switch to")
        if self.prefetch_device_batches < 0:
            raise ValueError(
                f"prefetch_device_batches must be >= 0, got "
                f"{self.prefetch_device_batches}")
        if self.grad_accum < 1:
            raise ValueError(
                f"grad_accum must be >= 1, got {self.grad_accum}")
        if self.batch_size % self.grad_accum:
            raise ValueError(
                f"batch_size ({self.batch_size}) must be a multiple of "
                f"grad_accum ({self.grad_accum}) — microbatches are "
                "batch_size/grad_accum")


# --------------------------------------------------------------------------
# Checkpoint-side config persistence (VERDICT r1 #3).
#
# The reference's Saver stored only variables; restoring required the user to
# re-specify every architecture flag, and a mismatch surfaced as an opaque
# restore error (image_train.py:233-245 had the same hazard). Here the
# trainer writes the full TrainConfig as `config.json` next to the Orbax step
# dirs, and generate/evals/resume read it back — so
# `python -m dcgan_tpu.generate --checkpoint_dir ckpt` needs zero
# architecture flags, and a resume with mismatched architecture fails with a
# clear message instead of an Orbax shape error.
# --------------------------------------------------------------------------

CONFIG_FILENAME = "config.json"


def config_to_dict(cfg: TrainConfig) -> Dict[str, Any]:
    return dataclasses.asdict(cfg)


def _known_fields(cls, d: Dict[str, Any], *, context: str) -> Dict[str, Any]:
    """Filter a dict to cls's fields; warn (don't fail) on unknown keys so a
    checkpoint written by a NEWER framework version still loads."""
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(d) - names)
    if unknown:
        print(f"[dcgan_tpu] ignoring unknown {context} config keys "
              f"{unknown} (checkpoint written by a newer version?)",
              file=sys.stderr)
    return {k: v for k, v in d.items() if k in names}


def config_from_dict(d: Dict[str, Any]) -> TrainConfig:
    d = dict(d)
    saved = dict(d.pop("model", {}))
    cls = TOKEN_MODEL_CONFIGS.get(saved.get("arch"), ModelConfig)
    model = cls(**_known_fields(cls, saved, context="model"))
    mesh = MeshConfig(**_known_fields(MeshConfig, dict(d.pop("mesh", {})),
                                      context="mesh"))
    rest = _known_fields(TrainConfig, d, context="train")
    if "sample_grid" in rest:  # JSON round-trips tuples as lists
        rest["sample_grid"] = tuple(rest["sample_grid"])
    return TrainConfig(model=model, mesh=mesh, **rest)


def save_config(cfg: TrainConfig, directory: str) -> str:
    """Write config.json atomically (tmp + rename); returns the path."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, CONFIG_FILENAME)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(config_to_dict(cfg), f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_config(directory: str) -> Optional[TrainConfig]:
    """The TrainConfig stored next to a checkpoint, or None if absent."""
    path = os.path.join(directory, CONFIG_FILENAME)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return config_from_dict(json.load(f))


# The ModelConfig knobs checkpoint consumers (generate/evals/export CLIs)
# expose as override flags — one list so the parsers cannot drift apart.
MODEL_OVERRIDE_FLAGS = ("arch", "output_size", "c_dim", "z_dim", "gf_dim",
                        "df_dim", "num_classes", "conditional_bn",
                        "attn_res", "attn_heads", "spectral_norm")


def add_model_override_flags(p) -> None:
    """Install the MODEL_OVERRIDE_FLAGS architecture flags on an argparse
    parser — the one shared definition for every checkpoint-consumer CLI
    (generate/evals/export; the trainer's parser wires these knobs with
    live defaults instead of the None='not passed' convention used here).
    Defaults are None so "explicitly passed" is distinguishable from
    "omitted"; precedence is explicit flag > --preset > checkpoint
    config.json > ModelConfig defaults (resolve_model_config).
    """
    import argparse

    p.add_argument("--arch", choices=["dcgan", "resnet", "stylegan"],
                   default=None,
                   help="match the checkpoint's model family")
    p.add_argument("--output_size", type=int, default=None)
    p.add_argument("--c_dim", type=int, default=None)
    p.add_argument("--z_dim", type=int, default=None)
    p.add_argument("--gf_dim", type=int, default=None)
    p.add_argument("--df_dim", type=int, default=None)
    p.add_argument("--num_classes", type=int, default=None)
    p.add_argument("--attn_res", type=int, default=None,
                   help="match the checkpoint's attention config "
                        "(presets supply it; explicit flag overrides)")
    p.add_argument("--attn_heads", type=int, default=None,
                   help="match the checkpoint's attention head count")
    p.add_argument("--spectral_norm", choices=["none", "d", "gd"],
                   default=None,
                   help="match the checkpoint's spectral-norm config")
    p.add_argument("--conditional_bn", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="match the checkpoint's conditional-BN config "
                        "([K, C] per-class BN tables in G)")


def _progressive_checkpoint_resolution(checkpoint_dir: str) -> Optional[int]:
    """The resolution tag of the NEWEST checkpoint sidecar carrying a
    progressive phase tag (ISSUE 15), or None. A run stopped mid-schedule
    saved a SHALLOWER tree than the config.json's final architecture —
    checkpoint consumers must build their restore template at the saved
    phase's resolution, not the schedule's end state."""
    import glob
    import re

    best: Optional[Tuple[int, int]] = None  # (step, resolution)
    for path in glob.glob(os.path.join(checkpoint_dir, "integrity",
                                       "*.sharding.json")):
        m = re.match(r"(\d+)\.sharding\.json$", os.path.basename(path))
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                tag = json.load(f).get("progressive")
            res = int(tag["resolution"])
        except (OSError, ValueError, KeyError, TypeError):
            continue
        step = int(m.group(1))
        if best is None or step > best[0]:
            best = (step, res)
    return None if best is None else best[1]


def resolve_model_config(checkpoint_dir: str, *, preset: Optional[str] = None,
                         overrides: Optional[Dict[str, Any]] = None
                         ) -> ModelConfig:
    """Architecture resolution for checkpoint consumers (generate/evals).

    Precedence: explicit flag overrides > --preset > the checkpoint's own
    config.json > ModelConfig defaults. `overrides` values of None mean
    "not passed" and are dropped.

    Progressive checkpoints (ISSUE 15): the config.json describes the
    schedule's FINAL model, but a mid-schedule checkpoint holds an earlier
    phase's shallower tree — the sidecar's phase tag names which, and the
    resolved output_size adopts it (an explicit --output_size flag still
    wins), so `generate --checkpoint_dir` works zero-flag at any point of
    the schedule instead of failing as an Orbax tree mismatch.
    """
    if preset:
        from dcgan_tpu.presets import get_preset  # lazy: presets imports us

        base = get_preset(preset).model
    else:
        saved = load_config(checkpoint_dir)
        base = saved.model if saved is not None else ModelConfig()
        if saved is not None and saved.progressive:
            res = _progressive_checkpoint_resolution(checkpoint_dir)
            if res is not None and res != base.output_size:
                print(f"[dcgan_tpu] progressive checkpoint: latest step was "
                      f"saved at r{res} (schedule "
                      f"{saved.progressive!r} ends at "
                      f"r{base.output_size}); building the r{res} model",
                      file=sys.stderr)
                base = dataclasses.replace(base, output_size=res)
    if is_token_arch(base.arch):
        raise ValueError(
            f"arch={base.arch!r} is a one-network token family: its "
            "checkpoint holds no sampler, so generate, evals, export and "
            "serve have nothing to run from it")
    given = {k: v for k, v in (overrides or {}).items() if v is not None}
    return dataclasses.replace(base, **given)
