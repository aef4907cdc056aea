"""TPU-compiler rehearsals: the Pallas kernels of the opt-in paths, compiled
at the shipped presets' shapes for a DESCRIBED v5e (no chip attached).

Interpret mode — what every other kernel test runs — checks the arithmetic
and nothing about tiling, lane alignment or VMEM: `gemm_bias_moments` passed
every interpret-mode test while the chip's compiler refused its block shape
at all three interior `celeba64` stages. These compiles raise here what the
chip's compiler would raise there, at no chip time. They prove a kernel
COMPILES; that it runs and is right on the chip is `chip_smoke.py`'s job.

`_interpret()` sees the CPU backend here and would route every kernel to the
interpreter, so each test steers it (the `compiled_kernels` fixture) — the
program gets no option for it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
# libtpu guards a chip with a host-wide lockfile; nothing is attached here,
# and test workers running side by side must each be able to load it
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dcgan_tpu.ops import pallas_attention, pallas_fused, pallas_kernels
from dcgan_tpu.presets import get_preset

#: the scoped VMEM a v5e kernel gets without asking for more
DEFAULT_SCOPED_VMEM_MIB = 16

BATCH = 64
#: [N*H*W, C] at the four BatchNorm sites of DCGAN-64 (G and D mirror)
BN_SHAPES = [(BATCH * 4 * 4, 512), (BATCH * 8 * 8, 256),
             (BATCH * 16 * 16, 128), (BATCH * 32 * 32, 64)]
#: (M, K, C) of the interior celeba64 stages whose K the old tiling got
#: refused at (K = Cin * 5 * 5): D conv1..3; G deconv2..3 repeat K 6400/3200
FUSED_SHAPES = [(BATCH * 16 * 16, 1600, 128), (BATCH * 8 * 8, 3200, 256),
                (BATCH * 4 * 4, 6400, 512)]


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e:2x2, persistent cache off around the
    module: a compile for a described device is written to the cache but
    cannot be read back without a chip, and the next one would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"v5e:2x2 topology cannot be described here: {e!r}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    for mod in (pallas_kernels, pallas_fused, pallas_attention):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _sds(shape, dtype, dev):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


def _compile(fn, *args):
    """Compile for the described device; the compiled text must hold the
    kernel itself, not an interpreted expansion of it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.usefixtures("compiled_kernels")
class TestBnKernels:
    @pytest.mark.parametrize("n,c", BN_SHAPES)
    def test_channel_moments_fwd_and_vjp(self, v5e, n, c):
        def loss(x):
            mean, mean_sq = pallas_kernels.channel_moments(x)
            return jnp.sum(mean) + jnp.sum(mean_sq)

        _compile(jax.value_and_grad(loss), _sds((n, c), jnp.bfloat16, v5e))

    @pytest.mark.parametrize("n,c", BN_SHAPES)
    def test_scale_shift_act_fwd_and_vjp(self, v5e, n, c):
        def loss(x, scale, shift):
            y = pallas_kernels.scale_shift_act(x, scale, shift, "lrelu", 0.2)
            return jnp.sum(y.astype(jnp.float32))

        vec = _sds((c,), jnp.float32, v5e)
        _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                 _sds((n, c), jnp.bfloat16, v5e), vec, vec)


@pytest.mark.usefixtures("compiled_kernels")
class TestFusedStageKernels:
    @pytest.mark.parametrize("m,k,c", FUSED_SHAPES)
    def test_gemm_bias_moments(self, v5e, m, k, c):
        _compile(lambda p, w, b: pallas_fused.gemm_bias_moments(
                     p, w, b, jnp.bfloat16),
                 _sds((m, k), jnp.bfloat16, v5e),
                 _sds((k, c), jnp.bfloat16, v5e), _sds((c,), jnp.float32, v5e))

    @pytest.mark.parametrize("m,k,c", FUSED_SHAPES)
    def test_gemm_bias_scale_act(self, v5e, m, k, c):
        vec = _sds((c,), jnp.float32, v5e)
        _compile(lambda p, w, b, s, t: pallas_fused.gemm_bias_scale_act(
                     p, w, b, s, t, "lrelu", 0.2, jnp.bfloat16),
                 _sds((m, k), jnp.bfloat16, v5e),
                 _sds((k, c), jnp.bfloat16, v5e), vec, vec, vec)


@pytest.mark.parametrize("preset", ["celeba64", "dcgan128"])
def test_fused_tiles_are_lane_aligned_and_fit_vmem(preset):
    """Every interior stage gets a K block the TPU lowering accepts (a
    multiple of the 128 lanes, or the whole K), and `kernel_cost`'s own
    VMEM model of the chosen tiles, double-buffered, stays inside the
    default scoped limit — so no stage needs `vmem_limit_bytes`."""
    for site in pallas_fused.fused_sites(get_preset(preset).model, BATCH):
        k = pallas_fused._k_padded(site["k"])
        tk = pallas_fused._k_tile(k)
        assert k % tk == 0 and (tk % 128 == 0 or tk == k), site
        for train in (True, False):
            for dtype in (jnp.bfloat16, jnp.float32):
                cost = pallas_fused.kernel_cost(
                    site["m"], site["k"], site["c"], train=train,
                    compute_dtype=dtype)
                assert 2 * cost["peak_temp_mib"] < DEFAULT_SCOPED_VMEM_MIB, \
                    (site, cost)


@pytest.mark.usefixtures("compiled_kernels")
@pytest.mark.parametrize("seq", [1024, 4096, 16384])
def test_flash_attention_fwd_and_vjp(v5e, seq):
    """sagan64 (S=1024), sagan128 (S=4096) and sagan256-lc (S=16384): 64
    channels at the attention site, so q/k project to 8 lanes and v to 32
    (ops/attention.py). The backward's whole-sequence dQ accumulator is
    what grows with S."""
    def loss(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(q, k, v, 8 ** -0.5))

    qk = _sds((BATCH, seq, 8), jnp.bfloat16, v5e)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), qk, qk,
                    _sds((BATCH, seq, 32), jnp.bfloat16, v5e))
    assert "flash_fwd" in text and "flash_dq_dkv" in text


@pytest.mark.usefixtures("compiled_kernels")
@pytest.mark.parametrize("d, dv", [(8, 32), (64, 64)])
def test_flash_attention_at_65536_tokens(v5e, d, dv):
    """The longest sequence the narrow widths compile at (PERF.md section
    7): every whole-sequence resident of the forward (v^T) and of the
    backward (q^T, do^T, lse, delta) is lane-dense along S; the forward's
    k [S, d] and the backward's dQ accumulator are the lane-padded ones."""
    def loss(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(q, k, v, d ** -0.5))

    qk = _sds((2, 65536, d), jnp.bfloat16, v5e)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), qk, qk,
                    _sds((2, 65536, dv), jnp.bfloat16, v5e))
    assert "flash_fwd" in text and "flash_dq_dkv" in text


@pytest.mark.usefixtures("compiled_kernels")
@pytest.mark.parametrize("seq, causal", [(4096, False), (16384, False),
                                         (8192, True)])
def test_flash_forward_alone(v5e, seq, causal):
    """The forward call by itself, as the sampler and the ring's hops make
    it: q^T / v^T in, o^T float32 and lse [B, 1, S] out, causal or not, at
    the shipped tiles (q-tile 2048 on the lane axis, 4 folds of 256 keys
    per loop iteration)."""
    text = _compile(
        lambda qT, k, vT: pallas_attention._fwd_core(qT, k, vT, 8 ** -0.5,
                                                     causal),
        _sds((BATCH, 8, seq), jnp.bfloat16, v5e),
        _sds((BATCH, seq, 8), jnp.bfloat16, v5e),
        _sds((BATCH, 32, seq), jnp.bfloat16, v5e))
    assert "flash_fwd" in text


@pytest.mark.usefixtures("compiled_kernels")
@pytest.mark.parametrize("heads, seq", [(32, 8192), (4, 16384)])
def test_causal_flash_attention_at_latent_attention_widths(v5e, heads, seq):
    """The token trunk's attention (models/mla_moe.py): q/k 192 wide (more
    than one lane tile), v 128, causal, heads folded into the batch axis:
    32 heads of 8,192 tokens as the shipped configuration runs them, and
    16,384-token rows, whose backward residents (dQ accumulator 16,384 x
    256 lanes x 4 B = 16 MiB, its output block, q^T and do^T
    double-buffered: 52 MiB) pass the default scoped VMEM and need the
    kernels' explicit `vmem_limit_bytes`; 65,536 tokens at this width
    (224 MB of residents) do not fit the chip's 128 MiB at all."""
    def loss(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(q, k, v, 192 ** -0.5,
                                                        True))

    qk = _sds((heads, seq, 192), jnp.bfloat16, v5e)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), qk, qk,
                    _sds((heads, seq, 128), jnp.bfloat16, v5e))
    assert "flash_fwd" in text and "flash_dq_dkv" in text


def test_grouped_expert_matmuls_compile_at_published_widths(v5e, monkeypatch):
    """The expert layer's grouped products (megablox `gmm` / `tgmm` through
    models/mla_moe.py::_gmm): 65,536 sorted rows (8,192 tokens x 8, the
    worst case) against 16 experts of 2048 x 768 and back, forward and
    gradients, with this module's own tile sizes."""
    from dcgan_tpu.models import mla_moe

    monkeypatch.setattr(mla_moe.jax, "default_backend", lambda: "tpu")

    def loss(x, w1, w2, sizes):
        h = mla_moe._gmm(x, w1, sizes, jnp.bfloat16)
        return jnp.sum(mla_moe._gmm(h, w2, sizes, jnp.bfloat16)
                       .astype(jnp.float32))

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    _sds((65536, 2048), jnp.bfloat16, v5e),
                    _sds((16, 2048, 768), jnp.bfloat16, v5e),
                    _sds((16, 768, 2048), jnp.bfloat16, v5e),
                    _sds((16,), jnp.int32, v5e))
    # the instructions take the kernels' jitted names (`jvp_jit_gmm___.N`,
    # `transpose_jvp_jit_tgmm___.N`): what `moe_gmm_roofline` reads
    assert "_gmm_" in text and "_tgmm_" in text


@pytest.mark.slow
def test_celeba64_train_step_compiles_for_one_chip(v5e):
    """The whole flagship step (gspmd backend, batch 64, bf16) on one
    described device, with its memory analysis inside a v5e's 16 GB."""
    from jax.sharding import Mesh, NamedSharding

    import numpy as np

    from dcgan_tpu.parallel import make_parallel_train
    from dcgan_tpu.train import warmup

    cfg = get_preset("celeba64")
    dev = next(iter(v5e.device_set))
    mesh = Mesh(np.array([dev]).reshape(1, 1), ("data", "model"))
    pt = make_parallel_train(cfg, mesh)
    state = warmup.state_example(pt)
    img = _sds((BATCH, 64, 64, 3), jnp.float32,
               NamedSharding(mesh, jax.sharding.PartitionSpec("data")))
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = pt.programs["train_step"].lower(state, img, key).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
