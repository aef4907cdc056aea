"""TPU-compiler rehearsals: the Pallas kernels the program runs (flash
attention, the token model's grouped matmuls), compiled at the shipped
presets' shapes for a DESCRIBED v5e (no chip attached).

Interpret mode — what every other kernel test runs — checks the arithmetic
and nothing about tiling, lane alignment or VMEM: a kernel can pass every
interpret-mode test while the chip's compiler refuses its block shape.
These compiles raise here what the chip's compiler would raise there, at no chip time. They prove a kernel
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

from dcgan_tpu.ops import pallas_attention, pallas_scan
from dcgan_tpu.presets import get_preset

BATCH = 64


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
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_scan, "_interpret", lambda: False)


def _sds(shape, dtype, dev):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


def _compile(fn, *args):
    """Compile for the described device; the compiled text must hold the
    kernel itself, not an interpreted expansion of it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


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


@pytest.mark.usefixtures("compiled_kernels")
def test_causal_flash_attention_at_the_looped_model_widths(v5e):
    """The looped trunk's attention (models/loop_lm.py): 16 heads of 128
    for q, k and v alike, causal, 4,096 tokens, heads folded into the batch
    axis, forward and backward."""
    def loss(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(q, k, v, 128 ** -0.5,
                                                        True))

    qkv = _sds((16, 4096, 128), jnp.bfloat16, v5e)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), qkv, qkv,
                    qkv)
    assert "flash_fwd" in text and "flash_dq_dkv" in text


@pytest.mark.usefixtures("compiled_kernels")
@pytest.mark.parametrize("window", [None, 512])
def test_differential_attention_at_the_hybrid_model_widths(v5e, window):
    """The decoder-hybrid-decoder's attention (models/sambay.py): 40 folded
    rows of 8,192 tokens, q/k 64 wide with v 128, causal; the full layer's
    call and the window layer's, which runs the windowed kernels at their
    own tiles."""
    def loss(q, k, v):
        return jnp.sum(pallas_attention.flash_attention(q, k, v, 0.125, True,
                                                        window))

    qk = _sds((40, 8192, 64), jnp.bfloat16, v5e)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), qk, qk,
                    _sds((40, 8192, 128), jnp.bfloat16, v5e))
    names = ("flash_fwd_win", "flash_dq_dkv_win") if window else \
        ("flash_fwd", "flash_dq_dkv")
    assert all(n in text for n in names)
    assert ("flash_fwd_win" in text) == bool(window)


@pytest.mark.usefixtures("compiled_kernels")
def test_selective_scan_at_the_hybrid_model_widths(v5e):
    """The Mamba layers' scan (ops/pallas_scan.py) at the shipped size: one
    row of 8,192 steps, 5,120 channels, 16 states, float32, forward and
    all five gradients (the backward's rebuilt states are 4 MB of VMEM
    scratch at a chunk of 128 steps and 512 channels)."""
    def loss(u, dt, a, b, c):
        return jnp.sum(pallas_scan.selective_scan(u, dt, a, b, c))

    seq = _sds((1, 8192, 5120), jnp.float32, v5e)
    bc = _sds((1, 8192, 16), jnp.float32, v5e)
    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), seq,
                    seq, _sds((5120, 16), jnp.float32, v5e), bc, bc)
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text


@pytest.mark.parametrize("rows", [65536, 16384])
def test_grouped_expert_matmuls_compile_at_published_widths(v5e, monkeypatch,
                                                            rows):
    """The expert layer's grouped products (megablox `gmm` / `tgmm` through
    models/mla_moe.py::_gmm): 65,536 sorted rows (8,192 tokens x 8, the
    worst case) or the 16,384 of the buffer sized for 16 of 256 experts,
    against 16 experts of 2048 x 768 and back, forward and gradients, with
    this module's own tile sizes."""
    from dcgan_tpu.models import mla_moe

    monkeypatch.setattr(mla_moe.jax, "default_backend", lambda: "tpu")

    def loss(x, w1, w2, sizes):
        h = mla_moe._gmm(x, w1, sizes, jnp.bfloat16)
        return jnp.sum(mla_moe._gmm(h, w2, sizes, jnp.bfloat16)
                       .astype(jnp.float32))

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    _sds((rows, 2048), jnp.bfloat16, v5e),
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
