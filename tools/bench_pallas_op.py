"""Op-level fused-BN kernel benchmark: Pallas vs XLA, out of conv context.

The captures table showed `use_pallas` losing ~23% at flagship shapes
*inside* the step, where XLA fuses the BN epilogue into the surrounding
convs. This tool isolates the op itself (VERDICT r3 #2's "find the config
where fusion wins" probe): forward+backward of batch-stat BN + lrelu on a
standalone activation tensor — no conv to fuse into, both forms reading and
writing the same HBM tensors — scanned K times per dispatch with
value-readback sync, best of 3 windows.

Measured conclusion (chip, 2026-07-31, DESIGN.md §8b): the kernels tie at
channel counts that fill the 128-wide vector lanes ([64,32,32,128] 0.95x,
[64,8,8,512] 0.99x) and lose 2-5x at C=64 or larger tensors — XLA's fusion
already saturates HBM for this op class, so `use_pallas` is a capability/
pattern flag, not a perf flag.

Prints one JSON line per shape:
  {"form": "bn_op", "shape": [...], "jnp_ms": a, "pallas_ms": b,
   "ratio_jnp_over_pallas": r}

Workload anchor: the BN the reference applies after nearly every conv
(distriubted_model.py:93-121).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EPS = 1e-5
K = int(os.environ.get("BENCH_OP_ITERS", 100))
SHAPES = [(64, 32, 32, 128), (64, 8, 8, 512), (64, 64, 64, 64),
          (256, 32, 32, 128), (256, 64, 64, 64)]


def main() -> None:
    import jax
    import jax.numpy as jnp

    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])

    from dcgan_tpu.ops.pallas_kernels import channel_moments, fused_bn_act

    def jnp_bn_act(x, gamma, beta):
        c = x.shape[-1]
        x2 = x.reshape(-1, c).astype(jnp.float32)
        mean = x2.mean(0)
        var = (x2 * x2).mean(0) - mean * mean
        inv = jax.lax.rsqrt(var + EPS)
        y = (x2 - mean) * inv * gamma + beta
        y = jnp.where(y > 0, y, 0.2 * y)
        return y.reshape(x.shape).astype(x.dtype)

    def pallas_bn_act(x, gamma, beta):
        c = x.shape[-1]
        x2 = x.reshape(-1, c)
        mean, msq = channel_moments(x2)
        var = msq - mean * mean
        return fused_bn_act(x, gamma, beta, mean, var, eps=EPS, act="lrelu")

    def bench(fn, shape):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(shape), dtype=jnp.bfloat16)
        gamma = jnp.ones((shape[-1],), jnp.float32)
        beta = jnp.zeros((shape[-1],), jnp.float32)

        def loss(x, gamma, beta):
            return fn(x, gamma, beta).astype(jnp.float32).sum()

        grad = jax.grad(loss, argnums=(0, 1, 2))

        @jax.jit
        def many(x, gamma, beta):
            # the carry feeds a tiny nonzero x perturbation so XLA cannot
            # hoist the loop-invariant grad computation out of the scan
            # (a 0.0 coefficient could legally be folded away)
            def body(carry, _):
                g = grad(x * (1.0 + 1e-7 * carry), gamma, beta)
                return carry + g[1][0], None
            acc, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(K))
            return acc

        out = many(x, gamma, beta)
        float(out)
        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = many(x, gamma, beta)
            float(out)
            dt = min(dt, time.perf_counter() - t0)
        return dt / K * 1e3

    for shape in SHAPES:
        tj = bench(jnp_bn_act, shape)
        tp = bench(pallas_bn_act, shape)
        print(json.dumps({
            "form": "bn_op", "shape": list(shape),
            "jnp_ms": round(tj, 4), "pallas_ms": round(tp, 4),
            "ratio_jnp_over_pallas": round(tj / tp, 3)}), flush=True)


if __name__ == "__main__":
    main()
