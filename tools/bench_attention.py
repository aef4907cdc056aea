"""Attention benchmark: dense vs flash (and ring vs ulysses on a mesh).

The long-context evidence artifact: measures one attention forward+backward
at growing sequence lengths, per execution form (ops/attention.py,
ops/pallas_attention.py). On the TPU chip this is where the flash kernels'
O(S) HBM property shows up as "still runs" after the dense path stops
compiling (~S=64k on one v5e); on a multi-device mesh it compares the two
sequence-parallel strategies. CPU runs are for smoke only.

    python tools/bench_attention.py                      # dense vs flash
    python tools/bench_attention.py --seq 1024 4096 16384
    python tools/bench_attention.py --mesh 4 --heads 4   # + ring/ulysses
    JAX_PLATFORMS=cpu python tools/bench_attention.py --seq 256 --steps 2
    python tools/bench_attention.py --causal --heads 32 --d 192 --dv 128 --seq 8192

Prints one JSON line per (form, S): {"form", "seq", "ms", "heads", ...};
forms that fail to compile/allocate report {"error": ...} instead of dying,
since hitting the dense wall IS the measurement.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, nargs="+",
                   default=[1024, 4096, 16384])
    p.add_argument("--d", type=int, default=64, help="qk head dim")
    p.add_argument("--dv", type=int, default=64, help="value head dim")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--heads", type=int, default=1)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--mesh", type=int, default=0,
                   help=">1: also run ring/ulysses over this many devices "
                        "(sequence axis)")
    p.add_argument("--forward_only", action="store_true")
    p.add_argument("--causal", action="store_true",
                   help="causal attention: the flash kernels' lower-"
                        "triangle path against dense masked attention "
                        "(e.g. --causal --heads 32 --d 192 --dv 128 --seq "
                        "8192: the token trunk's shape)")
    p.add_argument("--platform", default=None,
                   help="force a JAX platform (e.g. cpu)")
    args = p.parse_args()

    import jax
    from dcgan_tpu.utils.backend import shard_map

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from dcgan_tpu.ops.attention import (
        full_attention,
        ring_attention,
        ulysses_attention,
    )
    from dcgan_tpu.ops.pallas_attention import ATTN_GEN, flash_attention

    scale = args.d ** -0.5
    h = args.heads

    def make_qkv(S, key):
        ks = jax.random.split(key, 3)
        mk = lambda k, dim: jax.random.normal(
            k, (args.batch * h, S, dim), jnp.bfloat16)
        return mk(ks[0], args.d), mk(ks[1], args.d), mk(ks[2], args.dv)

    forms = {
        "dense": lambda q, k, v: full_attention(q, k, v, scale=scale),
        "flash": lambda q, k, v: flash_attention(q, k, v, scale),
    }
    if args.causal:
        if args.mesh:
            sys.exit("--causal times the single-device kernels (the ring "
                     "and ulysses forms have no mask)")

        def dense_causal(q, k, v):
            s = jnp.einsum("bqd,bkd->bqk", q, k,
                           preferred_element_type=jnp.float32) * scale
            keep = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
            p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32)

        forms = {
            "dense": dense_causal,
            "flash": lambda q, k, v: flash_attention(q, k, v, scale, True),
        }
    if args.mesh == 1:
        sys.exit("--mesh must be > 1 (a 1-device ring/ulysses is the dense "
                 "path)")
    if args.mesh > 1:
        devices = jax.devices()[:args.mesh]
        if len(devices) < args.mesh:
            sys.exit(f"need {args.mesh} devices, have {len(devices)}")
        mesh = Mesh(np.asarray(devices).reshape(1, args.mesh),
                    ("data", "model"))
        spec = P("data", "model", None)

        def smap(fn):
            f = shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                              out_specs=spec)
            return f

        forms["ring"] = smap(functools.partial(
            ring_attention, axis_name="model", n_shards=args.mesh,
            scale=scale))
        if h % args.mesh:
            print(json.dumps({"form": "ulysses",
                              "skipped": f"heads {h} not divisible by "
                                         f"mesh {args.mesh}"}))
        if h % args.mesh == 0:
            # ulysses works on [B, S, h*d] with heads unfolded
            def uly(q, k, v):
                B = args.batch
                qq = q.reshape(B, h, *q.shape[1:]).transpose(0, 2, 1, 3) \
                    .reshape(B, q.shape[1], -1)
                kk = k.reshape(B, h, *k.shape[1:]).transpose(0, 2, 1, 3) \
                    .reshape(B, k.shape[1], -1)
                vv = v.reshape(B, h, *v.shape[1:]).transpose(0, 2, 1, 3) \
                    .reshape(B, v.shape[1], -1)
                out = shard_map(
                    functools.partial(ulysses_attention, axis_name="model",
                                      n_shards=args.mesh, num_heads=h,
                                      scale=scale),
                    mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)(
                        qq, kk, vv)
                return out
            forms["ulysses"] = uly

    for S in args.seq:
        q, k, v = make_qkv(S, jax.random.key(0))
        for name, fn in forms.items():
            if args.forward_only:
                step = jax.jit(fn)
            else:
                # all three grads: argnums=0 alone would let XLA DCE the
                # dk/dv matmuls out of the dense backward while the flash
                # custom VJP always computes them — an unfair comparison
                step = jax.jit(jax.grad(
                    lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
                    argnums=(0, 1, 2)))

            def sync(out):
                float(jnp.sum(jax.tree_util.tree_leaves(out)[0]
                              .astype(jnp.float32)))

            try:
                sync(step(q, k, v))  # compile + warm
                # best of 3 windows — same methodology as bench.py /
                # bench_loader.py
                dt = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(args.steps):
                        out = step(q, k, v)
                    sync(out)
                    dt = min(dt, time.perf_counter() - t0)
                ms = dt / args.steps * 1e3
                print(json.dumps({"form": name, "seq": S,
                                  "ms": round(ms, 2), "heads": h,
                                  "batch": args.batch,
                                  "backward": not args.forward_only,
                                  "causal": args.causal,
                                  "gen": ATTN_GEN}))
            except Exception as e:  # the dense wall is the measurement
                print(json.dumps({"form": name, "seq": S,
                                  "error": f"{type(e).__name__}: "
                                           f"{str(e)[:160]}",
                                  "heads": h, "gen": ATTN_GEN}))


if __name__ == "__main__":
    main()
