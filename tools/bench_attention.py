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
    # a 512-key window over the same triangle, the windowed kernels' tiles:
    python tools/bench_attention.py --causal --window 512 --forms flash \
        --heads 40 --d 64 --dv 128 --seq 8192 --tq 256 512 --tk 128 256
    # the flash kernels alone, forward by itself and with the backward, over
    # a grid of the forward's tiles and folds per loop iteration (the sweep
    # behind FWD_BLOCK_Q / FWD_BLOCK_K / FWD_UNROLL of
    # ops/pallas_attention.py):
    python tools/bench_attention.py --forms flash --passes fwd fwd_bwd \
        --batch 256 --d 8 --dv 32 --seq 4096
    python tools/bench_attention.py --forms flash --passes fwd --batch 256 \
        --d 8 --dv 32 --seq 4096 --tq 1024 2048 --tk 256 512 --unroll 4 8

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
    p.add_argument("--forward_only", action="store_true",
                   help="same as --passes fwd")
    p.add_argument("--passes", nargs="+", choices=["fwd", "fwd_bwd"],
                   default=["fwd_bwd"],
                   help="time the forward alone, forward + backward, or "
                        "both in turn")
    p.add_argument("--forms", nargs="+", default=None,
                   help="time only these forms (e.g. --forms flash: a "
                        "kernel sweep has no use for the dense wall)")
    p.add_argument("--unroll", type=int, nargs="+", default=[None],
                   help="folds per loop iteration of the flash forward to "
                        "sweep: sets pallas_attention.FWD_UNROLL for this "
                        "process, to measure the constant itself; default: "
                        "the module's")
    p.add_argument("--tq", type=int, nargs="+", default=[None],
                   help="q-tile targets to sweep (DCGAN_FLASH_TQ, read when "
                        "each timed function is traced, by the forward AND "
                        "the backward: sweep the forward's with --passes "
                        "fwd); default: the module's constants")
    p.add_argument("--tk", type=int, nargs="+", default=[None])
    p.add_argument("--causal", action="store_true",
                   help="causal attention: the flash kernels' lower-"
                        "triangle path against dense masked attention "
                        "(e.g. --causal --heads 32 --d 192 --dv 128 --seq "
                        "8192: the token trunk's shape)")
    p.add_argument("--window", type=int, default=None,
                   help="with --causal: query i sees its last WINDOW keys "
                        "(itself counted); the flash form runs the windowed "
                        "kernels, whose tiles --tq / --tk then sweep "
                        "(WIN_BLOCK_Q / WIN_BLOCK_K), forward and backward "
                        "alike")
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
    from dcgan_tpu.ops import pallas_attention
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
            if args.window:
                keep &= ~jnp.tril(keep, -args.window)
            p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32)

        forms = {
            "dense": dense_causal,
            "flash": lambda q, k, v: flash_attention(q, k, v, scale, True,
                                                     args.window),
        }
    elif args.window:
        sys.exit("--window is the causal mask's: pass --causal")
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

    if args.forms:
        unknown = [f for f in args.forms if f not in forms]
        if unknown:
            sys.exit(f"unknown --forms {unknown}; have {sorted(forms)}")
        forms = {name: forms[name] for name in args.forms}
    passes = ["fwd"] if args.forward_only else args.passes

    def sync(out):
        float(jnp.sum(jax.tree_util.tree_leaves(out)[0].astype(jnp.float32)))

    def timed(fn, which, q, k, v):
        if which == "fwd":
            # a new function object each time: jit's cache is keyed by it
            step = jax.jit(lambda q, k, v: fn(q, k, v))
        else:
            # all three grads: argnums=0 alone would let XLA DCE the
            # dk/dv matmuls out of the dense backward while the flash
            # custom VJP always computes them — an unfair comparison
            step = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2)))
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
        return dt / args.steps * 1e3

    # one timed function per grid point: a new jit traces the kernels anew,
    # so the tile targets and the unroll are read as set here
    one = [None]
    grid = [(S, name, which, tq, tk, unroll)
            for S in args.seq for name in forms for which in passes
            for tq in (args.tq if name == "flash" else one)
            for tk in (args.tk if name == "flash" else one)
            for unroll in (args.unroll if name == "flash" else one)]
    qkv, qkv_seq = None, None
    for S, name, which, tq, tk, unroll in grid:
        if S != qkv_seq:
            qkv, qkv_seq = make_qkv(S, jax.random.key(0)), S
        row = {"form": name, "seq": S, "heads": h, "batch": args.batch,
               "d": args.d, "dv": args.dv, "backward": which == "fwd_bwd",
               "causal": args.causal, "gen": ATTN_GEN}
        if args.window:
            row["window"] = args.window
        for key, val in (("DCGAN_FLASH_TQ", tq), ("DCGAN_FLASH_TK", tk)):
            if val is not None:
                os.environ[key] = str(val)
                row[key[-2:].lower()] = val
        if unroll is not None:
            pallas_attention.FWD_UNROLL = row["unroll"] = unroll
        try:
            row["ms"] = round(timed(forms[name], which, *qkv), 3)
        except Exception as e:  # the dense wall is the measurement
            row["error"] = f"{type(e).__name__}: {str(e)[:160]}"
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
