"""Attention HBM footprint: dense O(S²) vs flash O(S), by memory analysis.

The flash kernels' value claim on one chip is the memory wall — dense
attention materializes [S, S] score tensors, flash streams fixed blocks
(DESIGN.md §8). Timing cannot show this below the wall, so "dense fails
to allocate at 64k" was CPU-inferred. This tool measures the claim a third
way: compile both
forms' forward+backward at growing S and read `compiled.memory_analysis()`
— the XLA-reported temp (scratch) HBM each program needs. No execution, so
the numbers are exact program requirements, not samples; the dense curve's
O(S²) growth extrapolated against the 16 GB HBM IS the wall, measured from
chip-compiled programs.

Prints one JSON line per (form, S):
  {"form": ..., "seq": S, "temp_mib": ..., "args_mib": ...}
plus a summary with the fitted dense S² coefficient and the projected
S where dense temp alone exceeds HBM.

    python tools/attention_memory.py --seq 8192 16384 32768 40960
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, nargs="+",
                   default=[8192, 16384, 32768, 40960])
    p.add_argument("--d", type=int, default=64)
    p.add_argument("--hbm_gib", type=float, default=16.0,
                   help="HBM capacity to project the dense wall against")
    p.add_argument("--platform", default=None)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from dcgan_tpu.ops.attention import full_attention
    from dcgan_tpu.ops.pallas_attention import flash_attention

    scale = args.d ** -0.5
    forms = {
        "dense": lambda q, k, v: full_attention(q, k, v, scale=scale),
        "flash": lambda q, k, v: flash_attention(q, k, v, scale),
    }

    dense_pts = []
    for S in args.seq:
        qkv_aval = jax.ShapeDtypeStruct((1, S, args.d), jnp.bfloat16)
        for name, fn in forms.items():
            step = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2)))
            try:
                compiled = step.lower(qkv_aval, qkv_aval, qkv_aval).compile()
                ma = compiled.memory_analysis()
                temp = getattr(ma, "temp_size_in_bytes", None)
                arg = getattr(ma, "argument_size_in_bytes", None)
                row = {"form": name, "seq": S,
                       "temp_mib": round(temp / 2**20, 1)
                       if temp is not None else None,
                       "args_mib": round(arg / 2**20, 1)
                       if arg is not None else None}
                if name == "dense" and temp:
                    dense_pts.append((S, temp))
                print(json.dumps(row), flush=True)
            except Exception as e:  # compile wall: also a data point
                print(json.dumps({"form": name, "seq": S,
                                  "error": f"{type(e).__name__}: "
                                           f"{str(e)[:120]}"}), flush=True)

    # fit temp ~ c*S^2 + fixed by least squares over ALL measured dense
    # points (dedup'd — repeated/unsorted --seq must not skew or crash the
    # fit; the quadratic term dominates at large S, small-S rows carry the
    # fixed overhead the intercept absorbs)
    dense_pts = sorted(dict(dense_pts).items())
    if len(dense_pts) >= 2:
        import numpy as np

        s2 = np.asarray([s ** 2 for s, _ in dense_pts], dtype=np.float64)
        t = np.asarray([t for _, t in dense_pts], dtype=np.float64)
        a = np.stack([s2, np.ones_like(s2)], axis=1)
        (c, fixed), *_ = np.linalg.lstsq(a, t, rcond=None)
        hbm = args.hbm_gib * 2**30
        s_wall = int(((hbm - fixed) / c) ** 0.5) if c > 0 else None
        print(json.dumps({
            "label": "attention-memory",
            "dense_s2_bytes_coeff": round(float(c), 4),
            "projected_dense_wall_seq": s_wall,
            "hbm_gib": args.hbm_gib,
        }), flush=True)


if __name__ == "__main__":
    main()
