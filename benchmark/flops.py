"""Operations and bytes one GAN train step needs, from the configuration's
shapes alone. The yardstick for `step_mfu` and `flash_attn_roofline`.

What is counted is what the algorithm requires, not what a program happens
to execute: multiply-adds against SAME padding are left out (a 5x5 stride-2
kernel over an 8x8 map has 72% of its taps inside the map), the generator's
forward pass is counted once (the D half and the G half of a sequential
step use the same weights and the same z), and nothing a kernel recomputes
(flash attention's scores in the backward pass) is counted. So the count is
at or below what XLA's cost analysis gives for the same step, and a share
of the peak worked out from it cannot pass 100%.

One step needs, per network pass (F = forward matmul operations):
  D half: G forward; D forward on real and on fake; for both D passes the
          weight gradients (F) and the input gradients of every layer but
          the first (F each).
  G half: D forward on fake; D's input gradients through every layer; G's
          weight gradients and the input gradients of every layer but the
          projection.
"""

from __future__ import annotations

import math
from typing import Dict


def _taps(n_in: int, kernel: int, stride: int) -> int:
    """Valid (output, tap) pairs along one axis of a SAME strided conv."""
    n_out = -(-n_in // stride)
    pad = max((n_out - 1) * stride + kernel - n_in, 0)
    lo = pad // 2
    total = 0
    for o in range(n_out):
        first = o * stride - lo
        total += sum(1 for t in range(kernel) if 0 <= first + t < n_in)
    return total


def conv_ops(n_in: int, c_in: int, c_out: int, kernel: int,
             stride: int = 2) -> int:
    """Operations (2 per multiply-add) of one image's strided SAME conv over
    an n_in x n_in map; a transposed conv to n_in x n_in costs the same."""
    return 2 * _taps(n_in, kernel, stride) ** 2 * c_in * c_out


def stages(m: dict) -> int:
    return int(round(math.log2(m["output_size"] / m["base_size"])))


def _attn(m: dict, ch: int) -> Dict[str, int]:
    """One image's attention block over attn_res^2 tokens of `ch` channels:
    projection matmuls and the two score matmuls, forward."""
    s = m["attn_res"] ** 2
    dqk, dv = ch // m["attn_qk_div"], ch // m["attn_v_div"]
    return {"proj": 2 * s * ch * (2 * dqk + dv) + 2 * s * dv * ch,
            "scores": 2 * s * s * (dqk + dv),
            "tokens": s, "dqk": dqk, "dv": dv, "ch": ch}


def layer_table(m: dict) -> Dict[str, dict]:
    """Forward operations per image of every matmul layer, for G and D."""
    k, ks, base = stages(m), m["kernel_size"], m["base_size"]
    top = m["gf_dim"] * 2 ** (k - 1)
    gen = {"proj": 2 * m["z_dim"] * top * base * base}
    c_in = top
    for i in range(1, k + 1):
        c_out = m["c_dim"] if i == k else m["gf_dim"] * 2 ** (k - 1 - i)
        gen[f"deconv{i}"] = conv_ops(base * 2 ** i, c_out, c_in, ks)
        c_in = c_out
    disc = {}
    c_in = m["c_dim"]
    for i in range(k):
        c_out = m["df_dim"] * 2 ** i
        disc[f"conv{i}"] = conv_ops(m["output_size"] >> i, c_in, c_out, ks)
        c_in = c_out
    disc["head"] = 2 * base * base * c_in
    out = {"gen": gen, "disc": disc}
    if m["attn_res"]:
        gi = int(round(math.log2(m["attn_res"] / base)))
        g_ch = top if gi == 0 else m["gf_dim"] * 2 ** (k - 1 - gi)
        di = int(round(math.log2(m["output_size"] / m["attn_res"]))) - 1
        out["gen_attn"] = _attn(m, g_ch)
        out["disc_attn"] = _attn(m, m["df_dim"] * 2 ** di)
    return out


def step_ops(m: dict, batch: int) -> Dict[str, float]:
    """Operations of one train step at global batch `batch`:
    {"conv": stacks and linears, "attn_proj", "attn_scores", "total"}."""
    t = layer_table(m)
    g = sum(t["gen"].values())
    d = sum(t["disc"].values())
    g_first, d_first = t["gen"]["proj"], t["disc"]["conv0"]
    conv = (g + 3 * d                       # forwards
            + 2 * (2 * d - d_first)         # D half: dW and dX, two passes
            + d                             # G half: dX through D
            + 2 * g - g_first)              # G half: dW and dX in G
    proj = scores = 0
    if "gen_attn" in t:
        ga, da = t["gen_attn"], t["disc_attn"]
        # forward 1x G and 3x D; backward (2x forward: dW/dX, dQ dK dV dP)
        # in both D passes of the D half and in D and G of the G half
        proj = ga["proj"] * (1 + 2) + da["proj"] * (3 + 2 * 3)
        scores = ga["scores"] * (1 + 2) + da["scores"] * (3 + 2 * 3)
    out = {"conv": conv * batch, "attn_proj": proj * batch,
           "attn_scores": scores * batch}
    out["total"] = sum(out.values())
    return out


def flash_step_cost(m: dict, batch: int, act_bytes: int = 2
                    ) -> Dict[str, float]:
    """What the flash kernels of one step must do at the least: the score
    matmuls (forward 1x G + 3x D, backward 1x G + 3x D at twice a forward)
    and the bytes of q, k, v, o (and their gradients in the backward pass)
    crossing HBM once, in the activation type."""
    t = layer_table(m)
    ops = step_ops(m, batch)["attn_scores"]
    byts = 0
    for net, n_fwd, n_bwd in (("gen_attn", 1, 1), ("disc_attn", 3, 3)):
        a = t[net]
        row = a["tokens"] * (2 * a["dqk"] + 2 * a["dv"]) * act_bytes
        byts += row * n_fwd          # read q k v, write o
        byts += 2 * row * n_bwd      # read q k v o do, write dq dk dv
    return {"ops": float(ops), "bytes": float(byts * batch)}
