"""One-command measurement harvester (VERDICT r2 #2).

This runs the measurement sections in priority order — headline bench →
preset/variant matrix → attention crossovers → chip FID trajectory →
loader ceiling — each as its own child process under its own bounded
timeout (this parent never touches jax, so each child has the chip to
itself), records every result (value or failure) to
``tools/captures.jsonl``, and rewrites the marker-delimited "Chip
captures" blocks of the docs that carry them from the accumulated log
(the JSONL is append-only, renders keep the best row per label). No
captures are committed: the log of the previous machine was deleted with
the figures it carried, and the benchmark matrix that replaces this tool
is ROADMAP Queue 1 item 0.

Usage:
    python tools/capture_all.py                  # everything, priority order
    python tools/capture_all.py --only headline matrix
    python tools/capture_all.py --render-only    # just re-render the docs

The workload anchor for the throughput sections is the reference's hot
loop, image_train.py:147-194; the FID section replaces its eval duty
(image_train.py:179-192).
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPTURES = os.path.join(REPO, "tools", "captures.jsonl")
# the captures table's home (created by the first render; the name is what
# the render tests patch, and dates from the file the table used to live in)
BASELINE_MD = os.path.join(REPO, "docs", "CAPTURES.md")
DESIGN_MD = os.path.join(REPO, "docs", "DESIGN.md")

BEGIN = "<!-- capture_all:begin -->"
END = "<!-- capture_all:end -->"


def _today() -> str:
    return datetime.date.today().isoformat()


# ---------------------------------------------------------------------------
# Step table: (section, label, argv, env overrides, timeout_s)
# Priority order IS file order — the headline number first.
# ---------------------------------------------------------------------------

def _bench(label: str, timeout: float = 420, **env: str):
    return ("matrix", label, [sys.executable, "bench.py"], env, timeout)


STEPS = [
    ("headline", "dcgan64-headline", [sys.executable, "bench.py"], {}, 600),
    _bench("dcgan128", BENCH_PRESET="dcgan128"),
    _bench("wgan-gp", BENCH_PRESET="wgan-gp"),
    _bench("cifar10-cond", BENCH_PRESET="cifar10-cond"),
    _bench("sngan-cifar10", BENCH_PRESET="sngan-cifar10"),
    _bench("sagan64-attn", BENCH_ATTN="1"),
    _bench("sagan64-attn-sn", BENCH_ATTN="1", BENCH_SN="1"),
    # the measured-best attention execution split (r5): flash kernels for
    # the attention block, XLA for BN; these rows keep that comparison
    # against the dense rows above live in the matrix (the sagan presets
    # default to this split since rev 2)
    _bench("sagan64-attn-flash", BENCH_ATTN="1", BENCH_PALLAS="1"),
    _bench("sagan64-attn-sn-flash", BENCH_ATTN="1", BENCH_SN="1",
           BENCH_PALLAS="1"),
    # the attention family's batch-scaling points: does the flash form keep
    # the headline's rising-throughput curve (DESIGN.md §1b) once the
    # score-matrix traffic is gone?
    _bench("sagan64-attn-flash-b256", BENCH_ATTN="1", BENCH_PALLAS="1",
           BENCH_BATCH="256"),
    _bench("sagan64-attn-flash-b512", BENCH_ATTN="1", BENCH_PALLAS="1",
           BENCH_BATCH="512"),
    # the full sagan64 preset (hinge + SN both nets + TTUR + EMA on the
    # rev-2 flash/XLA-BN split) — the recipe row, vs the knob rows above
    _bench("sagan64", BENCH_PRESET="sagan64"),
    # sagan128: attention at 64x64 (S=4096) — deeper into flash's winning
    # regime; the preset's first captured number
    _bench("sagan128", timeout=600, BENCH_PRESET="sagan128",
           BENCH_STEPS="200", BENCH_SCAN="25"),
    # inference (sampler) rows for the attention family — the serve path
    # with the flash kernels in the generator
    _bench("sagan64-attn-flash-sample", BENCH_MODE="sample",
           BENCH_ATTN="1", BENCH_PALLAS="1"),
    _bench("dcgan64-shard_map", BENCH_BACKEND="shard_map"),
    _bench("dcgan64-sample", BENCH_MODE="sample"),
    _bench("dcgan128-sample", BENCH_MODE="sample", BENCH_PRESET="dcgan128"),
    _bench("dcgan64-b256", BENCH_BATCH="256"),
    # batch-scaling series: the step is HBM-bandwidth-bound at batch 64
    # (DESIGN.md §1b), so img/s should keep rising with batch as weights
    # and optimizer traffic amortize — these rows are that curve
    _bench("dcgan64-b128", BENCH_BATCH="128"),
    _bench("dcgan64-b512", BENCH_BATCH="512"),
    _bench("dcgan64-b1024", BENCH_BATCH="1024"),
    _bench("dcgan64-accum4", BENCH_ACCUM="4"),
    _bench("stylegan64", BENCH_PRESET="stylegan64"),
    # Long-context IN-MODEL rows (DESIGN.md §8): self-attention over the
    # 128x128 feature map (S = 16384) inside a 256x256 DCGAN train step.
    # At batch 8 both forms fit and flash measures ~3.4x faster (the [S, S]
    # materialization is pure overhead); at the reference's batch-64
    # contract the dense form needs a 64 GiB f32[64, 16384, 16384] score
    # buffer and CANNOT allocate (the compiler names it in the error) —
    # its recorded failure is the measurement, and the flash row at the
    # same batch is the capability.
    _bench("dcgan256-attn128-flash", timeout=600, BENCH_SIZE="256",
           BENCH_ATTN_RES="128", BENCH_PALLAS="1", BENCH_BATCH="8",
           BENCH_STEPS="100", BENCH_SCAN="10"),
    _bench("dcgan256-attn128-dense", timeout=600, BENCH_SIZE="256",
           BENCH_ATTN_RES="128", BENCH_BATCH="8",
           BENCH_STEPS="100", BENCH_SCAN="10"),
    _bench("dcgan256-attn128-flash-b64", timeout=900, BENCH_SIZE="256",
           BENCH_ATTN_RES="128", BENCH_PALLAS="1", BENCH_BATCH="64",
           BENCH_STEPS="40", BENCH_SCAN="5"),
    _bench("dcgan256-attn128-dense-b64", timeout=600, BENCH_SIZE="256",
           BENCH_ATTN_RES="128", BENCH_BATCH="64",
           BENCH_STEPS="40", BENCH_SCAN="5"),
    # the named long-context preset (hinge + SN-D on top of the raw rows)
    _bench("sagan256-lc", timeout=900, BENCH_PRESET="sagan256-lc",
           BENCH_STEPS="40", BENCH_SCAN="5"),
    ("attention", "attn-crossover-small",
     [sys.executable, "tools/bench_attention.py",
      "--seq", "1024", "4096", "16384"], {}, 600),
    ("attention", "attn-crossover-wall",
     [sys.executable, "tools/bench_attention.py",
      "--seq", "32768", "40960", "45056", "49152", "65536"], {}, 900),
    ("attention", "attn-memory",
     [sys.executable, "tools/attention_memory.py",
      "--seq", "8192", "16384", "32768", "40960", "45056", "49152",
      "65536"],
     {}, 900),
    ("roofline", "matmul-rate", [sys.executable, "tools/matmul_rate.py"],
     {}, 600),
    ("roofline", "trainer-loop",
     [sys.executable, "tools/bench_trainer_loop.py"], {}, 900),
    ("fid", "fid-trajectory-chip",
     [sys.executable, "tools/fid_trajectory.py", "--preset", "cifar10-cond",
      "--snapshots", "0,500,2000,5000", "--num_samples", "10000", "--kid"],
     {}, 1800),
    # dense early-phase ladder for the same conditional preset: the long
    # trajectory's tail oscillates (GAN non-monotonicity — why best-FID
    # retention exists); the improvement-dominated early phase is where
    # the ranking signal must show, and this row measures it at scale
    ("fid", "fid-trajectory-cond-early",
     [sys.executable, "tools/fid_trajectory.py", "--preset", "cifar10-cond",
      "--snapshots", "0,100,250,500,1000", "--num_samples", "10000",
      "--kid"], {}, 1500),
    # the CANONICAL feature path at the 50k contract, stand-in embedder
    # (VERDICT r4 #4): torch tower -> convert_torch_embedder -> evals
    ("fid", "fid-50k-canonical-npz",
     [sys.executable, "tools/canonical_50k.py"], {}, 1500),
    ("realdata", "realdata-celeba64",
     [sys.executable, "tools/bench_realdata.py"], {}, 1200),
    ("loader", "loader-ceiling", [sys.executable, "tools/bench_loader.py"],
     {}, 900),
    # the default wire format's ceiling (uint8 since r4 — prepare.py)
    ("loader", "loader-ceiling-uint8",
     [sys.executable, "tools/bench_loader.py", "--record_dtype", "uint8"],
     {}, 900),
    # multi-process shard-ownership scaling + the host-core budget behind
    # "can the loader feed the 32.6k b512 peak" (VERDICT r4 #2)
    ("loader", "loader-scale",
     [sys.executable, "tools/bench_loader_scale.py", "--processes", "1",
      "2"], {}, 900),
    # CPU-bound, last: ~20 min of host time. Regenerates the cross-seed
    # rank-stability evidence.
    ("fid", "fid-seed-stability",
     [sys.executable, "tools/fid_seed_stability.py", "--platform", "cpu"],
     {"JAX_PLATFORMS": "cpu"}, 3600),
]


def run_step(section, label, argv, env, timeout, record):
    t0 = time.monotonic()
    row = {"date": _today(), "section": section, "label": label,
           "cmd": " ".join(argv)}
    try:
        res = subprocess.run(argv, cwd=REPO, env=dict(os.environ, **env),
                             timeout=timeout, capture_output=True, text=True)
        row["rc"] = res.returncode
        row["stderr_tail"] = (res.stderr or "")[-600:]
        parsed = []
        for line in (res.stdout or "").splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    parsed.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
        row["parsed"] = parsed
        m = re.search(r"ms_per_step=([0-9.]+)", res.stderr or "")
        if m:
            row["ms_per_step"] = float(m.group(1))
    except subprocess.TimeoutExpired:
        row["rc"] = None
        row["parsed"] = []
        row["stderr_tail"] = f"timed out after {timeout:.0f}s"
    row["elapsed_s"] = round(time.monotonic() - t0, 1)
    record(row)
    ok = row["rc"] == 0
    print(f"[capture_all] {label}: "
          f"{'ok' if ok else 'FAILED (' + str(row['rc']) + ')'} "
          f"in {row['elapsed_s']}s", file=sys.stderr)
    return ok, row


# ---------------------------------------------------------------------------
# Rendering: captures.jsonl -> marker-delimited doc blocks
# ---------------------------------------------------------------------------

def _load_captures():
    rows = []
    if os.path.exists(CAPTURES):
        with open(CAPTURES) as f:
            for line in f:
                line = line.strip()
                if line:
                    rows.append(json.loads(line))
    return rows


def _spread(values):
    """n / median / min / max over a value list (VERDICT r3 #5: best-of
    reporting alone hides the run-to-run swing)."""
    vs = sorted(values)
    n = len(vs)
    med = vs[n // 2] if n % 2 else (vs[n // 2 - 1] + vs[n // 2]) / 2
    return {"n": n, "median": med, "min": vs[0], "max": vs[-1]}


def _best_bench_rows(rows):
    """Per label: best successful value (matching bench.py's own
    best-of-windows policy) PLUS the spread over every successful capture,
    so the best is presented against the distribution it came from.

    Attention-bearing configs stamp a kernel generation into their JSON
    (bench.py; pre-stamp history is gen 0) and only captures at the HIGHEST
    generation present for a label enter the best/spread — a median over
    mixed kernel generations describes no code that exists (VERDICT r4 #1:
    the published sagan64-attn median was the superseded kernel's)."""
    by_label = {}
    for r in rows:
        if r["section"] not in ("headline", "matrix") or r["rc"] != 0:
            continue
        for p in r.get("parsed", []):
            if p.get("value") is None:
                continue
            by_label.setdefault(r["label"], []).append((p, r))
    best = {}
    for label, entries in by_label.items():
        top_gen = max(p.get("gen", 0) for p, _ in entries)
        entries = [(p, r) for p, r in entries if p.get("gen", 0) == top_gen]
        # same contract for preset revisions (presets.py::PRESET_REVS):
        # spread over the current preset config only. Missing stamps
        # default to 1 — unlisted presets ARE revision 1, so pre-stamp
        # history of unchanged configs stays in the spread (only history
        # behind an explicit bump is retired).
        top_rev = max(p.get("rev", 1) for p, _ in entries)
        entries = [(p, r) for p, r in entries if p.get("rev", 1) == top_rev]
        cur = {"value": -1.0,
               # show the generation only where a stamp exists — non-
               # attention configs have no kernel-generation concept
               "gen": top_gen if any("gen" in p for p, _ in entries)
               else None,
               "rev": top_rev if any("rev" in p for p, _ in entries)
               else None}
        values = []
        for p, r in entries:
            values.append(p["value"])
            if p["value"] > cur["value"]:
                cur.update(
                    value=p["value"], unit=p.get("unit", ""),
                    vs=p.get("vs_baseline"), metric=p.get("metric", ""),
                    ms=r.get("ms_per_step"), date=r["date"])
        cur.update(_spread(values))
        best[label] = cur
    return best


def _attention_rows(rows):
    """Latest result per (form, seq): ms or the error row (an allocation
    failure IS the measurement — the dense wall). Returns (timing, memory)
    maps; memory rows come from tools/attention_memory.py (temp_mib)."""
    out = {}
    mem = {}
    # Timing rows are selected as PAIRS: per seq, the single harvest run
    # whose dense+flash measurements (taken minutes apart) have
    # the lowest combined ms — a per-cell best-of would splice forms from
    # different windows and corrupt the dense/flash ratio the table exists
    # to show. Runs compete only within the HIGHEST kernel generation
    # present for that seq (bench_attention stamps ATTN_GEN into every
    # row; pre-tag history is gen 0), so measurements of superseded kernel
    # code never get published as the current kernels' numbers — the same
    # reason the memory branch keeps latest-only. A run with an error row
    # is only selected while no run of that generation has a complete pair
    # (the dense wall rows stay visible).
    pairs = {}   # seq -> {form: row} of the selected run
    for r in rows:
        if r["section"] != "attention":
            continue
        by_seq = {}
        for p in r.get("parsed", []):
            if "form" not in p or "seq" not in p:
                continue
            if r["label"] == "attn-memory":
                # memory rows are exact program properties of the CURRENT
                # kernels (the dense coefficient changed 8->6 bytes/S^2
                # with the precision policy) — keep the latest
                mem[(p["form"], p["seq"])] = dict(p, date=r["date"])
            else:
                by_seq.setdefault(p["seq"], {})[p["form"]] = \
                    dict(p, date=r["date"])
        def _score(cand):
            gen = max(p.get("gen", 0) for p in cand.values())
            oks = [p["ms"] for p in cand.values() if "ms" in p]
            # highest kernel generation first, then MOST ms-bearing forms
            # (a complete dense+flash pair must never lose to a single-form
            # run of the same generation just because the latter's sum(ms)
            # is smaller — advisor r4), then fastest window
            return (-gen, -len(oks), sum(oks))
        for seq, cand in by_seq.items():
            cur = pairs.get(seq)
            if cur is None or _score(cand) < _score(cur):
                pairs[seq] = cand
    for cand in pairs.values():
        for p in cand.values():
            out[(p["form"], p["seq"])] = p
    return out, mem


def _label_output_size(label):
    """Pixel resolution (H == W) of a bench label's workload, or None.

    The join key for the train table's workload-honest Mpx/s column
    (VERDICT Weak #2): resolution comes from the preset registry when the
    label IS a preset, else from the family token's trailing digits
    ("dcgan256-attn128-flash" -> 256 — the b<batch>/attn<res>/accum<k>
    tokens are knobs, not resolutions), with the cifar10 names pinned to
    their 32x32 workload.
    """
    try:
        from dcgan_tpu.presets import get_preset

        return get_preset(label).model.output_size
    except Exception:
        pass
    for tok in label.split("-"):
        if "cifar10" in tok:
            return 32
        m = re.fullmatch(r"([a-z]+)(\d+)", tok)
        if m and m.group(1) not in ("b", "attn", "accum", "x", "rev",
                                    "gen"):
            return int(m.group(2))
    return None


def _mpx_cell(label, img_per_sec):
    """Formatted Mpx/s (img/s x H x W / 1e6) or an em-dash."""
    size = _label_output_size(label)
    if not size or not isinstance(img_per_sec, (int, float)):
        return "—"
    return f"{img_per_sec * size * size / 1e6:.1f}"


def _render_roofline(rows):
    """Roofline group: matmul sweep (best per shape), trainer hot loop
    (best + spread)."""
    shapes = {}      # (m, n) -> best tflops row (+date)
    trainer = []
    for r in rows:
        if r["section"] != "roofline" or r["rc"] != 0:
            continue
        for p in r.get("parsed", []):
            if p.get("form") == "matmul":
                # older captures predate the K dim (square chains: K = N)
                key = (p["m"], p.get("k", p["n"]), p["n"])
                if key not in shapes or p["tflops"] > shapes[key]["tflops"]:
                    shapes[key] = dict(p, date=r["date"])
            elif p.get("label") == "trainer-loop" and \
                    p.get("images_per_sec_chip"):
                trainer.append(dict(p, date=r["date"]))
    out = []
    if shapes:
        out += ["Roofline: sustained bf16 matmul rate (tools/"
                "matmul_rate.py, best per shape) — the "
                "MFU denominator, regenerated with every harvest:", "",
                "| shape (M×K×N) | TFLOP/s | ms/matmul | captured |",
                "|---|---|---|---|"]
        for (m, k, n) in sorted(shapes):
            p = shapes[(m, k, n)]
            out.append(f"| {m}×{k}×{n} | {p['tflops']} | "
                       f"{p['ms_per_matmul']} | {p['date']} |")
    if trainer:
        best = max(trainer, key=lambda p: p["images_per_sec_chip"])
        sp = _spread([p["images_per_sec_chip"] for p in trainer])
        out += ["", f"Real trainer hot loop (tools/bench_trainer_loop.py — "
                f"`python -m dcgan_tpu.train --synthetic` with a device-"
                f"cached batch pool, steps_per_call "
                f"{best['steps_per_call']}): best "
                f"{best['images_per_sec_chip']:.0f} img/s/chip "
                f"({best['ms_per_step']} ms/step, {best['date']}); median "
                f"{sp['median']:.0f} over n={sp['n']} run(s). Chip-bound "
                "regime: the synthetic pool isolates the loop from the "
                "host->device feed."]
    return out


def _render_block(path, block_lines):
    text = ""
    if os.path.exists(path):
        with open(path) as f:
            text = f.read()
    block = BEGIN + "\n" + "\n".join(block_lines) + "\n" + END
    if BEGIN in text:
        # repl as a callable: captured error text may contain backslash
        # sequences re.sub would misread as replacement escapes
        text = re.sub(re.escape(BEGIN) + r".*?" + re.escape(END),
                      lambda m: block, text, flags=re.S)
    else:
        text = text.rstrip() + "\n\n" + block + "\n"
    with open(path, "w") as f:
        f.write(text)


def render_docs() -> None:
    rows = _load_captures()

    bench = _best_bench_rows(rows)
    # inference (BENCH_MODE=sample) rows get their own table: their
    # "ms" is per ~1024-image dispatch, not per 64-image train step —
    # mixing the columns would misread as a 16x per-step slowdown
    train = {k: v for k, v in bench.items()
             if "sampler" not in v.get("metric", "")}
    sample = {k: v for k, v in bench.items()
              if "sampler" in v.get("metric", "")}
    lines = ["## Chip captures (tools/capture_all.py)", ""]

    def _sp(b):
        if b["n"] < 2:
            return f"(n={b['n']})"
        return (f"{b['median']:.0f} (n={b['n']}, "
                f"{b['min']:.0f}–{b['max']:.0f})")

    if train:
        lines += ["Best successful capture per config, with the spread of "
                  "ALL successful captures (median, n, min–max) — the "
                  "best column alone would hide the run-to-run swing; "
                  "see README \"Benchmarks\" "
                  "for methodology. Attention configs are tagged with the "
                  "kernel generation (ops/pallas_attention.py::ATTN_GEN) "
                  "their captures come from; best and spread include only "
                  "the highest generation on record, so both columns "
                  "describe the current kernel code. Mpx/s is the "
                  "workload-honest pixel rate (img/s × H×W): a 256² row "
                  "moves 16× the pixels of a 64² row per image, so its "
                  "img/s — and the vs-baseline ratio derived from it — "
                  "understates the work by that factor:", "",
                  "| Config | best img/s/chip | Mpx/s | "
                  "median (n, min–max) | ms/step | vs baseline | "
                  "captured |",
                  "|---|---|---|---|---|---|---|"]
        for label in sorted(train):
            b = train[label]
            ms = f"{b['ms']:.2f}" if b.get("ms") else "—"
            vs = f"{b['vs']:.2f}×" if b.get("vs") is not None else "—"
            tag = (f" (attn gen {b['gen']})" if b.get("gen") is not None
                   else "")
            if b.get("rev") and b["rev"] > 1:
                tag += f" (rev {b['rev']})"
            lines.append(f"| {label}{tag} | {b['value']} | "
                         f"{_mpx_cell(label, b['value'])} | {_sp(b)} | "
                         f"{ms} | {vs} | {b['date']} |")
    if sample:
        lines += ["", "Inference (sampler path, `BENCH_MODE=sample` — "
                  "ms is per generation dispatch at the batch named in "
                  "the metric, not per train step):", "",
                  "| Config | best img/s/chip | median (n, min–max) | "
                  "ms/dispatch | captured |", "|---|---|---|---|---|"]
        for label in sorted(sample):
            b = sample[label]
            ms = f"{b['ms']:.2f}" if b.get("ms") else "—"
            # same provenance tags as the train table: gen filtering
            # applies to these rows too, so it must be visible
            tag = (f" (attn gen {b['gen']})" if b.get("gen") is not None
                   else "")
            if b.get("rev") and b["rev"] > 1:
                tag += f" (rev {b['rev']})"
            lines.append(f"| {label}{tag} | {b['value']} | {_sp(b)} | {ms} "
                         f"| {b['date']} |")
    else:
        lines += ["No successful chip captures yet (every attempt is "
                  "logged in `tools/captures.jsonl`)."]
    realdata = [r for r in rows
                if r["section"] == "realdata" and r["rc"] == 0
                and r.get("parsed")]
    if realdata:
        last = realdata[-1]  # latest complete run (rows are a matched set)
        lines += ["", f"Real-data loader-vs-chip balance "
                  f"(tools/bench_realdata.py, {last['date']}) — the "
                  "same compiled step fed from records and from the "
                  "synthetic stream:", "",
                  "| Source | img/s | vs synthetic |", "|---|---|---|"]
        for p in last["parsed"]:
            if "source" in p:
                lines.append(f"| {p['source']} | {p['value']} | "
                             f"{p.get('vs_synthetic', '—')} |")
    # canonical-path certification row (VERDICT r4 #4): its own paragraph,
    # not a trajectory table (one score, no steps axis)
    canon = [(p, r["date"]) for r in rows
             if r["label"] == "fid-50k-canonical-npz" and r["rc"] == 0
             for p in r.get("parsed", []) if "fid" in p]
    if canon:
        p, date = canon[-1]
        lines += ["", f"Canonical feature path at the 50k contract "
                  f"(tools/canonical_50k.py, {date}): a random-weight "
                  "torch conv tower "
                  f"({p.get('embedder', '?')}) exported, converted through "
                  "tools/convert_torch_embedder.py's .npz schema, and "
                  "scored end-to-end by `python -m dcgan_tpu.evals "
                  f"--feature_npz ...` over {p['num_samples']:,} samples "
                  f"per side (feature dim {p.get('feature_dim')}, "
                  f"{p.get('elapsed_s', '?')} s wall) — FID "
                  f"{p['fid']:.4f}, KID "
                  f"{(p['kid'] or 0):.6f}. The score itself is arbitrary "
                  "(random embedder, random generator); the row certifies "
                  "that the NON-surrogate eval path — the one real "
                  "Inception/trained-tower weights ride — executes the "
                  "full contract. See README \"Canonical FID\" for the "
                  "one-command recipe with real weights."]
    fid_rows = [r for r in rows
                if r["section"] == "fid" and r["rc"] == 0
                and r["label"] != "fid-50k-canonical-npz"
                and any("fid" in p for p in r.get("parsed", []))]
    # latest complete trajectory PER LABEL (each label is its own ladder —
    # e.g. the long oscillating-tail run vs the dense early-phase run)
    latest_by_label = {}
    for r in fid_rows:
        latest_by_label[r["label"]] = r
    for label in sorted(latest_by_label):
        last = latest_by_label[label]
        lines += ["", f"Chip FID/KID trajectory ({last['label']}, surrogate "
                  f"features, {last['date']} — `{last['cmd']}`):", "",
                  "| Step | surrogate FID | KID (×10³) |", "|---|---|---|"]
        for p in last["parsed"]:
            if "fid" in p:
                kid = (f"{p['kid'] * 1e3:.3f}" if p.get("kid") is not None
                       else "—")  # --kid is optional in fid_trajectory.py
                lines.append(f"| {p['step']} | {p['fid']:.4f} | {kid} |")
        summ = next((p for p in last["parsed"] if "monotonic" in p), None)
        if summ:
            lines += ["", f"monotonic={summ['monotonic']}, "
                      f"Spearman(steps, FID)="
                      f"{summ['spearman_steps_vs_fid']:.2f} over "
                      f"{summ['snapshots']} snapshots."]
    loader = [(p, r["date"]) for r in rows
              if r["section"] == "loader" and r["rc"] == 0
              for p in r["parsed"] if "images_per_sec" in p]
    if loader:
        # best capture per wire format, like the bench rows — with the
        # spread shown: the 1-core host swings ~2x run-to-run (and
        # harvests often share the core), which the best alone would hide
        lines += ["", "Loader re-check (CPU-bound, one host core), per "
                  "wire format:"]
        dtypes = sorted({p.get("record_dtype", "?") for p, _ in loader})
        for dt in dtypes:
            rows_dt = [(p, d) for p, d in loader
                       if p.get("record_dtype", "?") == dt]
            peak, date = max(rows_dt, key=lambda v: v[0]["images_per_sec"])
            sp = _spread([p["images_per_sec"] for p, _ in rows_dt])
            lines += [f"- {dt}: best {peak['images_per_sec']:.0f} img/s "
                      f"({peak.get('threads', '?')} threads, {date}); "
                      f"median {sp['median']:.0f}, range "
                      f"{sp['min']:.0f}–{sp['max']:.0f} over n={sp['n']} "
                      "captures."]
    scale = [(p, r["date"]) for r in rows
             if r["section"] == "loader" and r["rc"] == 0
             for p in r.get("parsed", [])
             if p.get("label") == "loader-scale"]
    if scale:
        cores = scale[-1][0].get("cores_visible", "?")
        lines += ["", f"Loader scaling by process-level shard ownership "
                  f"(tools/bench_loader_scale.py — M loader processes, "
                  f"each owning its `shard_for_process` slice, one shared "
                  f"measurement window; this host exposes {cores} "
                  "core(s), `os.sched_getaffinity`):", "",
                  "| processes | aggregate img/s | per-process img/s | "
                  "captured |", "|---|---|---|---|"]
        best_by_m = {}
        for p, d in scale:
            m = p["processes"]
            if m not in best_by_m or p["aggregate_images_per_sec"] > \
                    best_by_m[m][0]["aggregate_images_per_sec"]:
                best_by_m[m] = (p, d)
        for m in sorted(best_by_m):
            p, d = best_by_m[m]
            pp = ", ".join(f"{v:.0f}"
                           for v in p["per_process_images_per_sec"])
            lines.append(f"| {m} | {p['aggregate_images_per_sec']:.0f} | "
                         f"{pp} | {d} |")
        # host-core budget (VERDICT r4 #2): the per-core uint8 rate vs the
        # measured chip peak, derived from this same captures log so the
        # paragraph regenerates with every harvest
        # per-core uint8 rate: best of the single-thread-pool ceilings AND
        # the scale tool's M=1 row (same quantity, measured on the quiet
        # host through the shard-ownership path)
        uint8 = [p["images_per_sec"] for p, _ in loader
                 if p.get("record_dtype") == "uint8"]
        uint8 += [v for p, _ in scale
                  if p["processes"] == 1 and p.get("record_dtype") == "uint8"
                  for v in p["per_process_images_per_sec"]]

        def _vals(label):
            return [p["value"] for r in rows
                    if r["label"] == label and r["rc"] == 0
                    for p in r.get("parsed", []) if p.get("value")]

        peak_rows = _vals("dcgan64-b512")
        b64_rows = _vals("dcgan64-headline")
        if uint8 and peak_rows:
            per_core = max(uint8)
            peak = max(peak_rows)
            need = int((peak + per_core - 1) // per_core) if per_core else 0
            lines += ["", f"**Host-core budget at the peak-batch regime:** "
                      f"the b512 chip peak consumes {peak:,.0f} img/s/chip "
                      f"while one host core decodes uint8 records at "
                      f"{per_core:,.0f} img/s best — so the peak regime "
                      f"needs ~{need} loader processes on {need} host "
                      "cores per chip (per-process shard ownership; no "
                      "shared state). This build host exposes "
                      f"{cores} core(s) (the flat aggregate above is that "
                      "measurement, not a design ceiling); production TPU "
                      "hosts expose tens to hundreds."]
            if b64_rows:
                b64 = max(b64_rows)
                n64 = int((b64 + per_core - 1) // per_core) if per_core \
                    else 0
                lines[-1] += (
                    f" At the reference's batch-64 contract "
                    f"({b64:,.0f} img/s best) {n64} core(s) suffice at the "
                    "best-capture loader rate.")

    # roofline section (VERDICT r3 #1/#4): sustained matmul rate and the
    # real trainer loop measured as one group
    roof_lines = _render_roofline(rows)
    if roof_lines:
        lines += [""] + roof_lines
    _render_block(BASELINE_MD, lines)

    attn, attn_mem = _attention_rows(rows)
    lines = ["### Measured attention crossovers (chip)", ""]
    if attn:
        lines += ["| Form | S | ms (fwd+bwd) | status | captured |",
                  "|---|---|---|---|---|"]
        for (form, seq) in sorted(attn, key=lambda k: (k[1], k[0])):
            p = attn[(form, seq)]
            if "ms" in p:
                lines.append(f"| {form} | {seq} | {p['ms']:.2f} | ok | "
                             f"{p['date']} |")
            else:
                # table-safe error: first line only, ANSI stripped, bounded
                err = re.sub(r"\x1b\[[0-9;]*m", "",
                             p.get("error", "failed")).splitlines()[0][:90]
                lines.append(f"| {form} | {seq} | — | {err} | {p['date']} |")
    else:
        lines += ["Not measured on the current machine. CPU-side scaling "
                  "evidence is in the table above; "
                  "`python tools/capture_all.py` harvests this table."]
    if attn_mem:
        lines += ["", "Scratch-HBM requirement per compiled fwd+bwd "
                  "program (`compiled.memory_analysis()`, "
                  "tools/attention_memory.py — exact program requirements, "
                  "no execution involved; a compile failure at a size whose "
                  "dense requirement exceeds HBM IS the memory wall):", "",
                  "| Form | S | temp HBM (MiB) | captured |",
                  "|---|---|---|---|"]
        for (form, seq) in sorted(attn_mem, key=lambda k: (k[1], k[0])):
            p = attn_mem[(form, seq)]
            if p.get("temp_mib") is not None:
                lines.append(f"| {form} | {seq} | {p['temp_mib']} | "
                             f"{p['date']} |")
            else:
                err = re.sub(r"\x1b\[[0-9;]*m", "",
                             p.get("error", "failed")).splitlines()[0][:70]
                lines.append(f"| {form} | {seq} | — ({err}) | {p['date']} |")
    _render_block(DESIGN_MD, lines)
    print(f"[capture_all] rendered {len(bench)} bench row(s), "
          f"{len(attn)} attention row(s)", file=sys.stderr)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--only", nargs="+", default=None,
                   help="run only these sections "
                        "(headline matrix attention fid realdata loader)")
    p.add_argument("--skip", nargs="+", default=[],
                   help="skip these sections")
    p.add_argument("--labels", nargs="+", default=None,
                   help="run only these step labels (targeted re-captures; "
                        "composes with --only/--skip)")
    p.add_argument("--render-only", action="store_true")
    args = p.parse_args(argv)

    if args.render_only:
        render_docs()
        return

    os.makedirs(os.path.dirname(CAPTURES), exist_ok=True)

    def record(row):
        with open(CAPTURES, "a") as f:
            f.write(json.dumps(row) + "\n")

    ran = failures = 0
    for section, label, argv_, env, timeout in STEPS:
        if args.only and section not in args.only:
            continue
        if section in args.skip:
            continue
        if args.labels and label not in args.labels:
            continue
        ok, row = run_step(section, label, argv_, env, timeout, record)
        ran += 1
        if not ok:
            failures += 1
    render_docs()
    print(f"[capture_all] done: {ran} step(s) run, {failures} failed",
          file=sys.stderr)


if __name__ == "__main__":
    main()
