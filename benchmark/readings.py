"""The readings a cell's limits are set from (not part of a benchmark run).

    python3 benchmark/readings.py --workload <cell> --seeds 12 [--variants reference_fp8:3,half_batch:1] [--out <file.json>]
    python3 benchmark/readings.py --rejudge <file.json>

In one process, at the cell's own size: the program as the configuration
states it against the plain reference on `--seeds` seeds (the lower
readings), and each of the `--variants` on the first `:n` of them. The
variants are the model family's (`variants` of `families/<family>.py`, each
a set of keyword arguments of its `reference_readings` and whether it has
to come out correct); for the `gan` family:
- the control: the reference put in the program's place with the operands
  of every matmul and convolution rounded to fp8 (`reference_fp8`);
- a witness: the reference with bfloat16 operands (`reference_bf16`), which
  shows how far rounding at the stated precision alone moves each number
  and has to come out correct;
- the faults, planted in the reference put in the program's place: half of
  the batch left out, the mean taken over the rest (`half_batch`), and on
  several chips the exchange left out, each chip keeping to its own rows
  (`no_exchange`: the first chip's rows alone).
Every set of numbers goes through `check.judge` with the cell's committed
limits; the exit code is 0 only if every sound run and the witness come
out correct and the control and every fault do not. `--rejudge` does the
same to a saved file (no chip needed), after the limits were set from it.
The program's phases run first and are freed before the reference takes the
chip: both executables' temporaries do not fit side by side. The first
program phase also prints the step's `memory_analysis()` beside the
runtime's memory counters (PERF.md section 3).
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

STATED = "stated"    # the program as the configuration states it


def memory_facts(train, prog, state, images, key, devices) -> dict:
    """What the chip's compiler says the step needs, beside what the
    runtime's counters say is taken with the step loaded."""
    stats = devices[0].memory_stats() or {}
    facts = {"memory_stats": {k: int(v) for k, v in stats.items()
                              if isinstance(v, (int, float))},
             **train.memory_now(devices)}
    try:
        ma = prog.pt.step.lower(state, images, key).compile().memory_analysis()
        facts["step_memory_analysis"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "code_bytes": ma.generated_code_size_in_bytes}
    except Exception as e:   # a reading beside the point of this script
        facts["step_memory_analysis"] = repr(e)
    return facts


def program_phase(train, cell, devices, seeds, cache_root):
    import jax
    import numpy as np

    prog = train.build_program(cell, devices)     # reads every reading
    fed = cell.traffic["feed"] == "records"
    out, facts = {}, {}
    for seed in seeds:
        state = train.initial_state(prog, seed)
        feed, close = train.make_feed(cell, prog, seed, cache_root)
        try:
            state, base, read, kept = train.first_steps(prog, state, feed,
                                                        seed, keep_batches=fed)
            if not facts:
                facts = memory_facts(train, prog, state, next(feed),
                                     jax.random.fold_in(base, 99), devices)
                print(json.dumps({"memory": facts}), file=sys.stderr,
                      flush=True)
            extra = {}
            if cell.chips > 1:
                extra["replica_gap"] = train.replica_gap(state["params"],
                                                         cell.chips)
        finally:
            close()
        out[seed] = (read, [np.asarray(b) for b in kept], extra)
        del state, kept, feed
    inputs = prog.inputs
    del prog
    gc.collect()
    return out, inputs, facts


def judge_rows(rows: dict, limits: dict, check, must_pass=()) -> dict:
    """Every variant of every seed through `check.judge`; the summary with
    each number's lower reading (largest of the sound runs) and each
    variant's smallest, and whether all came out as they have to: the
    sound runs and the variants named in `must_pass` (a witness) correct,
    every other variant (the control, the faults) not."""
    verdicts, as_due = {}, True
    for seed, row in rows.items():
        for variant, numbers in row.items():
            if variant == "raw":
                continue
            ok = check.judge(numbers, limits)["correct"]
            verdicts.setdefault(variant, {})[str(seed)] = ok
            as_due = as_due and ok == (variant == STATED
                                       or variant in must_pass)
    stated = [r[STATED] for r in rows.values()]
    summary = {"limits": limits,
               "lower": {n: max(r[n] for r in stated) for n in stated[0]}}
    for variant in verdicts:
        got = [r[variant] for r in rows.values() if variant in r]
        if variant != STATED:
            summary[variant] = {n: min(g[n] for g in got) for n in got[0]}
    summary["correct"] = verdicts
    summary["all_as_due"] = as_due
    return summary


def cell_variants(cell) -> dict:
    from benchmark import manifest

    mix = cell.traffic
    return manifest.family(cell.root, cell.config).variants(
        cell.config, int(mix["per_chip_batch"]) * int(mix["chips"]),
        cell.chips)


def rejudge(path: str) -> int:
    from benchmark import check, manifest

    with open(path) as f:
        saved = json.load(f)
    cell = manifest.cell(ROOT, saved["workload"])
    summary = judge_rows(saved["rows"], cell.limits, check,
                         must_pass_of(cell_variants(cell)))
    print(json.dumps({"workload": saved["workload"], **summary}, indent=1))
    return 0 if summary["all_as_due"] else 1


def must_pass_of(variants: dict) -> tuple:
    return tuple(n for n, v in variants.items() if v["must_pass"])


def strip(read: dict) -> dict:
    """The readings a file can hold: those made of numbers, not of leaves."""
    import jax
    import numpy as np

    return {k: v for k, v in read.items()
            if all(np.ndim(x) == 0 for x in jax.tree.leaves(v))}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload")
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=2_500_000_001)
    p.add_argument("--variants", default="3", help="name:n,name:n ... or "
                   "one n for every variant of the cell's family")
    p.add_argument("--out")
    p.add_argument("--rejudge", metavar="FILE")
    args = p.parse_args(argv)
    if args.rejudge:
        return rejudge(args.rejudge)
    if not args.workload:
        p.error("--workload is required")

    import jax

    from benchmark import check, manifest
    from benchmark.run import configure_cache

    cache_root = configure_cache()
    cell = manifest.cell(ROOT, args.workload)
    train = manifest.driver(ROOT, cell.traffic["kind"])
    devices = jax.devices()
    manifest.peaks(ROOT, devices[0].device_kind)   # a chip, or an error
    devices = devices[:cell.chips]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    variants = cell_variants(cell)
    counts = (dict.fromkeys(variants, args.variants)
              if args.variants.isdigit()
              else dict(v.split(":") for v in args.variants.split(",")))
    wanted = {name: seeds[:int(n)] for name, n in counts.items()}
    t0 = time.time()

    stated, inputs, memory = program_phase(
        train, cell, devices, seeds, cache_root)
    t_prog = time.time() - t0

    rows = {}
    ref_s = []
    for seed in seeds:
        read, delivered, extra = stated.pop(seed)
        batches, feed_numbers = train.check_batches(cell, inputs, seed,
                                                    delivered)
        feed_numbers.update(extra)
        t = time.time()
        ref = train.reference_readings(cell, inputs, seed, batches)
        ref_s.append(time.time() - t)
        numbers = inputs.family.numbers
        rows[seed] = {STATED: {**numbers(read, ref, inputs.mesh),
                               **feed_numbers},
                      "raw": {"program": strip(read),
                              "reference": strip(ref)}}
        for name, variant in variants.items():
            if seed not in wanted.get(name, ()):
                continue
            got = train.reference_readings(cell, inputs, seed, batches,
                                           **variant["kwargs"])
            rows[seed][name] = {**numbers(got, ref, inputs.mesh),
                                **feed_numbers}
            rows[seed]["raw"][name] = strip(got)
            del got
        del batches, read, ref
        print(json.dumps({"seed": seed, **{k: v for k, v in rows[seed].items()
                                           if k != "raw"}}),
              file=sys.stderr, flush=True)
    gc.collect()

    summary = judge_rows(rows, cell.limits, check, must_pass_of(variants))
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "chips": cell.chips, "seeds": seeds,
           "variant_seeds": wanted,
           "program_phase_s": t_prog, "reference_s_per_seed": ref_s,
           "total_s": time.time() - t0, "memory": memory,
           "summary": summary, "rows": {str(k): v for k, v in rows.items()}}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(summary, indent=1))
    return 0 if summary["all_as_due"] else 1


if __name__ == "__main__":
    sys.exit(main())
