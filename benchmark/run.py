"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` also
`breakdown`, and last `check`: every number compared beside its limit.
Exits non-zero and prints no result when JAX finds no accelerator from the
table of peaks or fewer chips than the cell asks for.
"""

import time

T_START = time.time()   # set-up is counted from here

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def configure_cache() -> str:
    """Put JAX's persistent compile cache where the environment says, else
    at a fixed path in the checkout (the path is part of the cache's key).
    Returns the benchmark's own cache directory (records live there)."""
    import jax

    cache_root = os.path.join(ROOT, ".bench_cache")
    jax.config.update(
        "jax_compilation_cache_dir",
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(cache_root, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_root


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from benchmark import manifest

    try:
        cell = manifest.cell(ROOT, args.workload)
        driver = manifest.driver(ROOT, cell.traffic.get("kind", ""))
    except manifest.ManifestError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    cache_root = configure_cache()
    import jax

    devices = jax.devices()
    try:
        manifest.peaks(ROOT, devices[0].device_kind)
    except manifest.ManifestError as e:
        print(f"benchmark: no accelerator to measure on: {e}", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"benchmark: {args.workload} needs {cell.chips} chips, JAX "
              f"finds {len(devices)}", file=sys.stderr)
        return 3

    result = driver.run(cell, seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        t_start=T_START, devices=devices,
                        cache_root=cache_root)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
