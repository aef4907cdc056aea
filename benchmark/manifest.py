"""`BENCHMARK.json` and the by-name lookup of everything a cell needs.

Nothing here knows a cell, a configuration, a model family, a traffic mix
or a metric by name: a later PR adds `benchmark/configs/<config>.json`,
`benchmark/families/<family>.py`, `benchmark/traffic/<mix>.json`,
`benchmark/limits/<cell>.json`, `benchmark/layer_metrics/<metric>.py` and
entries in `BENCHMARK.json`, and edits no file that is there. `root` is the
directory that holds `BENCHMARK.json` (the checkout; a temporary directory
in the tests).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import zlib
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = "benchmark"


class ManifestError(ValueError):
    """`BENCHMARK.json` or a file it names is missing or malformed."""


def _load_json(path: str) -> Any:
    if not os.path.isfile(path):
        raise ManifestError(f"missing file: {path}")
    with open(path) as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as e:
            raise ManifestError(f"{path}: {e}") from None


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of `workloads`, with its files resolved and loaded."""
    root: str
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # the configuration file
    traffic_name: str
    traffic: Dict[str, Any]         # the traffic mix file
    limits: Dict[str, float]        # check number -> limit
    end_to_end: List[Dict[str, Any]]   # metric entries this cell reports
    per_layer: List[Dict[str, Any]]


def load(root: str) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(
        f"unknown {what} {name!r}; known: {sorted(e['name'] for e in entries)}")


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(root: str, name: str, bench: Optional[dict] = None) -> Cell:
    """Resolve a cell: its configuration file (the path `BENCHMARK.json`
    gives), `traffic/<mix>.json` and `limits/<cell>.json`."""
    bench = bench or load(root)
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "config")
    e2e = [m for m in bench["end_to_end"] if _reported_in(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _reported_in(m, name) and m["moves"] in moved]
    return Cell(
        root=root, name=name, chips=int(w["chips"]), config_name=w["config"],
        config=_load_json(os.path.join(root, c["file"])),
        traffic_name=w["traffic"],
        traffic=_load_json(os.path.join(root, BENCH_DIR, "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(root, BENCH_DIR, "limits",
                                       name + ".json")),
        end_to_end=e2e, per_layer=layer)


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise ManifestError(f"missing file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod   # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def layer_metric_reader(root: str, metric: str) -> Callable:
    """`read(ctx) -> float | None` of `layer_metrics/<metric>.py`."""
    mod = _load_module(
        os.path.join(root, BENCH_DIR, "layer_metrics", metric + ".py"),
        "bench_layer_metric_" + metric.replace(".", "_").replace("-", "_"))
    if not callable(getattr(mod, "read", None)):
        raise ManifestError(f"layer metric {metric!r} has no read(ctx)")
    return mod.read


def driver(root: str, kind: str):
    """The module that drives traffic of this kind, `drivers/<kind>.py`.
    A mix whose kind has no driver yet is data the harness accepts and
    refuses to run."""
    path = os.path.join(root, BENCH_DIR, "drivers", kind + ".py")
    if not os.path.isfile(path):
        raise ManifestError(
            f"traffic kind {kind!r} has no driver module yet ({path})")
    return _load_module(path, "bench_driver_" + kind)


FAMILY_API = ("step_ops", "kernel_costs", "batch_shape", "draw_batch",
              "drawn", "draw_leaf", "initial_state", "program_readings",
              "reference_readings", "numbers", "variants")


def family(root: str, config: Dict[str, Any]):
    """The module of the configuration's model family,
    `families/<family>.py`, named by the configuration file's `family` key:
    the operation counts, the draw of inputs and weights, the readings, the
    plain reference and its variants (benchmark/README.md lists the
    functions). The path decides the module, so a temporary root loads its
    own copy; the same path is loaded once."""
    name = config.get("family")
    if not isinstance(name, str) or not name:
        raise ManifestError(
            f"configuration {config.get('name')!r} names no `family`")
    path = os.path.join(root, BENCH_DIR, "families", name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(
            f"model family {name!r} has no module yet ({path})")
    mod_name = f"bench_family_{name}_{zlib.crc32(path.encode()):08x}"
    mod = sys.modules.get(mod_name) or _load_module(path, mod_name)
    missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
    if missing:
        raise ManifestError(f"model family {name!r} lacks {missing}")
    return mod


def peaks(root: str, device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a device that is not in the table is an
    error, not a default."""
    table = _load_json(os.path.join(root, BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise ManifestError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(table['devices'])}): no device metric can be reported")
    return table["devices"][device_kind]
