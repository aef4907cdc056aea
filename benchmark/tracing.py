"""From the profiler's trace to numbers: the reduction every PR shares.

`jax.profiler` writes one `.xplane.pb` per traced window. A TPU plane
(`/device:TPU:<n>`) carries the line `XLA Modules` (one event per program
execution), `XLA Ops` (one per HLO operation on the core, named by the
operation's full HLO text) and `Async XLA Ops` (copies and collectives in
flight, overlapping the core's operations). The host plane's `python3` line
carries the harness's own `bench_*` annotations on the same clock (checked
on a chip trace: the first step's dispatch and its start on the device lie
1.2 ms apart).

`load_xplane` cuts that down to a `Trace` of plain tuples, which is also
what the recorded fixture of the tests holds; everything below works on a
`Trace` and knows nothing of the profiler.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, str, float, float]      # name, kind, start_ns, dur_ns
Span = Tuple[float, float]

WINDOW = "bench_window"
HOST_SPANS = ("bench_next", "bench_step", "bench_readback")
KINDS = ("pallas", "convolution", "collective", "other")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")


def classify(hlo_text: str) -> str:
    """Class of one device operation from its HLO text."""
    if 'custom_call_target="tpu_custom_call"' in hlo_text:
        return "pallas"
    head, _, body = hlo_text.partition(" = ")
    for c in _COLLECTIVES:
        if c in head or re.search(rf"\s{c}(-start|-done)?\(", body):
            return "collective"
    # XLA:TPU roots a fusion that holds a convolution or a matmul as
    # kind=kOutput, whatever it names it (89% of the dcgan128 step's time
    # sits in such fusions, most of them named after their elementwise tail)
    if ("kind=kOutput" in body or "convolution" in head
            or re.search(r"\s(convolution|dot)\(", body)):
        return "convolution"
    return "other"


def short_name(hlo_text: str) -> str:
    return hlo_text.partition(" = ")[0].lstrip("%")[:64]


@dataclasses.dataclass
class DeviceTrace:
    modules: List[Event]
    ops: List[Event]
    async_ops: List[Event]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, DeviceTrace]
    host: List[Event]                       # the harness's annotations

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        def ev(rows):
            return [(r[0], r[1], float(r[2]), float(r[3])) for r in rows]
        return Trace(
            devices={n: DeviceTrace(ev(d["modules"]), ev(d["ops"]),
                                    ev(d["async_ops"]))
                     for n, d in obj["devices"].items()},
            host=ev(obj["host"]))


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, DeviceTrace] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DeviceTrace([], [], [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [(re.sub(r"\(\d+\)$", "", e.name), "module",
                                    e.start_ns, e.duration_ns)
                                   for e in line.events]
                elif line.name == "XLA Ops":
                    dev.ops = [(short_name(e.name), classify(e.name),
                                e.start_ns, e.duration_ns)
                               for e in line.events]
                elif line.name == "Async XLA Ops":
                    dev.async_ops = [(short_name(e.name), classify(e.name),
                                      e.start_ns, e.duration_ns)
                                     for e in line.events]
            devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, "host", e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith("bench_")]
    return Trace(devices, sorted(host, key=lambda e: e[2]))


# --- interval arithmetic ----------------------------------------------------

def merge(spans: Iterable[Span]) -> List[Span]:
    out: List[List[float]] = []
    for lo, hi in sorted(spans):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def total(spans: Iterable[Span]) -> float:
    return sum(hi - lo for lo, hi in spans)


def subtract(a: List[Span], b: List[Span]) -> List[Span]:
    """The parts of merged `a` that merged `b` does not cover."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _clip(events: Iterable[Event], window: Span,
          kinds: Optional[Tuple[str, ...]] = None) -> List[Span]:
    lo, hi = window
    return [(max(s, lo), min(s + d, hi)) for _, k, s, d in events
            if (kinds is None or k in kinds) and s + d > lo and s < hi]


# --- the reduction ------------------------------------------------------------

def window_of(trace: Trace) -> Optional[Span]:
    """The traced window: the harness's `bench_window` annotation, else the
    span of the device's own events."""
    for name, _, start, dur in trace.host:
        if name == WINDOW:
            return (start, start + dur)
    spans = [(s, s + d) for dev in trace.devices.values()
             for _, _, s, d in dev.modules or dev.ops]
    if not spans:
        return None
    return (min(s for s, _ in spans), max(e for _, e in spans))


def busy_spans(dev: DeviceTrace, window: Span) -> List[Span]:
    """Merged intervals in which an operation ran on this device's core."""
    return merge(_clip(dev.ops or dev.modules, window))


def reduce(trace: Trace) -> Optional[dict]:
    """Everything the per-layer readers and the result line take from a
    trace, or None where no operation ran on a device in the window."""
    window = window_of(trace)
    if window is None or not trace.devices:
        return None
    names = sorted(trace.devices)
    busy = {n: busy_spans(trace.devices[n], window) for n in names}
    busy_s = sum(total(b) for b in busy.values()) / len(names) / 1e9
    if busy_s <= 0:
        return None
    first = trace.devices[names[0]]
    # programs: the one with the most device time is the train step
    by_module: Dict[str, List[float]] = {}
    for name, _, s, d in first.modules:
        if window[0] <= s and s + d <= window[1]:
            by_module.setdefault(name, []).append(d)
    by_kind = {k: total(merge(_clip(first.ops, window, (k,)))) / 1e9
               for k in KINDS}
    by_op: Dict[str, float] = {}
    for name, kind, s, d in first.ops:
        if s + d > window[0] and s < window[1]:
            by_op[f"{kind}:{name}"] = by_op.get(f"{kind}:{name}", 0.0) + d / 1e9
    # collectives in flight (either line) while the core runs nothing else
    coll = merge(_clip(first.ops, window, ("collective",))
                 + _clip(first.async_ops, window, ("collective",)))
    compute = merge(_clip(first.ops, window,
                          tuple(k for k in KINDS if k != "collective")))
    exposed = total(subtract(coll, compute)) / 1e9
    # idle gaps of the first device, by what the host was doing
    gaps = subtract([window], busy[names[0]])
    host = [(n, s, s + d) for n, _, s, d in trace.host if n in HOST_SPANS]

    def doing(lo: float, hi: float) -> str:
        best, cover = "other", 0.0
        for n, a, b in host:
            c = min(hi, b) - max(lo, a)
            if c > cover:
                best, cover = n.replace("bench_", "in_"), c
        return best

    named = [(doing(lo, hi), (hi - lo) / 1e9) for lo, hi in gaps]
    idle_by: Dict[str, float] = {}
    for what, secs in named:
        idle_by[what] = idle_by.get(what, 0.0) + secs
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_s,
        "devices": len(names),
        "modules": {n: {"count": len(d), "total_s": sum(d) / 1e9}
                    for n, d in by_module.items()},
        "kind_s": by_kind,
        "collective_s": total(coll) / 1e9,
        "collective_exposed_s": exposed,
        "ops": sorted(by_op.items(), key=lambda kv: -kv[1]),
        "idle_by_host": sorted(idle_by.items(), key=lambda kv: -kv[1]),
        "longest_gaps": sorted(named, key=lambda g: -g[1])[:10],
    }


def step_module(reduced: dict) -> Optional[Tuple[str, dict]]:
    """The program with the most device time in the window."""
    if not reduced["modules"]:
        return None
    name = max(reduced["modules"], key=lambda n: reduced["modules"][n]["total_s"])
    return name, reduced["modules"][name]


def breakdown(reduced: dict) -> dict:
    """The result line's `breakdown`: device time by class and the largest
    single operations; the longest idle gaps by what the host was doing."""
    kinds = [[f"class:{k}", reduced["kind_s"][k]] for k in KINDS
             if reduced["kind_s"][k] > 0]
    ops = [[n, s] for n, s in reduced["ops"][:10 - len(kinds)]]
    return {"device_ops": kinds + ops,
            "idle_gaps": [[n, s] for n, s in reduced["longest_gaps"]]}
