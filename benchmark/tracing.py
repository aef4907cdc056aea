"""From the profiler's trace to numbers: the reduction every PR shares.

`jax.profiler` writes one `.xplane.pb` per traced window. A TPU plane
(`/device:TPU:<n>`) carries the line `XLA Modules` (one event per program
execution), `XLA Ops` (one per HLO operation on the core, named by the
operation's full HLO text) and `Async XLA Ops` (copies and collectives in
flight, overlapping the core's operations). The host plane's thread lines
carry the harness's own `bench_*` annotations and the program's `feed/*`
and `train/*` spans (`utils/profiling.py::span`) on the same clock (checked
on a chip trace: the first step's dispatch and its start on the device lie
1.2 ms apart).

An operation's place in the program, the `jax.named_scope` path that JAX
writes into the instruction's `op_name`, is in the trace as the `tf_op`
stat of the event's METADATA (read on a chip trace: 99.3% of the sagan128
step's device time carries one, such as
`jit(train_step)/d_step/loss/transpose(jvp(disc))/conv3/conv_general_dilated:`).
`jax.profiler.ProfileData` gives an event's own stats and not its
metadata's, so `op_names` reads them from the file's protobuf wire format
itself (XSpace / XPlane / XEventMetadata / XStat of tsl's `xplane.proto`).

`load_xplane` cuts all that down to a `Trace` of plain tuples, which is
also what the recorded fixtures of the tests hold; everything below works
on a `Trace` and knows nothing of the profiler.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import struct
from typing import Dict, Iterable, List, Optional, Tuple

# name, kind, start_ns, dur_ns; a device operation may carry a fifth: its scope
Event = Tuple
Span = Tuple[float, float]

WINDOW = "bench_window"
# host events kept: the harness's annotations and the program's spans
HOST_PREFIXES = ("bench_", "feed/", "train/")
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


def scope_of(op_name: str) -> str:
    """The `jax.named_scope` path of an instruction's `op_name`: the `jit(..)`
    wrappers dropped, a name taken out of the transformations around it
    (`transpose(jvp(disc))` is `disc`), and the path begun again where the
    backward pass repeats it (`d_step/loss/transpose(d_step)/loss/..`). The
    primitive's own name stays as the last part. An `op_name` that is no
    path of the program's (a parameter's name) has no scope."""
    parts: List[str] = []
    for part in op_name.rstrip(":").split("/"):
        name = part
        while (m := re.fullmatch(r"(\w+)\((.*)\)", name)):
            name = "" if m.group(1) == "jit" else m.group(2)
        if not name:                    # a `jit(..)` wrapper
            continue
        if not re.fullmatch(r"[\w.\-]+", name):
            return ""
        if parts and part.startswith("transpose(") and name == parts[0]:
            parts = []
        parts.append(name)
    return "/".join(parts)


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
        scopes = obj.get("scopes")      # rows hold an index into it

        def ev(rows):
            return [(r[0], r[1], float(r[2]), float(r[3]))
                    + ((scopes[r[4]],) if len(r) > 4 else ()) for r in rows]
        return Trace(
            devices={n: DeviceTrace(ev(d["modules"]), ev(d["ops"]),
                                    ev(d["async_ops"]))
                     for n, d in obj["devices"].items()},
            host=ev(obj["host"]))

    def to_json(self) -> dict:
        """What `from_json` reads: how a fixture is recorded."""
        scopes: Dict[str, int] = {}

        def rows(events):
            return [list(e[:4]) + [scopes.setdefault(e[4], len(scopes))]
                    if len(e) > 4 else list(e) for e in events]
        devices = {n: {"modules": rows(d.modules), "ops": rows(d.ops),
                       "async_ops": rows(d.async_ops)}
                   for n, d in self.devices.items()}
        return {"devices": devices, "host": rows(self.host),
                "scopes": list(scopes)}


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint
    or a fixed-width field, a slice of `buf` for a length-delimited one."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        val = shift = 0
        while True:
            b = buf[i]
            i += 1
            val |= (b & 0x7F) << shift
            if b < 0x80:
                return val
            shift += 7

    while i < n:
        key = varint()
        wire = key & 7
        if wire == 0:
            val = varint()
        elif wire == 2:
            size = varint()
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wire == 5:
            val = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, val


def _entry(buf):
    """(key, value) of one map entry."""
    got = dict(_fields(buf))
    return got.get(1), got.get(2)


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: op_name}} from the `tf_op` stat of the
    events' metadata (XSpace.planes = 1; XPlane.name = 2, event_metadata =
    4, stat_metadata = 5; XEventMetadata.name = 2, stats = 5; XStatMetadata
    .name = 2; XStat.metadata_id = 1, str_value = 5, ref_value = 7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stats = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(_entry(v)[1])
            elif f == 5:
                key, meta = _entry(v)
                stats[key] = bytes(dict(_fields(meta)).get(2, b"")).decode()
        if not name.startswith("/device:TPU:"):
            continue
        tf_op = [k for k, n in stats.items() if n == "tf_op"]
        names = out.setdefault(name, {})
        for meta in events if tf_op else ():
            event, found = "", None
            for f, v in _fields(meta):
                if f == 2:
                    event = bytes(v).decode(errors="replace")
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op[0]:
                        found = (bytes(stat[5]).decode() if 5 in stat
                                 else stats.get(stat.get(7), ""))
            if found:
                names[event] = found
    return out


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    scopes = op_names(path)
    devices: Dict[str, DeviceTrace] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = DeviceTrace([], [], [])
            scope = {name: scope_of(op) for name, op in
                     scopes.get(plane.name, {}).items()}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [(re.sub(r"\(\d+\)$", "", e.name), "module",
                                    e.start_ns, e.duration_ns)
                                   for e in line.events]
                elif line.name == "XLA Ops":
                    dev.ops = [(short_name(e.name), classify(e.name),
                                e.start_ns, e.duration_ns,
                                scope.get(e.name, ""))
                               for e in line.events]
                elif line.name == "Async XLA Ops":
                    dev.async_ops = [(short_name(e.name), classify(e.name),
                                      e.start_ns, e.duration_ns)
                                     for e in line.events]
            devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(e.name, "host", e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(HOST_PREFIXES)]
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
    return [(max(e[2], lo), min(e[2] + e[3], hi)) for e in events
            if (kinds is None or e[1] in kinds)
            and e[2] + e[3] > lo and e[2] < hi]


# --- the reduction ------------------------------------------------------------

def window_of(trace: Trace) -> Optional[Span]:
    """The traced window: the harness's `bench_window` annotation, else the
    span of the device's own events."""
    for name, _, start, dur in trace.host:
        if name == WINDOW:
            return (start, start + dur)
    spans = [(e[2], e[2] + e[3]) for dev in trace.devices.values()
             for e in dev.modules or dev.ops]
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
    by_scope: Dict[str, List[Span]] = {}
    for name, kind, s, d, *scope in first.ops:
        if s + d > window[0] and s < window[1]:
            by_op[f"{kind}:{name}"] = by_op.get(f"{kind}:{name}", 0.0) + d / 1e9
            parts = scope[0].split("/") if scope and scope[0] else []
            for i in range(1, len(parts) + 1):
                by_scope.setdefault("/".join(parts[:i]), []).append(
                    (max(s, window[0]), min(s + d, window[1])))
    # collectives in flight (either line) while the core runs nothing else
    coll = merge(_clip(first.ops, window, ("collective",))
                 + _clip(first.async_ops, window, ("collective",)))
    compute = merge(_clip(first.ops, window,
                          tuple(k for k in KINDS if k != "collective")))
    exposed = total(subtract(coll, compute)) / 1e9
    # idle gaps of the first device, by what the host was doing
    gaps = subtract([window], busy[names[0]])
    host = [(n, s, s + d) for n, _, s, d in trace.host if n != WINDOW]

    def doing(lo: float, hi: float) -> str:
        """The host span that covers most of the gap; of two that cover it
        alike the shorter, which lies inside the other (`feed/wait` inside
        `bench_next`)."""
        best, cover, length = "other", 0.0, 0.0
        for n, a, b in host:
            c = min(hi, b) - max(lo, a)
            if c > cover or (c == cover and c > 0 and b - a < length):
                best, cover, length = "in_" + n.replace("bench_", "", 1), c, b - a
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
        "scope_s": {path: total(merge(spans)) / 1e9
                    for path, spans in by_scope.items()},
        "idle_by_host": sorted(idle_by.items(), key=lambda kv: -kv[1]),
        "longest_gaps": sorted(named, key=lambda g: -g[1])[:10],
    }


def under(reduced: dict, scope: str) -> float:
    """Device seconds of the window under the scope named `scope`, wherever
    it lies in the path (`attn` under `d_step/loss/disc` and under
    `g_step/loss/gen`): `scope_s` is by path prefix, so the paths that END in
    the name hold each operation once. 0.0 where the trace names no scope."""
    return sum(secs for path, secs in reduced.get("scope_s", {}).items()
               if path.rsplit("/", 1)[-1] == scope)


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
