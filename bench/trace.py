"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: each device's busy intervals, the device time of each
compiled module and of collective operations, and the harness's own
spans (``bench.*`` TraceAnnotations) on the same clock.

``from_planes`` works on plain tuples so that it can be checked on a
small hand-made trace; ``load`` reads a trace file with JAX's own
``ProfileData`` reader.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")
# control flow whose event spans the operations of its body
WRAPPER = re.compile(r"(?<![\w%=.\-])(conditional|while|call)\(")
SPAN_PREFIX = "bench."


def short(name: str) -> str:
    """An XLA op event's name is its HLO text; keep the instruction name."""
    return name.split(" = ", 1)[0].strip()


def is_wrapper(name: str) -> bool:
    """A conditional, loop or call, whose event spans its body's ops."""
    lhs, eq, rhs = name.partition(" = ")
    return bool(eq) and WRAPPER.search(rhs) is not None


@dataclass
class Device:
    ops: list = field(default_factory=list)       # (start_ns, end_ns, name)
    modules: list = field(default_factory=list)   # (start_ns, end_ns, name)


@dataclass
class Trace:
    devices: dict                 # device index -> Device
    spans: list                   # (start_ns, end_ns, name), harness spans

    def window(self) -> tuple[int, int] | None:
        w = [s for s in self.spans if s[2] == SPAN_PREFIX + "window"]
        if w:
            return w[0][0], w[0][1]
        return None


def from_planes(planes) -> Trace:
    """``planes``: iterable of ``(plane_name, [(line_name, [(event_name,
    start_ns, duration_ns), ...]), ...])``."""
    devices, spans = {}, []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        for lname, events in lines:
            if m:
                dev = devices.setdefault(int(m.group(2)), Device())
                if lname == "XLA Ops":
                    dev.ops.extend((int(s), int(s + d), short(n))
                                   for n, s, d in events
                                   if not is_wrapper(n))
                elif lname == "XLA Modules":
                    dev.modules.extend((int(s), int(s + d), n)
                                       for n, s, d in events)
            else:
                spans.extend((int(s), int(s + d), n) for n, s, d in events
                             if n.startswith(SPAN_PREFIX))
    for dev in devices.values():
        dev.ops.sort()
        dev.modules.sort()
    spans.sort()
    return Trace(devices, spans)


def load(trace_dir) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    planes = []
    for plane in pd.planes:
        keep_dev = DEVICE_PLANE.match(plane.name) is not None
        lines = []
        for line in plane.lines:
            if keep_dev and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                   if keep_dev or e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines.append((line.name, evs))
        planes.append((plane.name, lines))
    return from_planes(planes)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e, *_ in intervals
            if e > lo and s < hi]


def overlap(merged: list, lo: int, hi: int) -> int:
    """Length of ``[lo, hi)`` covered by merged intervals."""
    return sum(e - s for s, e in clip(merged, lo, hi))


def busy(dev: Device, lo: int, hi: int) -> list:
    """Merged intervals in which an operation ran on the device."""
    return union(clip(dev.ops or dev.modules, lo, hi))


def module_time(dev: Device, pattern: str, lo: int, hi: int
                ) -> tuple[int, int]:
    """(ns, launches) of modules whose name contains ``pattern``."""
    hits = [(s, e) for s, e, n in dev.modules if pattern in n
            and s >= lo and e <= hi]
    return sum(e - s for s, e in hits), len(hits)


def collective_time(dev: Device, inside: list) -> int:
    """Device time of collective operations inside the merged intervals
    ``inside`` (e.g. the decode module's)."""
    coll = union((s, e) for s, e, n in dev.ops
                 if COLLECTIVE.search(n.lstrip("%").split(".")[0]))
    return sum(overlap(coll, s, e) for s, e in inside)


def idle_gaps(merged: list, spans: list, lo: int, hi: int, top: int = 10
              ) -> list:
    """The longest gaps between busy intervals in ``[lo, hi)``, each named
    by the innermost harness span that covers its middle."""
    gaps, t = [], lo
    for s, e in merged + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        inner = [sp for sp in spans if sp[0] <= mid < sp[1]
                 and sp[2] != SPAN_PREFIX + "window"]
        name = min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner \
            else "bench.between_steps"
        out.append((name, (e - s) / 1e9))
    return out
