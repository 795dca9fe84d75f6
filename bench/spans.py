"""The program's own spans in a traced run, and what the per-layer readers
``step_host_ms`` and ``queue_wait_ms`` read from them.

``Engine.step()`` marks its phases with ``jax.profiler`` annotations named
``engine.*`` (``engine.step``, ``engine.admit``, ``engine.prefill`` and
``engine.decode`` with their ``.prepare`` / ``.launch`` / ``.sync`` /
``.commit`` children), each with its arguments.  The profiler stamps them
on the clock of the device planes that ``bench/trace.py`` reads, so each
stretch of device-idle time can be put down to the host phase that covers
it.  A program without these spans gives nothing to read: every function
here then returns None and writes no note.

``from_planes`` works on plain tuples so that it can be checked on a small
hand-made trace; ``load`` reads the newest ``.xplane.pb`` under
``.bench_trace/`` with JAX's own ``ProfileData`` reader, once per run
(``of``).

The TPU's operation events in that reader carry no op-name path (only
their HLO text and device offsets), so the named scopes of the paged
entry points cannot be read from the trace here.
"""
from __future__ import annotations

import bisect
import glob
import os
import pathlib
from dataclasses import dataclass

from bench import trace as T

PREFIX = "engine."
TRACE_ROOT = pathlib.Path(__file__).resolve().parents[1] / ".bench_trace"


@dataclass
class Span:
    start: int
    end: int
    name: str
    args: dict


@dataclass
class Spans:
    spans: list          # Span, by start

    def __post_init__(self):
        self.starts = [s.start for s in self.spans]

    def inside(self, outer: Span) -> list:
        """The spans that lie within ``outer`` (itself excluded)."""
        i = bisect.bisect_left(self.starts, outer.start)
        j = bisect.bisect_right(self.starts, outer.end)
        return [s for s in self.spans[i:j]
                if s is not outer and s.end <= outer.end]

    def named(self, name: str, lo: int, hi: int) -> list:
        """Spans called ``name`` that lie within ``[lo, hi]``."""
        return [s for s in self.spans
                if s.name == name and s.start >= lo and s.end <= hi]


def from_planes(planes) -> Spans:
    """``planes``: iterable of ``(plane_name, [(line_name, [(event_name,
    start_ns, duration_ns[, stats]), ...]), ...])``; ``stats`` is a dict.
    Keeps the host events named ``engine.*`` with their arguments."""
    spans = []
    for pname, lines in planes:
        if T.DEVICE_PLANE.match(pname):
            continue
        for _, events in lines:
            for ev in events:
                n, s, d = ev[:3]
                if n.startswith(PREFIX):
                    stats = ev[3] if len(ev) > 3 else {}
                    spans.append(Span(int(s), int(s + d), n,
                                      {k: v for k, v in stats.items()
                                       if not k.startswith("_")}))
    spans.sort(key=lambda s: (s.start, -s.end))
    return Spans(spans)


def load(root=TRACE_ROOT) -> Spans | None:
    """The ``engine.*`` spans of the newest ``.xplane.pb`` under
    ``root``; None where there is none."""
    files = glob.glob(os.path.join(str(root), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    planes = []
    for plane in pd.planes:
        if T.DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                   for e in line.events if e.name.startswith(PREFIX)]
            if evs:
                lines.append((line.name, evs))
        planes.append((plane.name, lines))
    return from_planes(planes)


def of(run) -> Spans | None:
    """The run's spans, loaded once and kept on ``run``; None where the
    program emitted no ``engine.*`` span."""
    if not hasattr(run, "engine_spans"):
        run.engine_spans = load()
    sp = run.engine_spans
    return sp if sp is not None and sp.spans else None


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

def step_host_ms(run):
    """Mean over the working ``engine.step`` spans of the traced stretch
    (those holding a launch) of the span's length less the time covered
    by its ``engine.*.sync`` spans: the host's own work per step, ms.
    Also writes ``notes["idle_by_phase"]``."""
    sp = of(run)
    if sp is None or run.window is None:
        return None
    lo, hi = run.window
    host = []
    for st in sp.named("engine.step", lo, hi):
        inner = sp.inside(st)
        if not any(s.name.endswith(".launch") for s in inner):
            continue
        sync = T.union((s.start, s.end) for s in inner
                       if s.name.endswith(".sync"))
        host.append(st.end - st.start - sum(e - s for s, e in sync))
    if not host:
        return None
    idle = idle_by_phase(run, sp)
    if idle:
        run.notes["idle_by_phase"] = idle
    return sum(host) / len(host) / 1e6


def idle_by_phase(run, sp: Spans) -> dict | None:
    """Chip 0's device-idle time in the traced stretch, split by the
    innermost ``engine.*`` span covering it (``outside_step`` where none
    does): ``{phase: {"s": seconds, "pct": share of all idle}}``, largest
    first."""
    dev = run.trace.devices.get(0) if run.trace is not None else None
    if dev is None:
        return None
    lo, hi = run.window
    idle, t = [], lo
    for s, e in T.busy(dev, lo, hi) + [(hi, hi)]:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    spans = [s for s in sp.spans if s.end > lo and s.start < hi]
    starts = [s.start for s in spans]
    longest = max((s.end - s.start for s in spans), default=0)
    out: dict = {}
    for a, b in idle:
        i = bisect.bisect_left(starts, a - longest)
        j = bisect.bisect_left(starts, b)
        cover = [s for s in spans[i:j] if s.end > a]
        cuts = sorted({a, b} | {x for s in cover for x in (s.start, s.end)
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            inner = [s for s in cover if s.start <= x and s.end >= y]
            name = min(inner, key=lambda s: s.end - s.start).name \
                if inner else "outside_step"
            out[name] = out.get(name, 0) + (y - x)
    total = sum(out.values())
    if not total:
        return None
    return {k: {"s": v / 1e9, "pct": 100.0 * v / total}
            for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def queue_wait_ms(run):
    """Mean wait in the queue (ms) of the requests admitted in the traced
    stretch: the sum of ``queue_wait_ms`` over its ``engine.admit`` spans
    over the sum of their ``requests``."""
    sp = of(run)
    if sp is None or run.window is None:
        return None
    admits = sp.named("engine.admit", *run.window)
    n = sum(int(a.args.get("requests", 0)) for a in admits)
    if not n:
        return None
    run.notes["queue_wait_requests"] = n
    return sum(float(a.args.get("queue_wait_ms", 0.0)) for a in admits) / n
