"""Reduction of a profiler trace (``.xplane.pb``) to device intervals.

``load`` reads the trace with ``jax.profiler.ProfileData`` into plain
lists: per device, its operations and its program (module) runs, and the
host spans that the benchmark's drivers wrote with
``jax.profiler.TraceAnnotation``.  Everything after ``load`` is arithmetic
on ``(name, start_s, end_s)`` tuples, so a hand-built event list tests it.

Busy time is the union of the operation intervals, so overlapping or
nested operations are counted once.  A collective's exposed time is the
part of the union of collective intervals that no other operation covers.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start_s, end_s)
Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute")
WINDOW_SPAN = "trace.window"
UNTRACED = "host: no span"


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    def window(self) -> Interval:
        """The first ``trace.window`` host span."""
        for name, s, e in self.spans:
            if name == WINDOW_SPAN:
                return s, e
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, span_names: Iterable[str]) -> Trace:
    """Device operations and modules of every TPU plane, and the host
    events named in ``span_names`` (plus ``trace.window``)."""
    from jax.profiler import ProfileData

    wanted = set(span_names) | {WINDOW_SPAN}
    tr = Trace()
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            rest = plane.name[len(DEVICE_PREFIX):]
            if not rest.isdigit():
                continue
            dev = int(rest)
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                           for ev in line.events]
                    if line.name == OPS_LINE:
                        tr.ops.setdefault(dev, []).extend(
                            (short_name(n), s, e) for n, s, e in leaves(evs))
                    else:
                        tr.modules.setdefault(dev, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        tr.spans.append((ev.name, ev.start_ns * 1e-9,
                                         ev.end_ns * 1e-9))
    tr.spans.sort(key=lambda e: e[1])
    return tr


def leaves(events: List[Event]) -> List[Event]:
    """Drop the operations that hold others (a ``while`` loop's event
    spans its body's operations): only the innermost run on the core."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < ev[2] and nxt[2] <= ev[2]:
            continue
        out.append(ev)
    return out


OP_TEXT = re.compile(r"^(%\S+) = (\S+?)(?:\{\S*)?\s(?:.*?\s)?([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[64,32]{...} fusion(...)`` -> ``%fusion.12
    fusion bf16[64,32]``; a name that is not HLO text is kept."""
    m = OP_TEXT.match(name)
    if not m:
        return name[:120]
    op, shape, kind = m.groups()
    shape = shape if not shape.startswith("(") else "(tuple)"
    return f"{op} {kind} {shape}"[:120]


# ------------------------------------------------------------ arithmetic
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two unions (each sorted, disjoint)."""
    i = j = 0
    got = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            got += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return got


def busy_s(ops: List[Event], lo: float, hi: float) -> float:
    return total(union((s, e) for _, s, e in clip(ops, lo, hi)))


def idle_gaps(ops: List[Event], lo: float, hi: float) -> List[Interval]:
    gaps, t = [], lo
    for s, e in union((s, e) for _, s, e in clip(ops, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


def exposed_collective_s(ops: List[Event], lo: float, hi: float) -> float:
    """Time in which a collective runs and no other operation does."""
    ev = clip(ops, lo, hi)
    coll = union((s, e) for n, s, e in ev if is_collective(n))
    comp = union((s, e) for n, s, e in ev if not is_collective(n))
    return total(coll) - intersect(coll, comp)


def span_at(spans: List[Event], t: float) -> str:
    """The innermost (shortest) host span that holds time ``t``."""
    best: Optional[Event] = None
    for ev in spans:
        if ev[0] != WINDOW_SPAN and ev[1] <= t <= ev[2]:
            if best is None or ev[2] - ev[1] < best[2] - best[1]:
                best = ev
    return best[0] if best else UNTRACED


def named_gaps(tr: Trace, dev: int, lo: float, hi: float,
               top: int = 10) -> List[list]:
    """Idle time on ``dev`` summed by the host span each gap's midpoint
    falls in, largest first."""
    by: Dict[str, float] = {}
    for s, e in idle_gaps(tr.ops.get(dev, []), lo, hi):
        name = span_at(tr.spans, 0.5 * (s + e))
        by[name] = by.get(name, 0.0) + (e - s)
    return [[n, v] for n, v in sorted(by.items(), key=lambda kv: -kv[1])][:top]


def top_ops(ops: List[Event], lo: float, hi: float, top: int = 10):
    by: Dict[str, float] = {}
    for n, s, e in clip(ops, lo, hi):
        by[n] = by.get(n, 0.0) + (e - s)
    return [[n, v] for n, v in sorted(by.items(), key=lambda kv: -kv[1])][:top]


def module_runs(tr: Trace, dev: int, module: str, lo: float,
                hi: float) -> List[float]:
    """Device durations of the runs of one program, found by the name of
    its HLO module, that overlap the window (device and host clocks may
    differ by a little, so a run that the window's first call started
    can appear to begin just before it)."""
    return [e - s for n, s, e in tr.modules.get(dev, [])
            if e > lo and s < hi and (n == module or n.startswith(module + "("))]
