"""Reduction of one profiler trace to numbers: device busy and idle
time, time per op name and per step, and the longest idle gaps named by
what the benchmark's own loop was doing.

Two halves. ``read_xplane`` turns an ``.xplane.pb`` (read with
``jax.profiler.ProfileData``, nothing else) into plain ``Event`` tuples,
keeping only the lanes the reduction needs; the rest are pure functions
over those tuples, checked in ``tests/benchmarks`` on a hand-built
trace.

Only the "XLA Ops" lane of a device plane counts as device work: the
"Steps" and "XLA Modules" lanes are aggregates of it, and summing them
too counts every step twice (the fault ``observability/trace_agg.py``
was written to avoid; this is its sound part, finished)."""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench/"
# a traced slice is bounded by construction; this only stops a runaway
MAX_EVENTS = 2_000_000


class Event(NamedTuple):
    name: str
    start: float   # ns on the trace's clock
    dur: float     # ns


class Trace(NamedTuple):
    """What the reduction reads. ``ops``/``modules``: device lanes by
    device plane name. ``annotations``: the benchmark's own
    ``TraceAnnotation`` spans (host clock, same timeline)."""
    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    annotations: List[Event]
    truncated: bool
    path: str
    size_bytes: int


def newest_xplane(trace_dir: str) -> Tuple[Optional[str], List[str]]:
    """The newest ``.xplane.pb`` under ``trace_dir`` and every one
    found. A run's trace directory is its own and fresh, but nothing
    here assumes that it holds one profile."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    found.sort(key=lambda p: (os.path.getmtime(p), p))
    return (found[-1] if found else None), found


def read_xplane(path: str, max_events: int = MAX_EVENTS) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    annotations: List[Event] = []
    host_ops: List[Event] = []
    budget = max_events
    truncated = False

    def take(events: Iterable, keep) -> List[Event]:
        nonlocal budget, truncated
        out = []
        for e in events:
            if budget <= 0:
                truncated = True
                break
            budget -= 1
            if keep(e):
                out.append(Event(e.name, float(e.start_ns),
                                 float(e.duration_ns)))
        return out

    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == OPS_LINE:
                ops[plane.name] = take(line.events, lambda e: True)
            elif device and line.name == MODULES_LINE:
                modules[plane.name] = take(line.events, lambda e: True)
            elif not device and plane.name.startswith("/host:"):
                annotations += take(
                    line.events,
                    lambda e: e.name.startswith(ANNOTATION_PREFIX))
    if not ops:
        # No device plane (the CPU backend of a rehearsal): its
        # executions are host-thread events that carry an hlo_op stat.
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                if "XLA" not in line.name:
                    continue
                host_ops += take(
                    line.events,
                    lambda e: any(k == "hlo_op" for k, _ in e.stats))
        if host_ops:
            ops["/host:CPU"] = host_ops
    for lane in list(ops.values()) + list(modules.values()):
        lane.sort(key=lambda e: e.start)
    annotations.sort(key=lambda e: e.start)
    return Trace(ops, modules, annotations, truncated, path,
                 os.path.getsize(path))


# -- pure reductions ----------------------------------------------------------

def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to ``[lo, hi]``; those outside are dropped."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.start + e.dur, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def busy_union(events: Iterable[Event]) -> float:
    """Total time covered by at least one event (overlaps count
    once)."""
    total, end = 0.0, float("-inf")
    for e in sorted(events, key=lambda e: e.start):
        s, t = e.start, e.start + e.dur
        if s > end:
            total += t - s
            end = t
        elif t > end:
            total += t - end
            end = t
    return total


def idle_gaps(events: Iterable[Event], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The intervals of ``[lo, hi]`` that no event covers."""
    gaps, cursor = [], lo
    for e in sorted(clip(events, lo, hi), key=lambda e: e.start):
        if e.start > cursor:
            gaps.append((cursor, e.start))
        cursor = max(cursor, e.start + e.dur)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def sum_by_name(events: Iterable[Event]) -> Dict[str, Tuple[float, int]]:
    acc: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for e in events:
        acc[e.name][0] += e.dur
        acc[e.name][1] += 1
    return {k: (v[0], int(v[1])) for k, v in acc.items()}


def sum_matching(events: Iterable[Event], pattern: str) -> Tuple[float, int]:
    """Total duration and count of the events whose name matches the
    regular expression (searched, not anchored)."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for e in events:
        if rx.search(e.name):
            total += e.dur
            n += 1
    return total, n


def attribute_gaps(gaps: Iterable[Tuple[float, float]],
                   annotations: Iterable[Event],
                   top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing: every gap is charged
    to the innermost benchmark annotation that covers its middle
    ("unannotated" where none does), summed by name, longest first."""
    spans = list(annotations)
    acc: Dict[str, float] = defaultdict(float)
    for lo, hi in gaps:
        mid = (lo + hi) / 2
        cover = [a for a in spans if a.start <= mid <= a.start + a.dur]
        name = (min(cover, key=lambda a: a.dur).name if cover
                else "unannotated")
        acc[name] += (hi - lo) / 1e9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:top]


def op_kind(text: str, width: int = 96) -> str:
    """An "XLA Ops" event is named by its whole HLO line. Keep the
    result's name without its number, its type without layouts and
    tilings, and the opcode: the copies of one op in a model's twelve
    layers then share a kind, and ten kinds cover most of a step where
    ten single ops cover a tenth of it."""
    m = re.match(r"%?(\S+?)(?:\.\d+)? = (.*)", text)
    if not m:
        return text[:width]
    rhs = re.sub(r"\{[^{}]*\}", "", m.group(2))
    k = re.match(r"((?:\([^()]*\)|\S+)\s+[\w\-]+)\(", rhs)
    return f"{m.group(1)} = {k.group(1) if k else rhs}"[:width]


def slice_window(trace: Trace, name: str = ANNOTATION_PREFIX + "slice"
                 ) -> Optional[Tuple[float, float]]:
    """The traced slice: the last ``bench/slice`` annotation (the
    benchmark wraps exactly the profiled work in one)."""
    spans = [a for a in trace.annotations if a.name == name]
    if not spans:
        return None
    a = spans[-1]
    return a.start, a.start + a.dur


def summarize(trace: Trace, top: int = 10) -> Dict:
    """Busy and idle time of the slice and the contract's
    ``breakdown``. Busy is averaged over the device planes; the
    breakdown is device 0's (the lowest plane name), its operations
    summed by kind and named ``<kind> x<events>``."""
    window = slice_window(trace)
    planes = sorted(trace.ops)
    if window is None or not planes:
        return {}
    lo, hi = window
    clipped = {p: clip(trace.ops[p], lo, hi) for p in planes}
    busy = [busy_union(clipped[p]) for p in planes]
    first = planes[0]
    by_kind = sum_by_name(Event(op_kind(e.name), e.start, e.dur)
                          for e in clipped[first])
    device_ops = sorted(((f"{k} x{n}", t / 1e9)
                         for k, (t, n) in by_kind.items()),
                        key=lambda kv: -kv[1])[:top]
    gaps = attribute_gaps(idle_gaps(clipped[first], lo, hi),
                          [a for a in trace.annotations
                           if a.name != ANNOTATION_PREFIX + "slice"], top)
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "planes": planes,
            "device_ops": [[n, s] for n, s in device_ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
