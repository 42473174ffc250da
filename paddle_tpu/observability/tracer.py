"""Host span tracer with Chrome ``traceEvents`` export.

Successor of the reference's RecordEvent + chrome-trace profiler output
(/root/reference/paddle/fluid/platform/profiler.h:126 RecordEvent,
:208 Enable/DisableProfiler writing a chrome trace). Spans are nestable
(a per-thread stack tracks depth) and thread-aware (tid = real thread
id); every span is also forwarded to ``jax.profiler.TraceAnnotation``
so when a jax xplane capture is active the host spans land on the same
timeline as the XLA kernel events.

Export is the Chrome ``traceEvents`` JSON array-of-events form —
loadable in Perfetto (ui.perfetto.dev), chrome://tracing and
TensorBoard's trace viewer. Timestamps are microseconds, matching what
``trace_agg`` expects when it merges this file with an XLA
``*.trace.json.gz``.

The same tracer holds the **step timeline** of the train entry points
(docs/observability.md "The step timeline"): one record a dispatch in a
ring of fixed capacity, its three host phases with their wall and CPU
time, the moment its results were ready on the device and the step's
own scalars (both from one watcher thread), and the host's garbage
collections and compiles as events, all on ``time.perf_counter_ns()``.
While a profile is on, the completion and the events are annotations on
its clock too. It goes live at the first record made with metrics on
and is taken down when ``FLAGS_enable_metrics`` goes off, which logs
each entry point's slowest step once.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import gc
import json
import logging
import os
import queue
import statistics
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from . import metrics as _metrics

__all__ = ["SpanTracer", "tracer", "span", "export_chrome_trace",
           "PHASES", "TIMELINE_CAPACITY", "done_intervals", "step_costs",
           "slowest_step", "format_slowest_step"]

_log = logging.getLogger("paddle_tpu.observability")

# Cap on retained events: a runaway loop with tracing left on must not
# grow host memory without bound; drops are counted and reported.
MAX_EVENTS = 200_000

# The step timeline's rings: 8,192 records hold any 30 s window of the
# benchmark's cells (330 steps) and an hour of a 0.5 s step; a constant,
# since no two callers want different ones.
TIMELINE_CAPACITY = 8192
# The train entry points' host phases, in the order a call runs them.
# Their annotations are all that may start with ``pt/train_step/``: the
# benchmark's ``train.entry_host_ms_per_step`` sums that prefix.
PHASES = ("make_batch", "dispatch", "drain")
_PHASE_SPAN = {p: "pt/train_step/" + p for p in PHASES}
GC_EVENT = "pt/host/gc"
COMPILE_EVENT = "pt/host/compile"
DONE_ANNOTATION = "pt/step_done"
# how long taking the timeline down waits for the steps in flight
_WATCHER_JOIN_S = 10.0

_PID = os.getpid()


class _NoPhases:
    """What an entry point holds while metrics are off: every method a
    no-op, nothing stamped, nothing kept."""

    __slots__ = ()

    def phase(self, name: str, **args) -> "_NoPhases":
        return self

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None

    def dispatched(self, metrics, stacked: bool = False) -> None:
        return None


_NO_PHASES = _NoPhases()


class _StepPhases:
    """One dispatch of a train entry point while metrics are on: opens
    its host phases (``with phases.phase("dispatch", fn=...):``), each
    the ``pt/train_step/<phase>`` span it always was (a
    ``TraceAnnotation`` and a chrome event) and now also three numbers
    of the step's record: begin, end and the thread's CPU time inside.
    Phases do not nest. ``dispatched`` hands the step's metric leaves
    to the tracer's watcher thread."""

    __slots__ = ("_tracer", "record", "_name", "_args", "_ann", "_t0",
                 "_c0")

    def __init__(self, tracer: "SpanTracer", record: Dict[str, Any]
                 ) -> None:
        self._tracer = tracer
        self.record = record

    def phase(self, name: str, **args) -> "_StepPhases":
        self._name, self._args = name, args
        return self

    def __enter__(self) -> None:
        import jax
        self._ann = jax.profiler.TraceAnnotation(_PHASE_SPAN[self._name])
        self._ann.__enter__()
        self._c0 = time.thread_time_ns()
        self._t0 = time.perf_counter_ns()
        if self._name == "dispatch":
            self._tracer._tls.dispatch_t0 = self._t0

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        cpu = time.thread_time_ns() - self._c0
        self._ann.__exit__(None, None, None)
        tracer = self._tracer
        if self._name == "dispatch":
            tracer._tls.dispatch_t0 = None
        self.record["phases"][self._name] = (self._t0, t1, cpu)
        tracer._keep_span(_PHASE_SPAN[self._name],
                          (self._t0 - tracer._epoch_ns) / 1e3,
                          (t1 - self._t0) / 1e3, self._args)

    def dispatched(self, metrics: Dict[str, Any],
                   stacked: bool = False) -> None:
        """After the dispatch phase: ``metrics`` is what the step
        returned (reserved ``_pt_*`` leaves and all). ``stacked`` says
        its leaves carry a leading axis of K fused steps."""
        leaves = {k: v for k, v in metrics.items()
                  if not k.startswith("_")}
        if stacked:
            self.record["steps"] = next(
                (int(v.shape[0]) for v in leaves.values()
                 if getattr(v, "ndim", 0)), 1)
        _metrics.counter("optimizer_steps_total",
                         "optimizer update steps applied"
                         ).inc(self.record["steps"])
        self._tracer._watch_q.put((self.record, leaves))


def _scalars(leaves: Dict[str, Any], steps: int) -> Dict[str, Any]:
    """The step's own scalars as host numbers: a float for a leaf of
    one element, a list of ``steps`` floats for a leaf stacked over
    fused steps; any other leaf is not a scalar of the step."""
    import numpy as np
    out: Dict[str, Any] = {}
    for name, leaf in leaves.items():
        a = np.asarray(leaf)
        if a.size == 1:
            out[name] = float(a.reshape(()))
        elif steps > 1 and a.shape == (steps,):
            out[name] = [float(x) for x in a]
    return out


class SpanTracer:
    """Collects host spans as chrome trace events, and the train entry
    points' step timeline."""

    def __init__(self) -> None:
        # re-entrant: a collection can start between two bytecodes of
        # a block that holds it, and its callback records an event
        self._lock = threading.RLock()
        self._events: List[Dict[str, Any]] = []
        self._dropped = 0
        self._tls = threading.local()
        # perf_counter gives monotonic sub-µs deltas; anchor it once so
        # absolute ts values are comparable across threads.
        self._epoch_ns = time.perf_counter_ns()
        # -- the step timeline
        self._timeline = collections.deque(maxlen=TIMELINE_CAPACITY)  # guarded-by: self._lock
        self._host_events = collections.deque(maxlen=TIMELINE_CAPACITY)  # guarded-by: self._lock
        self._seq = 0  # guarded-by: self._lock
        self._reported: Dict[str, int] = {}     # fn -> seq reported to
        self._live = False
        self._watch_q: Optional[queue.SimpleQueue] = None
        self._watcher: Optional[threading.Thread] = None
        self._gc_open: Optional[Tuple] = None
        self._atexit = False

    # -- recording ---------------------------------------------------------

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._epoch_ns) / 1e3

    def _depth(self) -> int:
        return getattr(self._tls, "depth", 0)

    def _keep_span(self, name: str, ts_us: float, dur_us: float,
                   args: Dict[str, Any]) -> None:
        ev = {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
              "pid": _PID, "tid": threading.get_ident(), "cat": "host"}
        if args:
            ev["args"] = {k: str(v) for k, v in args.items()}
        self._keep(ev)

    def _keep(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) < MAX_EVENTS:
                self._events.append(ev)
            else:
                self._dropped += 1

    @contextlib.contextmanager
    def span(self, name: str, force: bool = False,
             **args) -> Iterator[None]:
        """Record a nested host span; no-op unless metrics are enabled
        (or ``force=True`` — the explicit user-API path)."""
        if not (force or _metrics.enabled()):
            yield
            return
        import jax
        self._tls.depth = self._depth() + 1
        t0 = self._now_us()
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        try:
            yield
        finally:
            ann.__exit__(None, None, None)
            dur = self._now_us() - t0
            self._tls.depth -= 1
            self._keep_span(name, t0, dur, args)

    def instant(self, name: str, force: bool = False, **args) -> None:
        """Zero-duration marker event."""
        if not (force or _metrics.enabled()):
            return
        ev = {"name": name, "ph": "i", "ts": self._now_us(), "pid": _PID,
              "tid": threading.get_ident(), "s": "t", "cat": "host"}
        if args:
            ev["args"] = {k: str(v) for k, v in args.items()}
        self._keep(ev)

    # -- the step timeline: recording --------------------------------------

    def step(self, fn: str, step: int):
        """Open the record of one dispatch of the train entry point
        ``fn`` (the recompile tracker's name of its program), ``step``
        being the entry point's own count of calls. While metrics are
        off: one cached-bool check, the shared no-op, no record."""
        if not _metrics.enabled():
            return _NO_PHASES
        import jax
        if not self._live:
            self._go_live()
        record = {"fn": fn, "step": step, "steps": 1,
                  "profiled": bool(
                      jax.profiler.TraceAnnotation.is_enabled()),
                  "tid": threading.get_ident(), "phases": {},
                  "done_ns": None, "scalars": {}, "read_ns": None}
        with self._lock:
            self._seq += 1
            record["seq"] = self._seq
            self._timeline.append(record)
        return _StepPhases(self, record)

    def dispatch_began_ns(self) -> Optional[int]:
        """The begin stamp of the dispatch phase open on this thread
        (``None`` outside one): the recompile tracker times the jitted
        call from it instead of taking a second pair of stamps."""
        return getattr(self._tls, "dispatch_t0", None)

    def _go_live(self) -> None:
        """At the first record with metrics on: the watcher thread, the
        collector's callback and the compile listener (the one
        ``sysconfig`` holds)."""
        from .. import sysconfig
        with self._lock:
            if self._live:
                return
            self._live = True
            self._watch_q = queue.SimpleQueue()
            self._watcher = threading.Thread(
                target=self._watch, args=(self._watch_q,),
                name="pt-step-timeline", daemon=True)
            if not self._atexit:
                # an interpreter that exits while the watcher waits on
                # the device must not tear the runtime down under it
                atexit.register(self._take_down)
                self._atexit = True
            self._watcher.start()
            gc.callbacks.append(self._on_gc)
        sysconfig._install_cache_listener()

    def _take_down(self) -> None:
        """Stop the watcher (after the steps in flight are done, or
        ``_WATCHER_JOIN_S``) and take the collector's callback out."""
        with self._lock:
            if not self._live:
                return
            self._live = False
            watcher, q = self._watcher, self._watch_q
            self._watcher = None
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self._gc_open = None
        q.put(None)
        watcher.join(_WATCHER_JOIN_S)

    def _watch(self, q: "queue.SimpleQueue") -> None:
        """The watcher thread: one wake a step. ``block_until_ready``
        releases the GIL while it waits."""
        import jax
        while True:
            item = q.get()
            if item is None:
                return
            record, leaves = item
            try:
                jax.block_until_ready(leaves)
                if record["profiled"]:
                    with jax.profiler.TraceAnnotation(
                            DONE_ANNOTATION, fn=record["fn"],
                            step=record["step"]):
                        record["done_ns"] = time.perf_counter_ns()
                else:
                    record["done_ns"] = time.perf_counter_ns()
                record["scalars"] = _scalars(leaves, record["steps"])
                record["read_ns"] = time.perf_counter_ns()
            # ptlint: disable=silent-failure -- a step that failed on the device raises on the caller's thread too; its record keeps done_ns None and the watcher goes on to the next step
            except Exception:  # noqa: BLE001
                pass

    def host_event_begin(self, name: str, **args) -> Optional[Tuple]:
        """Begin a host event (``pt/host/...``) of the timeline now;
        ``None`` while metrics are off. While a profile is on it is an
        annotation of that name too, ``args`` its metadata."""
        if not _metrics.enabled():
            return None
        import jax
        ann = None
        if jax.profiler.TraceAnnotation.is_enabled():
            ann = jax.profiler.TraceAnnotation(name, **args)
            ann.__enter__()
        return (name, time.perf_counter_ns(), args, ann)

    def host_event_end(self, token: Optional[Tuple], **more) -> None:
        if token is None:
            return
        name, t0, args, ann = token
        t1 = time.perf_counter_ns()
        if ann is not None:
            ann.__exit__(None, None, None)
        self.host_event(name, t0, t1, **args, **more)

    def host_event(self, name: str, begin_ns: int, end_ns: int,
                   **args) -> None:
        """Keep a host event that has ended, with the step it fell in:
        the newest record when it began."""
        if not _metrics.enabled():
            return
        ev = {"name": name, "begin_ns": int(begin_ns),
              "end_ns": int(end_ns), "tid": threading.get_ident()}
        ev.update(args)
        with self._lock:
            newest = self._timeline[-1] if self._timeline else None
            if newest is not None:
                ev["fn"], ev["step"] = newest["fn"], newest["step"]
            self._host_events.append(ev)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        # gc.callbacks entry, installed while the timeline is live. A
        # collection runs whole on one thread and collections do not
        # nest, so one open slot does.
        if phase == "start":
            self._gc_open = self.host_event_begin(
                GC_EVENT, generation=info.get("generation"))
        elif self._gc_open is not None:
            token, self._gc_open = self._gc_open, None
            self.host_event_end(token, collected=info.get("collected"))

    # -- views -------------------------------------------------------------

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def reset(self) -> None:
        """Drop every span, stop the watcher thread and empty the
        timeline's rings."""
        self._take_down()
        with self._lock:
            self._events.clear()
            self._dropped = 0
            self._timeline.clear()
            self._host_events.clear()
            self._reported.clear()

    # -- the step timeline: views ------------------------------------------

    def timeline(self, fn: Optional[str] = None,
                 last: Optional[int] = None) -> List[Dict[str, Any]]:
        """Copies of the ring's records, oldest first: those of the
        entry point ``fn`` (all of them without it), the newest
        ``last`` of them."""
        with self._lock:
            records = list(self._timeline)
        if fn is not None:
            records = [r for r in records if r["fn"] == fn]
        return [dict(r) for r in (records[-last:] if last else records)]

    def host_events(self) -> List[Dict[str, Any]]:
        """Copies of the ``pt/host/*`` events kept, oldest first."""
        # snapshot first: copying allocates, an allocation can start a
        # collection, and its callback appends to this ring
        with self._lock:
            events = list(self._host_events)
        return [dict(e) for e in events]

    def slowest_step(self, fn: str) -> Optional[Dict[str, Any]]:
        """``slowest_step`` over the ring's records of ``fn``."""
        return slowest_step(self.timeline(fn), self.host_events())

    def timeline_off(self) -> None:
        """``FLAGS_enable_metrics`` went from on to off: take the
        timeline down (the rings stay, for whoever reads them next) and
        log, once for every entry point that ran steps since its last
        report, its slowest step: over its newest stretch of steps with
        no compile among them, which in a run that warms up and then
        measures is the measured window. To the logger
        ``paddle_tpu.observability`` at WARNING, which without a
        handler of the caller's goes to stderr; never to stdout."""
        self._take_down()
        events = self.host_events()
        by_fn: Dict[str, List[Dict[str, Any]]] = {}
        for r in self.timeline():
            if r["seq"] > self._reported.get(r["fn"], 0):
                by_fn.setdefault(r["fn"], []).append(r)
        for fn, records in by_fn.items():
            self._reported[fn] = records[-1]["seq"]
            try:
                found = slowest_step(_steady_stretch(records, events),
                                     events)
                if found is not None:
                    _log.warning("step timeline: %s",
                                 format_slowest_step(found))
            except Exception as e:  # noqa: BLE001 — a report never breaks set_flags
                _log.warning("step timeline: no report for %s: %s: %s",
                             fn, type(e).__name__, e)

    def export_timeline(self, path: str) -> str:
        """Write the rings as ``step_timeline.jsonl`` under the
        directory ``path``: a ``{"kind": "step", ...}`` line a record,
        then a ``{"kind": "event", ...}`` line a host event."""
        os.makedirs(path, exist_ok=True)
        path = os.path.join(path, "step_timeline.jsonl")
        with open(path, "w") as f:
            for kind, rows in (("step", self.timeline()),
                               ("event", self.host_events())):
                for row in rows:
                    f.write(json.dumps(dict(row, kind=kind)) + "\n")
        return path

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Aggregated per-span table in SECONDS — the shape the old
        ``profiler.event_summary`` promised (calls/total/avg/max)."""
        agg: Dict[str, Dict[str, float]] = {}
        for e in self.events():
            if e.get("ph") != "X":
                continue
            a = agg.setdefault(e["name"], {"calls": 0, "total_s": 0.0,
                                           "max_s": 0.0})
            dur_s = e["dur"] / 1e6
            a["calls"] += 1
            a["total_s"] += dur_s
            a["max_s"] = max(a["max_s"], dur_s)
        for a in agg.values():
            a["avg_s"] = a["total_s"] / max(a["calls"], 1)
        return agg

    def chrome_trace(self) -> Dict[str, Any]:
        """Full trace dict: metadata events + recorded spans."""
        events = self.events()
        tids = sorted({e["tid"] for e in events})
        meta: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": _PID, "tid": 0,
            "args": {"name": "paddle_tpu host"}}]
        for i, tid in enumerate(tids):
            meta.append({"name": "thread_name", "ph": "M", "pid": _PID,
                         "tid": tid, "args": {"name": f"host thread {i}"}})
        return {"traceEvents": meta + events,
                "displayTimeUnit": "ms",
                "metadata": {"dropped_events": self.dropped()}}

    def export(self, path: Optional[str] = None) -> str:
        """Write the chrome trace JSON; returns the path written.

        ``path`` may be a directory (the file becomes
        ``host_trace.json`` inside it) or a file path. Defaults to
        FLAGS_trace_dir, then /tmp/pt_trace.
        """
        if path is None:
            from ..flags import GLOBAL_FLAGS
            path = GLOBAL_FLAGS.get("trace_dir") or "/tmp/pt_trace"
        if not path.endswith(".json"):
            os.makedirs(path, exist_ok=True)
            path = os.path.join(path, "host_trace.json")
        else:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


# -- the step timeline: what its records say (pure functions) ---------------

def _began(record: Dict[str, Any]) -> Optional[int]:
    """When the entry point was entered for this dispatch."""
    begins = [p[0] for p in record["phases"].values()]
    return min(begins) if begins else None


def _ended(record: Dict[str, Any]) -> Optional[int]:
    ends = [p[1] for p in record["phases"].values()]
    return max(ends) if ends else None


def done_intervals(records: List[Dict[str, Any]]
                   ) -> List[Tuple[int, int]]:
    """``(index, done_ns - done_ns of the record before)`` for every
    record of the list where both are known and neither was dispatched
    under a profile: the steps' cadence as the device completed them.
    ``records`` are one entry point's, oldest first."""
    out = []
    for i in range(1, len(records)):
        prev, rec = records[i - 1], records[i]
        if prev["profiled"] or rec["profiled"] \
                or prev["done_ns"] is None or rec["done_ns"] is None:
            continue
        out.append((i, rec["done_ns"] - prev["done_ns"]))
    return out


def step_costs(intervals: List[Tuple[int, int]], median: float
               ) -> List[Tuple[int, float, float]]:
    """``(index, cost, given_back)`` for every interval of
    ``done_intervals``: the interval less the median, less what the
    step after it gave back by being done sooner than the median after
    it. A stamp that came late (the watcher thread waited for the
    interpreter's lock under a collection, or was woken late) makes one
    interval long and the next one short by as much, and costs nothing;
    a step the device was late with is followed by a whole interval."""
    by_index = dict(intervals)
    out = []
    for i, interval in intervals:
        after = by_index.get(i + 1)
        back = max(0.0, median - after) if after is not None else 0.0
        out.append((i, interval - median - back, back))
    return out


def _steady_stretch(records: List[Dict[str, Any]],
                    events: List[Dict[str, Any]]
                    ) -> List[Dict[str, Any]]:
    """The newest run of two or more records with no compile between
    them: a record that began after a ``pt/host/compile`` event ended
    starts a new stretch."""
    cuts = sorted(e["end_ns"] for e in events
                  if e["name"] == COMPILE_EVENT)
    best: List[Dict[str, Any]] = []
    stretch: List[Dict[str, Any]] = []
    for r in records:
        began = _began(r)
        if began is None:
            continue
        cut = False
        while cuts and cuts[0] <= began:
            cuts.pop(0)
            cut = True
        if cut:
            if len(stretch) >= 2:
                best = stretch
            stretch = []
        stretch.append(r)
    return stretch if len(stretch) >= 2 else best


def _summed_events(events: List[Dict[str, Any]]
                   ) -> List[Dict[str, Any]]:
    """Host events by name: how many, their summed ms, and the longest
    with what it carried (a collection's generation, a compile's kind)."""
    by_name: Dict[str, Dict[str, Any]] = {}
    for e in events:
        ms = (e["end_ns"] - e["begin_ns"]) / 1e6
        row = by_name.setdefault(e["name"], {
            "name": e["name"], "count": 0, "ms": 0.0, "longest_ms": -1.0})
        row["count"] += 1
        row["ms"] += ms
        if ms > row["longest_ms"]:
            row["longest_ms"] = ms
            row["longest"] = {
                k: v for k, v in e.items()
                if k not in ("name", "begin_ns", "end_ns", "tid", "fn",
                             "step")}
    return list(by_name.values())


def slowest_step(records: List[Dict[str, Any]],
                 events: List[Dict[str, Any]],
                 group: Optional[int] = None) -> Optional[Dict[str, Any]]:
    """The step of ``records`` (one entry point's, oldest first) that
    the device completed longest after the step before it, and what the
    timeline knows of why; longest by ``step_costs``: what the step
    after it gave back is not counted, so a late stamp is not a slow
    step. ``None`` without two steps outside a profile to compare.
    ``late_stamps`` counts the stamps that came late by over a
    millisecond and were given back.

    ``host`` is ``"ahead"`` when the step's dispatch had returned
    before the step before it was done (the time went on the device or
    in the runtime) and ``"late"`` otherwise, with the longest of what
    the host did meanwhile: ``between_calls`` (outside the entry
    point: the caller's own code, a fetch) or one of the phases, wall
    and CPU. ``scalars`` sets the step's own counters beside the
    records' medians, with the largest value and the share of steps off
    the most common one for a counter that is whole on every step.
    ``events`` sums the ``pt/host/*`` events that overlap the step, by
    name (``_summed_events``).
    ``group`` (steps between two fetches) gives the step's place in its
    group, counted from the oldest record."""
    intervals = done_intervals(records)
    if not intervals:
        return None
    median = statistics.median(iv for _, iv in intervals)
    costs = step_costs(intervals, median)
    i, cost, back = max(costs, key=lambda x: x[1])
    prev, rec = records[i - 1], records[i]
    found: Dict[str, Any] = {
        "fn": rec["fn"], "step": rec["step"], "steps": rec["steps"],
        "intervals": len(intervals),
        "interval_ms": (cost + back + median) / 1e6,
        "median_ms": median / 1e6, "excess_ms": cost / 1e6,
        "given_back_ms": back / 1e6}
    # stamps that came late and cost nothing: more than a millisecond,
    # and at least half of the interval's excess, given back
    late = [(b, records[j]["step"]) for j, c, b in costs
            if b > 1e6 and b >= (c + b) / 2]
    found["late_stamps"] = len(late)
    if late:
        found["late_stamp_longest_ms"] = max(late)[0] / 1e6
        found["late_stamp_longest_step"] = max(late)[1]
    if group:
        found["place_in_group"] = (i % group + 1, group)
    began = _began(rec)
    dispatch = rec["phases"].get("dispatch")
    if dispatch is not None and dispatch[1] <= prev["done_ns"]:
        found["host"] = "ahead"
    else:
        found["host"] = "late"
        did = {p: (t1 - t0, cpu)
               for p, (t0, t1, cpu) in rec["phases"].items()}
        if began is not None and _ended(prev) is not None:
            did["between_calls"] = (began - _ended(prev), None)
        if did:
            what = max(did, key=lambda p: did[p][0])
            wall, cpu = did[what]
            found["late_in"] = what
            found["late_wall_ms"] = wall / 1e6
            found["late_cpu_ms"] = None if cpu is None else cpu / 1e6
    scalars: Dict[str, Any] = {}
    for name, value in rec["scalars"].items():
        seen = [r["scalars"][name] for r in records
                if isinstance(r["scalars"].get(name), float)]
        if not isinstance(value, float) or not seen:
            continue
        row = {"value": value, "median": statistics.median(seen)}
        if all(v.is_integer() for v in seen):
            mode = statistics.mode(seen)
            row["max"] = max(seen)
            row["off_mode_share"] = sum(v != mode for v in seen) / len(seen)
        scalars[name] = row
    found["scalars"] = scalars
    lo = min(prev["done_ns"], began if began is not None
             else prev["done_ns"])
    found["events"] = _summed_events(
        [e for e in events
         if e["begin_ns"] < rec["done_ns"] and e["end_ns"] > lo])
    return found


def format_slowest_step(found: Dict[str, Any]) -> str:
    """``slowest_step``'s finding as one line."""
    parts = [f"{found['fn']} slowest of {found['intervals']} steps: "
             f"step {found['step']}"]
    if "place_in_group" in found:
        parts.append("(%d of %d in its group)" % found["place_in_group"])
    parts.append(
        f"done {found['interval_ms']:.3f} ms after the step before it, "
        f"median {found['median_ms']:.3f}, excess {found['excess_ms']:.3f}"
        + (f" (the step after it gave back {found['given_back_ms']:.3f})"
           if found["given_back_ms"] >= 1 else "") + ";")
    if found["late_stamps"]:
        parts.append(
            f"{found['late_stamps']} late stamp(s), each given back by "
            f"the step after: the longest "
            f"{found['late_stamp_longest_ms']:.3f} ms at step "
            f"{found['late_stamp_longest_step']};")
    if found["host"] == "ahead":
        parts.append("host ahead (dispatched before the step before it "
                     "was done: the time went on the device or in the "
                     "runtime);")
    else:
        cpu = found.get("late_cpu_ms")
        parts.append(
            f"host late, longest in {found.get('late_in')}: wall "
            f"{found.get('late_wall_ms', float('nan')):.3f} ms"
            + ("" if cpu is None else f", cpu {cpu:.3f}") + ";")
    for name, row in found["scalars"].items():
        text = f"{name}={row['value']:g} (median {row['median']:g}"
        if "max" in row:
            text += (f", max {row['max']:g}, off the mode "
                     f"{100 * row['off_mode_share']:.1f}%")
        parts.append(text + ")")
    parts.append("host events over it: " + (", ".join(
        f"{e['name']} x{e['count']} {e['ms']:.3f} ms (longest "
        f"{e['longest_ms']:.3f}"
        + "".join(f" {k}={v}" for k, v in e["longest"].items()) + ")"
        for e in found["events"]) or "none"))
    return " ".join(parts)


_TRACER = SpanTracer()


def tracer() -> SpanTracer:
    return _TRACER


def span(name: str, force: bool = False, **args):
    """Module-level shortcut: ``with span("train/step"): ...``"""
    return _TRACER.span(name, force=force, **args)


def export_chrome_trace(path: Optional[str] = None) -> str:
    return _TRACER.export(path)
