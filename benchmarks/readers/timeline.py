"""Per-layer numbers read from the program's own step timeline
(``paddle_tpu/observability/tracer.py``, docs/observability.md "The
step timeline"): one record a dispatch of the train entry point, with
its host phases (begin, end, the thread's CPU time inside), the moment
its results were ready on the device (``done_ns``), the step's own
scalars, and the host's ``pt/host/*`` events, all on
``time.perf_counter_ns()``.

The window's steps are the newest ``observed["attempted"]`` records of
the entry point ``fn_pattern`` matches; the traced group is among them,
and every cadence metric leaves it out by ``profiled``. Unlike the
profile, the timeline covers the whole window, so these are the one
inside reading of what happens between the calls the benchmark times.

A program without a timeline (an older checkout) gives every reader
here nothing to read: ``None``, never an error."""

from __future__ import annotations

import re
import statistics
from typing import Any, Dict, List, Optional, Tuple

from .. import trace as tr
from ..harness import log
from . import program

_WINDOW_KEY = "_timeline_window"
DISPATCH_SPAN = "pt/train_step/dispatch"


# -- the window's records -----------------------------------------------------

def window(observed: Dict[str, Any], fn_pattern: str
           ) -> Optional[Dict[str, Any]]:
    """``{"records", "events"}`` of the measured window, asked of the
    program once and kept in ``observed``; logs the window's slowest
    step outside the profile, as the program's own report words it."""
    if _WINDOW_KEY in observed:
        return observed[_WINDOW_KEY]
    observed[_WINDOW_KEY] = None
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import tracer as pt_tracer
    tracer = obs.get_tracer()
    steps = int(observed.get("attempted") or 0)
    if not hasattr(tracer, "timeline") or not steps:
        return None
    rx = re.compile(fn_pattern)
    records = [r for r in tracer.timeline() if rx.search(r["fn"])][-steps:]
    if not records:
        return None
    events = tracer.host_events()
    observed[_WINDOW_KEY] = {"records": records, "events": events}
    group = observed.get("counters", {}).get("steps_per_group")
    found = pt_tracer.slowest_step(records, events, group)
    if found is not None:
        log("step timeline: " + pt_tracer.format_slowest_step(found))
    return observed[_WINDOW_KEY]


# -- pure parts (checked on hand-built records) -------------------------------

def done_intervals(records: List[Dict[str, Any]]) -> List[float]:
    """``done_ns[n] - done_ns[n-1]`` in ns for every step whose own
    dispatch and the one before it were outside a profile: the steps'
    cadence as the device completed them."""
    out = []
    for prev, rec in zip(records, records[1:]):
        if prev["profiled"] or rec["profiled"] \
                or prev["done_ns"] is None or rec["done_ns"] is None:
            continue
        out.append(float(rec["done_ns"] - prev["done_ns"]))
    return out


def step_costs(records: List[Dict[str, Any]]) -> List[float]:
    """For every interval of ``done_intervals``: the interval less the
    median, less what the step after it gave back by being done sooner
    than the median after it. A stamp that came late (the watcher
    thread waited for the interpreter's lock under a collection, or was
    woken late) makes one interval long and the next short by as much,
    and costs nothing; a step the device was late with is followed by a
    whole interval. An interval with no successor counts whole."""
    intervals: Dict[int, float] = {}
    for i in range(1, len(records)):
        pair = done_intervals(records[i - 1:i + 1])
        if pair:
            intervals[i] = pair[0]
    if not intervals:
        return []
    median = statistics.median(intervals.values())
    return [iv - median - max(0.0, median - intervals.get(i + 1, median))
            for i, iv in intervals.items()]


def phase_times(records: List[Dict[str, Any]]
                ) -> Optional[Tuple[float, float, int]]:
    """Summed wall and CPU ns inside the host phases of the steps
    dispatched outside a profile, and how many steps those were."""
    wall = cpu = 0.0
    steps = 0
    for r in records:
        if r["profiled"] or not r["phases"]:
            continue
        steps += r["steps"]
        for t0, t1, c in r["phases"].values():
            wall += t1 - t0
            cpu += c
    return (wall, cpu, steps) if steps else None


def window_span(records: List[Dict[str, Any]]
                ) -> Optional[Tuple[float, float]]:
    """From the first step's entry to the last thing known of the last
    step (its completion, or the end of its last phase)."""
    begins = [p[0] for p in records[0]["phases"].values()]
    ends = [p[1] for p in records[-1]["phases"].values()]
    if records[-1]["done_ns"] is not None:
        ends.append(records[-1]["done_ns"])
    if not begins or not ends:
        return None
    return float(min(begins)), float(max(ends))


def scalar_values(records: List[Dict[str, Any]], counter: str
                  ) -> List[float]:
    """The step's own ``counter`` on every step that reported it (a
    dispatch of K fused steps reports K)."""
    out: List[float] = []
    for r in records:
        v = r["scalars"].get(counter)
        if isinstance(v, list):
            out += [float(x) for x in v]
        elif v is not None:
            out.append(float(v))
    return out


def device_step_ends(data: tr.Trace, module_pattern: str, steps: int
                     ) -> Optional[List[float]]:
    """When device 0 finished each of the slice's ``steps`` steps, on
    the profile's clock: the end of the step's module event. A profile
    without a module lane (the CPU backend's) gives, for the
    instructions that ran exactly once a step, the latest end among
    their k-th runs: the device runs one step after the other."""
    span = tr.slice_window(data)
    if span is None:
        return None
    if data.modules:
        lane = tr.clip(data.modules[sorted(data.modules)[0]], *span)
        rx = re.compile(module_pattern)
        return [e.start + e.dur for e in lane if rx.search(e.name)]
    if not data.ops:
        return None
    runs: Dict[str, List[float]] = {}
    for e in tr.clip(data.ops[sorted(data.ops)[0]], *span):  # by start
        runs.setdefault(e.name, []).append(e.start + e.dur)
    once = [ends for ends in runs.values() if len(ends) == steps]
    if not once:
        return None
    return [max(ends[k] for ends in once) for k in range(steps)]


# -- the readers the metric files name ----------------------------------------

def _records(observed: Dict[str, Any], fn_pattern: str
             ) -> Optional[List[Dict[str, Any]]]:
    w = window(observed, fn_pattern)
    return w["records"] if w else None


def done_interval_ms(observed: Dict[str, Any], fn_pattern: str
                     ) -> Optional[float]:
    """Median interval between the completions of two steps, over the
    window's steps outside the profile."""
    records = _records(observed, fn_pattern)
    intervals = done_intervals(records) if records else []
    return statistics.median(intervals) / 1e6 if intervals else None


def done_excess_ms_max(observed: Dict[str, Any], fn_pattern: str
                       ) -> Optional[float]:
    """What the window's slowest step cost: the largest of the steps'
    costs (``step_costs``)."""
    records = _records(observed, fn_pattern)
    costs = step_costs(records) if records else []
    return max(costs) / 1e6 if costs else None


def phase_cpu_ms_per_step(observed: Dict[str, Any], fn_pattern: str
                          ) -> Optional[float]:
    """The dispatching thread's CPU time inside the three host phases,
    mean a step."""
    records = _records(observed, fn_pattern)
    times = phase_times(records) if records else None
    return times[1] / 1e6 / times[2] if times else None


def phase_wait_ms_per_step(observed: Dict[str, Any], fn_pattern: str
                           ) -> Optional[float]:
    """Wall less CPU inside the three host phases, mean a step: what
    the phases waited on (a transfer, the runtime's queue, a lock)."""
    records = _records(observed, fn_pattern)
    times = phase_times(records) if records else None
    return (times[0] - times[1]) / 1e6 / times[2] if times else None


def host_event_ms_per_step(observed: Dict[str, Any], fn_pattern: str,
                           event: str) -> Optional[float]:
    """Summed duration of the host events named ``event`` that began
    inside the window, per step of the window; 0.0 when none did."""
    w = window(observed, fn_pattern)
    span = window_span(w["records"]) if w else None
    if span is None:
        return None
    steps = sum(r["steps"] for r in w["records"])
    total = sum(e["end_ns"] - e["begin_ns"] for e in w["events"]
                if e["name"] == event and span[0] <= e["begin_ns"] <= span[1])
    return total / 1e6 / steps


def done_lag_ms(observed: Dict[str, Any], fn_pattern: str,
                module_pattern: str) -> Optional[float]:
    """The instrument's own lag, in the traced slice: median of a
    step's ``done_ns``, moved onto the profile's clock, less the end of
    that step on device 0. The offset between the clocks is the median
    difference of the dispatch phases, which the slice holds twice: as
    ``pt/train_step/dispatch`` annotations and in the records."""
    records = _records(observed, fn_pattern)
    view = program.view(observed, fn_pattern)
    if not records or view is None:
        return None
    steps = [r for r in records if r["profiled"]
             and r["done_ns"] is not None and "dispatch" in r["phases"]]
    spans = [a for a in view["annotations"] if a.name == DISPATCH_SPAN]
    if not steps or len(spans) != len(steps):
        log(f"done_lag_ms: {len(steps)} profiled records against "
            f"{len(spans)} dispatch spans in the slice: no reading")
        return None
    offset = statistics.median(
        a.start - r["phases"]["dispatch"][0] for a, r in zip(spans, steps))
    ends = device_step_ends(observed["trace"], module_pattern, len(steps))
    if not ends or len(ends) != len(steps):
        log(f"done_lag_ms: {len(ends or [])} step ends on device 0 "
            f"against {len(steps)} profiled records: no reading")
        return None
    lags = [(r["done_ns"] + offset - end) / 1e6
            for r, end in zip(steps, ends)]
    # what the main thread saw of the same thing: its fetch of the last
    # step's loss returned so long after that step ended on the device
    fetch = [a for a in view["annotations"] if a.name == "bench/loss_fetch"]
    log("done_lag_ms: by step " + " ".join(f"{x:.3f}" for x in lags)
        + (f"; the caller's own fetch returned "
           f"{(fetch[-1].start + fetch[-1].dur - ends[-1]) / 1e6:.3f} ms "
           "after the last step's end on device 0" if fetch else ""))
    return statistics.median(lags)


def scalar_max(observed: Dict[str, Any], fn_pattern: str, counter: str
               ) -> Optional[float]:
    """The largest value of the step's own ``counter`` over the window,
    every step seen."""
    records = _records(observed, fn_pattern)
    values = scalar_values(records, counter) if records else []
    return max(values) if values else None


def scalar_off_mode_share(observed: Dict[str, Any], fn_pattern: str,
                          counter: str) -> Optional[float]:
    """Share of the window's steps whose ``counter`` differs from the
    window's most common value."""
    records = _records(observed, fn_pattern)
    values = scalar_values(records, counter) if records else []
    if not values:
        return None
    mode = statistics.mode(values)
    return sum(v != mode for v in values) / len(values)
