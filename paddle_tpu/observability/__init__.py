"""Framework-wide telemetry.

TPU-native rebuild of the reference's three-part observability stack
(/root/reference/paddle/fluid/platform/: profiler.h RecordEvent spans +
chrome-trace output, device_tracer.cc CUPTI timelines, monitor.h stat
registry) as one subsystem:

- :mod:`metrics`   — typed counters/gauges/histograms with labeled
  series, Prometheus text exposition + JSON snapshot (absorbs the old
  ``profiler.StatRegistry``).
- :mod:`tracer`    — nestable, thread-aware host spans exported as
  Chrome ``traceEvents`` JSON (Perfetto/TensorBoard-loadable), each
  span forwarded to ``jax.profiler.TraceAnnotation`` so host and XLA
  timelines line up.
- :mod:`recompile` — jit cache hit/trace accounting, per-function
  compile latency, triggering shapes, recompile-storm warnings.
- :mod:`trace_agg` — chrome/perfetto trace parsing + the
  reference-style aggregated summary tables (shared by
  tools/profile_step.py and tools/trace_report.py).

Everything instrument-shaped is gated on ``FLAGS_enable_metrics``: off
(the default) is a near-free early return on every hot path; the old
explicit user APIs (``profiler.RecordEvent``/``stat_add``) stay
always-on because calling them is its own opt-in.
"""

from __future__ import annotations

import warnings
from typing import Any, Dict, Optional

from . import (anomaly, fleet, flight, goodput, metrics, recompile,
               reqtrace, rotation, seqtrace, server, slo, stacks,
               stepprof, trace_agg, tracer, tsdb, xprof)
from .anomaly import sentinel as anomaly_sentinel
from .flight import recorder as flight_recorder
from .goodput import ledger as goodput_ledger
from .metrics import (counter, enabled, gauge, histogram, registry,
                      set_enabled)
from .recompile import instrumented_jit
from .recompile import tracker as recompile_tracker
from .tracer import export_chrome_trace, span
from .tracer import tracer as get_tracer
from .xprof import cards as program_cards

__all__ = ["metrics", "tracer", "recompile", "trace_agg", "xprof",
           "anomaly", "server", "goodput", "flight", "rotation",
           "fleet", "reqtrace", "seqtrace", "stepprof", "tsdb", "slo",
           "stacks",
           "counter", "gauge", "histogram", "registry", "enabled",
           "set_enabled", "span", "export_chrome_trace", "get_tracer",
           "instrumented_jit", "recompile_tracker", "program_cards",
           "anomaly_sentinel", "native_stats", "goodput_ledger",
           "flight_recorder",
           "observe_traced", "device_memory_stats", "export_all",
           "reset_all"]

_mem_warned = False

# bytes_in_use plus the extra allocator fields ``full=True`` reports
_FULL_MEM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def device_memory_stats(include_unavailable: bool = False,
                        full: bool = False) -> Dict[str, Any]:
    """Per-device allocator stats (analogue of the reference's
    memory/stats + gpu_info mem flags).

    Default: ``{device: bytes_in_use}``. With ``full=True`` each device
    maps to ``{bytes_in_use, peak_bytes_in_use, bytes_limit}`` (fields
    the backend does not report are 0) — the true high-watermark and
    headroom the fit() memory gauges need.

    Backends without allocator stats (CPU returns None) are skipped, or
    reported as 0/zeros with ``include_unavailable=True`` (so dashboards
    keep the series). A backend that *errors* is surfaced with a
    one-time warning instead of being silently swallowed.
    """
    global _mem_warned
    import jax

    def empty():
        return {k: 0 for k in _FULL_MEM_KEYS} if full else 0

    out: Dict[str, Any] = {}
    for d in jax.local_devices():
        try:
            ms = d.memory_stats()
        except (RuntimeError, NotImplementedError, AttributeError) as e:
            if not _mem_warned:
                _mem_warned = True
                warnings.warn(
                    f"device_memory_stats: {d} raised "
                    f"{type(e).__name__}: {e} — memory series will be "
                    "missing for this backend (warning shown once)",
                    RuntimeWarning)
            if include_unavailable:
                out[str(d)] = empty()
            continue
        if ms:
            if full:
                out[str(d)] = {k: int(ms.get(k, 0))
                               for k in _FULL_MEM_KEYS}
            else:
                out[str(d)] = int(ms.get("bytes_in_use", 0))
        elif include_unavailable:
            out[str(d)] = empty()
    return out


def native_stats() -> Dict[str, int]:
    """Snapshot of the native stat registry (csrc/monitor.cc) — the
    bridge that makes ``pt_mon_add`` counters from data_feed.cc /
    ps_service.cc / serving.cc readable from Python. Returns {} when
    the native library has not been loaded (never triggers a build)."""
    try:
        from .. import native as _native
        if not _native.loaded():
            return {}
        return _native.stat_dump()
    except Exception:  # noqa: BLE001 — telemetry must not raise
        return {}


def observe_traced(name: str, value: Any, kind: str = "gauge") -> None:
    """Record a TRACED scalar into a host metric.

    For values that only exist inside a jitted computation (e.g. the
    global grad norm computed by the clip). Inserts a
    ``jax.debug.callback`` into the traced program — only when
    FLAGS_enable_metrics is on at trace time, so the compiled program
    carries zero callback overhead when metrics are off. Flipping the
    flag after compilation does not retrace: the callback presence is
    baked in at trace time (documented in docs/observability.md).
    """
    if not metrics.enabled():
        return
    import jax
    if kind == "counter":
        inst = metrics.counter(name)
        jax.debug.callback(lambda v: inst.inc(float(v)), value)
    else:
        inst = metrics.gauge(name)
        jax.debug.callback(lambda v: inst.set(float(v)), value)


def export_all(path: Optional[str] = None) -> Dict[str, str]:
    """Write the host chrome trace, the step timeline
    (``step_timeline.jsonl``) + snapshots under ``path`` (default
    FLAGS_trace_dir); returns written paths. Emits both the JSON
    snapshot (``metrics.json``: metrics + recompile + program cards +
    native stats) and the Prometheus text exposition (``metrics.prom``)
    so offline runs and scraped runs produce the same artifact."""
    import json
    import os
    if path is None:
        from ..flags import GLOBAL_FLAGS
        path = GLOBAL_FLAGS.get("trace_dir") or "/tmp/pt_trace"
    os.makedirs(path, exist_ok=True)
    out = {"trace": get_tracer().export(path),
           "timeline": get_tracer().export_timeline(path)}
    goodput_ledger().publish()
    snap = {"metrics": registry().snapshot(),
            "recompile": recompile_tracker().snapshot(),
            "programs": program_cards().snapshot(),
            "goodput": goodput_ledger().snapshot(),
            "native_stats": native_stats()}
    mpath = os.path.join(path, "metrics.json")
    with open(mpath, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True, default=str)
    out["metrics"] = mpath
    from .server import metrics_text
    ppath = os.path.join(path, "metrics.prom")
    with open(ppath, "w") as f:
        f.write(metrics_text())
    out["prometheus"] = ppath
    return out


def reset_all() -> None:
    """Clear metrics, spans and the step timeline (stopping its watcher
    thread), recompile records, program cards, anomaly
    state, the goodput ledger, the flight buffer, the request-span /
    seq-timeline / step-record rings, the fleet aggregator store, the
    tsdb sample ring (stopping its sampler thread), the SLO alert
    engine, and the hang-doctor plane (stack sampler + monitor
    stopped, profile cleared) (tests/new runs)."""
    registry().reset()
    get_tracer().reset()
    recompile_tracker().reset()
    program_cards().reset()
    xprof.reset_kernel_notes()
    anomaly_sentinel().reset()
    goodput_ledger().reset()
    flight_recorder().reset()
    reqtrace.ring().reset()
    seqtrace.ring().reset()
    stepprof.ring().reset()
    fleet.aggregator().reset()
    tsdb.stop()
    tsdb.ring().reset()
    slo.engine().reset()
    stacks.reset()
