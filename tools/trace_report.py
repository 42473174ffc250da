"""Merge a host chrome-trace with an optional XLA device trace and
print the reference-style aggregated span summary.

Usage:
    python tools/trace_report.py [TRACE_DIR] [--xla DIR_OR_GLOB]
                                 [--top K] [--self-test]

TRACE_DIR (default: FLAGS_trace_dir or /tmp/pt_trace) is what
``paddle_tpu.observability.export_all()`` / ``hapi.Model.fit`` with
FLAGS_trace_dir wrote: ``host_trace.json`` (chrome traceEvents) and
``metrics.json`` (metrics + recompile snapshot), and
``step_timeline.jsonl`` (the train entry points' step records and host
events: its slowest steps are tabled). With ``--xla`` (or
when XLA ``*.trace.json.gz`` files sit under TRACE_DIR, e.g. a
jax.profiler capture into the same directory), device op events join
the same table prefixed ``xla::`` and the device-op category rollup is
printed too.

``--self-test`` exercises the whole path without a TPU (or any
accelerator work): synthesizes spans, exports, re-parses, prints the
table, exits 0 — the CI hook for this tool.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu.observability import trace_agg  # noqa: E402


def _load_host_events(trace_dir: str):
    path = os.path.join(trace_dir, "host_trace.json")
    if os.path.isfile(trace_dir) and trace_dir.endswith(".json"):
        path = trace_dir
    if not os.path.exists(path):
        return None, path
    return trace_agg.load_trace_events(path), path


def _print_metrics_snapshot(trace_dir: str) -> None:
    mpath = os.path.join(trace_dir, "metrics.json")
    if not os.path.exists(mpath):
        return
    with open(mpath) as f:
        snap = json.load(f)
    metrics = snap.get("metrics", {})
    if metrics:
        print("\n== metrics snapshot ==")
        for name in sorted(metrics):
            m = metrics[name]
            for s in m.get("series", []):
                labels = ",".join(f"{k}={v}" for k, v in
                                  sorted(s.get("labels", {}).items()))
                tag = f"{name}{{{labels}}}" if labels else name
                if m.get("type") == "histogram":
                    cnt, tot = s.get("count", 0), s.get("sum", 0.0)
                    avg = tot / cnt if cnt else 0.0
                    print(f"  {tag:<52} count={cnt} sum={tot:.6g} "
                          f"avg={avg:.6g}")
                else:
                    print(f"  {tag:<52} {s.get('value')}")
    recomp = snap.get("recompile", {})
    if recomp:
        print("\n== jit recompile report ==")
        for name in sorted(recomp):
            r = recomp[name]
            n_sig = len(r.get("signatures", []))
            comp = sum(r.get("compile_times_s", []))
            print(f"  {name:<48} traces={r['traces']} "
                  f"hits={r['hits']} shapes={n_sig} "
                  f"compile_s={comp:.3f}")
    programs = snap.get("programs", {})
    if programs:
        print("\n== compiled-program cards ==")
        for name in sorted(programs):
            for sig, card in programs[name].items():
                if card.get("unavailable"):
                    print(f"  {name:<40} {sig[:40]:<42} "
                          f"unavailable: {card['unavailable']}")
                    continue
                flops = card.get("flops", 0.0)
                peak = card.get("peak_bytes_estimate", 0)
                print(f"  {name:<40} {sig[:40]:<42} "
                      f"flops={flops:.4g} "
                      f"bytes={card.get('bytes_accessed', 0):.4g} "
                      f"peak_mem={peak / 1e6:.3f}MB")
    native = snap.get("native_stats", {})
    if native:
        print("\n== native stats (pt_mon) ==")
        for k in sorted(native):
            print(f"  {k:<52} {native[k]}")


def _print_step_timeline(trace_dir: str, top: int = 5) -> None:
    """The slowest steps of each train entry point in
    ``step_timeline.jsonl`` (the tracer's step timeline): the longest
    intervals between two completions against the median, then what
    the timeline knows of the slowest one."""
    import statistics

    from paddle_tpu.observability import tracer as pt_tracer
    path = os.path.join(trace_dir, "step_timeline.jsonl")
    if not os.path.exists(path):
        return
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    events = [r for r in rows if r["kind"] == "event"]
    by_fn = {}
    for r in rows:
        if r["kind"] == "step":
            by_fn.setdefault(r["fn"], []).append(r)
    if by_fn:
        print("\n== step timeline: slowest steps ==")
    for fn in sorted(by_fn):
        records = by_fn[fn]
        intervals = pt_tracer.done_intervals(records)
        if not intervals:
            print(f"  {fn}: {len(records)} record(s), no two completed "
                  "steps to compare")
            continue
        median = statistics.median(iv for _, iv in intervals)
        print(f"  {fn}: {len(records)} records, median interval "
              f"{median / 1e6:.3f} ms")
        print(f"    {'step':>8} {'interval ms':>12} {'excess ms':>10}")
        for i, iv in sorted(intervals, key=lambda x: -x[1])[:top]:
            print(f"    {records[i]['step']:>8} {iv / 1e6:>12.3f} "
                  f"{(iv - median) / 1e6:>10.3f}")
        print("    " + pt_tracer.format_slowest_step(
            pt_tracer.slowest_step(records, events)))


def report(trace_dir: str, xla: str = "", top: int = 30) -> int:
    host_events, host_path = _load_host_events(trace_dir)
    summary = {}
    if host_events is None:
        print(f"note: no host trace at {host_path}", file=sys.stderr)
    else:
        summary.update(trace_agg.span_summary(host_events))

    # device side: explicit --xla dir/file, else any capture under
    # trace_dir
    xla_paths = []
    if xla:
        xla_paths = [xla] if os.path.isfile(xla) \
            else trace_agg.find_xla_traces(xla)
    elif os.path.isdir(trace_dir):
        xla_paths = trace_agg.find_xla_traces(trace_dir)
    if xla_paths:
        xla_events = trace_agg.load_trace_events(xla_paths[-1])
        try:
            rollup = trace_agg.xla_op_rollup(xla_events)
            print(trace_agg.format_xla_rollup(rollup, top=top))
            print()
            for name, op in rollup["ops"].items():
                summary["xla::" + name] = {
                    "calls": op["count"], "total_us": op["dur_us"],
                    "max_us": 0.0,
                    "avg_us": op["dur_us"] / max(op["count"], 1)}
        except trace_agg.TraceFormatError as e:
            print(f"warning: {e}", file=sys.stderr)

    if not summary:
        print("no spans found — run with FLAGS_enable_metrics=1 and "
              "FLAGS_trace_dir set (or pass a trace directory)",
              file=sys.stderr)
        return 1
    print(trace_agg.format_span_table(summary, top=top,
                                      title="merged span summary"))
    _print_metrics_snapshot(trace_dir)
    _print_step_timeline(trace_dir)
    return 0


def self_test() -> int:
    """No-TPU smoke: synthesize spans + metrics, export, re-report."""
    import tempfile
    import time

    from paddle_tpu import observability as obs

    with tempfile.TemporaryDirectory() as d:
        tr = obs.get_tracer()
        for i in range(3):
            with tr.span("selftest/step", force=True):
                with tr.span("selftest/inner", force=True):
                    time.sleep(0.001)
        obs.counter("selftest_total", always=True).inc(3)
        obs.export_all(d)
        rc = report(d)
        if rc != 0:
            return rc
        summary = trace_agg.span_summary(
            trace_agg.load_trace_events(
                os.path.join(d, "host_trace.json")))
        assert summary["selftest/step"]["calls"] == 3, summary
        assert summary["selftest/inner"]["total_us"] > 0, summary
    print("\nself-test OK")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir", nargs="?", default="")
    ap.add_argument("--xla", default="",
                    help="XLA profiler dir or *.trace.json.gz file")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--self-test", action="store_true")
    args = ap.parse_args()
    if args.self_test:
        return self_test()
    trace_dir = args.trace_dir
    if not trace_dir:
        from paddle_tpu.flags import GLOBAL_FLAGS
        trace_dir = GLOBAL_FLAGS.get("trace_dir") or "/tmp/pt_trace"
    return report(trace_dir, xla=args.xla, top=args.top)


if __name__ == "__main__":
    sys.exit(main())
