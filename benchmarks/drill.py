#!/usr/bin/env python3
"""The drill: the driver's exact command for one cell, as fresh child
processes one after the other in one checkout, in the pattern of the
driver's check — a set of runs, a traced run, the set again with the
same seeds, a second traced run — never cleaning anything between runs.
It stops at the first non-zero exit, malformed line or ``correct:
false`` and keeps that child's whole output. A cell is proved by one
passing drill on the chip, and its numbers come from that drill.

The drill's parent never imports JAX: a process that has touched JAX
holds the chip, and a child that needs it would fail or hang.

    python3 benchmarks/drill.py --workload <cell> --out chiprun_out/drill_<cell>

With ``--sweep N`` it makes no child and is the seed sweep instead: N
seeds through the cell's ``correct`` checks in THIS process (the
compiled programs are shared, so a seed costs seconds), every check's
margin recorded and the worst of each reported. Every tolerance in a
configuration file is set from a sweep on the chip before the cell's
drill; ``PERF.md`` records the worst margins.

    python3 benchmarks/drill.py --workload <cell> --sweep 16 --seconds 20 \\
        --out chiprun_out/sweep_<cell>
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.arithmetic import spread  # noqa: E402 — no JAX in there
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# seeds the builder never used while writing the harness, the first
# beyond 32 signed bits as the driver's are
SEEDS = [2_147_483_659, 3_000_000_019, 4_294_967_311, 17, 123_456_789,
         8_589_934_609]
TRACE_SEEDS = [6_442_450_967, 31_337]


def last_json_line(text: str) -> Optional[Dict[str, Any]]:
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        return None
    try:
        doc = json.loads(lines[-1])
    except ValueError:
        return None
    return doc if isinstance(doc, dict) else None


def run_child(command: List[str], workload: str, seed: int,
              seconds: int, trace: int, out_dir: str, tag: str,
              timeout_s: float, extra: List[str]) -> Dict[str, Any]:
    """One run exactly as the driver makes it. Returns a record with
    ``ok`` false, and the child's whole output kept in ``out_dir``,
    when the run is not one the driver would take."""
    argv = command + ["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)
                      ] + extra
    env = dict(os.environ, BENCH_RUN=tag)
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(argv, cwd=ROOT, env=env, text=True,
                              capture_output=True, timeout=timeout_s)
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc = 124
        out = e.stdout if isinstance(e.stdout, str) else \
            (e.stdout or b"").decode(errors="replace")
        err = e.stderr if isinstance(e.stderr, str) else \
            (e.stderr or b"").decode(errors="replace")
    wall = time.perf_counter() - t0
    doc = last_json_line(out)
    why = None
    if rc != 0:
        why = f"exit code {rc}"
    elif doc is None:
        why = "the last line of stdout is not a JSON object"
    elif not RESULT_KEYS <= set(doc):
        why = f"the last line lacks {sorted(RESULT_KEYS - set(doc))}"
    elif doc["correct"] is not True:
        why = "correct is not true"
    elif not doc["metrics"]:
        why = "no metric reported"
    record = {"tag": tag, "seed": seed, "trace": trace, "rc": rc,
              "wall_s": wall, "ok": why is None, "why": why,
              "result": doc}
    with open(os.path.join(out_dir, f"{tag}.tail.txt"), "w") as f:
        f.write("\n".join(out.splitlines()[-60:]) + "\n")
    if why is not None:
        with open(os.path.join(out_dir, f"{tag}.FAILED.txt"), "w") as f:
            f.write(f"# {' '.join(argv)}\n# {why}; wall {wall:.1f}s\n"
                    f"## stdout\n{out}\n## stderr\n{err}\n")
    return record


def summarize(records: List[Dict[str, Any]], bounds: Dict[str, float]
              ) -> Dict[str, Any]:
    """Per end-to-end metric: each set's median and spread, the wider
    spread, and how far the second median lies from the first."""
    sets: Dict[str, Dict[str, List[float]]] = {}
    for r in records:
        if r["trace"] or not r["ok"]:
            continue
        which = r["tag"].split(".")[0]
        for name, m in r["result"]["metrics"].items():
            if m["value"] is not None:   # a rehearsal reports none
                sets.setdefault(name, {}).setdefault(which, []).append(
                    m["value"])
    out: Dict[str, Any] = {}
    for name, by_set in sets.items():
        row: Dict[str, Any] = {"bound": bounds.get(name)}
        for which, values in sorted(by_set.items()):
            # each side's first run compiles: setup_s leaves it out
            v = values[1:] if name == "setup_s" and len(values) > 2 \
                else values
            row[which] = {"values": values,
                          "median": statistics.median(v),
                          "spread": spread(v) if len(v) >= 2 else None}
        spreads = [row[w]["spread"] for w in by_set
                   if row[w]["spread"] is not None]
        row["widest_spread"] = max(spreads) if spreads else None
        if "set1" in row and "set2" in row:
            row["second_over_first"] = \
                row["set2"]["median"] / row["set1"]["median"] - 1.0
        out[name] = row
    return out


def sweep(workload: str, n_seeds: int, first_seed: int, seconds: float,
          out_dir: str, extra: List[str]) -> int:
    """``n_seeds`` runs of ``workload`` in this process, none stopped by
    a failed check; writes ``sweep.json`` and returns 1 if any seed
    failed one."""
    import gc
    import traceback

    from benchmarks import harness, manifest as mf

    rows = []
    t_start = time.perf_counter()
    for i in range(n_seeds):
        # odd strides over more than 32 bits: seeds nobody used before
        seed = first_seed + i * 2_654_435_761
        run = harness.Run(harness.parse_args(
            ["--workload", workload, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", "0"] + extra), time.perf_counter())
        run.sweeping = True
        if i == 0:
            harness.prepare_backend(run.rehearsal, run.chips)
        harness.find_devices(run)
        if i == 0:
            harness.enable_cache()
        t0 = time.perf_counter()
        row: Dict[str, Any] = {"seed": seed}
        try:
            observed = mf.bench_module("runners", run.mix["kind"]).run(run)
            row["end_to_end"] = observed["end_to_end"]
        except Exception as e:  # noqa: BLE001 — the sweep reports it
            traceback.print_exc()
            run.failures.append(f"{type(e).__name__}: {e}")
        finally:
            run.cleanup()
            gc.collect()      # the last seed's arrays leave the chip
        row.update(margins=dict(run.margins), failures=run.failures,
                   seconds=time.perf_counter() - t0)
        print(f"[drill] sweep seed {seed}: {json.dumps(row)}", flush=True)
        rows.append(row)
    keys = sorted({k for r in rows for k in r["margins"]})
    worst = {k: {"min": min(r["margins"][k] for r in rows
                            if k in r["margins"]),
                 "max": max(r["margins"][k] for r in rows
                            if k in r["margins"])} for k in keys}
    failed = [r["seed"] for r in rows if r["failures"]]
    report = {"workload": workload, "seeds": len(rows),
              "seconds_each": seconds, "margins": worst,
              "seeds_with_failures": failed,
              "total_seconds": time.perf_counter() - t_start}
    with open(os.path.join(out_dir, "sweep.json"), "w") as f:
        json.dump(dict(report, rows=rows), f, indent=1)
    print("[drill] sweep " + json.dumps(report), flush=True)
    return 1 if failed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True,
                    help="directory the chip tool brings back")
    ap.add_argument("--runs", type=int, default=6,
                    help="runs in each of the two sets (at least 3)")
    ap.add_argument("--seconds", type=int, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--timeout", type=float, default=1200.0)
    ap.add_argument("--sweep", type=int, default=0, metavar="N",
                    help="no drill: N seeds through the cell's checks "
                         "in this process")
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("extra", nargs="*",
                    help="further arguments for the child, after -- "
                         "(--rehearsal, in the tests)")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    os.makedirs(args.out, exist_ok=True)
    if args.sweep:
        return sweep(args.workload, args.sweep, args.first_seed, seconds,
                     args.out, args.extra)
    seeds = SEEDS[:args.runs]
    plan = [(f"set1.run{i}", s, 0) for i, s in enumerate(seeds)] \
        + [("trace1", TRACE_SEEDS[0], 1)] \
        + [(f"set2.run{i}", s, 0) for i, s in enumerate(seeds)] \
        + [("trace2", TRACE_SEEDS[1], 1)]
    records: List[Dict[str, Any]] = []
    passed = True
    for tag, seed, trace in plan:
        rec = run_child(bench["command"], args.workload, seed, seconds,
                        trace, args.out, tag, args.timeout, args.extra)
        records.append(rec)
        metrics = {k: v["value"] for k, v in
                   ((rec["result"] or {}).get("metrics") or {}).items()}
        print(f"[drill] {tag} seed {seed} trace {trace}: rc {rec['rc']} "
              f"wall {rec['wall_s']:.1f}s "
              f"{'ok' if rec['ok'] else 'FAILED: ' + rec['why']} "
              f"{json.dumps(metrics)}", flush=True)
        if not rec["ok"]:
            passed = False
            print(f"[drill] stopped; the child's whole output is in "
                  f"{args.out}/{tag}.FAILED.txt", flush=True)
            break
    report = {"workload": args.workload, "seconds": seconds,
              "passed": passed, "runs": records,
              "end_to_end": summarize(records, bounds)}
    with open(os.path.join(args.out, "drill.json"), "w") as f:
        json.dump(report, f, indent=1)
    for name, row in report["end_to_end"].items():
        print(f"[drill] {name}: " + json.dumps(
            {k: (v if not isinstance(v, dict) else
                 {"median": v["median"], "spread": v["spread"]})
             for k, v in row.items()}), flush=True)
    print(f"[drill] {args.workload}: "
          f"{'PASSED' if passed else 'FAILED'} "
          f"({len(records)} of {len(plan)} runs made)", flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
