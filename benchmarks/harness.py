"""What every run shares: the driver's flags, the device check, the
hermetic scratch directory, the checks that decide ``correct``, the
reduction of what a runner observed into the named metrics, and the
contract's last line."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

from . import manifest as mf

def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class CheckFailed(AssertionError):
    """A ``correct`` condition did not hold; carries its numbers."""


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for: the
    run prints no result."""


class NotACheckout(RuntimeError):
    """The system under test is not here (a directory that holds only
    the benchmark's own files): the run prints no result."""


class Run:
    """One run of one cell: its data files, its seed, its clock, its
    scratch directory and the verdicts of its checks."""

    def __init__(self, args, t_process_start: float) -> None:
        self.args = args
        self.t_process_start = t_process_start
        self.manifest = mf.Manifest()
        self.cell = self.manifest.cell(args.workload)
        self.rehearsal = bool(args.rehearsal)
        self.trace = bool(args.trace)
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.chips = int(self.cell["chips"])
        self.config = self.manifest.config(self.cell["config"],
                                           self.rehearsal)
        mix = dict(self.manifest.traffic(self.cell["traffic"]))
        if self.rehearsal:
            mix.update(mix.get("rehearsal", {}))
        self.mix = mix
        # a seed sweep records every check's margin and goes on
        self.sweeping = False
        self.failures: List[str] = []
        self.margins: Dict[str, float] = {}
        self.tmpdir: Optional[str] = None
        self.devices: list = []
        self.held_bytes: Dict[int, int] = {}
        self.setup_s: Optional[float] = None

    # -- scratch ------------------------------------------------------------

    def scratch(self, name: str) -> str:
        """A fresh directory of this run's own, outside the checkout
        (under ``TMPDIR``), removed when the run ends."""
        if self.tmpdir is None:
            self.tmpdir = tempfile.mkdtemp(prefix="ptbench_")
        return tempfile.mkdtemp(prefix=name + "_", dir=self.tmpdir)

    def cleanup(self) -> None:
        if self.tmpdir is not None:
            shutil.rmtree(self.tmpdir, ignore_errors=True)
            self.tmpdir = None

    # -- clock --------------------------------------------------------------

    def window_starts(self) -> float:
        """Called by the runner at the start of the measured window:
        everything before it is set-up."""
        self.sample_memory()
        now = time.perf_counter()
        self.setup_s = now - self.t_process_start
        log(f"window starts: setup_s={self.setup_s:.3f}")
        return now

    def sample_memory(self) -> None:
        """Keep, per chip, the largest ``bytes_in_use + bytes_reserved``
        that one ``memory_stats()`` call has read (see
        ``memory_peak_bytes``)."""
        for d in self.devices:
            stats = d.memory_stats() or {}
            held = int(stats.get("bytes_in_use", 0)) \
                + int(stats.get("bytes_reserved", 0))
            self.held_bytes[d.id] = max(self.held_bytes.get(d.id, 0), held)

    # -- checks -------------------------------------------------------------

    def check(self, ok: bool, what: str) -> None:
        """One condition of ``correct``. ``what`` names the check and
        holds its numbers; a failure ends the run and is the line
        before the last."""
        if not ok:
            if not self.sweeping:
                raise CheckFailed(what)
            self.failures.append(what)
            log(f"FAILED (sweep goes on): {what}")
            return
        log(f"ok: {what}")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="paddle_tpu benchmark: one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="same control flow on the CPU backend at the "
                         "tiny widths of <config>.rehearsal.json; "
                         "reports the CPU device and no metric value")
    return ap.parse_args(argv)


def prepare_backend(rehearsal: bool, chips: int) -> None:
    """Before jax is imported. A rehearsal pins the CPU backend (with
    ``chips`` virtual devices); a real run leaves the choice to JAX,
    which on a machine with a chip is the TPU."""
    if rehearsal:
        import re
        os.environ["JAX_PLATFORMS"] = "cpu"
        rest = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                      os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            f"{rest} --xla_force_host_platform_device_count="
            f"{max(chips, 1)}").strip()


def find_devices(run: Run) -> Dict[str, Any]:
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX found no device: {e}") from e
    platform = devices[0].platform
    if run.rehearsal:
        if platform != "cpu":
            raise NoAccelerator("--rehearsal is for the CPU backend")
    elif platform != "tpu":
        raise NoAccelerator(
            f"no TPU: JAX found {platform!r} devices (the tiny CPU "
            "walk-through takes --rehearsal)")
    if len(devices) < run.chips:
        raise NoAccelerator(
            f"cell {run.cell['name']} needs {run.chips} chips, JAX "
            f"found {len(devices)}")
    run.devices = devices[:run.chips]
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_cache() -> str:
    """JAX's persistent cache where ``sysconfig.enable_compile_cache``
    puts it (``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed
    ``<checkout>/.jax_cache``), every executable kept, and the
    package's persistent-cache mode on so a train step holds no host
    callback and is itself persisted."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.sysconfig import enable_compile_cache
    enable_compile_cache(min_compile_secs=0.0)
    cache_dir = jax.config.jax_compilation_cache_dir
    pt.set_flags({"compile_cache_dir": cache_dir})
    n, size = 0, 0
    for base, _, files in os.walk(cache_dir):
        for f in files:
            path = os.path.join(base, f)
            if os.path.exists(path):   # not another run's vanished entry
                size += os.path.getsize(path)
                n += 1
    log(f"compile cache {cache_dir}: {n} files, {size / 2**20:.1f} MiB "
        "before this run")
    return cache_dir


def start_trace(trace_dir: str) -> None:
    """Start the profiler for a bounded slice. The Python tracer is
    off: the benchmark's own ``TraceAnnotation`` spans and the device
    lanes are all the reduction reads."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def read_trace(trace_dir: str) -> Dict[str, Any]:
    """The slice just profiled, as the readers take it. The newest
    profile under the run's own directory, whatever else is there; its
    size is logged."""
    from . import trace as tr
    path, found = tr.newest_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    data = tr.read_xplane(path)
    log(f"trace {path}: {data.size_bytes} bytes ({len(found)} "
        f"profile(s) in the directory), truncated={data.truncated}")
    return {"trace": data, "trace_summary": tr.summarize(data)}


def memory_peak_bytes(run: Run) -> int:
    """The most the fullest chip was seen to hold. The TPU runtime
    keeps two accounts, and what is free is the limit less both:
    ``bytes_in_use`` (live arrays: parameters, optimizer state) and
    ``bytes_reserved`` (the scratch a loaded program keeps while it is
    loaded: a train step's activations are there, so it grows with the
    batch and ``peak_bytes_in_use`` does not). One ``memory_stats()``
    call reads both at one instant, so their sum is a moment that
    happened, never two peaks from different moments. Such a reading is
    taken when the window starts and when it has ended; the larger of
    them and of ``peak_bytes_in_use`` is reported, and every part is
    logged."""
    run.sample_memory()
    peak = 0
    for d in run.devices:
        stats = d.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        held = run.held_bytes.get(d.id, 0)
        log(f"device {d.id}: held at one instant (bytes_in_use + "
            f"bytes_reserved) {held}; peak_bytes_in_use {in_use}, "
            f"peak_bytes_reserved {stats.get('peak_bytes_reserved')}, "
            f"largest_free_block_bytes "
            f"{stats.get('largest_free_block_bytes')} of bytes_limit "
            f"{stats.get('bytes_limit')}")
        peak = max(peak, held, in_use)
    return peak


def reduce_metrics(run: Run, observed: Dict[str, Any]) -> Dict[str, Dict]:
    """The line's ``metrics``: the cell's end-to-end metrics (taken by
    the runner itself) without a trace, its per-layer metrics (each
    through the reader its file names) with one."""
    out: Dict[str, Dict] = {}
    if not run.trace:
        values = dict(observed["end_to_end"], setup_s=run.setup_s)
        for m in run.manifest.metrics_of(run.cell["name"], "end_to_end"):
            if values.get(m["name"]) is None:
                raise CheckFailed(
                    f"end-to-end metric {m['name']} was not measured")
            out[m["name"]] = {"value": values[m["name"]],
                              "unit": m["unit"]}
        return out
    for m in run.manifest.metrics_of(run.cell["name"], "per_layer"):
        spec = run.manifest.metric_file(m["name"])
        reader: Callable = mf.resolve(spec["reader"])
        value = reader(observed, **spec.get("args", {}))
        if value is None:
            log(f"per-layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, t_process_start: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t_process_start is None \
        else t_process_start
    args = parse_args(argv)
    result: Dict[str, Any] = {"correct": False, "attempted": 0,
                              "failed": 0, "metrics": {}, "device": None}
    run: Optional[Run] = None
    failure = None
    try:
        run = Run(args, t0)
        prepare_backend(run.rehearsal, run.chips)
        try:
            import paddle_tpu  # noqa: F401 — the system under test
        except ImportError as e:
            raise NotACheckout(f"paddle_tpu cannot be imported: {e}") \
                from e
        result["device"] = find_devices(run)
        log(f"cell {run.cell['name']} seed {run.seed} seconds "
            f"{run.seconds:g} trace {int(run.trace)} device "
            f"{result['device']}")
        enable_cache()
        runner = mf.bench_module("runners", run.mix["kind"])
        observed = runner.run(run)
        result["attempted"] = int(observed["attempted"])
        result["failed"] = int(observed["failed"])
        metrics = reduce_metrics(run, observed)
        result["device"]["memory_peak_bytes"] = memory_peak_bytes(run)
        if run.trace:
            summary = observed.get("trace_summary") or {}
            result["device"]["busy_s"] = summary.get("busy_s")
            result["device"]["window_s"] = summary.get("window_s")
            result["breakdown"] = {
                "device_ops": summary.get("device_ops", []),
                "idle_gaps": summary.get("idle_gaps", [])}
            run.check(bool(summary.get("busy_s")),
                      f"the traced slice saw device work: busy_s="
                      f"{summary.get('busy_s')} window_s="
                      f"{summary.get('window_s')}")
        if run.rehearsal:
            log("rehearsal: readers gave a value for: "
                + ",".join(sorted(metrics)))
            # a CPU number is never written under a metric's name
            metrics = {k: {"value": None, "unit": v["unit"]}
                       for k, v in metrics.items()}
            result["device"].update(memory_peak_bytes=0)
            for k in ("busy_s", "window_s"):
                if k in result["device"]:
                    result["device"][k] = None
            result.pop("breakdown", None)
        result["metrics"] = metrics
        result["correct"] = True
    except (NoAccelerator, NotACheckout) as e:
        # no result line: there is nothing this machine can report
        print(f"[bench] cannot run: {e}", file=sys.stderr, flush=True)
        return 3
    except Exception as e:  # noqa: BLE001 — reported on the last lines
        failure = e
    finally:
        if run is not None:
            run.cleanup()
    if failure is not None:
        traceback.print_exception(type(failure), failure,
                                  failure.__traceback__)
        sys.stderr.flush()
        print(f"[bench] FAILED {type(failure).__name__}: {failure}",
              flush=True)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1
