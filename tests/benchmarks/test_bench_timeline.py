"""The readers of ``benchmarks/readers/timeline.py`` on hand-built
records of the program's step timeline, the nothing they read from a
checkout without one, and what they give in a ``--rehearsal --trace 1``
run of the one command."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import manifest as mf
from benchmarks import trace as tr
from benchmarks.readers import program, timeline

M = mf.Manifest()
FN = "TrainStep(Bert)"
NEW = ["train.step_done_interval_ms", "train.step_done_excess_ms_max",
       "train.entry_host_cpu_ms_per_step",
       "train.entry_host_wait_ms_per_step", "train.host_gc_ms_per_step",
       "train.step_done_lag_ms", "train.moe_windows_run_max",
       "train.moe_steps_off_mode_share"]
DECODERS = ["nemotron3_nano_ep16_s8k", "sdar_30b_a3b_ep8_s8k"]
MS = 1_000_000


def record(step, began_ms, done_ms, profiled=False, windows=6, steps=1,
           cpu_ms=(1.0, 2.0, 0.5)):
    """A step entered at ``began_ms``: make_batch 2 ms, dispatch 5 ms,
    drain 1 ms of wall, back to back, the thread's CPU time inside each
    ``cpu_ms``; done on the device at ``done_ms``."""
    t = began_ms * MS
    phases = {}
    for name, wall, cpu in zip(("make_batch", "dispatch", "drain"),
                               (2, 5, 1), cpu_ms):
        phases[name] = (t, t + wall * MS, int(cpu * MS))
        t += wall * MS
    return {"fn": FN, "step": step, "steps": steps, "profiled": profiled,
            "tid": 1, "seq": step, "phases": phases,
            "done_ns": None if done_ms is None else int(done_ms * MS),
            "scalars": {"loss": 2.0, "moe_windows_run": float(windows)}}


def hand_window():
    """Nine steps, 100 ms apart on the device but for one that took 350
    (step 4, which also ran a seventh window); steps 6 and 7 were
    dispatched under the profile, so the intervals into 6, 7 and 8 do
    not count."""
    done = [1100, 1200, 1300, 1650, 1750, 1850, 1950, 2050, 2150]
    records = [record(i + 1, 1000 + 10 * i, d,
                      profiled=i in (5, 6), windows=7 if i == 3 else 6)
               for i, d in enumerate(done)]
    events = [
        {"name": "pt/host/gc", "begin_ns": 1400 * MS, "end_ns": 1418 * MS,
         "tid": 1, "generation": 2, "fn": FN, "step": 4},
        {"name": "pt/host/gc", "begin_ns": 1500 * MS, "end_ns": 1509 * MS,
         "tid": 1, "generation": 0, "fn": FN, "step": 4},
        {"name": "pt/host/compile", "begin_ns": 1505 * MS,
         "end_ns": 1506 * MS, "tid": 1, "what": "cache_load"},
        # before the window began, and after it ended
        {"name": "pt/host/gc", "begin_ns": 900 * MS, "end_ns": 950 * MS,
         "tid": 1, "generation": 2},
        {"name": "pt/host/gc", "begin_ns": 2200 * MS, "end_ns": 2300 * MS,
         "tid": 1, "generation": 2},
    ]
    return {"records": records, "events": events}


def hand_slice(records, offset_ns=5_000 * MS, lag_ns=(200_000, 400_000)):
    """The profile of the traced group: the profiled records' dispatch
    phases as annotations on a clock ``offset_ns`` ahead, and a module
    event a step on device 0 that ends ``lag_ns`` before the record's
    ``done_ns`` on that clock."""
    profiled = [r for r in records if r["profiled"]]
    annotations, modules = [], []
    for r, lag in zip(profiled, lag_ns):
        t0, t1, _ = r["phases"]["dispatch"]
        annotations.append(tr.Event("pt/train_step/dispatch",
                                    float(t0 + offset_ns), float(t1 - t0)))
        end = r["done_ns"] + offset_ns - lag
        modules.append(tr.Event("jit__step(123)", float(end - 90 * MS),
                                float(90 * MS)))
    lo = annotations[0].start - 1 * MS
    hi = modules[-1].start + modules[-1].dur + 1 * MS
    modules.append(tr.Event("jit__other(9)", lo + 1, 10.0))
    trace = tr.Trace({"/device:TPU:0": []}, {"/device:TPU:0": modules},
                     [tr.Event("bench/slice", lo, hi - lo)], False,
                     "hand-built", 0)
    return trace, annotations


@pytest.fixture
def observed():
    w = hand_window()
    trace, annotations = hand_slice(w["records"])
    return {"attempted": 9, "trace": trace,
            "counters": {"steps_per_group": 3, "trace_steps": 2},
            timeline._WINDOW_KEY: w,
            program._VIEW_KEY: {"events": [], "annotations": annotations,
                                "charge": None, "fn": FN}}


def read(observed, name):
    spec = M.metric_file(name)
    return mf.resolve(spec["reader"])(observed, **spec["args"])


WANT = {
    # intervals that count: 100, 100, 350, 100 (into steps 2-5), 100
    # (into 9); the median of five
    "train.step_done_interval_ms": 100.0,
    "train.step_done_excess_ms_max": 250.0,
    # seven steps outside the profile, 3.5 ms of CPU in 8 ms of wall
    "train.entry_host_cpu_ms_per_step": 3.5,
    "train.entry_host_wait_ms_per_step": 4.5,
    # the two collections inside the window, over nine steps
    "train.host_gc_ms_per_step": 27.0 / 9,
    # the median of the two steps' lags
    "train.step_done_lag_ms": 0.3,
    "train.moe_windows_run_max": 7.0,
    "train.moe_steps_off_mode_share": 1 / 9,
}


@pytest.mark.parametrize("name", NEW)
def test_each_reader_on_hand_built_records(observed, name):
    assert read(observed, name) == pytest.approx(WANT[name], abs=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_a_checkout_without_a_timeline_gives_none(monkeypatch, name):
    from paddle_tpu import observability as obs

    class OldTracer:
        def events(self):
            return []
    monkeypatch.setattr(obs, "get_tracer", lambda: OldTracer())
    observed = {"attempted": 9, "counters": {"steps_per_group": 3}}
    assert read(observed, name) is None
    # and none where the window holds no step of the entry point
    assert read({"attempted": 0}, name) is None


def test_the_files_and_their_cells():
    by_name = {m["name"]: m for m in M.doc["per_layer"]}
    cells = [w["name"] for w in M.doc["workloads"]]
    for name in NEW:
        entry, spec = by_name[name], M.metric_file(name)
        assert spec["reader"].startswith("benchmarks.readers.timeline.")
        assert entry["moves"] == "train_tokens_per_s"
        want = DECODERS if "moe_" in name else cells
        assert entry["workloads"] == want
        assert entry["layer"] == ("train step program" if "moe_" in name
                                  else "train entry")
        assert entry["source"] == ("program_counter" if "moe_" in name
                                   else "program_span")
    # appended: nothing that was there moved
    assert [m["name"] for m in M.doc["per_layer"]][-len(NEW):] == NEW


def test_fused_steps_count_as_their_k(observed):
    records = [record(1, 0, 100, steps=4), record(2, 10, 500, steps=4)]
    records[1]["scalars"]["moe_windows_run"] = [6.0, 6.0, 8.0, 6.0]
    assert timeline.phase_times(records) == (16.0 * MS, 7.0 * MS, 8)
    assert timeline.scalar_values(records, "moe_windows_run") == [
        6.0, 6.0, 6.0, 8.0, 6.0]
    assert timeline.done_intervals(records) == [400.0 * MS]
    records[0]["done_ns"] = None            # a step that failed
    assert timeline.done_intervals(records) == []


def test_a_late_stamp_costs_nothing():
    """One interval long and the next short by as much is a stamp that
    came late, not a step the device was late with."""
    done = [100, 200, 300, 481, 500, 600, 730, 830, 930]
    records = [record(i + 1, 10 * i, d) for i, d in enumerate(done)]
    assert [c / MS for c in timeline.step_costs(records)] == [
        0, 0, 0, -81, 0, 30, 0, 0]
    w = {"records": records, "events": []}
    observed = {"attempted": 9, timeline._WINDOW_KEY: w}
    assert read(observed, "train.step_done_excess_ms_max") == 30.0
    assert read(observed, "train.step_done_interval_ms") == 100.0
    # the window's last interval has no successor and counts whole
    assert read({"attempted": 4, timeline._WINDOW_KEY: {
        "records": records[:4], "events": []}},
        "train.step_done_excess_ms_max") == 81.0


def test_a_profile_without_a_module_lane_reads_the_ops(observed):
    """The CPU backend's profile: instructions that ran once a step,
    the step's end the latest end among their k-th runs."""
    ops = [tr.Event("a.1", 10, 5), tr.Event("b.2", 12, 30),
           tr.Event("a.1", 100, 5), tr.Event("b.2", 101, 9),
           tr.Event("loop.3", 20, 1), tr.Event("loop.3", 22, 1),
           tr.Event("loop.3", 24, 1)]
    trace = tr.Trace({"/host:CPU": ops}, {},
                     [tr.Event("bench/slice", 0, 200)], False, "hand", 0)
    assert timeline.device_step_ends(trace, "^jit__step", 2) == [42, 110]
    assert timeline.device_step_ends(trace, "^jit__step", 4) is None
    # unequal counts on the two clocks: nothing is read
    observed[program._VIEW_KEY]["annotations"].pop()
    assert read(observed, "train.step_done_lag_ms") is None


def test_the_window_is_asked_of_the_program_once_and_logged(capsys):
    import paddle_tpu as pt
    from paddle_tpu import observability as obs
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        tracer = obs.get_tracer()
        for i, r in enumerate(hand_window()["records"]):
            phases = tracer.step(FN if i else "TrainStep(Other).multi",
                                 r["step"])
            phases.record.update(
                phases=r["phases"], done_ns=r["done_ns"],
                scalars=r["scalars"], profiled=r["profiled"])
        observed = {"attempted": 8,
                    "counters": {"steps_per_group": 4}}
        w = timeline.window(observed, "TrainStep\\(Bert")
        assert [r["step"] for r in w["records"]] == list(range(2, 10))
        assert timeline.window(observed, "ignored") is w
        said = [ln for ln in capsys.readouterr().out.splitlines()
                if "step timeline:" in ln]
        assert len(said) == 1
        assert "step 4 (3 of 4 in its group)" in said[0]
        assert "excess 250.000" in said[0] and "host ahead" in said[0]
        assert "moe_windows_run=7 (median 6, max 7" in said[0]
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()


@pytest.mark.parametrize("cell", ["bert_base_s512", "sdar_30b_a3b_ep8_s8k"])
def test_rehearsal_gives_a_value_for_every_new_metric(tmp_path, cell):
    """The one command, traced, on the CPU backend, with a compile
    cache of its own (``test_bench_program_readers`` says why)."""
    env = dict(os.environ, PYTHONPATH=mf.ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", str(2 ** 40 + 1), "--seconds", "1", "--trace", "1",
         "--rehearsal"], cwd=mf.ROOT, env=env, text=True,
        capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    doc = json.loads(lines[-1])
    assert doc["correct"] is True
    listed = {m["name"] for m in M.metrics_of(cell, "per_layer")}
    assert set(NEW) & listed <= set(doc["metrics"])
    assert all(m["value"] is None for m in doc["metrics"].values())
    # the slowest step twice: by the reader on stdout, and by the
    # program when the runner switched metrics off, on stderr
    assert sum("[bench] step timeline:" in ln for ln in lines) == 1
    assert sum(ln.startswith("step timeline:")
               for ln in proc.stderr.splitlines()) == 1
    assert lines[-1].startswith("{")
