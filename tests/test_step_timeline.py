"""The step timeline inside the train entry points
(``observability/tracer.py``, docs/observability.md "The step
timeline"): one record a dispatch under one step number and one clock,
the host's collections and compiles as events, the slowest-step report
when metrics go off, and nothing at all while metrics are off."""

import gc
import json
import logging
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.observability import tracer as pt_tracer
from paddle_tpu.static import TrainStep

WATCHER = "pt-step-timeline"
SPANS = ["pt/train_step/" + p for p in pt_tracer.PHASES]


class _MLP(pt.nn.Layer):
    def __init__(self):
        super().__init__()
        self.a = pt.nn.Linear(8, 16)
        self.b = pt.nn.Linear(16, 4)

    def forward(self, x):
        return self.b(pt.nn.functional.relu(self.a(x)))


X = np.linspace(-1, 1, 32, dtype=np.float32).reshape(4, 8)
Y = np.array([0, 1, 2, 3])


def _hits(out, y):
    return (out.argmax(-1) == y).sum()


def _step(kind="TrainStep"):
    args = (_MLP(), pt.optimizer.SGD(learning_rate=1e-2),
            pt.nn.CrossEntropyLoss())
    if kind == "TrainStep":
        return TrainStep(*args, extra_metrics={"hits": _hits})
    from paddle_tpu.parallel import ShardedTrainStep, create_mesh
    return ShardedTrainStep(*args, create_mesh({"dp": -1}),
                            extra_metrics={"hits": _hits})


def _watchers():
    return [t for t in threading.enumerate() if t.name == WATCHER]


def _settled(step, n):
    """``n`` calls, the last one's results fetched: every record of
    them is done once the watcher has seen the last."""
    for _ in range(n):
        metrics = step(X, labels=Y)
    float(metrics["loss"])
    deadline = time.monotonic() + 20
    tracer = obs.get_tracer()
    while time.monotonic() < deadline:
        records = tracer.timeline(step._span_name)
        if records and records[-1]["done_ns"] is not None \
                and records[-1]["scalars"]:
            return records
        time.sleep(0.005)
    raise AssertionError("the watcher never stamped the last step")


@pytest.fixture
def metrics_on():
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    yield
    pt.set_flags({"enable_metrics": False})
    obs.reset_all()


@pytest.mark.parametrize("kind", ["TrainStep", "ShardedTrainStep"])
def test_a_record_a_call_under_one_step_number(metrics_on, kind):
    step = _step(kind)
    records = _settled(step, 4)
    assert [r["step"] for r in records] == [1, 2, 3, 4]
    assert {r["fn"] for r in records} == {step._span_name}
    for r in records:
        assert r["steps"] == 1 and r["profiled"] is False
        # the sharded entry point drains only a deferred verdict
        assert list(r["phases"])[:2] == ["make_batch", "dispatch"]
        assert set(r["phases"]) <= set(pt_tracer.PHASES)
        last = 0
        for t0, t1, cpu in r["phases"].values():
            assert last <= t0 <= t1 and 0 <= cpu
            last = t1
        # the watcher is handed a step after its dispatch returned
        assert r["done_ns"] >= r["phases"]["dispatch"][1]
        assert r["read_ns"] >= r["done_ns"]
        assert set(r["scalars"]) == {"loss", "hits"}
        assert np.isfinite(r["scalars"]["loss"])
        assert r["scalars"]["hits"] == int(r["scalars"]["hits"])
    done = [r["done_ns"] for r in records]
    assert done == sorted(done)
    # the spans are what they were: three names, fn on the dispatch
    events = [e for e in obs.get_tracer().events()
              if e["name"].startswith("pt/")]
    assert {e["name"] for e in events} <= set(SPANS)
    assert [e.get("args") for e in events[:2]] == [
        None, {"fn": step._span_name}]


def test_the_jitted_call_is_timed_from_the_dispatch_stamp(metrics_on):
    step = _step()
    tracer = obs.get_tracer()
    seen = []
    real = step._jitted._jitted

    def spy(*a, **k):
        seen.append(tracer.dispatch_began_ns())
        return real(*a, **k)
    object.__setattr__(step._jitted, "_jitted", spy)
    records = _settled(step, 3)
    assert seen == [r["phases"]["dispatch"][0] for r in records]
    assert tracer.dispatch_began_ns() is None       # outside a phase
    rec = obs.recompile_tracker().get(step._span_name)
    assert rec.calls == 3 and rec.hits == 2
    # the trace's time runs from that stamp to the call's return
    (dt,) = rec.compile_times_s
    t0, t1, _ = records[0]["phases"]["dispatch"]
    assert 0 < dt <= (t1 - t0) / 1e9


def test_run_steps_writes_one_record_of_k_steps(metrics_on):
    step = _step()
    k = 3
    metrics = step.run_steps(np.stack([X] * k), labels=np.stack([Y] * k))
    assert metrics["loss"].shape == (k,)
    float(metrics["loss"][-1])
    fn = step._span_name + ".multi"
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        (record,) = obs.get_tracer().timeline(fn)
        if record["scalars"]:
            break
        time.sleep(0.005)
    assert record["steps"] == k and record["step"] == 1
    assert len(record["scalars"]["loss"]) == k
    assert len(record["scalars"]["hits"]) == k
    assert obs.counter("optimizer_steps_total").total() == k


def test_the_ring_wraps_at_capacity_without_growing(metrics_on):
    tracer = obs.get_tracer()
    for n in range(pt_tracer.TIMELINE_CAPACITY + 50):
        tracer.step("wrap", n)
        tracer.host_event("pt/host/gc", n, n + 1)
    records = tracer.timeline("wrap")
    assert len(records) == pt_tracer.TIMELINE_CAPACITY
    assert records[0]["step"] == 50 and records[-1]["step"] == \
        pt_tracer.TIMELINE_CAPACITY + 49
    assert len(tracer.host_events()) == pt_tracer.TIMELINE_CAPACITY
    assert tracer.timeline("wrap", last=7) == records[-7:]


def test_a_late_host_and_a_collection_are_named(metrics_on, caplog):
    """A planted sleep between two calls is the window's slowest step
    and a late host; a planted collection inside a slower call is named
    by its ``pt/host/gc`` event."""
    step = _step()
    _settled(step, 3)                       # compiled, steady
    step(X, labels=Y)
    time.sleep(0.2)
    _settled(step, 3)
    found = obs.get_tracer().slowest_step(step._span_name)
    assert found["step"] == 5 and found["host"] == "late"
    assert found["late_in"] == "between_calls"
    assert found["late_wall_ms"] >= 190 and found["excess_ms"] >= 150
    assert found["scalars"]["hits"]["off_mode_share"] == 0.0
    assert "max" not in found["scalars"]["loss"]
    with caplog.at_level(logging.WARNING, "paddle_tpu.observability"):
        pt.set_flags({"enable_metrics": False})
        said = [r.getMessage() for r in caplog.records
                if "step timeline" in r.getMessage()]
        # the readers switch the flag on and off again: said once
        pt.set_flags({"enable_metrics": True})
        pt.set_flags({"enable_metrics": False})
    again = [r.getMessage() for r in caplog.records
             if "step timeline" in r.getMessage()]
    assert len(said) == 1 and again == said
    assert "step 5" in said[0] and "host late" in said[0]
    assert "between_calls" in said[0] and step._span_name in said[0]

    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    step = _step()
    _settled(step, 3)
    real = step._make_batch

    def collecting(*a):
        gc.collect()
        return real(*a)
    step._make_batch = collecting
    step(X, labels=Y)
    step._make_batch = real
    _settled(step, 2)
    found = obs.get_tracer().slowest_step(step._span_name)
    assert found["step"] == 4
    assert found["host"] == "late" and found["late_in"] == "make_batch"
    (gcs,) = [e for e in found["events"] if e["name"] == "pt/host/gc"]
    assert gcs["count"] >= 1 and gcs["longest"]["generation"] == 2
    assert 0 < gcs["longest_ms"] <= gcs["ms"]
    assert "pt/host/gc" in pt_tracer.format_slowest_step(found)
    # every event fell in a step, and names the entry point
    for e in obs.get_tracer().host_events():
        assert e["fn"] == step._span_name and e["step"] >= 1


def test_a_late_stamp_is_not_a_slow_step():
    """A stamp that came late makes one interval long and the next
    short by as much: the step after gave it back, and it costs
    nothing; a step the device was late with is followed by a whole
    interval and is the slowest."""
    ms = 1_000_000

    def rec(step, done):
        began = 10 * step
        return {"fn": "f", "step": step, "steps": 1, "profiled": False,
                "phases": {"dispatch": (began, began + 5, 1)},
                "done_ns": done * ms, "read_ns": done * ms + 1,
                "scalars": {}}
    done = [100, 200, 300, 481, 500, 600, 730, 830, 930]
    records = [rec(i + 1, d) for i, d in enumerate(done)]
    intervals = pt_tracer.done_intervals(records)
    assert [iv // ms for _, iv in intervals] == [
        100, 100, 181, 19, 100, 130, 100, 100]
    costs = pt_tracer.step_costs(intervals, 100 * ms)
    assert [(c // ms, b // ms) for _, c, b in costs] == [
        (0, 0), (0, 0), (0, 81), (-81, 0), (0, 0), (30, 0), (0, 0), (0, 0)]
    found = pt_tracer.slowest_step(records, [])
    assert found["step"] == 7 and found["excess_ms"] == 30.0
    assert found["given_back_ms"] == 0.0 and found["host"] == "ahead"
    assert found["late_stamps"] == 1
    assert found["late_stamp_longest_ms"] == 81.0
    assert found["late_stamp_longest_step"] == 4
    said = pt_tracer.format_slowest_step(found)
    assert "1 late stamp(s)" in said and "81.000 ms at step 4" in said
    # alone, the late stamp is the longest interval and still no cost
    found = pt_tracer.slowest_step(records[:5], [])
    assert found["excess_ms"] == 0.0 and found["late_stamps"] == 1


def test_a_step_of_nan_does_not_break_the_report(metrics_on, caplog):
    step = _step()
    records = _settled(step, 4)
    tracer = obs.get_tracer()
    with tracer._lock:
        tracer._timeline[2]["scalars"] = {"loss": float("nan"),
                                          "hits": float("nan")}
    found = tracer.slowest_step(step._span_name)
    assert found is not None and "max" not in found["scalars"].get(
        "hits", {})
    with caplog.at_level(logging.WARNING, "paddle_tpu.observability"):
        pt.set_flags({"enable_metrics": False})
    assert any("slowest of" in r.getMessage() for r in caplog.records)
    assert len(records) == 4


def test_compiles_are_events_of_the_timeline(metrics_on):
    step = _step()
    _settled(step, 2)
    compiles = [e for e in obs.get_tracer().host_events()
                if e["name"] == "pt/host/compile"]
    whats = {e["what"] for e in compiles}
    assert "trace" in whats
    assert whats & {"backend_compile", "cache_load"}
    (trace,) = [e for e in compiles if e["what"] == "trace"
                and e.get("of") == step._span_name]
    first = obs.get_tracer().timeline(step._span_name)[0]
    t0, t1, _ = first["phases"]["dispatch"]
    assert t0 <= trace["begin_ns"] <= trace["end_ns"] <= t1
    # the report leaves the compiling step out: its stretch starts after
    stretch = pt_tracer._steady_stretch(
        obs.get_tracer().timeline(step._span_name) + [
            dict(first, step=3, phases={
                "dispatch": (t1 + 10, t1 + 20, 5)})],
        obs.get_tracer().host_events())
    assert [r["step"] for r in stretch] == [2, 3]


def test_metrics_off_takes_no_stamp_and_starts_nothing():
    obs.reset_all()
    assert not obs.enabled()
    callbacks = list(gc.callbacks)
    step = _step()
    for _ in range(3):
        metrics = step(X, labels=Y)
    float(metrics["loss"])
    tracer = obs.get_tracer()
    assert tracer.step("any", 1) is pt_tracer._NO_PHASES
    assert tracer.timeline() == [] and tracer.host_events() == []
    assert not _watchers() and gc.callbacks == callbacks
    assert tracer.dispatch_began_ns() is None
    assert step._dispatches == 3           # its own count goes on
    assert not [e for e in tracer.events() if e["name"].startswith("pt/")]


def test_going_off_and_reset_all_take_the_timeline_down():
    obs.reset_all()
    callbacks = list(gc.callbacks)
    pt.set_flags({"enable_metrics": True})
    try:
        assert not _watchers()              # live at the first record
        step = _step()
        _settled(step, 2)
        (watcher,) = _watchers()
        assert watcher.daemon
        assert len(gc.callbacks) == len(callbacks) + 1
        pt.set_flags({"enable_metrics": False})
        assert not _watchers() and not watcher.is_alive()
        assert gc.callbacks == callbacks
        # the rings stay for whoever reads them next
        assert len(obs.get_tracer().timeline(step._span_name)) == 2
        pt.set_flags({"enable_metrics": True})
        _settled(step, 1)
        assert len(_watchers()) == 1
        obs.reset_all()
        assert not _watchers() and gc.callbacks == callbacks
        assert obs.get_tracer().timeline() == []
        assert obs.get_tracer().host_events() == []
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()


def test_annotations_under_a_profile_carry_the_step(metrics_on, tmp_path):
    from jax.profiler import ProfileData

    from benchmarks import trace as tr
    step = _step()
    _settled(step, 2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    assert not jax.profiler.TraceAnnotation.is_enabled()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert jax.profiler.TraceAnnotation.is_enabled()
        step(X, labels=Y)
        gc.collect()
        records = _settled(step, 1)
    finally:
        jax.profiler.stop_trace()
    assert [r["profiled"] for r in records] == [False, False, True, True]
    path, _ = tr.newest_xplane(str(tmp_path))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("pt/"):
                    seen.setdefault(e.name, []).append(dict(e.stats))
    with_prefix = {n for n in seen if n.startswith("pt/train_step/")}
    assert with_prefix == set(SPANS)        # the three, and no other
    assert [s["step"] for s in seen["pt/step_done"]] == [3, 4]
    assert {s["fn"] for s in seen["pt/step_done"]} == {step._span_name}
    assert any(s.get("generation") == 2 for s in seen["pt/host/gc"])


def test_export_all_and_the_flight_dump_carry_the_timeline(metrics_on,
                                                           tmp_path):
    step = _step()
    _settled(step, 3)
    gc.collect()
    out = obs.export_all(str(tmp_path))
    rows = [json.loads(ln) for ln in open(out["timeline"])]
    assert out["timeline"].endswith("step_timeline.jsonl")
    steps = [r for r in rows if r["kind"] == "step"]
    assert [r["step"] for r in steps] == [1, 2, 3]
    assert all(len(r["phases"]["dispatch"]) == 3 for r in steps)
    assert any(r["kind"] == "event" and r["name"] == "pt/host/gc"
               for r in rows)
    path = obs.flight.dump("test", str(tmp_path))
    dumped = [json.loads(ln) for ln in open(path)]
    marks = [r for r in dumped if r["kind"] == "step_record"]
    assert [r["step"] for r in marks] == [1, 2, 3]
    assert marks[-1]["fn"] == step._span_name and marks[-1]["done_ns"]
