"""Observability subsystem tests: metrics registry (threads, labels,
exposition), span tracer (nesting, chrome-trace schema), recompile
tracker (hit/miss, storm warning), hot-path instrumentation smoke
(hapi.Model.fit with FLAGS_enable_metrics=1), the profiler compat shim,
and the tools/trace_report.py CLI self-test.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu import profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def metrics_on():
    pt.set_flags({"enable_metrics": True})
    try:
        yield
    finally:
        pt.set_flags({"enable_metrics": False, "trace_dir": ""})
        obs.reset_all()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics(metrics_on):
    c = obs.counter("t_requests_total", "help text")
    c.inc()
    c.inc(2, route="train")
    assert c.value() == 1
    assert c.value(route="train") == 2
    # idempotent registration returns the same instrument
    assert obs.counter("t_requests_total") is c
    with pytest.raises(TypeError):
        obs.gauge("t_requests_total")

    g = obs.gauge("t_gauge")
    g.set(3.5)
    g.set_max(2.0)          # watermark keeps 3.5
    assert g.value() == 3.5
    g.set_max(9.0)
    assert g.value() == 9.0

    h = obs.histogram("t_lat_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 4
    snap = obs.registry().snapshot()
    hs = snap["t_lat_seconds"]["series"][0]
    assert hs["buckets"]["0.1"] == 1
    assert hs["buckets"]["1.0"] == 2
    assert hs["buckets"]["10.0"] == 3
    assert hs["buckets"]["+Inf"] == 4
    assert hs["sum"] == pytest.approx(55.55)
    assert snap["t_requests_total"]["type"] == "counter"


def test_disabled_is_noop_and_always_overrides():
    # flag is off (default): gated instruments drop writes
    assert not obs.enabled()
    c = obs.counter("t_gated_total")
    c.inc(5)
    assert c.value() == 0
    a = obs.counter("t_always_total", always=True)
    a.inc(5)
    assert a.value() == 5
    h = obs.histogram("t_gated_seconds")
    h.observe(1.0)
    assert h.count() == 0
    obs.reset_all()


def test_flag_toggles_enabled_cache():
    assert not obs.enabled()
    pt.set_flags({"enable_metrics": True})
    assert obs.enabled()
    pt.set_flags({"enable_metrics": False})
    assert not obs.enabled()


def test_metrics_under_threads(metrics_on):
    c = obs.counter("t_mt_total")
    h = obs.histogram("t_mt_seconds")

    def work():
        for _ in range(500):
            c.inc()
            h.observe(0.01, worker="w")

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value() == 4000
    assert h.count(worker="w") == 4000
    assert h.sum(worker="w") == pytest.approx(40.0)


def test_prometheus_text_exposition(metrics_on):
    obs.counter("t_pc_total", "a counter").inc(3, op="x")
    obs.histogram("t_ph_seconds", buckets=(1.0,)).observe(0.5)
    text = obs.registry().prometheus_text()
    assert "# TYPE t_pc_total counter" in text
    assert 't_pc_total{op="x"} 3' in text
    assert 't_ph_seconds_bucket{le="1.0"} 1' in text
    assert 't_ph_seconds_count 1' in text


def test_gauge_holds_device_array_without_sync(metrics_on):
    g = obs.gauge("t_dev_gauge")
    g.set(jnp.float32(2.5))  # stored as-is; float()ed only at snapshot
    snap = obs.registry().snapshot()
    assert snap["t_dev_gauge"]["series"][0]["value"] == 2.5


# ---------------------------------------------------------------------------
# span tracer + chrome trace schema
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_schema(metrics_on, tmp_path):
    tr = obs.get_tracer()
    tr.reset()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    with tr.span("outer"):
        pass
    summary = tr.summary()
    assert summary["outer"]["calls"] == 2
    assert summary["inner"]["calls"] == 1

    path = tr.export(str(tmp_path))
    with open(path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    for e in xs:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["dur"] >= 0 and e["ts"] >= 0
    assert any(e["name"] == "process_name" for e in ms)
    assert any(e["name"] == "thread_name" for e in ms)
    # nesting: inner fully contained in its outer span
    inner = next(e for e in xs if e["name"] == "inner")
    outer = max((e for e in xs if e["name"] == "outer"),
                key=lambda e: e["dur"])
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_span_disabled_records_nothing():
    assert not obs.enabled()
    tr = obs.get_tracer()
    tr.reset()
    with tr.span("gated"):
        pass
    assert tr.events() == []
    with tr.span("forced", force=True):
        pass
    assert [e["name"] for e in tr.events()] == ["forced"]
    tr.reset()


def test_span_threads_get_distinct_tids(metrics_on):
    tr = obs.get_tracer()
    tr.reset()
    # hold all threads alive inside their span: thread idents are
    # reused once a thread exits, which would alias tids
    gate = threading.Barrier(3)

    def work():
        with tr.span("threaded"):
            gate.wait(timeout=10)

    ts = [threading.Thread(target=work) for _ in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    tids = {e["tid"] for e in tr.events()}
    assert len(tids) == 3


# ---------------------------------------------------------------------------
# recompile tracker
# ---------------------------------------------------------------------------

def test_recompile_tracker_hits_and_traces(metrics_on):
    @pt.jit.to_static
    def f(x):
        return x * 2 + 1

    f(jnp.ones((3,)))
    f(jnp.ones((3,)))          # cache hit
    f(jnp.ones((4,)))          # new shape -> retrace
    # records are keyed by qualname ("to_static:<qualname>.f")
    name = next(n for n in obs.recompile_tracker().snapshot()
                if n.startswith("to_static:") and n.endswith(".f"))
    st = obs.recompile_tracker().get(name).stats()
    assert st["traces"] == 2
    assert st["hits"] == 1
    assert st["calls"] == 3
    assert len(st["signatures"]) == 2
    assert "float32[3]" in st["signatures"][0]
    assert len(st["compile_times_s"]) == 2
    assert obs.counter("jit_traces_total").value(fn=name) == 2
    assert obs.counter("jit_cache_hits_total").value(fn=name) == 1


def test_recompile_storm_warning(metrics_on):
    pt.set_flags({"recompile_warn_threshold": 2})
    try:
        @pt.jit.to_static
        def g(x):
            return x + 1

        g(jnp.ones((2,)))
        with pytest.warns(RuntimeWarning, match="recompilation storm"):
            g(jnp.ones((5,)))
        # warned once only
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error", RuntimeWarning)
            g(jnp.ones((7,)))
    finally:
        pt.set_flags({"recompile_warn_threshold": 8})


def test_instrumented_jit_preserves_lower(metrics_on):
    f = obs.instrumented_jit(lambda x: x + 1, "t_lower")
    hlo = f.lower(jnp.ones((2,))).compile().as_text()
    assert hlo  # attribute passthrough works


# ---------------------------------------------------------------------------
# profiler compat shim
# ---------------------------------------------------------------------------

def test_profiler_compat_record_event_and_summary():
    profiler.reset_host_events()
    with profiler.RecordEvent("compat_span"):
        pass
    events = profiler.get_host_events()
    assert events and events[0]["name"] == "compat_span"
    assert "dur_s" in events[0] and "ts" in events[0]
    summary = profiler.event_summary()
    assert summary["compat_span"]["calls"] == 1
    assert set(summary["compat_span"]) >= {"calls", "total_s", "avg_s",
                                           "max_s"}
    profiler.reset_host_events()


def test_profiler_compat_stats():
    profiler.stat_add("t_compat_stat", 3)
    profiler.stat_add("t_compat_stat")
    assert profiler.stats.get("t_compat_stat") == 4
    profiler.stats.set("t_compat_stat", 10)
    assert profiler.stats.get("t_compat_stat") == 10
    assert profiler.stats.snapshot()["t_compat_stat"] == 10


def test_steptimer_stop_without_start_returns_zero():
    t = profiler.StepTimer(items_per_step=8)
    assert t.stop() == 0.0
    assert t.times == []          # the bogus sample is not recorded


def test_steptimer_throughput_single_sample_not_double_counted():
    t = profiler.StepTimer(items_per_step=8)
    t.times = [10.0]              # only the warmup/compile sample
    assert t.throughput(skip_first=1) == 0.0
    t.times = [10.0, 1.0, 1.0]
    assert t.throughput(skip_first=1) == pytest.approx(8.0)


def test_device_memory_stats():
    out = obs.device_memory_stats()
    assert isinstance(out, dict)
    out_all = obs.device_memory_stats(include_unavailable=True)
    assert len(out_all) >= 1     # CPU devices report 0 rather than vanish
    assert all(isinstance(v, int) for v in out_all.values())


# ---------------------------------------------------------------------------
# trace aggregation (shared with tools/)
# ---------------------------------------------------------------------------

def _fake_xla_events():
    return [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 3,
         "args": {"name": "XLA Modules"}},
        {"ph": "X", "name": "fusion.1", "pid": 1, "tid": 2, "ts": 0,
         "dur": 100.0, "args": {"hlo_category": "convolution"}},
        {"ph": "X", "name": "fusion.1", "pid": 1, "tid": 2, "ts": 200,
         "dur": 100.0, "args": {"hlo_category": "convolution"}},
        {"ph": "X", "name": "copy.2", "pid": 1, "tid": 2, "ts": 300,
         "dur": 50.0, "args": {"hlo_category": "copy"}},
        {"ph": "X", "name": "module", "pid": 1, "tid": 3, "ts": 0,
         "dur": 400.0},
        {"ph": "X", "name": "module", "pid": 1, "tid": 3, "ts": 400,
         "dur": 400.0},
    ]


def test_xla_op_rollup():
    from paddle_tpu.observability import trace_agg
    rollup = trace_agg.xla_op_rollup(_fake_xla_events())
    assert rollup["ops"]["fusion.1"] == {"dur_us": 200.0, "count": 2}
    assert rollup["categories"] == {"convolution": 200.0, "copy": 50.0}
    assert rollup["total_us"] == 250.0
    assert rollup["steps"] == 2
    text = trace_agg.format_xla_rollup(rollup, top=5)
    assert "convolution" in text and "ms/step" in text


def test_xla_op_rollup_refuses_without_lane_metadata():
    from paddle_tpu.observability import trace_agg
    events = [e for e in _fake_xla_events()
              if e.get("args", {}).get("name") != "XLA Ops"]
    with pytest.raises(trace_agg.TraceFormatError):
        trace_agg.xla_op_rollup(events)


def test_span_summary_and_table():
    from paddle_tpu.observability import trace_agg
    events = [
        {"ph": "X", "name": "step", "ts": 0, "dur": 10.0},
        {"ph": "X", "name": "step", "ts": 20, "dur": 30.0},
        {"ph": "M", "name": "process_name"},
    ]
    s = trace_agg.span_summary(events)
    assert s["step"] == {"calls": 2, "total_us": 40.0, "max_us": 30.0,
                         "avg_us": 20.0}
    table = trace_agg.format_span_table(s, top=10)
    assert "step" in table and "calls" in table


# ---------------------------------------------------------------------------
# instrumented hot paths
# ---------------------------------------------------------------------------

class _MLP(pt.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = pt.nn.Linear(8, 16)
        self.fc2 = pt.nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(pt.nn.functional.relu(self.fc1(x)))


def _loader(n=96, batch=32):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = rng.integers(0, 4, n).astype(np.int64)
    return pt.data.DataLoader(pt.data.TensorDataset(x, y),
                              batch_size=batch)


def test_fit_smoke_populates_metrics(metrics_on, tmp_path):
    """Tier-1-safe CPU smoke: one fit with FLAGS_enable_metrics=1 must
    populate step-time, throughput, recompile and device-memory series
    (the ISSUE acceptance criteria)."""
    from paddle_tpu.clip import ClipGradByGlobalNorm
    pt.set_flags({"trace_dir": str(tmp_path)})
    m = pt.hapi.Model(_MLP())
    m.prepare(optimizer=pt.optimizer.Adam(
                  learning_rate=1e-2,
                  grad_clip=ClipGradByGlobalNorm(1.0)),
              loss=pt.nn.CrossEntropyLoss())
    m.fit(_loader(), epochs=1, verbose=0)

    snap = obs.registry().snapshot()
    # step-time histogram: one sample per step (96/32 = 3 steps)
    assert snap["hapi_step_time_seconds"]["series"][0]["count"] == 3
    assert snap["hapi_throughput_items_per_sec"]["series"][0]["value"] > 0
    assert snap["hapi_loss"]["series"][0]["value"] > 0
    assert any(s["labels"].get("device")
               for s in snap["device_mem_bytes_in_use"]["series"])
    assert snap["optimizer_steps_total"]["series"][0]["value"] == 3
    # recompile series: the train step traced exactly once
    traces = {s["labels"]["fn"]: s["value"]
              for s in snap["jit_traces_total"]["series"]}
    assert traces.get("TrainStep(_MLP)") == 1
    hits = {s["labels"]["fn"]: s["value"]
            for s in snap["jit_cache_hits_total"]["series"]}
    assert hits.get("TrainStep(_MLP)") == 2
    # grad-norm gauge (clipping on -> debug callback recorded a value)
    assert snap["grad_global_norm"]["series"][0]["value"] > 0
    # data pipeline instrumentation
    assert snap["data_batches_total"]["series"][0]["value"] == 3
    # trace_dir export happened at train end
    assert os.path.exists(tmp_path / "host_trace.json")
    assert os.path.exists(tmp_path / "metrics.json")
    with open(tmp_path / "metrics.json") as f:
        dumped = json.load(f)
    assert "hapi_step_time_seconds" in dumped["metrics"]
    assert "TrainStep(_MLP)" in dumped["recompile"]


def test_trace_report_on_fit_output(metrics_on, tmp_path, capsys):
    """ISSUE acceptance: trace_report on a 3-step CPU fit run prints a
    non-empty per-span summary table."""
    pt.set_flags({"trace_dir": str(tmp_path)})
    m = pt.hapi.Model(_MLP())
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=1e-2),
              loss=pt.nn.CrossEntropyLoss())
    m.fit(_loader(), epochs=1, verbose=0)

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import trace_report
        rc = trace_report.report(str(tmp_path))
    finally:
        sys.path.pop(0)
    out = capsys.readouterr().out
    assert rc == 0
    assert "TrainStep(_MLP)" in out           # the recompile report
    assert "merged span summary" in out
    # the entry point's three host spans (they replaced the one span
    # named after the step)
    for phase in ("make_batch", "dispatch", "drain"):
        assert f"pt/train_step/{phase}" in out
    # and the step timeline's slowest-step table
    assert "step timeline: slowest steps" in out
    assert "TrainStep(_MLP) slowest of" in out
    assert "hapi_step_time_seconds" in out


def test_fit_disabled_adds_no_metrics():
    assert not obs.enabled()
    obs.reset_all()
    m = pt.hapi.Model(_MLP())
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=1e-2),
              loss=pt.nn.CrossEntropyLoss())
    m.fit(_loader(n=32), epochs=1, verbose=0)
    snap = obs.registry().snapshot()
    assert "hapi_step_time_seconds" not in snap
    assert obs.get_tracer().events() == []
    obs.reset_all()


def test_dataloader_and_reader_instrumentation(metrics_on):
    list(_loader(n=64, batch=16))
    assert obs.counter("data_batches_total").value() == 4
    assert obs.histogram("data_batch_wait_seconds").count() == 4

    r = pt.reader.batch(lambda: iter(range(10)), 3)
    n = sum(1 for _ in r())
    assert n == 4
    assert obs.counter("reader_batches_total").value() == 4
    buf = pt.reader.buffered(lambda: iter(range(5)), 2)
    assert list(buf()) == [0, 1, 2, 3, 4]
    assert obs.histogram("reader_buffer_wait_seconds").count() > 0


def test_collective_accounting(metrics_on):
    from paddle_tpu.parallel import collective
    n = jax.local_device_count()
    f = jax.pmap(lambda x: collective.all_reduce(x, group="dp"),
                 axis_name="dp")
    out = f(jnp.ones((n, 4), jnp.float32))
    assert out.shape == (n, 4)
    # accounted once per TRACE, not per execution
    assert obs.counter("collective_calls_total").value(
        op="all_reduce") == 1
    assert obs.counter("collective_bytes_total").value(
        op="all_reduce") == 16  # per-shard payload: 4 x float32


def test_eager_optimizer_step_counter(metrics_on):
    lin = pt.nn.Linear(4, 2)
    opt = pt.optimizer.SGD(learning_rate=0.1,
                           parameters=lin.parameters())
    grads = [jnp.ones_like(p.value) for p in lin.parameters()
             if p.trainable]
    opt.step(grads)
    assert obs.counter("optimizer_steps_total").value() == 1


def test_trace_report_self_test_subprocess():
    """CI hook: the CLI must pass its self-test without a TPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_report.py"),
         "--self-test"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "self-test OK" in proc.stdout


# ---------------------------------------------------------------------------
# live HTTP exporter (/metrics /healthz /varz /trace)
# ---------------------------------------------------------------------------

def _get(port, path, timeout=10):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.fixture
def http_server(metrics_on):
    """Exporter on an ephemeral port; torn down with flags reset."""
    srv = obs.server.start(0)
    try:
        yield srv
    finally:
        obs.server.stop()


def test_http_endpoints_during_fit(metrics_on, tmp_path):
    """ISSUE acceptance: with FLAGS_enable_metrics=1 and
    FLAGS_metrics_port=0 (ephemeral bind — the parallel-test-safe
    default), GET /metrics DURING a CPU fit returns Prometheus text
    with the step-time histogram, recompile counters and the anomaly
    counter; /varz carries a program card with non-empty analyses (or
    an explicit unavailable marker)."""
    pt.set_flags({"metrics_port": 0, "trace_dir": str(tmp_path)})
    pages = {}

    class Probe(pt.hapi.Callback):
        def on_batch_end(self, step, logs=None):
            if step == 1 and not pages:
                port = obs.server.get().port
                pages["metrics"] = _get(port, "/metrics")

    try:
        m = pt.hapi.Model(_MLP())
        m.prepare(optimizer=pt.optimizer.SGD(learning_rate=1e-2),
                  loss=pt.nn.CrossEntropyLoss())
        m.fit(_loader(), epochs=1, verbose=0, callbacks=[Probe()])

        code, text = pages["metrics"]
        assert code == 200
        assert "hapi_step_time_seconds_bucket" in text
        assert "jit_traces_total" in text
        assert "anomalies_total" in text          # registered at trace time
        assert "train_heartbeat_timestamp_seconds" in text
        assert "# TYPE hapi_step_time_seconds histogram" in text

        port = obs.server.get().port
        code, text = _get(port, "/varz")
        assert code == 200
        varz = json.loads(text)
        cards = varz["programs"]
        name = next(n for n in cards if n.startswith("TrainStep"))
        card = list(cards[name].values())[0]
        assert (card.get("cost_analysis") or card.get("memory_analysis")
                or card.get("unavailable"))
        assert "device_memory" in varz and "recompile" in varz
        # the achieved-FLOPs gauge derived from the card (CPU has a
        # cost model, so it must be present and positive here)
        g = obs.gauge("achieved_flops_per_sec")
        assert g.value() and g.value() > 0
    finally:
        pt.set_flags({"metrics_port": 0})
        obs.server.stop()


def test_healthz_ok_and_wedged(http_server):
    code, text = _get(http_server.port, "/healthz")
    assert code == 200 and json.loads(text)["status"] == "ok"
    # a stale heartbeat must flip the endpoint to 503 (wedged loop)
    obs.gauge(obs.server.HEARTBEAT_GAUGE).set(
        __import__("time").time() - 10_000)
    code, text = _get(http_server.port, "/healthz")
    body = json.loads(text)
    assert code == 503 and body["wedged"] is True, body


def test_trace_window_endpoint(http_server):
    import threading as _t
    stop = _t.Event()

    def spin():
        while not stop.is_set():
            with obs.span("windowed"):
                pass

    th = _t.Thread(target=spin, daemon=True)
    th.start()
    try:
        code, text = _get(http_server.port, "/trace?ms=100")
    finally:
        stop.set()
        th.join(timeout=5)
    assert code == 200
    trace = json.loads(text)
    assert trace["metadata"]["window_ms"] == 100
    assert any(e.get("name") == "windowed"
               for e in trace["traceEvents"])


def test_http_server_unknown_path_404(http_server):
    code, _ = _get(http_server.port, "/nope")
    assert code == 404


# ---------------------------------------------------------------------------
# program cards (xprof)
# ---------------------------------------------------------------------------

def test_program_card_harvested_on_trace(metrics_on):
    @pt.jit.to_static
    def f(x):
        return x * 2 + 1

    f(jnp.ones((3,)))
    f(jnp.ones((3,)))          # cache hit: no second card
    snap = obs.program_cards().snapshot()
    name = next(n for n in snap if n.endswith(".f"))
    cards = snap[name]
    assert len(cards) == 1
    card = list(cards.values())[0]
    assert card["signature"] == "(float32[3])"
    # CPU backend has a cost model: flops present and sane
    assert card.get("flops", 0) > 0 or card.get("unavailable")
    # the harvest's own re-trace must not pollute recompile stats
    st = obs.recompile_tracker().get(name).stats()
    assert st["traces"] == 1 and st["hits"] == 1


def test_program_card_empty_analysis_marked_unavailable(metrics_on,
                                                        monkeypatch):
    """Backends that return empty analyses get an explicit marker, not
    an error (the graceful-fallback path of the ISSUE acceptance)."""
    from paddle_tpu.observability import xprof
    monkeypatch.setattr(xprof, "_cost_dict", lambda c: {})
    monkeypatch.setattr(xprof, "_memory_dict", lambda c: {})
    import jax
    jitted = jax.jit(lambda x: x + 1)
    card = xprof.harvest("t_unavail", jitted,
                         (jax.ShapeDtypeStruct((2,), jnp.float32),),
                         {}, "(float32[2])")
    assert card["unavailable"] == "backend returned empty analyses"
    assert obs.program_cards().get("t_unavail")


def test_program_card_lower_failure_is_contained(metrics_on):
    from paddle_tpu.observability import xprof

    class Boom:
        def lower(self, *a, **k):
            raise RuntimeError("no lowering here")

    card = xprof.harvest("t_boom", Boom(), (), {}, "()")
    assert "lower/compile failed" in card["unavailable"]


def test_flops_of_missing_returns_none():
    from paddle_tpu.observability import xprof
    assert xprof.flops_of("never_registered") is None


def test_analytics_flag_gates_harvest(metrics_on):
    pt.set_flags({"program_analytics": False})
    try:
        @pt.jit.to_static
        def g2(x):
            return x - 1

        g2(jnp.ones((4,)))
        assert obs.program_cards().snapshot() == {}
    finally:
        pt.set_flags({"program_analytics": True})


# ---------------------------------------------------------------------------
# anomaly sentinel
# ---------------------------------------------------------------------------

def test_anomaly_sentinel_nan_and_spike(metrics_on, tmp_path):
    pt.set_flags({"trace_dir": str(tmp_path)})
    s = obs.anomaly_sentinel()
    assert s.observe("t_loss", float("nan")) == "nan"
    for _ in range(8):                      # warmup around ~1.0
        assert s.observe("t_loss", 1.0) is None
    assert s.observe("t_loss", 1e6) == "spike"
    c = obs.counter("anomalies_total")
    assert c.value(kind="nan", series="t_loss") == 1
    assert c.value(kind="spike", series="t_loss") == 1
    lines = [json.loads(l) for l in
             open(tmp_path / "events.jsonl").read().splitlines()]
    assert [e["kind"] for e in lines] == ["nan", "spike"]
    assert lines[1]["series"] == "t_loss" and "ewma" in lines[1]


def test_anomaly_probe_inside_jitted_fn(metrics_on):
    import jax

    @jax.jit
    def f(x):
        obs.anomaly.probe("t_traced", x.sum())
        return x * 0 / 0                    # NaN output, probed input ok

    f(jnp.ones((3,)))
    jax.effects_barrier()
    # the probed value (3.0) is finite -> no anomaly, but the callback
    # ran (series registered in the sentinel)
    assert obs.counter("anomalies_total").value(
        kind="nan", series="t_traced") == 0

    @jax.jit
    def g(x):
        obs.anomaly.probe("t_traced_nan", x[0] / x[1])
        return x

    g(jnp.array([1.0, 0.0]))
    jax.effects_barrier()
    assert obs.counter("anomalies_total").value(
        kind="nan", series="t_traced_nan") == 1


def test_fit_nan_loss_counts_anomaly(metrics_on, tmp_path):
    """A training run whose loss goes NaN must surface in
    anomalies_total via the TrainStep probes."""
    pt.set_flags({"trace_dir": str(tmp_path)})
    import jax

    def nan_loss(out, label):
        return jnp.mean(out) * jnp.float32(float("nan"))

    m = pt.hapi.Model(_MLP())
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=1e-2),
              loss=nan_loss)
    m.fit(_loader(n=32), epochs=1, verbose=0)
    jax.effects_barrier()
    assert obs.counter("anomalies_total").value(
        kind="nan", series="loss") >= 1
    events = open(tmp_path / "events.jsonl").read()
    assert '"series": "loss"' in events


def test_anomaly_disabled_inserts_no_callback():
    assert not obs.enabled()
    import jax

    @jax.jit
    def f(x):
        obs.anomaly.probe("t_gated_series", x.sum())
        return x

    f(jnp.ones((2,)))
    jax.effects_barrier()
    snap = obs.registry().snapshot()
    series = snap.get("anomalies_total", {}).get("series", [])
    assert not any(s["labels"].get("series") == "t_gated_series"
                   for s in series)
    obs.reset_all()


# ---------------------------------------------------------------------------
# satellite: device memory / export_all / native bridge
# ---------------------------------------------------------------------------

def test_device_memory_stats_full():
    out = obs.device_memory_stats(include_unavailable=True, full=True)
    assert len(out) >= 1
    for stats in out.values():
        assert set(stats) == {"bytes_in_use", "peak_bytes_in_use",
                              "bytes_limit"}
        assert all(isinstance(v, int) for v in stats.values())


def test_export_all_writes_prometheus_artifact(metrics_on, tmp_path):
    obs.counter("t_export_total").inc(2)
    out = obs.export_all(str(tmp_path))
    assert os.path.exists(out["prometheus"])
    prom = open(out["prometheus"]).read()
    assert "t_export_total 2" in prom
    assert "# TYPE t_export_total counter" in prom
    snap = json.load(open(out["metrics"]))
    assert set(snap) >= {"metrics", "recompile", "programs",
                         "native_stats"}


def test_native_stats_bridge(metrics_on):
    native = pytest.importorskip("paddle_tpu.native")
    if not native.available():
        pytest.skip("native library unavailable")
    native.stat_add("t_bridge_stat", 7)
    stats = obs.native_stats()
    assert stats.get("t_bridge_stat") == 7
    text = obs.server.metrics_text()
    assert 'pt_native_stat{name="t_bridge_stat"} 7' in text
    native.stat_reset("t_bridge_stat")


# ---------------------------------------------------------------------------
# CI tooling: flags-doc check + exporter self-test
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("checker", ["check_flags_doc.py",
                                     "check_metrics_doc.py"])
def test_check_flags_doc_passes(checker):
    """One gate for both doc contracts: every flag AND every literal
    metric name registered in code must be documented."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", checker)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "OK" in proc.stdout


def test_check_flags_doc_catches_undocumented(tmp_path):
    """The checker must actually fail on an undocumented flag."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_flags_doc as cfd
        flags_py = tmp_path / "flags.py"
        flags_py.write_text(
            'define_flag("totally_new_flag", 1, "has help")\n'
            'define_flag("no_help_flag", 2, "")\n')
        flags = cfd.collect_flags(str(flags_py))
    finally:
        sys.path.pop(0)
    assert ("totally_new_flag", True) in flags
    assert ("no_help_flag", False) in flags
    docs = cfd.docs_text()
    assert "FLAGS_totally_new_flag" not in docs


def test_exporter_self_test_subprocess():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.server",
         "--self-test"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "self-test OK" in proc.stdout


def test_check_metrics_doc_catches_undocumented(tmp_path):
    """The metrics checker must actually fail on an unlisted name."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_metrics_doc as cmd
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            'from obs import counter, gauge\n'
            'counter("totally_new_metric_total", "help").inc()\n'
            'gauge("selftest_ignored").set(1)\n'
            'name = "dyn"; counter(name)\n')
        found = cmd.collect_metrics(str(pkg))
    finally:
        sys.path.pop(0)
    assert set(found) == {"totally_new_metric_total"}
    assert "totally_new_metric_total" not in open(cmd.DOC).read()


def test_check_metrics_doc_scans_native_stats(tmp_path):
    """ISSUE satellite: pt_mon stat names in csrc/*.cc (and Python
    stat_add literals) are scanned too, so C++-side metrics can't
    drift undocumented."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_metrics_doc as cmd
        # the real tree: serving.cc's pt_mon names are collected
        native = cmd.collect_native_metrics()
        assert "serving.traced_total" in native
        assert any(site.startswith("csrc/serving.cc")
                   for site in native["serving.traced_total"])
        # a synthetic tree: literal pt_mon_add / stat_add names found,
        # dynamic ones skipped
        csrc = tmp_path / "csrc"
        csrc.mkdir()
        (csrc / "x.cc").write_text(
            'pt_mon_add("demo.native_total", 1);\n'
            'pt_mon_add(name.c_str(), 1);\n')
        found = cmd.collect_native_metrics(str(csrc))
        assert set(found) == {"demo.native_total"}
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "m.py").write_text(
            'from native import stat_add\n'
            'stat_add("demo.py_total")\n'
            'stat_add(f"demo.le_{b}")\n')
        found = cmd.collect_metrics(str(pkg))
        assert set(found) == {"demo.py_total"}
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------

def test_goodput_ledger_exclusive_buckets(metrics_on):
    import time as _time
    led = obs.goodput.GoodputLedger()
    led.start()
    led.attribute("data_wait", 0.05)
    with led.measure("eval"):
        _time.sleep(0.02)
        with led.measure("checkpoint"):      # nested: self-time only
            _time.sleep(0.02)
    led.attribute("step_compute", 0.1)
    led.stop()
    snap = led.snapshot()
    # exclusivity: the eval bucket holds only its SELF time
    assert 0.015 <= snap["buckets"]["eval"] <= 0.035, snap["buckets"]
    assert 0.015 <= snap["buckets"]["checkpoint"] <= 0.035
    # completeness: buckets (incl. the residual) sum to wall exactly
    assert sum(snap["buckets"].values()) == \
        pytest.approx(snap["wall_seconds"], rel=1e-6)
    assert sum(snap["ratios"].values()) == pytest.approx(1.0, abs=1e-6)
    assert snap["goodput_ratio"] == pytest.approx(
        0.1 / snap["wall_seconds"], rel=1e-6)
    # a second start/stop keeps accumulating without double-counting
    led.start()
    led.attribute("step_compute", 0.05)
    led.stop()
    snap2 = led.snapshot()
    assert snap2["buckets"]["step_compute"] == pytest.approx(0.15)
    assert sum(snap2["buckets"].values()) == \
        pytest.approx(snap2["wall_seconds"], rel=1e-6)


def test_goodput_ledger_publishes_registry_series(metrics_on):
    led = obs.goodput.GoodputLedger()
    led.start()
    led.attribute("step_compute", 0.2)
    led.attribute("jit_compile_cold", 0.1)
    led.stop()
    led.publish()
    assert obs.counter("goodput_seconds_total").value() == \
        pytest.approx(0.2)
    bad = obs.counter("badput_seconds_total")
    assert bad.value(bucket="jit_compile_cold") == pytest.approx(0.1)
    assert 0 < obs.gauge("goodput_ratio").value() < 1


def test_goodput_ledger_seeds_restart_idle(metrics_on, monkeypatch):
    monkeypatch.setenv("PT_RESTART_IDLE_S", "2.5")
    monkeypatch.setenv("PT_ELASTIC_ATTEMPT", "1")
    led = obs.goodput.GoodputLedger()
    led.start()
    led.stop()
    snap = led.snapshot()
    # launcher hand-off plus this process's own import-to-start time
    assert snap["buckets"]["restart_idle"] >= 2.5
    # seed applied once, not per start()
    led.start()
    led.stop()
    assert led.snapshot()["buckets"]["restart_idle"] == \
        snap["buckets"]["restart_idle"]


def test_fit_populates_goodput_and_flight(metrics_on, tmp_path):
    """A CPU fit must leave a coherent ledger: compile split out of
    step time, data_wait measured, buckets exclusive, metrics.json
    carrying the goodput section, and the flight ring holding the
    step markers."""
    pt.set_flags({"trace_dir": str(tmp_path)})
    m = pt.hapi.Model(_MLP())
    m.prepare(optimizer=pt.optimizer.SGD(learning_rate=1e-2),
              loss=pt.nn.CrossEntropyLoss())
    m.fit(_loader(), eval_loader=_loader(n=32), epochs=1, verbose=0)

    with open(tmp_path / "metrics.json") as f:
        snap = json.load(f)
    gp = snap["goodput"]
    assert gp["wall_seconds"] > 0
    assert gp["buckets"]["step_compute"] > 0
    assert gp["buckets"]["jit_compile_cold"] > 0  # first dispatch traced
    assert gp["buckets"]["eval"] > 0
    assert sum(gp["buckets"].values()) == \
        pytest.approx(gp["wall_seconds"], rel=0.02)
    assert gp["goodput_ratio"] == pytest.approx(
        gp["buckets"]["step_compute"] / gp["wall_seconds"], rel=1e-6)
    # registry series mirror the ledger
    bad = {s["labels"]["bucket"]: s["value"]
           for s in snap["metrics"]["badput_seconds_total"]["series"]}
    assert bad["jit_compile_cold"] == pytest.approx(
        gp["buckets"]["jit_compile_cold"], rel=1e-6)
    assert "step_compute" not in bad          # goodput is not badput
    # flight ring: lifecycle + one marker per step (3 steps)
    kinds = [e["kind"] for e in obs.flight_recorder().events()]
    assert kinds.count("step") == 3
    assert "fit_begin" in kinds and "fit_end" in kinds
    assert "recompile" in kinds               # the TrainStep trace


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------

def test_flag_stragglers_policy():
    from paddle_tpu.observability.goodput import flag_stragglers
    assert flag_stragglers([1.0, 1.0, 1.0, 5.0], 2.0) == [3]
    assert flag_stragglers([1.0, 1.0, 1.0, 1.4], 1.5) == []
    assert flag_stragglers([1.0], 2.0) == []          # fleet of one
    assert flag_stragglers([1.0, 9.0], 0.0) == []     # disabled
    assert flag_stragglers([0.0, 0.0], 2.0) == []     # degenerate


def test_straggler_detector_exchange_and_dedup(metrics_on):
    from paddle_tpu.parallel import data_parallel_mesh
    pt.set_flags({"straggler_factor": 1.5})
    try:
        det = obs.goodput.StragglerDetector(data_parallel_mesh(), "dp",
                                            interval=2)
        det.observe(0, 0.1)          # off-interval: no dispatch
        assert det._exchange is None
        det.observe(1, 0.1)          # exchange (all shards equal)
        jax.effects_barrier()
        assert det._last_processed == 1
        assert obs.counter("straggler_events_total").value(host=0) == 0
        # one slow host in a synthetic fleet vector: flagged ONCE even
        # when the per-shard callback replays it
        fleet = np.array([0.1] * 7 + [0.9])
        det.on_fleet(fleet, 3)
        det.on_fleet(fleet, 3)       # duplicate shard callback
        assert obs.counter("straggler_events_total").value(host=7) == 1
        ev = [e for e in obs.flight_recorder().events()
              if e["kind"] == "straggler"]
        assert len(ev) == 1 and ev[0]["host"] == 7
        assert ev[0]["fleet_median_seconds"] == pytest.approx(0.1)
    finally:
        pt.set_flags({"straggler_factor": 0.0})


def test_straggler_disabled_by_default(metrics_on):
    from paddle_tpu.parallel import data_parallel_mesh
    det = obs.goodput.StragglerDetector(data_parallel_mesh(), "dp",
                                        interval=1)
    det.observe(0, 0.5)              # factor 0.0: no exchange built
    assert det._exchange is None


# ---------------------------------------------------------------------------
# flight recorder + rotation
# ---------------------------------------------------------------------------

def test_flight_ring_capacity_and_gating(metrics_on):
    rec = obs.flight.FlightRecorder(capacity=16)
    for i in range(40):
        rec.record("step", step=i)
    evs = rec.events()
    assert len(evs) == 16
    assert evs[-1]["step"] == 39 and evs[0]["step"] == 24  # newest kept
    pt.set_flags({"enable_metrics": False})
    rec.record("dropped")
    assert len(rec.events()) == 16   # gated off
    rec.record("forced", force=True)
    assert rec.events()[-1]["kind"] == "forced"
    pt.set_flags({"enable_metrics": True})


def test_flight_buffer_flag_resizes_ring(metrics_on):
    rec = obs.flight_recorder()
    rec.reset()
    for i in range(20):
        rec.record("step", step=i)
    pt.set_flags({"flight_buffer_events": 8})
    try:
        assert rec.capacity == 8
        assert [e["step"] for e in rec.events()] == list(range(12, 20))
    finally:
        pt.set_flags({"flight_buffer_events": 512})


def test_flight_dump_format_and_rotation(metrics_on, tmp_path):
    rec = obs.flight.FlightRecorder(capacity=64)
    for i in range(10):
        rec.record("step", step=i)
    paths = [rec.dump(f"manual:{i}", str(tmp_path)) for i in range(3)]
    assert all(paths)
    lines = [json.loads(l) for l in open(paths[-1])]
    assert lines[0]["kind"] == "flight_header"
    assert lines[0]["reason"] == "manual:2"
    assert [e["step"] for e in lines[1:-1]] == list(range(10))
    assert lines[-1]["kind"] == "final_metrics"
    assert "metrics" in lines[-1] and "goodput" in lines[-1]
    # repeated dumps keep only the newest two files
    flights = [f for f in os.listdir(tmp_path)
               if f.startswith("flight_")]
    assert len(flights) <= 2
    assert os.path.basename(paths[-1]) in flights


def test_flight_dump_without_trace_dir_is_noop(metrics_on):
    rec = obs.flight.FlightRecorder(capacity=8)
    rec.record("x")
    assert rec.dump("nowhere") == ""     # FLAGS_trace_dir unset


def test_rotation_append_jsonl_rolls_over(tmp_path):
    from paddle_tpu.observability import rotation
    path = str(tmp_path / "ev.jsonl")
    rec = {"kind": "x", "pad": "p" * 80}
    for _ in range(30):
        rotation.append_jsonl(path, [rec], max_bytes=1000, keep=2)
    assert os.path.exists(path) and os.path.exists(path + ".1")
    assert not os.path.exists(path + ".2")          # keep=2 only
    assert os.path.getsize(path) <= 1000 + 200      # fresh generation
    # every surviving line is intact JSON
    for p in (path, path + ".1"):
        for line in open(p):
            assert json.loads(line)["kind"] == "x"


def test_anomaly_events_rotate_and_enter_flight(metrics_on, tmp_path,
                                                monkeypatch):
    from paddle_tpu.observability import rotation
    pt.set_flags({"trace_dir": str(tmp_path)})
    monkeypatch.setattr(rotation, "DEFAULT_MAX_BYTES", 500)
    s = obs.anomaly_sentinel()
    for _ in range(20):
        s.observe("t_rot", float("nan"))
    assert os.path.exists(tmp_path / "events.jsonl")
    assert os.path.exists(tmp_path / "events.jsonl.1")
    fl = [e for e in obs.flight_recorder().events()
          if e["kind"] == "anomaly"]
    assert fl and fl[-1]["series"] == "t_rot" \
        and fl[-1]["anomaly"] == "nan"


# ---------------------------------------------------------------------------
# /goodput + /flight endpoints, port semantics
# ---------------------------------------------------------------------------

def test_goodput_and_flight_endpoints(http_server):
    led = obs.goodput_ledger()
    led.start()
    led.attribute("step_compute", 0.3)
    led.attribute("data_wait", 0.1)
    obs.flight.record("probe_event", step=4)
    code, text = _get(http_server.port, "/goodput")
    assert code == 200
    gp = json.loads(text)
    assert gp["buckets"]["step_compute"] == pytest.approx(0.3)
    assert set(gp["buckets"]) == set(obs.goodput.BUCKETS)
    assert sum(gp["ratios"].values()) == pytest.approx(1.0, abs=1e-6)
    code, text = _get(http_server.port, "/flight")
    fl = json.loads(text)
    assert code == 200 and fl["capacity"] >= 8
    assert any(e["kind"] == "probe_event" for e in fl["events"])
    led.stop()


def test_metrics_port_semantics(metrics_on):
    # negative: exporter disabled
    obs.server.stop()
    pt.set_flags({"metrics_port": -1})
    try:
        assert obs.server.maybe_start() is None
        # 0 (default): ephemeral bind, port published on the gauge
        pt.set_flags({"metrics_port": 0})
        srv = obs.server.maybe_start()
        assert srv is not None and srv.port > 0
        assert obs.gauge("observability_server_port").value() == srv.port
        # idempotent across fit/Server start sites, even with a
        # different explicit port requested
        assert obs.server.start(srv.port + 1) is srv
        assert obs.server.maybe_start() is srv
    finally:
        pt.set_flags({"metrics_port": 0})
        obs.server.stop()


# ---------------------------------------------------------------------------
# trace_report merged host+XLA path
# ---------------------------------------------------------------------------

def test_trace_report_merges_host_and_xla(metrics_on, tmp_path, capsys):
    """The merged path: host spans from export_all + an XLA capture in
    the same directory must land in ONE table (xla:: prefix) with the
    device-category rollup printed."""
    import gzip
    tr = obs.get_tracer()
    tr.reset()
    with tr.span("host/step", force=True):
        pass
    obs.export_all(str(tmp_path))
    with gzip.open(tmp_path / "t.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": _fake_xla_events()}, f)

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import trace_report
        rc = trace_report.report(str(tmp_path))
    finally:
        sys.path.pop(0)
    out = capsys.readouterr().out
    assert rc == 0
    assert "host/step" in out
    assert "xla::fusion.1" in out
    assert "convolution" in out          # category rollup
    assert "merged span summary" in out


def test_goodput_report_self_test_subprocess():
    """ISSUE acceptance: the goodput CLI self-test passes on CPU —
    short fit, exclusive ledger summing to wall time, and a simulated
    SIGTERM leaving a parseable flight_*.jsonl with >= 50 events."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "goodput_report.py"),
         "--self-test"],
        capture_output=True, text=True, env=env, timeout=540)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "self-test OK" in proc.stdout
    assert "goodput_ratio" in proc.stdout


def test_compile_cache_report_self_test_subprocess():
    """ISSUE acceptance: two sequential fits sharing one persistent
    cache dir — the second (warm) process books < 10% of the first's
    cold-compile seconds and its cache-hit counter is > 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "compile_cache_report.py"),
         "--self-test"],
        capture_output=True, text=True, env=env, timeout=540)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "self-test OK" in proc.stdout
    assert "warm share" in proc.stdout


def test_serving_report_self_test_subprocess():
    """ISSUE acceptance: the flight-deck attribution CLI self-test
    passes on CPU — each latency cause injected in isolation via
    testing.faults wins the plurality of its engineered gap with
    exclusive buckets, the chrome export round-trips, and the rings
    stay bounded under a 200-stream flood with zero KV leak."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "serving_report.py"),
         "--self-test"],
        capture_output=True, text=True, env=env, timeout=540)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "self-test OK" in proc.stdout
    assert "flood bounding OK" in proc.stdout


def test_llm_flight_deck_endpoints(http_server):
    """/llm/seqs serves live + finished timelines with a ?trace_id=
    filter joining the wire id; /llm/steps serves the bounded step
    ring plus the live in-flight step."""
    from paddle_tpu.observability import seqtrace, stepprof
    try:
        seqtrace.begin(7, trace_id=0xFEED, engine=1, prompt_tokens=3)
        seqtrace.event(7, "token", index=0)
        seqtrace.finish(7, "finished", tokens=1)
        seqtrace.begin(8, trace_id=0xBEEF, engine=1, prompt_tokens=2)
        stepprof.ring().step_begin(1, step=3, begin_unix=0.0)
        stepprof.ring().record(1, {
            "step": 3, "dur_ms": 2.5, "begin_mono": 0.0,
            "phase_ms": {"decode": 2.0}})
        stepprof.ring().step_begin(1, step=4, begin_unix=0.0)

        code, text = _get(http_server.port, "/llm/seqs")
        body = json.loads(text)
        assert code == 200
        assert [t["seq_id"] for t in body["live"]] == [8]
        assert [t["seq_id"] for t in body["finished"]] == [7]
        assert body["capacity"] == seqtrace.ring().capacity

        code, text = _get(http_server.port,
                          f"/llm/seqs?trace_id={0xFEED}")
        body = json.loads(text)
        assert code == 200 and int(body["trace_id"]) == 0xFEED
        assert [t["seq_id"] for t in body["timelines"]] == [7]
        assert [e["ev"] for e in body["timelines"][0]["events"]] \
            == ["queued", "token", "finished"]

        code, text = _get(http_server.port, "/llm/steps")
        body = json.loads(text)
        assert code == 200
        assert [r["step"] for r in body["steps"]] == [3]
        assert [r["step"] for r in body["live"]] == [4]
        assert body["live"][0]["age_s"] >= 0
    finally:
        seqtrace.ring().reset()
        stepprof.ring().reset()


def test_deferred_probes_reach_host_handlers(metrics_on, monkeypatch):
    """Persistent-cache mode strips the step's jax.debug.callbacks (an
    HLO host callback disqualifies the executable from the cache) and
    returns the signals as reserved metric leaves instead. The drained
    signals must hit the same host handlers: the skip-guard counter
    still counts an engineered non-finite step, the anomaly sentinel
    still sees the loss/grad-norm series, and the reserved keys never
    leak to callers."""
    from paddle_tpu import static as _static
    from paddle_tpu.observability import anomaly as _anomaly
    from paddle_tpu.static import TrainStep

    monkeypatch.setattr(_static, "_defer_probes_default", lambda: True)
    _anomaly.sentinel().reset()
    try:
        model = pt.nn.Linear(4, 2)
        step = TrainStep(model, pt.optimizer.Adam(learning_rate=1e-3),
                         pt.nn.CrossEntropyLoss())
        assert step._defer_probes
        before = obs.counter("nonfinite_steps_total").value()
        x = np.ones((2, 4), dtype=np.float32)
        y = np.zeros((2,), dtype=np.int64)
        metrics = step(x, labels=(y,))
        assert not any(k.startswith("_pt_") for k in metrics)
        # engineered non-finite step: Inf input puts NaN in the grads
        params_before = {k: np.asarray(v)
                         for k, v in step.state["params"].items()}
        step(np.full((2, 4), np.inf, dtype=np.float32), labels=(y,))
        step.flush_signals()
        assert obs.counter("nonfinite_steps_total").value() \
            == before + 1
        # skip-step guard still discarded the poisoned update
        for k, v in step.state["params"].items():
            np.testing.assert_array_equal(np.asarray(v),
                                          params_before[k])
        # anomaly sentinel saw the drained series
        series = _anomaly.sentinel()._series
        assert series.get("loss", {}).get("n", 0) >= 1
        assert "grad_norm" in series
    finally:
        _anomaly.sentinel().reset()


def test_exporter_concurrent_scrape_under_fit(metrics_on):
    """ISSUE satellite: hammer /metrics + /varz from threads while a
    fit loop mutates the registry — every scrape must return 200 with
    parseable output, no exception anywhere."""
    import re
    import urllib.request

    from paddle_tpu.observability import server as obs_server

    srv = obs_server.ObservabilityServer(0)
    stop = threading.Event()
    results = {"metrics": [], "varz": []}
    errors = []
    prom_line = re.compile(r"^[a-zA-Z_:][\w:.]*(\{.*\})? \S+$")

    def scrape(path, bucket):
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}{path}",
                        timeout=10) as r:
                    body = r.read().decode()
                    if path == "/metrics":
                        for line in body.splitlines():
                            if line and not line.startswith("#"):
                                assert prom_line.match(line), line
                    else:
                        json.loads(body)
                    bucket.append(r.status)
            except Exception as e:  # noqa: BLE001 — the assertion
                errors.append(f"{path}: {type(e).__name__}: {e}")
                return

    threads = [threading.Thread(
        target=scrape,
        args=(p, results[k]), daemon=True)
        for p, k in (("/metrics", "metrics"), ("/metrics", "metrics"),
                     ("/varz", "varz"), ("/varz", "varz"))]
    for t in threads:
        t.start()
    try:
        m = pt.hapi.Model(_MLP())
        m.prepare(optimizer=pt.optimizer.Adam(learning_rate=1e-2),
                  loss=pt.nn.CrossEntropyLoss())
        m.fit(_loader(n=256, batch=16), epochs=2, verbose=0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        srv.stop()
    assert not errors, errors
    assert all(s == 200 for b in results.values() for s in b)
    # the scrapers genuinely overlapped the fit
    assert len(results["metrics"]) >= 5, len(results["metrics"])
    assert len(results["varz"]) >= 2, len(results["varz"])


# ---------------------------------------------------------------------------
# tsdb rings + SLO engine (/alerts /slo, tools/slo_report.py)
# ---------------------------------------------------------------------------

def test_quantile_from_buckets_shared_estimator():
    """The ONE bucket-percentile estimator all consumers share: both
    input shapes agree, the +Inf bucket clamps to the top finite
    boundary, and empty histograms answer nan."""
    from paddle_tpu.observability.metrics import (percentile,
                                                  quantile_from_buckets)
    # 4 obs <= 10, 4 more in (10, 100]: median splits the second
    # bucket's mass exactly at its midpoint
    snap = {"10.0": 4, "100.0": 8, "+Inf": 8}
    assert quantile_from_buckets(snap, 0.5) == pytest.approx(10.0)
    assert quantile_from_buckets(snap, 0.75) == pytest.approx(55.0)
    pair = ((10.0, 100.0, float("inf")), (4, 8, 8))
    for q in (0.1, 0.5, 0.75, 0.99):
        assert quantile_from_buckets(pair, q) \
            == pytest.approx(quantile_from_buckets(snap, q))
    # mass in +Inf clamps to the highest finite boundary
    assert quantile_from_buckets({"10.0": 1, "+Inf": 4}, 0.99) == 10.0
    # empty -> nan, q clamped into [0, 1]
    assert np.isnan(quantile_from_buckets({}, 0.5))
    assert np.isnan(quantile_from_buckets({"10.0": 0, "+Inf": 0}, 0.5))
    assert quantile_from_buckets(snap, 7.0) == \
        quantile_from_buckets(snap, 1.0)
    # list percentile: linear interpolation, nan on empty
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
    assert percentile([7.0], 99) == 7.0
    assert np.isnan(percentile([], 50))


def test_tsdb_windowed_reads(metrics_on):
    """Windowed increase/rate/quantile against injected monotonic
    stamps: baseline at the window's left edge, counter resets clamp,
    histogram deltas interpolate, resize keeps the newest samples."""
    from paddle_tpu.observability import tsdb
    ring = tsdb.ring()
    c = obs.counter("selftest_tsdb_reqs_total", "h")
    h = obs.histogram("selftest_tsdb_lat_ms", "h",
                      buckets=(10.0, 100.0, 1000.0))
    tsdb.watch("selftest_tsdb_reqs_total", "selftest_tsdb_lat_ms")

    for _ in range(4):
        h.observe(5.0)                      # 4 obs in the <=10 bucket
    assert ring.sample_once(now=100.0) == 2
    c.inc(5)
    assert ring.sample_once(now=101.0) == 2
    c.inc(2)
    for _ in range(4):
        h.observe(50.0)                     # 4 obs in (10, 100]
    ring.sample_once(now=102.0)

    # wide window reaches the t=100 baseline; narrow only t=101
    assert ring.increase("selftest_tsdb_reqs_total", 1.5, now=102.0) == 7
    assert ring.increase("selftest_tsdb_reqs_total", 0.5, now=102.0) == 2
    assert ring.rate("selftest_tsdb_reqs_total", 0.5, now=102.0) \
        == pytest.approx(4.0)
    # unknown series and single-sample windows answer 0
    assert ring.increase("selftest_tsdb_nope_total", 9.0) == 0.0

    # only the 4 late observations are inside the narrow window:
    # p50 interpolates to the (10, 100] bucket midpoint
    d = ring.hist_increase("selftest_tsdb_lat_ms", 0.5, now=102.0)
    assert d["counts"] == (0, 4, 4) and d["count"] == 4
    assert ring.quantile_over_window(
        "selftest_tsdb_lat_ms", 0.5, 0.5, now=102.0) \
        == pytest.approx(55.0)
    # a window with a baseline but no new observations answers nan
    ring.sample_once(now=102.5)
    assert np.isnan(ring.quantile_over_window(
        "selftest_tsdb_lat_ms", 0.5, 0.4, now=102.5))
    assert ring.value("selftest_tsdb_reqs_total") == 7.0

    # registry reset mid-flight: the newer, smaller sample IS the
    # increase (everything it holds happened after the restart)
    obs.registry().reset()
    obs.counter("selftest_tsdb_reqs_total", "h").inc(3)
    ring.sample_once(now=103.0)
    # baseline (t=101) holds 5; unclamped the increase would be -2
    assert ring.increase("selftest_tsdb_reqs_total", 1.6,
                         now=103.0) == 3

    # FLAGS_tsdb_ring on_change hook rebuilds deques, newest kept
    try:
        pt.set_flags({"tsdb_ring": 8})
        assert ring.capacity == 8
        for i in range(20):
            ring.sample_once(now=104.0 + i)
        stats = ring.stats()
        assert stats["capacity"] == 8
        assert all(n <= 8 for n in stats["samples"].values())
        assert stats["samples"]["selftest_tsdb_reqs_total"] == 8
    finally:
        pt.set_flags({"tsdb_ring": 512})
    ring.reset()
    assert ring.stats()["series"] == 0


def test_slo_state_machine_with_injected_clock(metrics_on):
    """inactive -> pending (one window over) -> firing (both fast
    windows over) -> resolved (load gone) -> inactive (hold expired),
    all driven through evaluate(now=) on hand-stamped samples."""
    from paddle_tpu.observability import slo, tsdb
    eng = slo.engine()
    ring = tsdb.ring()
    spec = slo.SLOSpec(
        "selftest_burn", "ratio", target=0.99,
        good="selftest_slo_good_total", total="selftest_slo_req_total")
    eng.register(spec)
    good = obs.counter("selftest_slo_good_total", "h")
    req = obs.counter("selftest_slo_req_total", "h")

    def state(now):
        view = {a["slo"]: a for a in eng.evaluate(now=now)}
        return view["selftest_burn"]

    try:
        # fast pair 0.3s/3.6s, slow 1.8s/21.6s, hold 0.6s
        pt.set_flags({"slo_window_scale": 0.001})
        ring.sample_once(now=1000.0)
        assert state(1000.0)["state"] == "inactive"

        # 400 good then a 10-bad burst: the short windows burn hot but
        # the long windows are diluted -> over on one side only
        good.inc(400); req.inc(400)
        ring.sample_once(now=1001.0)
        req.inc(10)
        ring.sample_once(now=1004.5)
        a = state(1004.5)
        assert a["state"] == "pending"
        assert not any(w["over"] for w in a["windows"].values())

        # a second burst puts bad mass in the fast long window too:
        # both fast windows over threshold -> page
        req.inc(10)
        ring.sample_once(now=1005.0)
        a = state(1005.0)
        assert a["state"] == "firing" and a["trigger_pair"] == "fast"
        assert a["windows"]["fast"]["over"]
        assert a["windows"]["fast"]["short"]["burn_rate"] > 14.4
        assert a["windows"]["fast"]["severity"] == "page"
        assert a["budget_remaining"] == pytest.approx(
            1.0 - 20.0 / ((1.0 - 0.99) * 420.0))

        # traffic stops; every window ages past the burst
        ring.sample_once(now=1050.0)
        assert state(1050.0)["state"] == "resolved"
        a = state(1051.0)         # 1 s > hold (0.6 s) after resolve
        assert a["state"] == "inactive"
        tos = [t["to"] for t in eng.alerts_view(now=1051.5)
               ["alerts"][0]["history"]]
        assert tos == ["pending", "firing", "resolved", "inactive"]

        # transitions counted, flight-recorded, gauges published
        assert obs.counter("slo_alert_transitions_total").value(
            slo="selftest_burn", to="firing") == 1
        fired = [e for e in obs.flight_recorder().events()
                 if e["kind"] == "slo_alert"
                 and e["slo"] == "selftest_burn"]
        assert [e["to_state"] for e in fired] \
            == ["pending", "firing", "resolved", "inactive"]
        assert obs.gauge("slo_alert_state").value(
            slo="selftest_burn") == 0.0
    finally:
        pt.set_flags({"slo_window_scale": 1.0})


def test_alerts_and_slo_endpoints(http_server):
    """/alerts serves the default-pack state machine + tsdb stats,
    /slo the spec sheet + window pairs, and /metrics?name= filters the
    exposition to the requested prefixes."""
    from paddle_tpu.observability import slo, tsdb
    slo.ensure_default_pack()
    obs.counter("serving_stream_requests_total", "h").inc(4)
    tsdb.sample_once()
    tsdb.sample_once()

    code, text = _get(http_server.port, "/alerts")
    body = json.loads(text)
    assert code == 200
    names = {a["slo"] for a in body["alerts"]}
    assert {"serving_availability", "serving_ttft_p99",
            "kv_audit_clean"} <= names
    assert body["worst_state"] == "inactive"
    assert body["transition_cap"] == 256
    assert all(a["budget_remaining"] <= 1.0 for a in body["alerts"])

    code, text = _get(http_server.port, "/slo")
    body = json.loads(text)
    assert code == 200
    assert [p["pair"] for p in body["window_pairs"]] == ["fast", "slow"]
    avail = next(s for s in body["slos"]
                 if s["spec"]["name"] == "serving_availability")
    assert avail["lifetime"]["total"] == 4.0
    assert avail["lifetime"]["compliance"] == 1.0

    # evaluate() published the slo_* gauges; ?name= narrows to them
    code, text = _get(http_server.port, "/metrics?name=slo_")
    assert code == 200
    assert "slo_alert_state" in text
    assert "slo_error_budget_remaining_ratio" in text
    assert "serving_stream_requests_total" not in text
    sample_lines = [l for l in text.splitlines()
                    if l and not l.startswith("#")]
    assert sample_lines and all(l.startswith("slo_")
                                for l in sample_lines)


def test_slo_report_self_test_subprocess():
    """ISSUE acceptance: the SLO CLI self-test passes on CPU — an
    engineered admission-watermark + prefill-delay overload trips the
    fast burn pair on availability and TTFT with exact error-budget
    math, alerts resolve when the load stops, and the tsdb/transition
    rings stay bounded under a 200-stream flood."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "slo_report.py"),
         "--self-test"],
        capture_output=True, text=True, env=env, timeout=540)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "self-test OK" in proc.stdout
    assert "budget math exact OK" in proc.stdout
    assert "flood bounding OK" in proc.stdout
