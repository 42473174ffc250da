"""The reduction from a trace to numbers, on the hand-built trace kept
beside this file, and the xplane reader on a tiny profile recorded here
on the CPU."""

import json
import os
import time

import pytest

from benchmarks import trace as tr
from benchmarks.readers import device_trace

HERE = os.path.dirname(os.path.abspath(__file__))


def hand_trace() -> tr.Trace:
    with open(os.path.join(HERE, "hand_trace.json")) as f:
        doc = json.load(f)

    def lane(d):
        return {k: [tr.Event(n, float(s), float(dur)) for n, s, dur in v]
                for k, v in d.items()}

    return tr.Trace(lane(doc["ops"]), lane(doc["modules"]),
                    [tr.Event(n, float(s), float(d))
                     for n, s, d in doc["annotations"]],
                    False, "hand_trace.json", 0)


def test_busy_union_counts_overlaps_once():
    ev = [tr.Event("a", 0, 10), tr.Event("b", 5, 10), tr.Event("c", 30, 5),
          tr.Event("inside", 31, 1), tr.Event("d", 35, 5)]
    assert tr.busy_union(ev) == 15 + 10
    assert tr.busy_union([]) == 0


def test_clip_and_idle_gaps():
    ev = [tr.Event("a", 0, 10), tr.Event("b", 20, 10)]
    assert tr.clip(ev, 5, 25) == [tr.Event("a", 5, 5), tr.Event("b", 20, 5)]
    assert tr.idle_gaps(ev, -5, 40) == [(-5, 0), (10, 20), (30, 40)]
    assert tr.idle_gaps([], 0, 7) == [(0, 7)]


def test_summary_of_the_hand_built_trace():
    s = tr.summarize(hand_trace())
    assert s["window_s"] == pytest.approx(2000e-9)
    # device 0 is busy 100..600, 700..1200 and 1950..2000 (clipped):
    # 1050 ns; device 1 for 1100 ns; the mean is reported
    assert s["busy_s"] == pytest.approx((1050 + 1100) / 2 * 1e-9)
    assert s["planes"] == ["/device:TPU:0", "/device:TPU:1"]
    # device 0's operations summed by kind, longest first; the last is
    # clipped to the slice
    assert [n for n, _ in s["device_ops"]] == [
        "all-reduce = bf16[8,8] all-reduce x2",
        "fusion = f32[8,8] fusion x2",
        "flash = bf16[8,8] custom-call x2",
        "late = f32[1] fusion x1"]
    assert [t for _, t in s["device_ops"]] == pytest.approx(
        [600e-9, 400e-9, 200e-9, 50e-9])
    # gaps on device 0: 0..100 and 600..700 (step_call), 1200..1950
    # (its middle, 1575, is inside loss_fetch only)
    gaps = dict(s["idle_gaps"])
    assert gaps["bench/step_call"] == pytest.approx(200e-9)
    assert gaps["bench/loss_fetch"] == pytest.approx(750e-9)
    assert "bench/slice" not in gaps


@pytest.mark.parametrize("text, kind", [
    # the number and the layouts go, so twelve layers share one kind
    ("%fusion.400 = (bf16[32,512,3072]{2,1,0:T(8,128)(2,1)}, "
     "bf16[32,512,3072]{2,1,0}) fusion(bf16[3072]{0} %copy-done.1490, "
     "bf16[32,512,768]{2,1,0} %x), kind=kOutput",
     "fusion = (bf16[32,512,3072], bf16[32,512,3072]) fusion"),
    ("%transpose_jvp___.13 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} %q)"
     ", custom_call_target=\"tpu_custom_call\"",
     "transpose_jvp___ = bf16[8,8] custom-call"),
    # not an HLO line (the CPU backend's events): kept as it is
    ("dot.3", "dot.3"),
])
def test_op_kind(text, kind):
    assert tr.op_kind(text) == kind


def test_gap_goes_to_the_innermost_annotation_or_to_none():
    ann = [tr.Event("bench/outer", 0, 100), tr.Event("bench/inner", 40, 20)]
    got = dict(tr.attribute_gaps([(45, 55), (70, 80), (200, 230)], ann))
    assert got == {"bench/inner": pytest.approx(10e-9),
                   "bench/outer": pytest.approx(10e-9),
                   "unannotated": pytest.approx(30e-9)}


def test_per_name_and_per_step_readers():
    observed = {"trace": hand_trace(), "counters": {"trace_steps": 2}}
    observed["trace_summary"] = tr.summarize(observed["trace"])
    assert device_trace.ms_per_unit(
        observed, pattern="^jit__step", per_counter="trace_steps",
        lane="modules") == pytest.approx(1000 / 1e6 / 2)
    assert device_trace.ms_per_unit(
        observed, pattern=r" custom-call\(", per_counter="trace_steps"
    ) == pytest.approx(200 / 1e6 / 2)
    assert device_trace.ms_per_unit(
        observed, pattern="all-reduce|all-gather|reduce-scatter",
        per_counter="trace_steps") == pytest.approx(600 / 1e6 / 2)
    # nothing matches: the reader returns nothing, not zero
    assert device_trace.ms_per_unit(
        observed, pattern="no_such_kernel", per_counter="trace_steps"
    ) is None
    assert device_trace.idle_pct(observed) == pytest.approx(
        100 * (1 - 1075 / 2000))
    assert device_trace.idle_pct({}) is None
    assert device_trace.ms_per_unit(
        {}, pattern="x", per_counter="trace_steps") is None


def test_sum_by_name():
    by = tr.sum_by_name(hand_trace().ops["/device:TPU:0"])
    assert by[next(k for k in by if k.startswith("%fusion.1"))] == (400, 2)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two profiles in one directory, recorded here on the CPU."""
    import jax
    import jax.numpy as jnp

    d = str(tmp_path_factory.mktemp("profiles"))
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    for tag in ("first", "second"):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench/slice"):
            with jax.profiler.TraceAnnotation(f"bench/{tag}"):
                for _ in range(3):
                    f(x).block_until_ready()
        jax.profiler.stop_trace()
        time.sleep(1.1)   # profile directories are named by the second
    return d


def test_two_profile_directories_the_newest_is_read(recorded):
    path, found = tr.newest_xplane(recorded)
    assert len(found) == 2 and path == found[-1]
    data = tr.read_xplane(path)
    names = {a.name for a in data.annotations}
    assert "bench/second" in names and "bench/first" not in names
    assert data.size_bytes == os.path.getsize(path)
    assert not data.truncated
    # the CPU backend has no device plane: its executions are found by
    # their hlo_op stat, and lie inside the slice
    assert data.ops, "XLA executions were found"
    s = tr.summarize(data)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert tr.newest_xplane(os.path.join(recorded, "nothing_here")) \
        == (None, [])


def test_bounded_read(recorded):
    path, _ = tr.newest_xplane(recorded)
    data = tr.read_xplane(path, max_events=5)
    assert data.truncated
    assert sum(len(v) for v in data.ops.values()) \
        + len(data.annotations) <= 5
