"""The readers of ``benchmarks/readers/program.py`` on a hand-built
trace and scope map (every new metric exactly), and in a
``--rehearsal --trace 1`` run of the one command."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import manifest as mf
from benchmarks import trace as tr
from benchmarks.readers import program

M = mf.Manifest()
FN = "TrainStep(Toy)"
NEW = [m["name"] for m in M.doc["per_layer"]
       if M.metric_file(m["name"])["reader"].startswith(
           "benchmarks.readers.program.")]
BLOCKS = ["train.embed_ms_per_step", "train.attn_block_ms_per_step",
          "train.ffn_block_ms_per_step", "train.head_loss_ms_per_step",
          "train.guard_ms_per_step", "train.probe_ms_per_step",
          "train.optimizer_ms_per_step", "train.unscoped_ms_per_step"]

# Two steps of a toy program on device 0, times in ns; each step is
# 1000 long and busy for 940 (a 60 ns hole after the optimizer). The
# chip names an event by its whole HLO line.
STEP = [
    # (instruction, start, dur)
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0, 50),
    ("%flash_fwd.2 = bf16[8]{0} custom-call(bf16[8]{0} %q), "
     "custom_call_target=\"tpu_custom_call\"", 50, 100),
    ("%layer_norm_fwd.3 = bf16[8]{0} custom-call(bf16[8]{0} %x), "
     "custom_call_target=\"tpu_custom_call\"", 150, 20),
    ("%fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kOutput", 170,
     200),
    ("%fusion.5 = f32[]{} fusion(bf16[8]{0} %h), kind=kLoop", 370, 80),
    ("%flash_bwd.6 = bf16[8]{0} custom-call(bf16[8]{0} %do), "
     "custom_call_target=\"tpu_custom_call\"", 450, 150),
    ("%is-finite_reduce_fusion.7 = pred[]{} fusion(bf16[8]{0} %g), "
     "kind=kInput", 600, 120),
    ("%fusion.8 = f32[]{} fusion(f32[8]{0} %g2), kind=kLoop", 720, 40),
    # a while round its body: 100 long, the body covers 70 of it
    ("%while.9 = (s32[]) while((s32[]) %t), body=%b", 760, 100),
    ("%fusion.10 = u32[2]{0} fusion(u32[2]{0} %k), kind=kLoop", 770, 70),
    ("%fusion.11 = f32[8]{0} fusion(f32[8]{0} %m), kind=kLoop", 860, 70),
    ("%copy.12 = f32[8]{0} copy(f32[8]{0} %s)", 930, 10),
]
SCOPES = {
    "fusion.1": "jit(_step)/jvp(pt.embed)/gather",
    "flash_fwd.2": "jit(_step)/jvp(pt.attn)/flash_fwd/pallas_call",
    "layer_norm_fwd.3": "jit(_step)/jvp(pt.attn)/layer_norm_fwd/"
                        "pallas_call",
    "fusion.4": "jit(_step)/jvp(pt.ffn)/dot_general",
    "fusion.5": "jit(_step)/jvp(pt.head_loss)/reduce_sum",
    "flash_bwd.6": "jit(_step)/transpose(jvp(pt.attn))/flash_bwd/"
                   "pallas_call",
    # merged metadata naming two blocks: the first counts
    "is-finite_reduce_fusion.7":
        "jit(_step)/pt.guard/reduce_and;"
        "jit(_step)/transpose(jvp(pt.ffn))/dot_general",
    "fusion.8": "jit(_step)/pt.probe/reduce_sum",
    "while.9": "jit(_step)/jit(_threefry_split)/while",
    "fusion.10": "jit(_step)/jit(_threefry_split)/while/body/add",
    "fusion.11": "jit(_step)/pt.optimizer/pt.optimizer/sub",
    "copy.12": "",
}
WANT_NS = {       # device self time a step
    "train.embed_ms_per_step": 50, "train.attn_block_ms_per_step": 270,
    "train.ffn_block_ms_per_step": 200,
    "train.head_loss_ms_per_step": 80, "train.guard_ms_per_step": 120,
    "train.probe_ms_per_step": 40, "train.optimizer_ms_per_step": 70,
    "train.unscoped_ms_per_step": 30 + 70 + 10,
    "train.flash_fwd_ms_per_step": 100,
    "train.flash_bwd_ms_per_step": 150,
    "train.layer_norm_kernel_ms_per_step": 20,
    "train.entry_host_ms_per_step": 30 + 400 + 20,
}
NOTES = [("flash_fwd", 4.0e6, 1.0), ("flash_bwd", 1.2e7, 1.0),
         ("layer_norm_fwd", 10.0, 5.0)]
PEAK = 197e12


def hand_trace(steps=2, drop_last_flash=False):
    ops, annotations = [], [tr.Event("bench/slice", 0.0, 1000.0 * steps)]
    for s in range(steps):
        base = 1000.0 * s
        for name, start, dur in STEP:
            if drop_last_flash and s == steps - 1 \
                    and name.startswith("%flash_fwd"):
                continue
            ops.append(tr.Event(name, base + start, float(dur)))
        annotations += [
            tr.Event("bench/step_call", base, 1000.0),
            tr.Event("pt/train_step/make_batch", base + 10, 30.0),
            tr.Event("pt/train_step/dispatch", base + 40, 400.0),
            tr.Event("pt/train_step/drain", base + 940, 20.0)]
    ops.append(tr.Event("%late = f32[1]{0} fusion()", 1000.0 * steps + 5,
                        10.0))                     # outside the slice
    annotations.append(tr.Event("pt/train_step/make_batch",
                                1000.0 * steps + 5, 30.0))
    return tr.Trace({"/device:TPU:0": ops, "/device:TPU:1": []}, {},
                    [a for a in annotations
                     if a.name.startswith("bench/")],
                    False, "hand-built", 0), annotations


@pytest.fixture
def observed(monkeypatch):
    """What a runner hands the readers, with the three things the view
    takes from the process replaced by the hand-built ones."""
    import jax
    from paddle_tpu.observability import xprof

    def make(**kw):
        trace, annotations = hand_trace(**kw)
        monkeypatch.setattr(program, "read_annotations",
                            lambda path: annotations)
        return {"trace": trace, "counters": {"trace_steps": 2}}

    monkeypatch.setattr(program, "entry_point", lambda pattern: FN)
    monkeypatch.setattr(program, "compiled_scopes", lambda fn: SCOPES)
    monkeypatch.setattr(xprof, "kernel_notes",
                        lambda fn: NOTES if fn == FN else [])

    class Chip:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    return make


def read(observed, name):
    spec = M.metric_file(name)
    return mf.resolve(spec["reader"])(observed, **spec["args"])


def test_the_fourteen_are_there():
    assert len(NEW) == 14 and set(BLOCKS) <= set(NEW)
    for name in NEW:
        assert M.metric_file(name)["args"]["fn_pattern"] == "TrainStep\\("


@pytest.mark.parametrize("name", sorted(WANT_NS))
def test_every_time_metric_exactly(observed, name):
    assert read(observed(), name) == pytest.approx(WANT_NS[name] / 1e6,
                                                   rel=1e-12)


def test_the_eight_blocks_sum_to_the_busy_time(observed):
    obs = observed()
    total = sum(read(obs, name) for name in BLOCKS)
    events = tr.clip(obs["trace"].ops["/device:TPU:0"], 0.0, 2000.0)
    assert total == pytest.approx(tr.busy_union(events) / 1e6 / 2)
    assert total == pytest.approx(940 / 1e6)
    # the share that merged metadata touched is known to the view
    charge = obs[program._VIEW_KEY]["charge"]
    assert charge["merged_ns"] == pytest.approx(240.0)
    assert charge["joined_ns"] == charge["total_ns"]
    # the same time by kind of operation, for the log's named breakdown
    assert charge["by_kind"][("flash_bwd = bf16[8] custom-call",
                              "pt.attn")] == [300.0, 2]
    assert sum(t for t, _ in charge["by_kind"].values()) \
        == pytest.approx(charge["total_ns"])


def test_peak_shares_from_the_noted_work(observed):
    obs = observed()
    assert read(obs, "train.flash_fwd_mxu_pct") == pytest.approx(
        100 * 4.0e6 * 2 / 200e-9 / PEAK)
    assert read(obs, "train.flash_bwd_mxu_pct") == pytest.approx(
        100 * 1.2e7 * 2 / 300e-9 / PEAK)


def test_events_that_disagree_with_the_noted_sites_read_none(observed):
    obs = observed(drop_last_flash=True)
    assert read(obs, "train.flash_fwd_mxu_pct") is None
    # the time is still read, and the other kernel's share
    assert read(obs, "train.flash_fwd_ms_per_step") == pytest.approx(
        50 / 1e6)
    assert read(obs, "train.flash_bwd_mxu_pct") is not None


def test_a_program_without_the_names_reads_nothing(observed, monkeypatch):
    """The parent of the PR that added the names: no ``op_scopes``, no
    named kernels, no ``pt/`` spans."""
    trace, _ = hand_trace()
    plain = {"/device:TPU:0": [
        tr.Event(e.name.replace("flash_fwd", "jvp__")
                 .replace("flash_bwd", "transpose_jvp___")
                 .replace("layer_norm_fwd", "_unknown_"), e.start, e.dur)
        for e in trace.ops["/device:TPU:0"]]}
    monkeypatch.setattr(program, "read_annotations",
                        lambda path: list(trace.annotations))
    monkeypatch.setattr(program, "compiled_scopes", lambda fn: None)
    obs = {"trace": trace._replace(ops=plain),
           "counters": {"trace_steps": 2}}
    assert [read(obs, name) for name in NEW] == [None] * 14


def test_no_trace_reads_nothing():
    assert [read({"counters": {"trace_steps": 2}}, name)
            for name in NEW] == [None] * 14


def test_block_of_takes_the_last_token_and_the_first_of_merged():
    assert program.block_of("jit(_step)/pt.optimizer/pt.guard/x") == (
        "pt.guard", False)
    assert program.block_of("a/transpose(jvp(pt.attn))/flash_bwd/p") == (
        "pt.attn", False)
    assert program.block_of("jit(_step)/jit(_threefry_split)") == (
        None, False)
    assert program.block_of("x/pt.guard/y;x/jvp(pt.ffn)/z") == (
        "pt.guard", True)
    assert program.block_of("x/copy;x/jvp(pt.ffn)/z") == ("pt.ffn", False)
    assert program.block_of("x/pt.ffn/a;y/pt.ffn/b") == ("pt.ffn", False)


def test_self_times_partition_nested_and_overlapping_events():
    ev = [tr.Event("outer", 0, 100), tr.Event("inner", 10, 30),
          tr.Event("straddles", 90, 30), tr.Event("alone", 200, 5)]
    got = {e.name: t for e, t in program.self_times(ev)}
    assert got == {"outer": 60, "inner": 30, "straddles": 30, "alone": 5}
    assert sum(got.values()) == tr.busy_union(ev)
    assert program.instruction("%a.1 = f32[] add(%b, %c)") == "a.1"
    assert program.instruction("copy.617") == "copy.617"


def test_rehearsal_gives_a_value_for_each_metric_the_cpu_can_run(tmp_path):
    """The one command, traced, on the CPU backend. The Mosaic kernels
    are not routed off the chip, so their five metrics have nothing to
    read here; every other new metric must. The run gets a compile
    cache of its own: the persistent cache's key leaves ``op_name``
    metadata out, so a shared one may hand this program an executable
    that a checkout without the scopes compiled, names and all."""
    env = dict(os.environ, PYTHONPATH=mf.ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "bert_base_s512", "--seed", str(2 ** 33 + 5), "--seconds", "1",
         "--trace", "1", "--rehearsal"], cwd=mf.ROOT, env=env, text=True,
        capture_output=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    doc = json.loads(lines[-1])
    assert doc["correct"] is True
    kernels = {n for n in NEW if "flash" in n or "layer_norm" in n}
    assert set(NEW) - kernels <= set(doc["metrics"])
    assert all(m["value"] is None for m in doc["metrics"].values())
    said = [ln for ln in lines if "program view:" in ln]
    assert any("ms per step by block" in ln for ln in said)
    assert any("pt/train_step/" in ln and "idle seconds" in ln
               for ln in said)
