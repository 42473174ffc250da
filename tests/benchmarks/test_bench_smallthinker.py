"""The ``smallthinker_21b_a3b`` configuration and what its cell adds to
the benchmark: the configuration file's two views and its parameter
count, the three new per-layer metrics on a hand-made trace, the FLOPs
function, the comparison and the control drill's rehearsal."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import manifest as mf
from benchmarks import smallthinker_lm_arithmetic as arith
from benchmarks import trace as tr
from benchmarks.readers import program

M = mf.Manifest()
CELL = "smallthinker_21b_a3b_ep4_s16k"
CONFIG = M.config("smallthinker_21b_a3b")
NEW = ["train.swa_attn_fwd_roofline", "train.swa_attn_bwd_roofline",
       "train.swa_attn_kernel_ms_per_step"]
LAYOUT = [0, 1, 1, 1] * 13
# the source's config.json, as the catalog of configurations has it
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}


# -- the configuration ----------------------------------------------------------

def test_every_published_key_is_kept_but_for_reduced():
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "rope_layout",
                       "sliding_window_layout", "moe_num_primary_experts",
                       "vocab_size"} \
        == set(M.config_entry("smallthinker_21b_a3b")["reduced"])
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    # the cut layouts are the published ones' first period
    for key in ("rope_layout", "sliding_window_layout"):
        assert CONFIG[key] == PUBLISHED[key][:4] == [0, 1, 1, 1]


def test_the_config_entry_holds_but_for_the_depth_key():
    """What ``test_bench_manifest.py::test_config_entry`` asserts of an
    entry, for this one, with a width read as a width: that test's
    pattern refuses the depth key ``num_hidden_layers`` (PERF.md section
    7 (h)), so its case for this entry fails, as the block-diffusion
    and Kimi entries' do, and stops at that line; the assertions after
    it are made here."""
    entry = M.config_entry("smallthinker_21b_a3b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in M.doc["paths"])
    for key in ("source", "why"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
            and "\t" not in entry[key]
    for key in entry["reduced"]:
        assert mf.NAME_RE.match(key)
        assert not re.search(r"(_dim|_rank|hidden_size|intermediate|head"
                             r"|window_size|ffn|active)", key), \
            "a width may never be reduced"
    assert CONFIG["reduced"] == entry["reduced"]
    assert M.config("smallthinker_21b_a3b", rehearsal=True)["model"].keys() \
        == CONFIG["model"].keys()
    assert any(w["config"] == entry["name"] for w in M.doc["workloads"])
    assert os.path.exists(os.path.join(
        mf.ROOT, "benchmarks", "references", entry["name"] + ".py"))
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")


def test_the_model_view_is_the_published_view():
    model = CONFIG["model"]
    shared = set(model) & set(PUBLISHED)
    assert shared >= {"hidden_size", "num_attention_heads",
                      "num_key_value_heads", "head_dim", "rms_norm_eps",
                      "rope_theta", "rope_layout", "sliding_window_layout",
                      "sliding_window_size", "moe_num_primary_experts",
                      "moe_num_active_primary_experts",
                      "moe_ffn_hidden_size",
                      "moe_primary_router_apply_softmax", "norm_topk_prob",
                      "num_hidden_layers", "vocab_size"}
    for key in shared:
        assert model[key] == CONFIG[key], key
    # the share: the router scores the published experts, 16 held of 4 x 16
    assert model["moe_num_primary_experts_total"] \
        == PUBLISHED["moe_num_primary_experts"]
    assert model["moe_num_primary_experts"] * 4 \
        == model["moe_num_primary_experts_total"]
    assert model["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert {"router_input", "secondary_experts", "attention", "window",
            "rope", "experts", "learning_rate", "initial_weights",
            "e_score_correction_bias", "auxiliary_balance_loss",
            "document_boundaries"} <= set(CONFIG["assumed"])


def test_the_parameter_count_is_the_built_models():
    """Counted from shapes alone (``jax.eval_shape``: no array of the
    full size is made): 656,529,920, 10.50 GB at 16 bytes."""
    import jax

    from paddle_tpu.models import (SmallThinkerConfig,
                                   SmallThinkerForCausalLM)

    def shapes():
        return SmallThinkerForCausalLM(SmallThinkerConfig(
            **CONFIG["model"])).param_dict()

    count = sum(int(np.prod(v.shape)) for v in
                jax.eval_shape(shapes).values())
    stated = CONFIG["parameters"]
    assert count == stated["count"] == 656_529_920
    assert stated["bytes_at_16_a_parameter"] == 16 * count
    per = stated["per_layer"]
    assert per["attention"] == 2560 * 3584 * 2 + 2560 * 512 * 2
    assert per["expert"] == 3 * 2560 * 768
    assert per["layer_with_16_experts"] == per["attention"] \
        + per["router"] + per["norms"] + 16 * per["expert"]
    assert count == 4 * per["layer_with_16_experts"] \
        + 2 * stated["embedding_or_head_slice"] + stated["final_norm"]


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    cell = M.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "smallthinker_21b_a3b"
    mix = M.traffic(cell["traffic"])
    assert cell["traffic"] == "swa_lm_pretrain_s16k_b1"
    assert mix["kind"] == "smallthinker_lm_train_step"
    assert (mix["seq_len"], mix["batch_per_chip"], mix["pool_batches"],
            mix["loss_fetch_every"], mix["zipf_s"],
            mix["parity_sequences"]) == (16384, 1, 16, 5, 1.0, 2)
    reported = {m["name"] for m in M.metrics_of(CELL, "per_layer")}
    assert set(NEW) <= reported
    # the global layer's causal kernels and the step's shares of peaks
    assert {"train.flash_fwd_ms_per_step", "train.flash_bwd_ms_per_step",
            "train.flash_fwd_roofline", "train.flash_fwd_mxu_pct",
            "train.flash_bwd_mxu_pct", "train.mfu_pct",
            "train.moe_gmm_roofline"} <= reported
    # no dense feed-forward layer and no shared expert here; the rotary
    # turn's block metric is pinned to the block-diffusion cell by that
    # cell's own test, so this cell does not report it
    assert not {"train.ffn_block_ms_per_step",
                "train.moe_shared_ms_per_step",
                "train.attn_qk_ms_per_step"} & reported
    names = [m["name"] for m in M.doc["per_layer"]]
    assert names[-len(NEW):] == NEW, "appended"
    for name in NEW:
        entry = next(m for m in M.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["layer"] == "kernels"
        spec = M.metric_file(name)
        assert {k: spec[k] for k in entry if k != "workloads"} \
            == {k: v for k, v in entry.items() if k != "workloads"}
    assert {m["name"] for m in M.metrics_of(CELL, "end_to_end")} \
        == {"train_tokens_per_s", "setup_s"}
    assert M.doc["workloads"][-1]["name"] == CELL


# -- the new metrics on a hand-made trace ----------------------------------------

FN = "TrainStep(Toy)"
PEAK = 197e12
CALL = ' custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"'
# one step of a toy program with a window layer and a global layer, ns;
# each forward kernel runs once (its result is kept by the recomputation)
STEP = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0, 30),
    ("%swa_flash_fwd.2 = bf16[8]{0}" + CALL, 30, 100),
    ("%flash_fwd.3 = bf16[8]{0}" + CALL, 130, 80),   # the global layer's
    ("%swa_flash_bwd_dq.4 = bf16[8]{0}" + CALL, 210, 150),
    ("%swa_flash_bwd_dkv.5 = bf16[8]{0}" + CALL, 360, 250),
    ("%flash_bwd_dq.6 = bf16[8]{0}" + CALL, 610, 120),
]
SCOPES = {
    "fusion.1": "jit(_step)/jvp(pt.attn)/pt.attn_qk/mul",
    "swa_flash_fwd.2": "jit(_step)/jvp(pt.attn)/swa_flash_fwd/pallas_call",
    "flash_fwd.3": "jit(_step)/jvp(pt.attn)/flash_fwd/pallas_call",
    "swa_flash_bwd_dq.4": "jit(_step)/transpose(jvp(pt.attn))/x",
    "swa_flash_bwd_dkv.5": "jit(_step)/transpose(jvp(pt.attn))/y",
    "flash_bwd_dq.6": "jit(_step)/transpose(jvp(pt.attn))/z",
}
NOTES = [("swa_flash_fwd", 4.0e6, 1.0), ("swa_flash_bwd_dq", 6.0e6, 1.0),
         ("swa_flash_bwd_dkv", 8.0e6, 1.0), ("flash_fwd", 1.0e6, 1.0),
         ("flash_bwd_dq", 1.5e6, 1.0)]


@pytest.fixture
def observed(monkeypatch):
    import jax
    from paddle_tpu.observability import xprof

    ops, annotations = [], [tr.Event("bench/slice", 0.0, 2000.0)]
    for s in range(2):
        for name, start, dur in STEP:
            ops.append(tr.Event(name, 1000.0 * s + start, float(dur)))
        annotations.append(tr.Event("bench/step_call", 1000.0 * s, 1000.0))
    trace = tr.Trace({"/device:TPU:0": ops}, {}, annotations, False,
                     "hand-made", 0)
    monkeypatch.setattr(program, "read_annotations",
                        lambda path: annotations)
    monkeypatch.setattr(program, "entry_point", lambda pattern: FN)
    monkeypatch.setattr(program, "compiled_scopes", lambda fn: SCOPES)
    monkeypatch.setattr(xprof, "kernel_notes",
                        lambda fn: NOTES if fn == FN else [])

    class Chip:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    return {"trace": trace, "counters": {"trace_steps": 2}}


WANT = {
    # a noted forward site of 4e6 FLOPs ran once a step: 2 events, 200 ns
    "train.swa_attn_fwd_roofline": 100.0 * 4.0e6 * 2 / 200e-9 / PEAK,
    # a dq site and a dk/dv site once a step: 2 x 14e6 FLOPs in 800 ns
    "train.swa_attn_bwd_roofline": 100.0 * 14.0e6 * 2 / 800e-9 / PEAK,
    "train.swa_attn_kernel_ms_per_step": (100 + 150 + 250) / 1e6,
}


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_the_hand_made_trace(observed, name):
    spec = M.metric_file(name)
    got = mf.resolve(spec["reader"])(observed, **spec["args"])
    assert got == pytest.approx(WANT[name]), name


def test_the_causal_kernels_metrics_read_the_global_layer_alone(observed):
    """The anchored patterns of the accepted ``flash_*`` metrics do not
    count the window's kernels."""
    spec = M.metric_file("train.flash_fwd_ms_per_step")
    got = mf.resolve(spec["reader"])(observed, **spec["args"])
    assert got == pytest.approx(80 / 1e6)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_names_gives_nothing_to_read(observed, name,
                                                           monkeypatch):
    """A parent of this PR: no such kernel. The reader returns nothing
    (``None`` or no time) and does not raise."""
    from paddle_tpu.observability import xprof
    monkeypatch.setattr(xprof, "kernel_notes", lambda fn: [
        n for n in NOTES if not n[0].startswith("swa_")])
    observed["trace"] = tr.Trace(
        {"/device:TPU:0": [e for e in observed["trace"].ops[
            "/device:TPU:0"] if "swa_flash" not in e.name]}, {},
        observed["trace"].annotations, False, "hand-made", 0)
    spec = M.metric_file(name)
    got = mf.resolve(spec["reader"])(observed, **spec["args"])
    assert not got, name


# -- arithmetic ----------------------------------------------------------------------

def test_model_flops_of_the_cell_by_hand():
    cfg = CONFIG["model"]
    tokens, held = 16384, 4 * 16384 * 6 * 16 / 64        # balanced
    assert arith.window_pairs(16384, 4096) == 58_722_304 \
        == int(np.sum(np.minimum(np.arange(16384), 4095) + 1))
    assert arith.window_pairs(100, 4096) == 5050
    attn = 4.0 * 28 * 128 * (16384 * 16385 // 2 + 3 * 58_722_304)
    assert arith.attention_flops_forward(cfg, 1, 16384) == attn
    proj = 2 * 2560 * 3584 + 2 * 2560 * 512
    per_token = 4 * (proj + 2560 * 64) + 2560 * 37984
    forward = 2.0 * (tokens * per_token + held * 3 * 2560 * 768) + attn
    flops = arith.smallthinker_flops_per_step(cfg, 1, 16384, held)
    assert flops == pytest.approx(3.0 * forward)
    # the issue's count: 705.9 MFLOP a token forward, 34.7 TFLOP a step
    assert forward / tokens == pytest.approx(705.93e6, rel=1e-4)
    assert 34.6e12 < flops < 34.8e12
    # attention 62% of the model's FLOPs, its products 38%
    assert 0.6 < (attn + 2.0 * tokens * 4 * proj) / forward < 0.64
    assert 0.37 < attn / forward < 0.40


# -- the comparison and the control drill ------------------------------------------

def test_the_comparison_at_tiny_widths():
    """The runner's ``check_parity`` on its sequences in the timed state
    (the selection bias fitted): every margin under its limit, the model
    in training mode, and the choice handed to the reference the step's
    own."""
    from benchmarks.runners import smallthinker_lm_train_step as runner
    from benchmarks.runners.lm_train_step import fit_router_bias
    from test_bench_parity import make_run

    run = make_run(CELL)
    model = runner.build_model(run)
    batches = runner.make_batches(run)
    fit_router_bias(run, model, batches[0][0])
    assert any(np.any(np.asarray(b) != 0)
               for b in model.buffer_dict().values()), "a bias is fitted"
    ids, labels = runner.parity_batch(run, batches)
    assert ids.shape == (2, run.mix["seq_len"])
    runner.check_parity(run, model, (ids, labels))
    assert not run.failures, run.failures
    tol = run.config["tolerances"]
    assert run.margins["parity_loss_abs"] <= tol["loss_abs"]
    for leaf, limit in tol["grad_rel_l2"].items():
        assert 0 < run.margins["parity_grad_rel:" + leaf] <= limit, leaf
    assert run.margins["parity_routing_otherwise_share"] \
        <= tol["routing_otherwise_share"]
    assert run.margins["parity_routing_shortfall"] \
        <= tol["routing_shortfall"]
    assert model.training, "the timed path is compared: training mode"


def test_every_control_of_the_rehearsed_drill_ends_not_correct():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (mf.ROOT, env.get("PYTHONPATH", "")) if p)
    env.pop("JAX_PLATFORMS", None)      # --rehearsal pins the CPU itself
    proc = subprocess.run(
        [sys.executable, "benchmarks/smallthinker_control_drill.py",
         "--workload", CELL, "--rehearsal"], cwd=mf.ROOT, env=env, text=True,
        capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["passed"] is True
    assert set(report["controls"]) == {
        "lower_precision", "no_window", "half_window", "rope_on_global",
        "router_after_attention", "silu_experts"}
    for name, r in report["controls"].items():
        assert r["correct"] is False and r["failed"], name
    assert any("parity loss" in f for f in
               report["controls"]["lower_precision"]["failed"])
    assert any("parity routing" in f for f in
               report["controls"]["router_after_attention"]["failed"])
