"""The ``kimi_linear_48b_a3b`` configuration and what its cell adds to
the benchmark: the configuration file's two views and its parameter
count, every new per-layer metric on a hand-made trace, the FLOPs
function, the comparison and the control drill's rehearsal."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import kimi_lm_arithmetic, manifest as mf
from benchmarks import trace as tr
from benchmarks.readers import program

M = mf.Manifest()
CELL = "kimi_linear_48b_a3b_ep32_s16k"
CONFIG = M.config("kimi_linear_48b_a3b")
NEW = ["train.kda_scan_ms_per_step", "train.kda_proj_ms_per_step",
       "train.kda_conv_ms_per_step", "train.mla_latent_ms_per_step"]
# the source's config.json, as the catalog of configurations has it
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                       19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


# -- the configuration ----------------------------------------------------------

def test_every_published_key_is_kept_but_for_reduced():
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "linear_attn_config",
                       "num_experts", "vocab_size"} \
        == set(M.config_entry("kimi_linear_48b_a3b")["reduced"])
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    # the cut group keeps every width: only its layer lists change
    cut, whole = CONFIG["linear_attn_config"], PUBLISHED["linear_attn_config"]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert cut[key] == whole[key], key
    assert cut["kda_layers"] == [1, 2, 3, 5] and cut["full_attn_layers"] \
        == [4]
    assert all(i in whole["kda_layers"] for i in cut["kda_layers"])
    assert all(i in whole["full_attn_layers"]
               for i in cut["full_attn_layers"])


def test_the_config_entry_holds_but_for_the_depth_key():
    """What ``test_bench_manifest.py::test_config_entry`` asserts of an
    entry, for this one, with a width read as a width: that test's
    pattern refuses the depth key ``num_hidden_layers`` (PERF.md section
    7 (h)), so its case for this entry fails, as the block-diffusion
    entry's does, and stops at that line; the assertions after it are
    made here."""
    entry = M.config_entry("kimi_linear_48b_a3b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in M.doc["paths"])
    for key in ("source", "why"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
            and "\t" not in entry[key]
    for key in entry["reduced"]:
        assert mf.NAME_RE.match(key)
        assert not re.search(
            r"(_dim|_rank|hidden_size|intermediate|head|_per_tok)", key), \
            "a width may never be reduced"
    assert CONFIG["reduced"] == entry["reduced"]
    assert M.config("kimi_linear_48b_a3b", rehearsal=True)["model"].keys() \
        == CONFIG["model"].keys()
    assert any(w["config"] == entry["name"] for w in M.doc["workloads"])
    assert os.path.exists(os.path.join(
        mf.ROOT, "benchmarks", "references", entry["name"] + ".py"))
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")


def test_the_model_view_is_the_published_view():
    model = CONFIG["model"]
    shared = set(model) & set(PUBLISHED)
    assert shared >= {"hidden_size", "intermediate_size",
                      "num_attention_heads", "kv_lora_rank",
                      "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                      "rms_norm_eps", "moe_intermediate_size",
                      "num_experts_per_token", "num_shared_experts",
                      "routed_scaling_factor", "moe_renormalize",
                      "first_k_dense_replace", "linear_attn_config"}
    for key in shared:
        assert model[key] == CONFIG[key], key
    # the share: the router scores the published experts, 8 held of 32
    assert model["num_experts_total"] == PUBLISHED["num_experts"]
    assert model["num_experts"] * 32 == model["num_experts_total"]
    assert model["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert {"head_dim", "mla_use_nope", "e_score_correction_bias",
            "chunk_and_stability", "learning_rate", "initial_weights",
            "auxiliary_balance_loss", "document_boundaries"} \
        <= set(CONFIG["assumed"])


def test_the_parameter_count_is_the_built_models():
    """Counted from shapes alone (``jax.eval_shape``: no array of the
    full size is made)."""
    import jax

    from paddle_tpu.models import KimiLinearConfig, KimiLinearForCausalLM

    def shapes():
        return KimiLinearForCausalLM(KimiLinearConfig(
            **CONFIG["model"])).param_dict()

    count = sum(int(np.prod(v.shape)) for v in
                jax.eval_shape(shapes).values())
    stated = CONFIG["parameters"]
    assert count == stated["count"] == 602_449_792
    assert stated["bytes_at_16_a_parameter"] == 16 * count
    per = stated["per_layer"]
    experts = per["router"] + per["shared_expert"] \
        + 8 * per["routed_expert"] + per["norms"]
    assert per["layer_1_kda_dense"] == per["kda_mixer"] \
        + per["dense_mlp"] + per["norms"]
    assert per["kda_expert_layer"] == per["kda_mixer"] + experts
    assert per["mla_expert_layer"] == per["mla_mixer"] + experts
    assert count == per["layer_1_kda_dense"] + 3 * per["kda_expert_layer"] \
        + per["mla_expert_layer"] + 2 * stated["embedding_or_head_slice"] \
        + stated["final_norm"]


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    cell = M.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "kimi_linear_48b_a3b"
    mix = M.traffic(cell["traffic"])
    assert mix["kind"] == "kimi_lm_train_step"
    assert (mix["seq_len"], mix["batch_per_chip"], mix["pool_batches"],
            mix["loss_fetch_every"], mix["zipf_s"]) == (16384, 1, 16, 5, 1.0)
    reported = {m["name"] for m in M.metrics_of(CELL, "per_layer")}
    assert set(NEW) <= reported
    # the value-width flash kernels' shares of their roofline
    assert {"train.flash_fwd_roofline", "train.flash_bwd_mxu_pct",
            "train.mfu_pct", "train.moe_gmm_roofline"} <= reported
    for name in NEW:
        entry = next(m for m in M.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
        spec = M.metric_file(name)
        assert {k: spec[k] for k in entry if k != "workloads"} \
            == {k: v for k, v in entry.items() if k != "workloads"}
        assert spec["reader"] == "benchmarks.readers.blocks.block_ms_per_unit"
    assert {m["name"] for m in M.metrics_of(CELL, "end_to_end")} \
        == {"train_tokens_per_s", "setup_s"}


# -- the new metrics on a hand-made trace ----------------------------------------

FN = "TrainStep(Toy)"
STEP = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0, 30),
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 30, 70),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop", 100, 20),
    ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop", 120, 10),
    ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %d), kind=kLoop", 130, 40),
    ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %e), kind=kLoop", 170, 15),
]
SCOPES = {
    "fusion.1": "jit(_step)/jvp(pt.kda_proj)/dot_general",
    "fusion.2": "jit(_step)/jvp(pt.kda_scan)/while/pt.kda_scan/dot_general",
    "fusion.3": "jit(_step)/transpose(jvp(pt.kda_conv))/mul",
    "fusion.4": "jit(_step)/jvp(pt.attn)/pt.mla_latent/dot_general",
    "fusion.5": "jit(_step)/transpose(jvp(pt.kda_scan))/pt.kda_scan/mul",
    "fusion.6": "jit(_step)/jvp(pt.attn)/dot_general",
}
WANT = {
    "train.kda_proj_ms_per_step": 30 / 1e6,
    "train.kda_scan_ms_per_step": (70 + 40) / 1e6,
    "train.kda_conv_ms_per_step": 20 / 1e6,
    "train.mla_latent_ms_per_step": 10 / 1e6,
}


@pytest.fixture
def observed(monkeypatch):
    ops, annotations = [], [tr.Event("bench/slice", 0.0, 400.0)]
    for s in range(2):
        for name, start, dur in STEP:
            ops.append(tr.Event(name, 200.0 * s + start, float(dur)))
        annotations.append(tr.Event("bench/step_call", 200.0 * s, 200.0))
    trace = tr.Trace({"/device:TPU:0": ops}, {}, annotations, False,
                     "hand-made", 0)
    monkeypatch.setattr(program, "read_annotations",
                        lambda path: annotations)
    monkeypatch.setattr(program, "entry_point", lambda pattern: FN)
    monkeypatch.setattr(program, "compiled_scopes", lambda fn: SCOPES)
    return {"trace": trace, "counters": {"trace_steps": 2}}


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_the_hand_made_trace(observed, name):
    spec = M.metric_file(name)
    got = mf.resolve(spec["reader"])(observed, **spec["args"])
    assert got == pytest.approx(WANT[name]), name


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_names_gives_nothing_to_read(observed, name,
                                                           monkeypatch):
    """A parent of this PR: no such scope. The reader returns nothing
    (``None`` or no time) and does not raise."""
    monkeypatch.setattr(program, "compiled_scopes", lambda fn: {
        k: re.sub(r"pt\.(kda_\w+|mla_latent)", "pt.attn", v)
        for k, v in SCOPES.items()})
    spec = M.metric_file(name)
    got = mf.resolve(spec["reader"])(observed, **spec["args"])
    assert not got, name


# -- arithmetic ----------------------------------------------------------------------

def test_model_flops_of_the_cell_by_hand():
    cfg = CONFIG["model"]
    tokens, held = 16384, 4 * 16384 * 8 / 32       # balanced
    flops = kimi_lm_arithmetic.kimi_flops_per_step(cfg, 1, 16384, held)
    kda = 4 * 2304 * 4096 + 2 * (2304 + 4096) * 128 + 2304 * 32
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304
    expert = 2304 * 256 + 3 * 2304 * 1024
    per_token = 4 * kda + mla + 3 * 2304 * 9216 + 4 * expert \
        + 2304 * 20480
    rule = 4.0 * 64 * 64 * 128 + 64 * 64 * 256 + 6.0 * 64 * 128 * 128 \
        + 2.0 * 64 * 64 * 128
    assert kimi_lm_arithmetic.kda_rule_flops_per_chunk(64, 128, 128) == rule
    attn = 2.0 * 32 * 16384 * 16385 / 2 * 320
    assert kimi_lm_arithmetic.mla_attention_flops_forward(cfg, 1, 16384) \
        == attn
    forward = 2.0 * (tokens * per_token + held * 3 * 2304 * 1024) \
        + 4 * 256 * 32 * rule + attn
    assert flops == pytest.approx(3.0 * forward)
    assert 41e12 < flops < 44e12          # 2.59 GFLOP a token
    # the two mixers are most of it
    mixers = 3.0 * (2.0 * tokens * (4 * kda + mla) + 4 * 256 * 32 * rule
                    + attn)
    assert 0.6 < mixers / flops < 0.7


# -- the comparison and the control drill ------------------------------------------

def test_the_comparison_at_tiny_widths():
    """``check_parity`` on the runner's sequences: every margin under its
    limit, the model in training mode."""
    from benchmarks.runners import kimi_lm_train_step as runner
    from benchmarks.runners.lm_train_step import check_parity
    from test_bench_parity import make_run

    run = make_run(CELL)
    model = runner.build_model(run)
    batches = runner.make_batches(run)
    runner.fit_router_bias(run, model, batches[0][0])
    ids, labels = runner.parity_batch(run, batches)
    assert ids.shape == (2, run.mix["seq_len"])
    check_parity(run, model, (ids, labels))
    tol = run.config["tolerances"]
    assert run.margins["parity_loss_abs"] <= tol["loss_abs"]
    for leaf, limit in tol["grad_rel_l2"].items():
        assert 0 < run.margins["parity_grad_rel:" + leaf] <= limit, leaf
    assert model.training, "the timed path is compared: training mode"


def test_every_control_of_the_rehearsed_drill_ends_not_correct():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (mf.ROOT, env.get("PYTHONPATH", "")) if p)
    env.pop("JAX_PLATFORMS", None)      # --rehearsal pins the CPU itself
    proc = subprocess.run(
        [sys.executable, "benchmarks/kimi_control_drill.py", "--workload",
         CELL, "--rehearsal"], cwd=mf.ROOT, env=env, text=True,
        capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["passed"] is True
    assert set(report["controls"]) == {
        "lower_precision", "scalar_decay", "no_delta", "rope_in_mla",
        "softmax_router"}
    for name, r in report["controls"].items():
        assert r["correct"] is False and r["failed"], name
    assert any("parity loss" in f for f in
               report["controls"]["lower_precision"]["failed"])
