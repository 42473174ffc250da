"""The ``sdar_30b_a3b`` configuration and what its cell adds to the
benchmark: the configuration file's two views and its parameter count,
every new per-layer metric on a hand-made trace, the FLOPs function,
the generator of noised batches, and the control drill's rehearsal."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import bd_generator, bd_lm_arithmetic, manifest as mf
from benchmarks import trace as tr
from benchmarks.readers import program

M = mf.Manifest()
CELL = "sdar_30b_a3b_ep8_s8k"
CONFIG = M.config("sdar_30b_a3b")
NEW = ["train.bd_attn_fwd_roofline", "train.bd_attn_bwd_roofline",
       "train.bd_attn_kernel_ms_per_step", "train.attn_qk_ms_per_step",
       "train.bd_masked_share"]
# the source's config.json, as the catalog of configurations has it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


# -- the configuration ----------------------------------------------------------

def test_every_published_key_is_kept_but_for_reduced():
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"} \
        == set(M.config_entry("sdar_30b_a3b")["reduced"])
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key


def test_the_config_entry_holds_but_for_the_depth_key():
    """What ``test_bench_manifest.py::test_config_entry`` asserts of an
    entry, for this one, with a width read as a width. That test's
    pattern refuses any ``reduced`` key that matches ``hidden``, and so
    the depth key ``num_hidden_layers``, which this entry has to reduce
    (6 of the 48 layers): its case for this entry FAILS until a
    ``benchmark`` PR narrows the pattern (PERF.md section 7 (h),
    CHANGES.md PR 35), and stops at that line, so the assertions after
    it are made here. This copy goes with that repair."""
    import re
    entry = M.config_entry("sdar_30b_a3b")
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
    assert M.config("sdar_30b_a3b", rehearsal=True)["model"].keys() \
        == CONFIG["model"].keys()
    assert any(w["config"] == entry["name"] for w in M.doc["workloads"])
    assert os.path.exists(os.path.join(
        mf.ROOT, "benchmarks", "references", entry["name"] + ".py"))
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
        "config.json")


def test_the_model_view_is_the_published_view():
    model = CONFIG["model"]
    shared = set(model) & set(PUBLISHED)
    assert shared >= {"hidden_size", "num_attention_heads",
                      "num_key_value_heads", "head_dim", "rope_theta",
                      "rms_norm_eps", "moe_intermediate_size",
                      "num_experts_per_tok", "norm_topk_prob"}
    for key in shared:
        assert model[key] == CONFIG[key], key
    # the share: the router scores the published experts, 16 held
    assert model["num_experts_total"] == PUBLISHED["num_experts"]
    assert model["num_experts"] * 8 == model["num_experts_total"]
    assert model["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert model["mask_token_id"] == model["vocab_size"] - 1
    assert {"block_length", "noise_law", "mask_token_id",
            "e_score_correction_bias", "learning_rate", "initial_weights",
            "auxiliary_balance_loss"} <= set(CONFIG["assumed"])


def test_the_parameter_count_is_the_built_models():
    """Counted from shapes alone (``jax.eval_shape``: no array of the
    full size is made)."""
    import jax

    from paddle_tpu.models import SdarMoeConfig, SdarMoeForCausalLM

    def shapes():
        return SdarMoeForCausalLM(SdarMoeConfig(
            **CONFIG["model"])).param_dict()

    count = sum(int(np.prod(v.shape)) for v in
                jax.eval_shape(shapes).values())
    stated = CONFIG["parameters"]
    assert count == stated["count"] == 645_623_296
    assert stated["bytes_at_16_a_parameter"] == 16 * count
    per = stated["per_layer"]
    assert per["outside_experts"] == per["attention_q_k_v_o"] \
        + per["router"] + per["norms"]
    assert per["layer"] == per["outside_experts"] + 16 * per["routed_expert"]
    assert count == 6 * per["layer"] \
        + 2 * stated["embedding_or_head_slice"] + stated["final_norm"]


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    cell = M.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "sdar_30b_a3b"
    mix = M.traffic(cell["traffic"])
    assert mix["kind"] == "bd_train_step"
    assert (mix["seq_len"], mix["batch_per_chip"]) == (8192, 2)
    reported = {m["name"] for m in M.metrics_of(CELL, "per_layer")}
    assert set(NEW) <= reported
    for name in NEW:
        entry = next(m for m in M.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
        spec = M.metric_file(name)
        assert {k: spec[k] for k in entry if k != "workloads"} \
            == {k: v for k, v in entry.items() if k != "workloads"}
        assert not spec["reader"].startswith("benchmarks.readers.program.")
    assert {m["name"] for m in M.metrics_of(CELL, "end_to_end")} \
        == {"train_tokens_per_s", "setup_s"}


# -- the new metrics on a hand-made trace ----------------------------------------

FN = "TrainStep(Toy)"
PEAK = 197e12
CALL = ' custom-call(bf16[8]{0} %q), custom_call_target="tpu_custom_call"'
# one step of a toy program with one recomputed attention layer, ns
STEP = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0, 30),
    ("%bd_flash_fwd.2 = bf16[8]{0}" + CALL, 30, 100),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 130, 40),
    ("%bd_flash_fwd.4 = bf16[8]{0}" + CALL, 170, 100),   # recomputed
    ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop", 270, 25),
    ("%bd_flash_bwd_dq.6 = bf16[8]{0}" + CALL, 300, 150),
    ("%bd_flash_bwd_dkv.7 = bf16[8]{0}" + CALL, 450, 250),
    ("%flash_fwd.8 = bf16[8]{0}" + CALL, 700, 60),   # another mask's
]
SCOPES = {
    "fusion.1": "jit(_step)/jvp(pt.attn)/pt.attn_qk/mul",
    "bd_flash_fwd.2": "jit(_step)/jvp(pt.attn)/bd_flash_fwd/pallas_call",
    "fusion.3": "jit(_step)/jvp(pt.attn)/dot_general",
    "bd_flash_fwd.4": "jit(_step)/checkpoint/pt.attn/bd_flash_fwd",
    "fusion.5": "jit(_step)/transpose(jvp(pt.attn))/pt.attn_qk/mul",
    "bd_flash_bwd_dq.6": "jit(_step)/transpose(jvp(pt.attn))/x",
    "bd_flash_bwd_dkv.7": "jit(_step)/transpose(jvp(pt.attn))/y",
    "flash_fwd.8": "jit(_step)/jvp(pt.attn)/flash_fwd/pallas_call",
}
NOTES = [("bd_flash_fwd", 4.0e6, 1.0), ("bd_flash_bwd_dq", 6.0e6, 1.0),
         ("bd_flash_bwd_dkv", 8.0e6, 1.0), ("flash_fwd", 1.0e6, 1.0)]


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
    return {"trace": trace,
            "counters": {"trace_steps": 2, "bd_masked_share": 0.4975}}


WANT = {
    # a noted forward site of 4e6 FLOPs ran twice a step: 4 events, 400 ns
    "train.bd_attn_fwd_roofline": 100.0 * 4.0e6 * 4 / 400e-9 / PEAK,
    # a dq site and a dk/dv site once a step: 2 x 14e6 FLOPs in 800 ns
    "train.bd_attn_bwd_roofline": 100.0 * 14.0e6 * 2 / 800e-9 / PEAK,
    "train.bd_attn_kernel_ms_per_step": (200 + 150 + 250) / 1e6,
    "train.attn_qk_ms_per_step": (30 + 25) / 1e6,
    "train.bd_masked_share": 0.4975,
}


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_reads_the_hand_made_trace(observed, name):
    spec = M.metric_file(name)
    got = mf.resolve(spec["reader"])(observed, **spec["args"])
    assert got == pytest.approx(WANT[name]), name


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_names_gives_nothing_to_read(observed, name,
                                                           monkeypatch):
    """A parent of this PR: no such kernel, scope or counter. The reader
    returns ``None`` and does not raise."""
    from paddle_tpu.observability import xprof
    monkeypatch.setattr(xprof, "kernel_notes", lambda fn: [])
    monkeypatch.setattr(program, "compiled_scopes", lambda fn: {
        k: v.replace("pt.attn_qk/", "") for k, v in SCOPES.items()
        if not k.startswith("bd_")})
    observed["trace"] = tr.Trace(
        {"/device:TPU:0": [e for e in observed["trace"].ops[
            "/device:TPU:0"] if "bd_flash" not in e.name]}, {},
        observed["trace"].annotations, False, "hand-made", 0)
    observed["counters"].pop("bd_masked_share")
    spec = M.metric_file(name)
    got = mf.resolve(spec["reader"])(observed, **spec["args"])
    assert not got, name        # None, or no time at all


# -- arithmetic and generator ------------------------------------------------------

def test_model_flops_of_the_cell_by_hand():
    cfg = CONFIG["model"]
    assert bd_lm_arithmetic.allowed_pairs(8192, 4) == 67_141_632
    attn = bd_lm_arithmetic.attention_flops_forward(cfg, 2, 8192)
    assert attn == 4.0 * 2 * 32 * 128 * 67_141_632
    held = 6 * 32768.0                  # balanced: one pair a position
    flops = bd_lm_arithmetic.sdar_flops_per_step(cfg, 2, 8192, held)
    per_position = 2048 * 128 * (2 * 32 + 2 * 4) + 2048 * 128
    forward = 2.0 * (6 * 32768 * per_position + held * 3 * 2048 * 768
                     + 16384 * 2048 * 18992) + 6 * attn
    assert flops == pytest.approx(3.0 * forward)
    assert 70e12 < flops < 73e12
    assert 0.5 < 6 * attn / forward < 0.6   # attention does most of it
    # no recomputation and no masked tile: more held pairs, more FLOPs
    assert bd_lm_arithmetic.sdar_flops_per_step(cfg, 2, 8192, 2 * held) \
        - flops == pytest.approx(3.0 * 2.0 * held * 3 * 2048 * 768)


def test_noised_batches_follow_the_law_and_the_seed():
    mix = dict(M.traffic("bd_pretrain_s8k_b2"), seq_len=512,
               pool_batches=3)
    draw = lambda seed: bd_generator.block_diffusion_batches(
        mix, 100, 99, 4, 2, seed)
    batches = draw(2 ** 33 + 5)
    again = draw(2 ** 33 + 5)
    other = draw(2 ** 33 + 6)
    assert len(batches) == 3
    for (ids, x0, t), (ids2, _, _), (ids3, _, _) in zip(batches, again,
                                                       other):
        assert ids.shape == (2, 1024) and ids.dtype == np.int32
        assert x0.shape == t.shape == (2, 512) and t.dtype == np.float32
        assert np.array_equal(ids, ids2) and not np.array_equal(ids, ids3)
        assert np.array_equal(ids[:, 512:], x0)       # the clean copy
        assert x0.max() < 99, "the MASK row is never drawn as a token"
        masked = ids[:, :512] == 99
        assert np.array_equal(ids[:, :512][~masked], x0[~masked])
        # one level a block, inside (t_min, 1]
        assert np.all(t.reshape(2, -1, 4) == t.reshape(2, -1, 4)[..., :1])
        assert t.min() >= 1e-3 and t.max() <= 1.0
        assert 0.35 < masked.mean() < 0.65
        # masked with its block's probability: more where t is large
        assert masked[t > 0.8].mean() > masked[t < 0.2].mean() + 0.4


def test_seq_len_must_be_whole_blocks():
    mix = dict(M.traffic("bd_pretrain_s8k_b2"), seq_len=510)
    with pytest.raises(ValueError, match="blocks"):
        bd_generator.block_diffusion_batches(mix, 100, 99, 4, 2, 0)


# -- the comparison and the control drill ------------------------------------------

def test_the_comparison_reads_the_first_blocks_too():
    """``check_parity`` at tiny widths: the whole sequence's margins and
    the first blocks', each under its limit; the embedding table is the
    model's own draw at ``train.embedding_table_std``."""
    from benchmarks.runners import bd_train_step
    from test_bench_parity import make_run

    run = make_run(CELL)
    model = bd_train_step.build_model(run)
    table = np.asarray(model.embed_tokens.weight, np.float32)
    assert abs(float(table.std())
               - run.config["train"]["embedding_table_std"]) < 0.05
    assert abs(float(np.asarray(model.lm_head.weight,
                                np.float32).std()) - 0.02) < 0.003
    batch = bd_train_step.make_batches(run)[0]
    bd_train_step.check_parity(run, model, batch)
    tol = run.config["tolerances"]
    assert run.margins["parity_loss_abs"] <= tol["loss_abs"]
    for leaf, limit in tol["grad_rel_l2"].items():
        assert run.margins["parity_grad_rel:" + leaf] <= limit, leaf
    for leaf, limit in tol["first_blocks"]["grad_rel_l2"].items():
        assert 0 < run.margins["parity_first_blocks_grad_rel:" + leaf] \
            <= limit, leaf
    assert set(tol["first_blocks"]["grad_rel_l2"]) <= set(
        tol["grad_rel_l2"]), "no leaf the reference does not watch"
    assert model.training, "the timed path is compared: training mode"



def test_every_control_of_the_rehearsed_drill_ends_not_correct():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (mf.ROOT, env.get("PYTHONPATH", "")) if p)
    env.pop("JAX_PLATFORMS", None)      # --rehearsal pins the CPU itself
    proc = subprocess.run(
        [sys.executable, "benchmarks/bd_control_drill.py", "--workload",
         CELL, "--rehearsal"], cwd=mf.ROOT, env=env, text=True,
        capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["passed"] is True
    assert set(report["controls"]) == {
        "lower_precision", "causal_mask", "unshared_positions",
        "sigmoid_router", "no_inverse_t"}
    for name, r in report["controls"].items():
        assert r["correct"] is False and r["failed"], name
    # a causal mask in the rule's place is refused over the first blocks
    assert any("over the first blocks" in f for f in
               report["controls"]["causal_mask"]["failed"])
    # the lower precision gives itself away by its loss alone
    assert all("parity loss" in f for f in
               report["controls"]["lower_precision"]["failed"])
