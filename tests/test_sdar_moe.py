"""SDAR-MoE trained by diffusion over blocks (q/k norm, rotary
positions, the block-diffusion mask, a softmax router over gated
experts) against the plain float32 reference of
``benchmarks/references``, at tiny widths on the CPU. Both sides compute
in float32 here, so the only difference is the order of summation:
every tolerance is 1e-4 relative, far under what any of the planted
faults does."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmarks import bd_control_drill
from benchmarks.references import sdar_30b_a3b as ref
from paddle_tpu import models
from paddle_tpu.models import (SdarMoeConfig, SdarMoeForCausalLM,
                               balance_router_bias,
                               block_diffusion_metrics)
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.nn.layers import moe
from paddle_tpu.static import TrainStep

TOL = 1e-4
CFG = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rope_theta=1e6, rms_norm_eps=1e-6, num_experts=4,
    num_experts_total=16, expert_offset=4, num_experts_per_tok=3,
    moe_intermediate_size=24, norm_topk_prob=True, block_length=4,
    mask_token_id=95)
SEQ = 20


@pytest.fixture(autouse=True)
def _small_windows(monkeypatch):
    """Windows of a few rows, so every test walks more than one."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)


def build(seed=0, **over):
    pt.seed(seed)
    model = SdarMoeForCausalLM(SdarMoeConfig(**{**CFG, **over}))
    # no gain at its initial 1: a norm that is left out must show
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p.value = p.value + jnp.asarray(
                rng.normal(0, 0.3, p.shape), p.value.dtype)
    return model


def batch(seed=1, rows=2, seq=SEQ):
    """(ids [B, 2 L], x0 [B, L], t [B, L]) as the benchmark's generator
    draws them."""
    rng = np.random.default_rng(seed)
    k, mask_id = CFG["block_length"], CFG["mask_token_id"]
    x0 = rng.integers(0, mask_id, (rows, seq)).astype(np.int32)
    t = np.repeat(1.0 - rng.random((rows, seq // k)) * 0.999, k, axis=1) \
        .astype(np.float32)
    xt = np.where(rng.random((rows, seq)) < t, mask_id, x0).astype(np.int32)
    return np.concatenate([xt, x0], axis=1), x0, t


def system(model, ids, labels, t):
    buffers = model.buffer_dict()

    @jax.jit
    def run(params):
        def loss_of(p):
            out = functional_call(model, p, buffers, ids)
            # looked up at call time: a fault may have replaced it
            return models.block_diffusion_loss(out, labels, t), out

        (loss, out), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        return out, loss, grads

    return run(model.param_dict())


@jax.jit
def _reference(params, buffers, ids, labels, t):
    loss, grads = jax.value_and_grad(
        lambda p: ref.loss(p, CFG, ids, labels, t, buffers))(params)
    return ref.logits(params, CFG, ids, buffers), loss, grads


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    off = np.linalg.norm(a - b)
    # a layer none of whose held experts was chosen has no gradient
    return 0.0 if off == 0 else off / np.linalg.norm(b)


def compare(model, seq=SEQ):
    ids, labels, t = batch(seq=seq)
    out, loss, grads = system(model, ids, labels, t)
    ref_logits, ref_loss, ref_grads = _reference(
        model.param_dict(), model.buffer_dict(), ids, labels, t)
    assert rel(out.logits(), ref_logits) < TOL
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    for name in ref_grads:          # every gradient
        assert rel(grads[name], ref_grads[name]) < TOL, name
    assert int(out.moe_pairs_dropped) == 0
    return out


@pytest.mark.parametrize("recompute", ["none", "layer"])
def test_logits_loss_and_gradients_match_the_reference(recompute):
    out = compare(build(recompute=recompute))
    # two layers, 80 positions, 3 choices each, a quarter of the experts
    assert 0 < int(out.moe_pairs_held) < 2 * 80 * 3
    ids, _, _ = batch()
    assert int(out.bd_masked_tokens) == int(
        (ids[:, :SEQ] == CFG["mask_token_id"]).sum())


@pytest.mark.parametrize("fault", sorted(bd_control_drill.FAULTS))
def test_a_fault_fails_the_comparison(fault):
    """The benchmark's controls, planted in the float32 comparison: a
    causal mask, unshared positions, no q/k norm, a sigmoid router, a
    missing 1 / t."""
    with bd_control_drill.FAULTS[fault]():
        with pytest.raises(AssertionError):
            compare(build())
    compare(build())        # and the fault is gone


def test_a_train_step_moves_every_parameter_by_the_references_gradient():
    """Through ``static.TrainStep``: plain SGD at rate 1 moves a
    parameter by minus its gradient."""
    model = build(recompute="layer")
    before = {k: np.asarray(v) for k, v in model.param_dict().items()}
    buffers = model.buffer_dict()
    step = TrainStep(model, pt.optimizer.SGD(1.0),
                     models.block_diffusion_loss,
                     extra_metrics=block_diffusion_metrics())
    ids, labels, t = batch()
    _, ref_loss, ref_grads = _reference(before, buffers, ids, labels, t)
    got = step(ids, labels=(labels, t))
    assert abs(float(got["loss"]) - float(ref_loss)) < TOL * float(ref_loss)
    for name, g in ref_grads.items():
        moved = before[name] - np.asarray(step.state["params"][name])
        # the subtraction rounds at the parameter's size
        assert np.linalg.norm(moved - g) < TOL * np.linalg.norm(g) \
            + 1e-6 * np.linalg.norm(before[name]), name
    assert int(got["bd_masked_tokens"]) == int(
        (ids[:, :SEQ] == CFG["mask_token_id"]).sum())
    assert int(got["moe_pairs_dropped"]) == 0
    assert int(got["moe_windows_run"]) >= 2


def test_the_eight_shares_add_up_to_the_uncut_expert_layer():
    """128 experts as 8 ranks of 16 at a small width: the ranks' routed
    parts, summed, are the uncut reference's expert layer."""
    cfg = dict(CFG, num_experts_total=128, num_experts_per_tok=8,
               num_experts=128, expert_offset=0)
    pt.seed(5)
    whole = pt.nn.DroplessMoE(32, 24, 128, 8, score_func="softmax",
                              gated=True)
    params = whole.param_dict()
    bias = jnp.asarray(np.random.default_rng(2).normal(0, 0.003, 128),
                       jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, SEQ, 32)),
                    jnp.float32)
    tokens = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(params, "", cfg, tokens, bias)
    total, pairs = 0.0, 0
    for rank in range(8):
        share = pt.nn.DroplessMoE(32, 24, 128, 8, experts_held=16,
                                  expert_offset=16 * rank,
                                  score_func="softmax", gated=True)
        mine = {**params,
                "w_in": params["w_in"][16 * rank:16 * rank + 16],
                "w_out": params["w_out"][16 * rank:16 * rank + 16]}
        out, stats = functional_call(
            share, mine, {"e_score_correction_bias": bias}, x)
        with jax.default_matmul_precision("highest"):
            alone = ref.routed_experts(mine, "", cfg, tokens, bias, 16,
                                       16 * rank)
        assert rel(out.reshape(-1, 32), alone) < TOL
        total = total + out.reshape(-1, 32)
        pairs += int(stats["pairs_held"])
        assert int(stats["pairs_dropped"]) == 0
    assert rel(total, want) < TOL
    assert pairs == tokens.shape[0] * 8, "every pair is someone's"


def _zipf_ids(rows=4, seq=64, seed=7):
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, CFG["mask_token_id"] + 1)
    x0 = rng.choice(CFG["mask_token_id"], (rows, seq), p=p / p.sum())
    xt = np.where(rng.random((rows, seq)) < 0.5, CFG["mask_token_id"], x0)
    return np.concatenate([xt, x0], axis=1).astype(np.int32)


def test_fitting_the_selection_bias_balances_the_experts():
    model = build()
    ids = _zipf_ids()
    before = model(ids).moe_expert_load
    worst_before = float(jnp.max(before.max(1) / before.mean(1)))
    worst = balance_router_bias(model, ids)
    after = model(ids).moe_expert_load
    assert int(after.sum()) == int(before.sum()), "no pair went missing"
    assert worst < 0.75 * worst_before, (worst, worst_before)
    assert model.training
    # the fitted bias is what selects, in the reference too
    compare(model)
    # 64 passes, sized for softmax scores over 128 experts
    names, rounds, first, last = model.router_bias_fit()
    assert (rounds, first, last) == (64, 4e-3 * 8, 3e-5 * 8)
    assert names == [f"layers.{i}.mlp.e_score_correction_bias"
                     for i in range(2)]


def test_the_bias_moves_a_step_at_a_time_while_training():
    model = build(router_bias_update_rate=3e-4, recompute="layer")
    step = TrainStep(model, pt.optimizer.AdamW(1e-3),
                     models.block_diffusion_loss)
    ids, labels, t = batch()
    step(ids, labels=(labels, t))
    name = "layers.1.mlp.e_score_correction_bias"
    once = np.asarray(step.state["buffers"][name])
    assert set(np.unique(np.abs(once))) <= {0.0, np.float32(3e-4)}
    assert np.any(once != 0)


@pytest.mark.parametrize("blocks", [1, 3])
def test_the_first_blocks_loss_is_the_models_on_the_first_blocks_alone(
        blocks):
    """What the benchmark's second comparison rests on
    (``runners.bd_train_step.check_parity``): blocks are causal to one
    another, so the loss over a sequence's first blocks, every later
    position's ``t`` infinite so that it weighs nothing, is the loss of
    those blocks of both copies run alone, times their share of the
    tokens; and so are its gradients, in the system and in the
    reference."""
    model = build()
    ids, labels, t = batch()
    n = blocks * CFG["block_length"]
    far = np.where(np.arange(SEQ) < n, t, np.float32(np.inf))
    cut = np.concatenate([ids[:, :n], ids[:, SEQ:SEQ + n]], axis=1)
    _, loss, grads = system(model, ids, labels, far)
    _, ref_whole, _ = _reference(model.param_dict(), model.buffer_dict(),
                                 ids, labels, far)
    _, ref_cut, ref_grads = _reference(
        model.param_dict(), model.buffer_dict(), cut, labels[:, :n],
        t[:, :n])
    share = n / SEQ
    assert abs(float(ref_whole) - share * float(ref_cut)) \
        < TOL * float(ref_whole)
    assert abs(float(loss) - share * float(ref_cut)) < TOL * float(loss)
    for name, g in ref_grads.items():
        assert rel(grads[name], share * np.asarray(g)) < TOL, name
    # and a causal mask in the rule's place shows on them
    with bd_control_drill.FAULTS["causal_mask"]():
        _, _, wrong = system(model, ids, labels, far)
    name = "layers.0.self_attn.q_proj.weight"
    assert rel(wrong[name], share * np.asarray(ref_grads[name])) > 0.05


def test_train_step_returns_the_counters_and_learns():
    model = build(recompute="layer")
    model.to(dtype="bfloat16")
    step = TrainStep(model, pt.optimizer.AdamW(3e-3),
                     models.block_diffusion_loss,
                     extra_metrics=block_diffusion_metrics())
    ids, labels, t = batch(rows=4)
    first = step(ids, labels=(labels, t))
    for _ in range(14):
        last = step(ids, labels=(labels, t))
    assert float(last["loss"]) < float(first["loss"]) - 0.3
    assert int(last["moe_pairs_dropped"]) == 0
    assert 0 < int(last["moe_pairs_held"]) < 2 * 4 * 2 * SEQ * 3
    assert 0 < int(last["bd_masked_tokens"]) <= 4 * SEQ


def test_the_step_names_its_blocks_and_counts_no_kernel_site_off_a_tpu():
    """``pt.attn_qk`` beside the hybrid decoder's scopes in the compiled
    step, and the trace-time gauge of block-diffusion attention sites at
    0 where the seam runs plain XLA attention."""
    import re

    from paddle_tpu import observability as obs
    from paddle_tpu.observability import xprof
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        step = TrainStep(build(recompute="layer"),
                         pt.optimizer.AdamW(1e-3),
                         models.block_diffusion_loss,
                         extra_metrics=block_diffusion_metrics())
        ids, labels, t = batch()
        step(ids, labels=(labels, t))
        scopes = xprof.op_scopes(step._span_name)
        named = {m for s in scopes.values()
                 for m in re.findall(r"pt\.[a-z_]+", s)}
        assert {"pt.embed", "pt.attn", "pt.attn_qk", "pt.moe_route",
                "pt.moe_experts", "pt.head_loss", "pt.optimizer",
                "pt.guard"} <= named, named
        assert obs.gauge("pt_bd_attention_sites").value(
            fn=step._span_name) == 0
        assert xprof.kernel_notes(step._span_name) == []
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", was)


def test_input_must_be_two_copies_of_whole_blocks():
    model = build()
    with pytest.raises(ValueError, match="whole"):
        model(np.zeros((1, 2 * 18), np.int32))
    with pytest.raises(ValueError, match="whole"):
        model(np.zeros((1, 41), np.int32))


def test_a_step_on_the_kernels_publishes_the_census_of_its_tile_walk(
        monkeypatch):
    """With the seam on the ``bd_flash_*`` kernels (interpreted here),
    the three trace-time gauges of the step are the census of one head
    and sequence's forward walk, summed over its layers' one forward
    call site each."""
    import functools

    from paddle_tpu import kernels
    from paddle_tpu import observability as obs
    from paddle_tpu.kernels import flash_attention as fa
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    monkeypatch.setattr(fa, "BLOCK_Q", 16)
    monkeypatch.setattr(fa, "BLOCK_K", 16)
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        step = TrainStep(build(recompute="layer", head_dim=128,
                               num_attention_heads=2,
                               num_key_value_heads=1),
                         pt.optimizer.AdamW(1e-3),
                         models.block_diffusion_loss,
                         extra_metrics=block_diffusion_metrics())
        ids, labels, t = batch(seq=32)
        assert np.isfinite(float(step(ids, labels=(labels, t))["loss"]))
        fn = step._span_name
        assert obs.gauge("pt_bd_attention_sites").value(fn=fn) == 2
        one = fa.flash_tile_census(64, 64, 16, 16, False, (32, 4))
        assert one[1] > 0 and one[0] > one[1]
        for kind, count in zip(("visited", "whole", "diagonal"), one):
            assert obs.gauge("pt_flash_tiles_" + kind).value(fn=fn) \
                == CFG["num_hidden_layers"] * count, kind
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()
