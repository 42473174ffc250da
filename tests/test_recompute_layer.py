"""What ``recompute="layer"`` keeps of a recomputed layer
(``nn.recompute_layer``): the flash kernel's result and the expert
layer's routing plan, by name; everything else is made again. Both
decoders at tiny widths on the CPU, their attention sent to the flash
kernels under the Pallas interpreter, against the same step under a bare
``jax.checkpoint``."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import kernels, models, nn
from paddle_tpu import observability as obs
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.nn.layers import moe
from paddle_tpu.observability import xprof
from paddle_tpu.static import TrainStep

ROWS, HEADS, HEAD_DIM, TOP_K, EXPERTS, HELD = 2, 4, 8, 3, 16, 4
SDAR = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=HEADS, num_key_value_heads=2, head_dim=HEAD_DIM,
    rope_theta=1e6, rms_norm_eps=1e-6, num_experts=HELD,
    num_experts_total=EXPERTS, expert_offset=4,
    num_experts_per_tok=TOP_K, moe_intermediate_size=24,
    norm_topk_prob=True, block_length=4, mask_token_id=95)
NEMOTRON = dict(
    vocab_size=96, hidden_size=32, hybrid_override_pattern="MEM*E",
    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
    conv_kernel=4, chunk_size=8, n_routed_experts=HELD,
    n_routed_experts_total=EXPERTS, expert_offset=4,
    num_experts_per_tok=TOP_K, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, routed_scaling_factor=2.5,
    norm_topk_prob=True, num_attention_heads=HEADS,
    num_key_value_heads=2, head_dim=HEAD_DIM, layer_norm_epsilon=1e-5)


class Case:
    """A decoder, a batch for it, and what its step should keep."""

    def __init__(self, name: str) -> None:
        rng = np.random.default_rng(1)
        if name == "sdar_moe":
            self.config = lambda **kw: models.SdarMoeConfig(**SDAR, **kw)
            self.model = models.SdarMoeForCausalLM
            self.loss = models.block_diffusion_loss
            self.metrics = models.block_diffusion_metrics()
            length = 20
            x0 = rng.integers(0, 95, (ROWS, length)).astype(np.int32)
            t = np.repeat(1.0 - rng.random((ROWS, length // 4)) * 0.999,
                          4, axis=1).astype(np.float32)
            xt = np.where(rng.random((ROWS, length)) < t, 95, x0)
            self.ids = np.concatenate([xt, x0], axis=1).astype(np.int32)
            self.labels = (x0, t)
            self.flash, self.attention, self.expert = "bd_flash_fwd", 2, 2
        else:
            self.config = lambda **kw: models.NemotronHConfig(**NEMOTRON,
                                                              **kw)
            self.model = models.NemotronHForCausalLM
            self.loss = models.next_token_loss
            self.metrics = models.routing_metrics()
            ids = rng.integers(0, 96, (ROWS, 25)).astype(np.int32)
            self.ids, self.labels = ids[:, :-1], (ids[:, 1:],)
            self.flash, self.attention, self.expert = "flash_fwd", 1, 2
        self.positions = self.ids.shape[1]

    def build(self, recompute="layer"):
        pt.seed(0)
        model = self.model(self.config(recompute=recompute))
        model.train()
        return model

    def kept(self):
        """(named results, their bytes) a step keeps, from the shapes:
        float32 here, the kernel's output as it wrote it [B H, T, D]
        (whole tiles: the lengths here are) and statistics [B, H, T];
        int32 chosen [N, k], order [windows x rows], load [E]."""
        tokens = ROWS * self.positions
        pairs = tokens * TOP_K
        rows = min(pairs, -(-moe.WINDOW_FACTOR * pairs * HELD
                            // (EXPERTS * moe._ROW_TILE)) * moe._ROW_TILE)
        order = -(-pairs // rows) * rows
        return (2 * self.attention + 3 * self.expert,
                4 * (self.attention * tokens * HEADS * (HEAD_DIM + 1)
                     + self.expert * (pairs + order + EXPERTS)))


@pytest.fixture(params=["sdar_moe", "nemotron_h"])
def case(request, monkeypatch):
    """A case with the attention seam sent to the flash kernels, as on
    a TPU, under the interpreter, in the layout narrow heads take."""
    monkeypatch.setattr(fa, "BLOCK_Q", 8)
    monkeypatch.setattr(fa, "BLOCK_K", 8)
    monkeypatch.setattr(moe, "_ROW_TILE", 8)

    def seam(q, k, v, scale=None, causal=False, training=False,
             layout="bthd", block_diffusion=None):
        assert layout == "bthd"
        out = fa.flash_attention(
            *(jnp.moveaxis(x, 2, 1) for x in (q, k, v)), causal=causal,
            scale=scale, interpret=True, block_diffusion=block_diffusion)
        return jnp.moveaxis(out, 1, 2)

    monkeypatch.setattr(kernels, "maybe_flash_attention", seam)
    return Case(request.param)


def _bare_checkpoint(monkeypatch):
    """The layers under ``jax.checkpoint`` with no policy, as they ran
    before anything was kept."""
    monkeypatch.setattr(
        nn, "recompute_layer",
        lambda layer: jax.checkpoint(lambda *args: layer(*args)))


def _value_and_grad(case):
    model = case.build()
    buffers = model.buffer_dict()

    def run(params):
        return case.loss(functional_call(model, params, buffers, case.ids),
                         *case.labels)

    return jax.value_and_grad(run), model.param_dict()


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":     # not a kernel's own
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(inner)


def _made(case):
    """How often the gradient's program makes what a layer may keep."""
    fn, params = _value_and_grad(case)
    made = collections.Counter()
    for eqn in _equations(jax.make_jaxpr(fn)(params).jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            name = eqn.params["name"]
        if name in (case.flash, "top_k", "sort"):
            made[name] += 1
    return made


def test_the_gradient_makes_a_kept_result_once(case, monkeypatch):
    once = {case.flash: case.attention, "top_k": case.expert,
            "sort": case.expert}
    assert _made(case) == once
    _bare_checkpoint(monkeypatch)
    assert _made(case) == {name: 2 * n for name, n in once.items()}


def test_loss_and_gradients_are_bitwise_a_bare_checkpoints(case,
                                                           monkeypatch):
    fn, params = _value_and_grad(case)
    loss, grads = jax.jit(fn)(params)
    _bare_checkpoint(monkeypatch)
    fn, params = _value_and_grad(case)
    bare_loss, bare_grads = jax.jit(fn)(params)
    assert np.isfinite(float(loss))
    assert np.array_equal(loss, bare_loss)
    assert grads.keys() == bare_grads.keys()
    for name in grads:
        assert np.array_equal(grads[name], bare_grads[name]), name


def _traced_step(case, recompute):
    """(kernel notes, kept sites, kept bytes) of a step's trace, and of
    the trace ``op_scopes`` makes when it lowers the step again."""
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        step = TrainStep(case.build(recompute), pt.optimizer.AdamW(1e-3),
                         case.loss, extra_metrics=case.metrics)
        out = step(case.ids, labels=case.labels)
        assert np.isfinite(float(out["loss"]))

        def read():
            return (collections.Counter(
                n[0] for n in xprof.kernel_notes(step._span_name)),
                obs.gauge("pt_remat_kept_sites").value(
                    fn=step._span_name),
                obs.gauge("pt_remat_kept_bytes").value(
                    fn=step._span_name))

        first = read()
        assert xprof.op_scopes(step._span_name)
        return first, read()
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()


@pytest.mark.parametrize("recompute", ["layer", "none"])
def test_the_step_notes_the_flash_sites_that_run_and_what_it_keeps(
        case, recompute):
    """One forward flash site a layer with or without the checkpoint
    (the trace ``jax.checkpoint`` makes of the layer is not the one the
    gradient runs, and notes nothing), and the gauges at what the shapes
    give, or 0 where no layer is recomputed."""
    first, again = _traced_step(case, recompute)
    assert first == again
    notes, sites, kept_bytes = first
    backward = case.flash.replace("fwd", "bwd")
    assert notes[case.flash] == case.attention
    assert notes[backward + "_dq"] == notes[backward + "_dkv"] \
        == case.attention
    assert (sites, kept_bytes) == (case.kept() if recompute == "layer"
                                   else (0, 0))


def test_a_step_that_recomputes_no_layer_keeps_nothing_and_notes_as_before():
    """A name outside a policy is the identity: BERT's step, whose
    layers no checkpoint wraps, reads 0 on both gauges; the flash
    kernel's gradient alone traces the calls it traced and notes them."""
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   pretraining_loss)

    def flash(q):
        return jax.grad(lambda x: jnp.sum(fa.flash_attention(
            x, x, x, interpret=True)))(q)

    rng = np.random.default_rng(0)
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        step = TrainStep(BertForPretraining(BertConfig(
            vocab_size=128, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=64,
            max_position_embeddings=16)), pt.optimizer.AdamW(1e-3),
            pretraining_loss)
        step(rng.integers(0, 128, (8, 16)).astype(np.int32),
             labels=(rng.integers(0, 128, (8, 3)).astype(np.int32),
                     rng.integers(0, 2, (8,)).astype(np.int32)),
             masked_positions=rng.integers(0, 16, (8, 3)).astype(np.int32))
        q = jnp.ones((1, 2, 16, 8), jnp.float32)
        jax.block_until_ready(obs.instrumented_jit(flash, "flash_probe")(q))
        for fn in (step._span_name, "flash_probe"):
            assert obs.gauge("pt_remat_kept_sites").value(fn=fn) == 0
            assert obs.gauge("pt_remat_kept_bytes").value(fn=fn) == 0
        assert [n[0] for n in xprof.kernel_notes("flash_probe")] \
            == ["flash_fwd", "flash_bwd"]
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()
    calls = [e.params["name"] for e in _equations(
        jax.make_jaxpr(flash)(q).jaxpr) if e.primitive.name == "pallas_call"]
    assert calls == ["flash_fwd", "flash_bwd"]
