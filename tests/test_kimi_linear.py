"""Kimi Linear (Kimi Delta Attention and latent attention beside routed
experts) against the plain float32 reference of ``benchmarks/references``,
at tiny widths on the CPU, and the chunked gated delta rule against the
recurrence written a position at a time, at decays no float32
factorisation of a chunk survives. Both sides compute in float32 here,
so the only difference is the order of summation: every tolerance is
1e-4 relative, far under what any of the planted faults does."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmarks import kimi_control_drill
from benchmarks.references import kimi_linear_48b_a3b as ref
from paddle_tpu import models, nn
from paddle_tpu.models import (KimiLinearConfig, KimiLinearForCausalLM,
                               routing_metrics)
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.nn.layers import moe
from paddle_tpu.nn.layers import kda
from paddle_tpu.nn.layers.kda import kda_chunked
from paddle_tpu.static import TrainStep

TOL = 1e-4
LAYERS = {"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
          "head_dim": 8, "num_heads": 4, "short_conv_kernel_size": 4}
CFG = dict(
    vocab_size=96, hidden_size=32, intermediate_size=48,
    num_hidden_layers=5, rms_norm_eps=1e-5, num_attention_heads=4,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
    v_head_dim=8, linear_attn_config=LAYERS, chunk_size=16,
    first_k_dense_replace=1, num_experts=4, num_experts_total=16,
    expert_offset=4, num_experts_per_token=3, moe_intermediate_size=24,
    num_shared_experts=1, routed_scaling_factor=2.446,
    moe_renormalize=True)
SEQ = 40            # two chunks of 16 and a padded tail


@pytest.fixture(autouse=True)
def _small_windows(monkeypatch):
    """Windows of a few rows, so every test walks more than one."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    off = np.linalg.norm(a - b)
    # a layer none of whose held experts was chosen has no gradient
    return 0.0 if off == 0 else off / np.linalg.norm(b)


def _perturbed(layer, seed):
    """No gain at its initial 1 and no zero bias: a norm or a bias that
    is left out must show."""
    rng = np.random.default_rng(seed)
    for name, p in layer.named_parameters():
        if "norm" in name or name.endswith("bias"):
            p.value = p.value + jnp.asarray(
                rng.normal(0, 0.3, p.shape), p.value.dtype)
    return layer


# -- the chunked rule --------------------------------------------------------

def recurrence(q, k, v, g, beta, scale):
    """The delta rule a position at a time, [B, L, H, *] in and out."""
    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = state * jnp.exp(g_t)[..., None]
        kv = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t,
                                   b_t[..., None] * (v_t - kv))
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state) * scale

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    b, _, h, dk = q.shape
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


# the log-decay a position and channel, drawn in [lo, hi]:
# flash-linear-attention's largest A (16) at its largest dt (0.1) sums to
# below -100 over a chunk of 64, where exp(-g) is past float32; "any" is
# what a wide gate can give, channels that forget at once beside ones
# that keep
DECAYS = {"gentle": (0.019, 0.021), "a_16_dt_max": (1.52, 1.68),
          "any": (0.0, 40.0)}


def unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def sharing_one_direction(key, shape, mix):
    """Unit keys ``mix`` of the way along one shared direction: a trained
    layer's keys are correlated, and at ``mix`` 0.9 and beta near 1 a
    chunk's ``I + A`` is where a product of powers of ``A`` cancels."""
    shared, own = jax.random.split(key)
    u = unit(jax.random.normal(shared, shape[-1:]))
    z = unit(jax.random.normal(own, shape))
    return unit(mix * u + math.sqrt(1 - mix * mix) * z)


# the keys: at random, or 0.9 along one direction with beta near 1, at
# gentle decays, so the chunk's A is large
CASES = {**{name: (lohi, None) for name, lohi in DECAYS.items()},
         "correlated": (DECAYS["gentle"], 0.9)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_rule_is_the_recurrence(case):
    """Output and the gradients of q, k, v, the log-decay and beta, at
    several chunks of 64 and a padded tail."""
    b, length, h, dk, dv = 2, 200, 2, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    (lo, hi), mix = CASES[case]
    q = unit(jax.random.normal(keys[0], (b, length, h, dk)))
    k = unit(jax.random.normal(keys[1], (b, length, h, dk)))
    v = jax.random.normal(keys[2], (b, length, h, dv))
    g = -jax.random.uniform(keys[3], (b, length, h, dk), minval=lo,
                            maxval=hi)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, length, h)))
    if mix is not None:
        k = sharing_one_direction(keys[6], (b, length, h, dk), mix)
        beta = jax.random.uniform(keys[4], (b, length, h), minval=0.97,
                                  maxval=0.999)
    cot = jax.random.normal(keys[5], (b, length, h, dv))
    if (lo, hi) != DECAYS["gentle"]:
        assert float(jnp.max(jnp.sum(g[:, :64], axis=1))) < -100
    args = (q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(lambda *a: kda_chunked(*a, 0.25), *args)
        got_grads = pull(cot)
        want, pull = jax.vjp(lambda *a: recurrence(*a, 0.25), *args)
        want_grads = pull(cot)
    assert rel(got, want) < TOL
    for name, x, y in zip("q k v g beta".split(), got_grads, want_grads):
        assert np.all(np.isfinite(np.asarray(x))), name
        assert rel(x, y) < 5 * TOL, name


def test_the_chunked_rule_in_bf16_stays_near_the_recurrence():
    """bf16 operands, float32 states and decays: the rule's own error is
    that of its operands, at the published chunk and sub-chunk."""
    b, length, h, d = 1, 256, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    q, k = (unit(jax.random.normal(x, (b, length, h, d))) for x in keys[:2])
    v = jax.random.normal(keys[2], (b, length, h, d))
    g = -jax.random.uniform(keys[3], (b, length, h, d), maxval=0.3)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, length, h)))
    bf = jnp.bfloat16
    got = kda_chunked(q.astype(bf), k.astype(bf), v.astype(bf), g, beta,
                      d ** -0.5)
    assert got.dtype == bf
    with jax.default_matmul_precision("highest"):
        want = recurrence(q, k, v, g, beta, d ** -0.5)
    assert rel(got.astype(jnp.float32), want) < 0.03


# -- the chunk's solve ---------------------------------------------------------

def chunk_matrix(c, mix, beta, seed=0, dk=128, decay=0.02):
    """A chunk's strictly lower ``A`` [2, 4, C, C] as the rule forms it,
    ``A_ts = beta sum_c k_tc k_sc exp(g_tc - g_sc)``, at gentle decays;
    ``mix`` None draws the keys at random."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = (2, 4, c, dk)
    k = (unit(jax.random.normal(keys[0], shape)) if mix is None
         else sharing_one_direction(keys[0], shape, mix))
    g = jnp.cumsum(-jax.random.uniform(keys[1], shape, minval=0.9 * decay,
                                       maxval=1.1 * decay), axis=2)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)[..., None]
    pair = jnp.exp(jnp.where(strict, g[:, :, :, None] - g[:, :, None],
                             -jnp.inf))
    return beta * jnp.sum(k[:, :, :, None] * k[:, :, None] * pair, -1)


def doubling_product(a):
    """``prod (I + (-a)^(2^i))``: exact for a nilpotent ``a`` in exact
    arithmetic, and what the chunk solve must not be."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    x, power, n = eye - a, -a, 1
    while 2 * n < c:
        power = jnp.matmul(power, power, precision="highest")
        x = jnp.matmul(x, eye + power, precision="highest")
        n *= 2
    return x


# keys at random; 0.9 along one direction at beta 0.9; one direction at
# beta 0.99
KEYS = {"random": (None, 0.7), "correlated": (0.9, 0.9),
        "one_direction": (1.0, 0.99)}
INVERSES = {"block": kda._unit_lower_inverse, "doubling": doubling_product}


@pytest.mark.parametrize("c", [16, 64])
@pytest.mark.parametrize("keys", sorted(KEYS))
@pytest.mark.parametrize("inverse", sorted(INVERSES))
def test_the_chunk_inverse_is_the_inverse(inverse, keys, c):
    """``_unit_lower_inverse`` against ``jnp.linalg.inv`` in float32,
    within 1e-5 of the inverse's largest entry. The doubling product
    passes on keys at random and fails on correlated ones, so the cases
    tell the two apart."""
    a = chunk_matrix(c, *KEYS[keys])
    want = jnp.linalg.inv(jnp.eye(c) + a)
    got = INVERSES[inverse](a)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    if inverse == "doubling" and keys != "random":
        assert err > 1e-5
    else:
        assert err < 1e-5


@pytest.mark.parametrize("c", [16, 64])
def test_the_chunk_solves_gradients_are_the_triangular_solves(c):
    """The solve's own backward (one inverse, reused) against
    ``jax.lax.linalg.triangular_solve``'s, at correlated keys."""
    a = chunk_matrix(c, 0.9, 0.9, seed=1)
    rhs = jax.random.normal(jax.random.PRNGKey(2), (2, 4, c, 24))
    cot = jax.random.normal(jax.random.PRNGKey(3), (2, 4, c, 24))
    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(kda._unit_lower_solve, a, rhs)
        got_grads = pull(cot)
        want, pull = jax.vjp(lambda x, y: jax.lax.linalg.triangular_solve(
            x, y, left_side=True, lower=True, unit_diagonal=True), a, rhs)
        want_grads = pull(cot)
    assert rel(got, want) < 1e-5
    strict = np.tril(np.ones((c, c), bool), -1)
    for x, y in zip(got_grads, want_grads):
        assert rel(x, y) < 1e-5
    assert not np.any(np.asarray(got_grads[0])[..., ~strict])


# -- the two mixers against the reference's ------------------------------------

def _ref_cfg():
    return {**CFG, "linear_attn_config": LAYERS}


def _layer_against(layer, reference, seed):
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(2, SEQ, 32)),
                    jnp.float32)
    params = layer.param_dict()

    def system(p):
        return jnp.sum(jnp.square(functional_call(layer, p, {}, x)))

    def plain(p):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.square(jnp.stack(
                [reference(p, "", _ref_cfg(), row) for row in x])))

    with jax.default_matmul_precision("highest"):
        out = functional_call(layer, params, {}, x)
        want = jnp.stack([reference(params, "", _ref_cfg(), row)
                          for row in x])
        grads = jax.grad(system)(params)
    ref_grads = jax.grad(plain)(params)
    assert rel(out, want) < TOL
    for name in ref_grads:
        assert rel(grads[name], ref_grads[name]) < TOL, name


def test_kda_is_the_reference_recurrence():
    pt.seed(0)
    layer = _perturbed(nn.KimiDeltaAttention(32, 4, 8, chunk_size=16), 1)
    _layer_against(layer, ref.kda, 2)


def test_latent_attention_is_dense_attention_over_its_latent():
    pt.seed(0)
    layer = _perturbed(nn.MultiHeadLatentAttention(32, 4, 16, 8, 8, 8), 1)
    _layer_against(layer, ref.mla, 3)


# -- the model ---------------------------------------------------------------------

def build(seed=0, **over):
    pt.seed(seed)
    return _perturbed(KimiLinearForCausalLM(KimiLinearConfig(
        **{**CFG, **over})), seed)


def batch(seed=1, rows=2, seq=SEQ):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CFG["vocab_size"], (rows, seq + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def system(model, ids, labels):
    buffers = model.buffer_dict()

    @jax.jit
    def run(params):
        def loss_of(p):
            out = functional_call(model, p, buffers, ids)
            return models.next_token_loss(out, labels), out

        (loss, out), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        return out, loss, grads

    return run(model.param_dict())


@jax.jit
def _reference(params, buffers, ids, labels):
    loss, grads = jax.value_and_grad(
        lambda p: ref.loss(p, _ref_cfg(), ids, labels, buffers))(params)
    return ref.logits(params, _ref_cfg(), ids, buffers), loss, grads


def compare(model):
    ids, labels = batch()
    out, loss, grads = system(model, ids, labels)
    ref_logits, ref_loss, ref_grads = _reference(
        model.param_dict(), model.buffer_dict(), ids, labels)
    assert rel(out.logits(), ref_logits) < TOL
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    for name in ref_grads:          # every gradient
        assert rel(grads[name], ref_grads[name]) < TOL, name
    assert int(out.moe_pairs_dropped) == 0
    return out


@pytest.mark.parametrize("recompute", ["none", "layer"])
def test_logits_loss_and_gradients_match_the_reference(recompute):
    with jax.default_matmul_precision("highest"):
        out = compare(build(recompute=recompute))
    # four expert layers, 80 tokens, 3 choices each, a quarter held
    assert 0 < int(out.moe_pairs_held) < 4 * 80 * 3
    kinds = [build().layers[i].kind for i in range(5)]
    assert kinds == ["kda", "kda", "kda", "mla", "kda"]


@pytest.mark.parametrize("fault", sorted(kimi_control_drill.FAULTS))
def test_a_planted_fault_fails_the_comparison(fault):
    model = build()
    with jax.default_matmul_precision("highest"):
        with kimi_control_drill.FAULTS[fault](model=model):
            with pytest.raises(AssertionError):
                compare(model)


def test_the_layers_are_read_from_the_published_lists():
    cfg = KimiLinearConfig()
    kinds = [cfg.layer_kind(i) for i in range(cfg.num_hidden_layers)]
    assert kinds.count("kda") == 20 and kinds.count("mla") == 7
    assert [i + 1 for i, k in enumerate(kinds) if k == "mla"] == \
        [4, 8, 12, 16, 20, 24, 27]
    with pytest.raises(ValueError, match="neither"):
        KimiLinearConfig(num_hidden_layers=6, linear_attn_config=LAYERS)


# -- one expert-parallel rank's share ---------------------------------------------

def _moe_layer(held, offset):
    pt.seed(7)
    return nn.DroplessMoE(32, 24, 16, 3, d_shared=24, experts_held=held,
                          expert_offset=offset, routed_scaling_factor=2.446,
                          score_func="sigmoid", gated=True)


def _ref_layer(params, x, held, offset):
    with jax.default_matmul_precision("highest"):
        return ref.routed_experts(params, "", _ref_cfg(), x, 0.0, held,
                                  offset) \
            + ref.shared_expert(params, "", _ref_cfg(), x)


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """16 experts as 4 shares of 4: the four routed parts, with the
    shared expert counted once, are the whole layer's result, in the
    system and in the reference."""
    whole = _moe_layer(16, 0)
    params = whole.param_dict()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, SEQ, 32)),
                    jnp.float32)
    tokens = x.reshape(-1, 32)
    want = _ref_layer(params, tokens, 16, 0)
    with jax.default_matmul_precision("highest"):
        shared = ref.shared_expert(params, "", _ref_cfg(), tokens)
        total, pairs = -3 * shared, 0
        for i in range(4):
            share = _moe_layer(4, 4 * i)
            mine = {**params, "w_in": params["w_in"][4 * i:4 * i + 4],
                    "w_out": params["w_out"][4 * i:4 * i + 4]}
            out, stats = functional_call(share, mine, share.buffer_dict(), x)
            assert rel(out.reshape(-1, 32),
                       _ref_layer(mine, tokens, 4, 4 * i)) < TOL
            total = total + out.reshape(-1, 32)
            pairs += int(stats["pairs_held"])
            assert int(stats["pairs_dropped"]) == 0
        whole_out, _ = whole(x)
    assert rel(total, want) < TOL
    assert pairs == tokens.shape[0] * 3, "every pair is someone's"
    assert rel(whole_out.reshape(-1, 32), want) < TOL


# -- the step ------------------------------------------------------------------------

def test_a_train_step_notes_the_chunks_of_its_kda_layers():
    """``pt_kda_chunks``: KDA layers x sequences x chunks a sequence,
    set when the step is traced, and ``pt_kda_inverse_sites``: the KDA
    layers whose chunk solve is the block-product inverse; the step
    trains."""
    from paddle_tpu import observability as obs
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        step = TrainStep(build(recompute="layer"), pt.optimizer.AdamW(1e-3),
                         models.next_token_loss,
                         extra_metrics=routing_metrics())
        ids, labels = batch()
        first = float(step(ids, labels=(labels,))["loss"])
        for _ in range(3):
            last = float(step(ids, labels=(labels,))["loss"])
        chunks = obs.gauge("pt_kda_chunks").value(fn=step._span_name)
        inverses = obs.gauge("pt_kda_inverse_sites").value(
            fn=step._span_name)
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()
    assert chunks == 4 * 2 * 3
    assert inverses == 4
    assert np.isfinite(last) and last < first


def test_a_recomputed_latent_layer_keeps_its_flash_result(monkeypatch):
    """The latent attention layer under ``nn.recompute_layer``, its
    attention sent to the flash kernels under the interpreter at q/k 16
    and v 8 (in the layout narrow heads take): the gradient's program runs the forward kernel once (its
    output and statistics are kept by name, as for grouped-query
    attention) where a bare ``jax.checkpoint`` runs it twice, with the
    same gradients."""
    from paddle_tpu import kernels
    from paddle_tpu.kernels import flash_attention as fa
    monkeypatch.setattr(fa, "BLOCK_Q", 8)
    monkeypatch.setattr(fa, "BLOCK_K", 8)

    def seam(q, k, v, scale=None, causal=False, training=False,
             layout="bthd"):
        out = fa.flash_attention(
            *(jnp.moveaxis(x, 2, 1) for x in (q, k, v)), causal=causal,
            scale=scale, interpret=True)
        return jnp.moveaxis(out, 1, 2)

    monkeypatch.setattr(kernels, "maybe_flash_attention", seam)
    pt.seed(0)
    layer = _perturbed(nn.MultiHeadLatentAttention(32, 4, 16, 8, 8, 8), 1)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 24, 32)),
                    jnp.float32)

    def grads(wrap):
        def loss(p):
            return jnp.sum(jnp.square(wrap(
                lambda h: functional_call(layer, p, {}, h))(x)))
        fn = jax.grad(loss)
        jaxpr = jax.make_jaxpr(fn)(layer.param_dict()).jaxpr
        return fn(layer.param_dict()), _flash_forwards(jaxpr)

    kept, runs = grads(nn.recompute_layer)
    bare, bare_runs = grads(lambda f: jax.checkpoint(lambda h: f(h)))
    assert (runs, bare_runs) == (1, 2)
    for name in bare:
        assert rel(kept[name], bare[name]) < TOL, name


def _flash_forwards(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == "flash_fwd"
        else:
            n += sum(_flash_forwards(inner)
                     for inner in jax.core.jaxprs_in_params(eqn.params))
    return n
