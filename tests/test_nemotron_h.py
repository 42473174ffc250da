"""Nemotron-H (Mamba-2 + dropless routed experts + GQA) against the
plain float32 reference of ``benchmarks/references``, at tiny widths on
the CPU. Both sides compute in float32 here, so the only difference is
the order of summation (chunked scan against the recurrence, grouped
matmul against a loop over experts): every tolerance is 1e-4 relative,
a hundred times what that order moves a value and far under what any
of the faults below does."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmarks.references import nemotron3_nano_30b_a3b as ref
from paddle_tpu.models import (NemotronHConfig, NemotronHForCausalLM,
                               balance_router_bias, next_token_loss,
                               routing_metrics)
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.nn.layers import moe, ssm
from paddle_tpu.static import TrainStep

TOL = 1e-4
CFG = dict(
    vocab_size=96, hidden_size=32, hybrid_override_pattern="MEM*E",
    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
    conv_kernel=4, chunk_size=8, n_routed_experts=4,
    n_routed_experts_total=16, expert_offset=4, num_experts_per_tok=3,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    layer_norm_epsilon=1e-5)
WATCHED = ["layers.0.mixer.in_proj.weight", "layers.0.mixer.A_log",
           "layers.1.mixer.w_in", "layers.1.mixer.router_weight",
           "layers.3.mixer.q_proj.weight", "lm_head.weight"]
SEQ = 21          # not a multiple of the chunk


@pytest.fixture(autouse=True)
def _small_windows(monkeypatch):
    """Windows of a few rows, so every test walks more than one."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)


def build(seed=0, **over):
    pt.seed(seed)
    model = NemotronHForCausalLM(NemotronHConfig(**{**CFG, **over}))
    # nothing at its initial value: a term multiplied by a 0 or a 1
    # would hide its own absence
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith(("conv_bias", "mixer.D", "norm.weight",
                          "norm_f.weight")):
            p.value = p.value + jnp.asarray(
                rng.normal(0, 0.3, p.shape), p.value.dtype)
    return model


def batch(seed=1, rows=2):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, CFG["vocab_size"], (rows, SEQ + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


def system(model, ids, labels):
    buffers = model.buffer_dict()

    @jax.jit
    def run(params):
        def loss_of(p):
            out = functional_call(model, p, buffers, ids)
            return next_token_loss(out, labels), out

        (loss, out), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        return out, loss, grads

    return run(model.param_dict())


@jax.jit
def _reference(params, buffers, ids, labels):
    loss, grads = jax.value_and_grad(
        lambda p: ref.loss(p, CFG, ids, labels, buffers))(params)
    return ref.logits(params, CFG, ids, buffers), loss, grads


def reference(model, ids, labels):
    return _reference(model.param_dict(), model.buffer_dict(), ids, labels)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def compare(model, told=None):
    ids, labels = batch()
    out, loss, grads = system(model, ids, labels)
    ref_logits, ref_loss, ref_grads = reference(told or model, ids, labels)
    assert rel(out.logits(), ref_logits) < TOL
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    for name in WATCHED:
        assert rel(grads[name], ref_grads[name]) < TOL, name
    assert int(out.moe_pairs_dropped) == 0
    return out


@pytest.mark.parametrize("recompute", ["none", "layer"])
def test_logits_loss_and_gradients_match_the_reference(recompute):
    model = build(recompute=recompute)
    out = compare(model)
    # two E layers, 42 tokens, 3 choices each, a quarter of the experts
    assert 0 < int(out.moe_pairs_held) < 2 * 42 * 3


def _bf16_scan(monkeypatch):
    true_scan = ssm.ssd_chunked_scan

    def scan(x, dt, b_mat, c_mat, a, chunk):
        return true_scan(*(t.astype(jnp.bfloat16) for t in
                           (x, dt, b_mat, c_mat)), a, chunk) \
            .astype(x.dtype)

    monkeypatch.setattr(ssm, "ssd_chunked_scan", scan)
    return build()


def _softmax_router(monkeypatch):
    def route(self, tokens):
        s = jax.nn.softmax(tokens @ self.router_weight, axis=-1)
        _, chosen = jax.lax.top_k(s + self.e_score_correction_bias,
                                  self.top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        return chosen, w * self.routed_scaling_factor

    monkeypatch.setattr(moe.DroplessMoE, "route", route)
    return build()


def _dropped_pairs(monkeypatch):
    true_routed = moe.DroplessMoE._routed

    def capped(self, tokens, weights, order, ends, rows, windows):
        return true_routed(self, tokens, weights, order, ends,
                           rows=8, windows=1)

    monkeypatch.setattr(moe.DroplessMoE, "_routed", capped)
    return build()


def _no_skip(monkeypatch):
    model = build()
    for name, p in model.named_parameters():
        if name.endswith("mixer.D"):
            p.value = jnp.zeros_like(p.value)
    return model


def _no_scaling(monkeypatch):
    return build(routed_scaling_factor=1.0)


FAULTS = {"bf16_scan": _bf16_scan, "softmax_router": _softmax_router,
          "dropped_pairs": _dropped_pairs, "no_D_x": _no_skip,
          "no_scaling_factor": _no_scaling}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails_the_comparison(fault, monkeypatch):
    model = FAULTS[fault](monkeypatch)
    with pytest.raises(AssertionError):
        compare(model, told=build())     # the reference is told no fault


@pytest.mark.parametrize("length", [5, 8, 21, 37])
def test_chunked_scan_matches_the_recurrence(length):
    rng = np.random.default_rng(length)
    h, p, g, n = 4, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(1, length, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (1, length, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, (h,)), jnp.float32)
    b_mat = jnp.asarray(rng.normal(size=(1, length, g, n)), jnp.float32)
    c_mat = jnp.asarray(rng.normal(size=(1, length, g, n)), jnp.float32)
    spread = lambda t: jnp.repeat(t[0], h // g, axis=1)
    want = ref.recurrence(x[0], dt[0], a, spread(b_mat), spread(c_mat))
    got = ssm.ssd_chunked_scan(x, dt, b_mat, c_mat, a, chunk=8)
    assert rel(got[0], want) < TOL


def _moe_layer(held, offset, seed=3):
    pt.seed(seed)
    return pt.nn.DroplessMoE(32, 24, 16, 3, d_shared=40,
                             experts_held=held, expert_offset=offset,
                             routed_scaling_factor=2.5)


def _ref_layer(params, x, held, offset, bias=0.0):
    with jax.default_matmul_precision("highest"):
        return ref.routed_experts(params, "", CFG, x, bias, held, offset) \
            + ref.shared_expert(params, "", x)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4: the four routed parts, with the
    shared expert counted once, are the whole layer's result."""
    whole = _moe_layer(16, 0)
    params = whole.param_dict()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, SEQ, 32)),
                    jnp.float32)
    tokens = x.reshape(-1, 32)
    want = _ref_layer(params, tokens, 16, 0)
    with jax.default_matmul_precision("highest"):
        shared = ref.shared_expert(params, "", tokens)
    total, pairs = -3 * shared, 0
    for i in range(4):
        share = _moe_layer(4, 4 * i)
        mine = {**params, "w_in": params["w_in"][4 * i:4 * i + 4],
                "w_out": params["w_out"][4 * i:4 * i + 4]}
        out, stats = functional_call(share, mine, share.buffer_dict(), x)
        # each share alone is what the reference gives for that share
        assert rel(out.reshape(-1, 32),
                   _ref_layer(mine, tokens, 4, 4 * i)) < TOL
        total = total + out.reshape(-1, 32)
        pairs += int(stats["pairs_held"])
        assert int(stats["pairs_dropped"]) == 0
    assert rel(total, want) < TOL
    assert pairs == tokens.shape[0] * 3, "every pair is someone's"
    whole_out, _ = whole(x)
    assert rel(whole_out.reshape(-1, 32), want) < TOL


# a selection bias that pushes every token's choices onto the held
# experts, or onto none of them, or leaves the router alone
PUSH = {"all_held": 10.0, "none_held": -10.0, "balanced": 0.0}


def _layer_against_reference(where):
    """Experts 8 to 11 of 16 under the bias ``PUSH[where]``: the result
    and the gradients are the reference's; returns the layer's stats."""
    layer = _moe_layer(4, 8)
    bias = jnp.zeros((16,), jnp.float32).at[8:12].set(PUSH[where])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, SEQ, 32)),
                    jnp.float32)
    params = layer.param_dict()

    def run(p):
        out, stats = functional_call(
            layer, p, {"e_score_correction_bias": bias}, x)
        return jnp.sum(out * out), (out, stats)

    (_, (out, stats)), grads = jax.value_and_grad(run, has_aux=True)(params)
    want_fn = lambda p: jnp.sum(jnp.square(
        _ref_layer(p, x.reshape(-1, 32), 4, 8, bias)))
    want_grads = jax.grad(want_fn)(params)
    assert rel(out.reshape(-1, 32),
               _ref_layer(params, x.reshape(-1, 32), 4, 8, bias)) < TOL
    assert int(stats["pairs_dropped"]) == 0
    for name in ("w_in", "w_out", "router_weight", "shared_in.weight"):
        if where == "none_held" and name in ("w_in", "w_out"):
            assert not np.any(np.asarray(grads[name]))
        elif where == "none_held" and name == "router_weight":
            continue                    # the reference's is zero too
        else:
            assert rel(grads[name], want_grads[name]) < TOL, name
    return stats


@pytest.mark.parametrize("where", ["all_held", "none_held"])
def test_adversarial_routing_drops_nothing(where):
    """Every pair held, or none: the windows cover the worst case."""
    stats = _layer_against_reference(where)
    pairs = 2 * SEQ * 3
    assert int(stats["pairs_held"]) == (pairs if where == "all_held"
                                        else 0)


@pytest.mark.parametrize("where", sorted(PUSH))
def test_the_loop_runs_the_windows_that_hold_a_pair(where):
    """``windows_run`` is the loop's trip count: every window when every
    pair is held, none when none is, one for the balanced layer (a
    window is twice its load), and the result is the reference's each
    time."""
    stats = _layer_against_reference(where)
    total = 2 * SEQ * 3
    rows = -(-moe.WINDOW_FACTOR * total * 4 // (16 * moe._ROW_TILE)) \
        * moe._ROW_TILE
    assert -(-total // rows) == 2, "the layer walks two windows at most"
    held = int(stats["pairs_held"])
    assert int(stats["windows_run"]) == -(-held // rows) \
        == {"all_held": 2, "none_held": 0, "balanced": 1}[where]


# the grouped product a window runs: `jax.lax.ragged_dot`, the path off
# a TPU, or the kernels a TPU runs, here under the Pallas interpreter
PRODUCTS = ["ragged_dot", "kernels"]


def _route_the_seam(monkeypatch, product, wrap=lambda seam: seam):
    """Send the seam a window calls, ``kernels.maybe_grouped_matmul``,
    to ``product``, through ``wrap``."""
    from paddle_tpu import kernels
    if product == "kernels":
        from paddle_tpu.kernels import grouped_matmul as G
        monkeypatch.setattr(
            kernels, "maybe_group_tiles",
            lambda sizes, rows: G.group_tiles(sizes, rows, moe._ROW_TILE))
        seam = lambda lhs, rhs, sizes, tiles=None: G.grouped_matmul(
            lhs, rhs, sizes, tiles, interpret=True)
    else:
        seam = kernels.maybe_grouped_matmul
    monkeypatch.setattr(kernels, "maybe_grouped_matmul", wrap(seam))


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of what it nests."""
    for eqn in jaxpr.eqns:
        yield eqn
        yield from _nested(eqn)


def _nested(eqn):
    if eqn.primitive.name == "pallas_call":
        return      # a kernel's own branches are not the program's
    for inner in jax.core.jaxprs_in_params(eqn.params):
        yield from _equations(inner)


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("recompute", [False, True])
def test_the_gradient_walks_the_windows_twice_and_skips_none(
        recompute, product, monkeypatch):
    """One loop over windows forward and one backward, each bounded by
    the step's count (a ``while``, not a ``scan`` over every position),
    and no ``cond`` inside either: a window that holds no pair is not
    reached, so nothing is paid to skip it. The layer's checkpoint adds
    no third loop: nothing after the mixer needs its output again."""
    _route_the_seam(monkeypatch, product)
    layer = _moe_layer(4, 8)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, SEQ, 32)),
                    jnp.float32)

    def run(p):
        call = lambda h: functional_call(layer, p, layer.buffer_dict(),
                                         h)[0]
        out = (jax.checkpoint(call) if recompute else call)(x)
        return jnp.sum(out * out)

    jaxpr = jax.make_jaxpr(jax.grad(run))(layer.param_dict()).jaxpr
    loops = {e: {i.primitive.name for i in _nested(e)}
             for e in _equations(jaxpr)
             if e.primitive.name in ("while", "scan")}
    over_windows = [(e.primitive.name, "cond" in inside)
                    for e, inside in loops.items()
                    if {"ragged_dot": "ragged_dot_general",
                        "kernels": "pallas_call"}[product] in inside]
    assert over_windows == [("while", False)] * 2


@pytest.mark.parametrize("product", PRODUCTS)
def test_rows_past_the_last_group_are_never_read(product, monkeypatch):
    """XLA:TPU's own grouped-matmul kernel leaves the rows past the last
    group uninitialised (the CPU's zero-fills them, and so do the
    repo's kernels). A window hands the product groups that cover every
    row of it, and the layer's result and gradients do not depend on
    what a kernel would leave past them."""
    def garbage_past_the_groups(seam):
        def product_of(x, w, sizes, *tiles):
            dead = jnp.arange(x.shape[0]) >= jnp.sum(sizes)
            return jnp.where(dead[:, None], jnp.nan,
                             seam(x, w, sizes, *tiles))
        return product_of

    layer = _moe_layer(4, 8)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, SEQ, 32)),
                    jnp.float32)

    def run(p):
        out, _ = functional_call(layer, p, layer.buffer_dict(), x)
        return jnp.sum(out * out), out

    params = layer.param_dict()
    (_, want), want_grads = jax.value_and_grad(run, has_aux=True)(params)
    _route_the_seam(monkeypatch, product, garbage_past_the_groups)
    (_, got), grads = jax.value_and_grad(run, has_aux=True)(params)
    assert np.isfinite(np.asarray(got)).all()
    assert rel(got, want) < 1e-6
    for name in params:
        assert rel(grads[name], want_grads[name]) < 1e-6, name


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("held_pairs", [0, 5, 16])
def test_a_window_is_computed_whole(held_pairs, product, monkeypatch):
    """Whatever share of a window holds pairs, the grouped matmuls get
    groups that sum to the window's rows (the rows past the last pair
    are zeros in the last group), so a window's time does not follow
    the routing; the zeros add nothing. The kernels walk every row tile
    of such a window, whatever the groups."""
    layer = _moe_layer(4, 0)
    rows, n = 16, 12
    rng = np.random.default_rng(held_pairs)
    tokens = jnp.asarray(rng.normal(size=(n, 32)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1, (n, 3)), jnp.float32)
    # sorted (token, choice) pairs: `held_pairs` of them on held experts
    sizes = np.bincount(rng.integers(0, 4, held_pairs), minlength=4)
    order = jnp.asarray(np.pad(rng.permutation(n * 3), (0, rows)))
    ends = jnp.asarray(np.cumsum(sizes), jnp.int32)
    seen = []

    def counting(seam):
        def product_of(x, w, group_sizes, tiles=None):
            seen.append((x.shape[0], int(jnp.sum(group_sizes))))
            if tiles is not None:
                walked = np.asarray(tiles.tile_of)[:int(tiles.visits[0])]
                assert set(walked) == set(range(rows // moe._ROW_TILE))
            return seam(x, w, group_sizes, tiles)
        return product_of

    _route_the_seam(monkeypatch, product, counting)
    carry = jnp.asarray(rng.normal(size=(n, 32)), jnp.float32)
    got = layer._window(carry, tokens, weights, layer.w_in, layer.w_out,
                        order, ends, 0, rows) - carry
    assert seen == [(rows, rows)] * 2
    want = np.zeros((n, 32), np.float32)
    expert = np.repeat(np.arange(4), sizes)
    for pair, e in zip(np.asarray(order)[:held_pairs], expert):
        t = tokens[pair // 3]
        want[pair // 3] += weights.reshape(-1)[pair] * (
            jnp.square(jax.nn.relu(t @ layer.w_in[e])) @ layer.w_out[e])
    assert np.allclose(got, want, atol=1e-5)


def _zipf_ids(rows=4, seed=7):
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, CFG["vocab_size"] + 1)
    return rng.choice(CFG["vocab_size"], (rows, 64), p=p / p.sum()) \
        .astype(np.int32)


def test_fitting_the_selection_bias_balances_the_experts():
    model = build()
    ids = _zipf_ids()
    before = model(ids).moe_expert_load
    worst_before = float(jnp.max(before.max(1) / before.mean(1)))
    worst = balance_router_bias(model, ids)
    after = model(ids).moe_expert_load
    assert int(after.sum()) == int(before.sum()), "no pair went missing"
    assert worst < 0.6 * worst_before, (worst, worst_before)
    assert any(float(jnp.abs(b).max()) > 0 for n, b in
               model.buffer_dict().items() if "correction_bias" in n)
    assert model.training
    # the fitted bias is what selects, in the reference too
    compare(model)


def test_the_bias_moves_a_step_at_a_time_while_training():
    model = build(router_bias_update_rate=0.01, recompute="layer")
    step = TrainStep(model, pt.optimizer.AdamW(1e-3), next_token_loss)
    ids = _zipf_ids()
    step(ids, labels=(ids,))
    name = "layers.1.mixer.e_score_correction_bias"
    once = np.asarray(step.state["buffers"][name])
    assert set(np.unique(np.abs(once))) <= {0.0, np.float32(0.01)}
    assert np.any(once != 0)
    step(ids, labels=(ids,))
    assert np.abs(np.asarray(step.state["buffers"][name])).max() \
        <= 0.02 + 1e-6
    # evaluation leaves it alone
    model.eval()
    out, buffers = functional_call(model, step.state["params"],
                                   step.state["buffers"], ids,
                                   capture_buffers=True)
    assert np.array_equal(buffers[name], step.state["buffers"][name])


def test_rms_norm_groups():
    norm = pt.nn.RMSNorm(16, epsilon=1e-5, num_groups=4)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 16)),
                    jnp.float32)
    grouped = np.asarray(x).reshape(3, 4, 4)
    want = grouped / np.sqrt((grouped ** 2).mean(-1, keepdims=True) + 1e-5)
    assert rel(norm(x), want.reshape(3, 16)) < 1e-6


def test_train_step_returns_the_routing_counters_and_learns():
    model = build(recompute="layer")
    model.to(dtype="bfloat16")
    step = TrainStep(model, pt.optimizer.AdamW(3e-3), next_token_loss,
                     extra_metrics=routing_metrics())
    ids, labels = batch(rows=4)
    first = step(ids, labels=(labels,))
    for _ in range(14):
        last = step(ids, labels=(labels,))
    assert float(last["loss"]) < float(first["loss"]) - 0.3
    assert int(last["moe_pairs_dropped"]) == 0
    assert 0 < int(last["moe_pairs_held"]) < 2 * 4 * SEQ * 3
    assert float(last["moe_load_max_over_mean"]) >= 1.0
    # two E layers of at most two windows each, and pairs in both
    assert 2 <= int(last["moe_windows_run"]) <= 4


def test_the_step_names_its_blocks():
    """Every block of the model is a ``pt.`` scope in the compiled
    step, forward and backward, loops included (a loop body is lowered
    with its own name stack: the scope is entered inside it)."""
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
                         pt.optimizer.AdamW(1e-3), next_token_loss,
                         extra_metrics=routing_metrics())
        ids, labels = batch()
        step(ids, labels=(labels,))
        names = list(xprof.op_scopes(step._span_name).values())
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", was)
    by_block = {}
    for name in names:
        found = re.findall(r"pt\.[a-z_]+", name)
        if found:
            by_block.setdefault(found[-1], []).append(name)
    for scope in ("pt.embed", "pt.ssm_proj", "pt.ssm_conv", "pt.ssm_scan",
                  "pt.moe_route", "pt.moe_experts", "pt.moe_shared",
                  "pt.attn", "pt.head_loss"):
        assert any("transpose(" in n for n in by_block[scope]), scope
        assert any("transpose(" not in n for n in by_block[scope]), scope
    # the scan's loop bodies carry the scope themselves
    assert any("while/body" in n for n in by_block["pt.ssm_scan"])
    assert any("while/body" in n for n in by_block["pt.head_loss"])


@pytest.mark.parametrize("recompute", ["layer", "none"])
def test_the_step_notes_every_grouped_kernel_that_runs(recompute,
                                                       monkeypatch):
    """A live window runs eight grouped kernels a layer: the two
    products forward, the two again inside the backward's ``jax.vjp``,
    the two input gradients (``moe_gmm``) and the two weight gradients
    (``moe_tgmm``). The step's trace notes exactly those, with or
    without the layers' checkpoint: under it JAX traces the window loop
    once more, as the forward rule of the recomputation, whose products
    nothing reads; a site noted and never run would leave
    ``train.moe_gmm_roofline`` with nothing to read."""
    from collections import Counter

    from paddle_tpu import observability as obs
    from paddle_tpu.kernels.grouped_matmul import gmm_work
    from paddle_tpu.observability import xprof
    _route_the_seam(monkeypatch, "kernels")
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        step = TrainStep(build(recompute=recompute),
                         pt.optimizer.AdamW(1e-3), next_token_loss,
                         extra_metrics=routing_metrics())
        ids, labels = batch()
        out = step(ids, labels=(labels,))
        notes = xprof.kernel_notes(step._span_name)
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()
    assert np.isfinite(float(out["loss"]))
    layers = CFG["hybrid_override_pattern"].count("E")
    assert Counter(n[0] for n in notes) == {"moe_gmm": 6 * layers,
                                            "moe_tgmm": 2 * layers}
    # every site does one pass over the window's rows
    rows = -(-moe.WINDOW_FACTOR * 2 * SEQ * 3 * 4 // (16 * moe._ROW_TILE)) \
        * moe._ROW_TILE
    assert {n[1:] for n in notes} == {gmm_work(rows, 32, 24, 4, 4)}


def _scan_seam_as_on_a_tpu(monkeypatch):
    """The Mamba-2 layers' seam, ``kernels.maybe_ssd_scan``, as a TPU
    would see it and no other seam with it: the seam itself runs, under
    a backend that reads as a TPU while it does, and its kernels run
    under the interpreter."""
    import functools

    from paddle_tpu import kernels
    from paddle_tpu.kernels import ssd_scan as K
    real = kernels.maybe_ssd_scan
    monkeypatch.setattr(K, "ssd_scan",
                        functools.partial(K.ssd_scan, interpret=True))

    def seam(*args):
        with monkeypatch.context() as m:
            m.setattr(kernels, "_on_tpu", lambda: True)
            return real(*args)

    monkeypatch.setattr(kernels, "maybe_ssd_scan", seam)


@pytest.mark.parametrize("recompute", ["layer", "none"])
def test_the_step_notes_every_scan_kernel_that_runs(recompute, monkeypatch):
    """A Mamba-2 layer whose scan takes the fused kernels runs ``ssd_fwd``
    and ``ssd_bwd`` once each, and under the layers' checkpoint
    ``ssd_fwd`` once more, in the forward pass. The step's trace notes
    as many: under the checkpoint JAX traces the scan alone when it
    traces the layer and the ``custom_vjp``'s forward rule when it
    differentiates it, and runs the rule both times; every ``ssd_fwd``
    call does the same work, states and all, so the notes are the
    calls' whichever trace each came from. The gauge
    ``pt_ssd_scan_kernel_sites`` counts the layers."""
    from collections import Counter

    from paddle_tpu import observability as obs
    from paddle_tpu.kernels.ssd_scan import ssd_work
    from paddle_tpu.observability import xprof
    _scan_seam_as_on_a_tpu(monkeypatch)
    # the narrowest Mamba-2 layer the kernels take: whole lane tiles
    wide = dict(mamba_head_dim=64, ssm_state_size=128, chunk_size=128)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, CFG["vocab_size"], (2, 129)).astype(np.int32)
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        step = TrainStep(build(recompute=recompute, **wide),
                         pt.optimizer.AdamW(1e-3), next_token_loss,
                         extra_metrics=routing_metrics())
        out = step(ids[:, :-1], labels=(ids[:, 1:],))
        notes = xprof.kernel_notes(step._span_name)
        sites = obs.gauge("pt_ssd_scan_kernel_sites").value(
            fn=step._span_name)
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()
    assert np.isfinite(float(out["loss"]))
    layers = CFG["hybrid_override_pattern"].count("M")
    assert sites == layers
    forwards = 2 if recompute == "layer" else 1
    assert Counter(n[0] for n in notes) == {"ssd_fwd": forwards * layers,
                                            "ssd_bwd": layers}
    shapes = ((2, 128, 4, 64), (2, 128, 2, 128), 128, 4)
    assert set(notes) == {("ssd_fwd",) + ssd_work(*shapes),
                          ("ssd_bwd",) + ssd_work(*shapes, backward=True)}


def test_off_a_tpu_no_layer_of_the_step_takes_the_scan_kernels():
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import xprof
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        step = TrainStep(build(recompute="layer"), pt.optimizer.AdamW(1e-3),
                         next_token_loss, extra_metrics=routing_metrics())
        ids, labels = batch()
        step(ids, labels=(labels,))
        notes = xprof.kernel_notes(step._span_name)
        sites = obs.gauge("pt_ssd_scan_kernel_sites").value(
            fn=step._span_name)
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()
    assert sites == 0
    assert not [n for n in notes if n[0].startswith("ssd_")]


def test_the_scan_kernels_time_is_data_for_the_reader_that_is_there():
    """``train.ssd_kernel_ms_per_step`` is data for the reader of the
    flash kernels' time (named through ``readers.kernel_time``, as the
    later blocks name theirs through ``readers.blocks``), over every
    kernel name ``kernels/ssd_scan.py`` gives a ``pallas_call``."""
    import re

    from benchmarks import manifest as mf
    from benchmarks.readers import program
    from paddle_tpu.kernels import ssd_scan as K
    manifest = mf.Manifest()
    spec = manifest.metric_file("train.ssd_kernel_ms_per_step")
    accepted = manifest.metric_file("train.flash_bwd_ms_per_step")
    assert mf.resolve(spec["reader"]) is program.kernel_ms_per_unit
    assert spec["args"] == dict(accepted["args"],
                                kernels=["ssd_fwd", "ssd_bwd"])
    with open(K.__file__) as f:
        named = re.findall(r'name="(\w+)"', f.read())
    assert sorted(named) == sorted(spec["args"]["kernels"])
    for name in named:
        assert callable(getattr(K, name))
    entry = [m for m in manifest.doc["per_layer"]
             if m["name"] == spec["name"]]
    assert entry[0]["workloads"] == ["nemotron3_nano_ep16_s8k"]
    assert entry[0]["layer"] == "kernels"


def test_hapi_fit_trains_it_like_any_model():
    from paddle_tpu import hapi
    from paddle_tpu.data import DataLoader, TensorDataset
    ids = np.random.default_rng(0).integers(
        0, CFG["vocab_size"], (16, SEQ + 1)).astype(np.int32)
    model = hapi.Model(build(recompute="layer"))
    model.prepare(pt.optimizer.AdamW(3e-3), next_token_loss)
    history = model.fit(
        DataLoader(TensorDataset([ids[:, :-1], ids[:, 1:]]), batch_size=4),
        epochs=4, verbose=0)
    assert history["loss"][-1] < history["loss"][0] - 0.3


def test_a_step_on_the_flash_kernels_publishes_the_census_of_its_tile_walk(
        monkeypatch):
    """With the attention layer on the causal flash kernels (interpreted
    here), the three trace-time gauges of the step are the census of
    one head and sequence's forward walk: a recomputed layer keeps the
    kernel's result, so its one forward site counts once."""
    import functools

    from paddle_tpu import kernels
    from paddle_tpu import observability as obs
    from paddle_tpu.kernels import flash_attention as fa
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    monkeypatch.setattr(fa, "BLOCK_Q", 8)
    monkeypatch.setattr(fa, "BLOCK_K", 8)
    saved = pt.get_flags(["flash_attention_min_seq",
                          "flash_attention_min_seq_train"])
    obs.reset_all()
    pt.set_flags({"enable_metrics": True, "flash_attention_min_seq": 1,
                  "flash_attention_min_seq_train": 1})
    try:
        step = TrainStep(build(recompute="layer", head_dim=128,
                               num_attention_heads=2,
                               num_key_value_heads=1),
                         pt.optimizer.AdamW(1e-3), next_token_loss,
                         extra_metrics=routing_metrics())
        ids, labels = batch()
        assert np.isfinite(float(step(ids, labels=(labels,))["loss"]))
        fn = step._span_name
        one = fa.flash_tile_census(SEQ, SEQ, 8, 8, True)
        assert one == (6, 3, 0)        # 21 positions in tiles of 8
        for kind, count in zip(("visited", "whole", "diagonal"), one):
            assert obs.gauge("pt_flash_tiles_" + kind).value(fn=fn) \
                == count, kind
    finally:
        pt.set_flags({"enable_metrics": False, **saved})
        obs.reset_all()
