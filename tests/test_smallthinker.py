"""SmallThinker (windowed and global attention, a router that reads the
layer's input before attention, ReGLU experts) and what it added: the
sliding window as a mode of the flash kernels, in interpret mode on the
CPU against dense masked attention written here; the tiles the kernels
walk against the tiles that hold an allowed pair; ``DroplessMoE``'s
router on a given input and its ReLU gate; the model against the plain
float32 reference of ``benchmarks/references`` at tiny widths, where
every tolerance is 1e-4 relative, far under what any planted fault
does."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmarks import smallthinker_control_drill as drill
from benchmarks.references import smallthinker_21b_a3b as ref
from paddle_tpu import models, nn
from paddle_tpu import observability as obs
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import maybe_flash_attention
from paddle_tpu.models import SmallThinkerConfig, SmallThinkerForCausalLM
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.nn.layers import moe
from paddle_tpu.static import TrainStep

TOL = 1e-4


def dense_rule(tq, tk, window):
    """[tq, tk] bool from the words of the rule: query q (bottom-right
    aligned) sees the keys q + o - W < k <= q + o."""
    q = np.arange(tq)[:, None] + (tk - tq)
    k = np.arange(tk)[None, :]
    return (k <= q) & (k > q - window)


def dense_attention(q, k, v, mask):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# (tq, tk, tile, window): windows of whole tiles, of no whole number of
# tiles, shorter than a tile and at least the sequence; tails; fewer
# queries than keys; one tile for everything (the one-tile backward)
SHAPES = [(64, 64, 16, 32), (64, 64, 16, 20), (64, 64, 16, 7),
          (60, 60, 16, 24), (64, 64, 16, 64), (64, 64, 16, 100),
          (48, 64, 16, 20), (64, 64, 32, 16), (40, 40, 64, 16),
          (128, 128, 16, 48)]


@pytest.mark.parametrize("bthd", [False, True], ids=["bhtd", "bthd"])
@pytest.mark.parametrize("tq,tk,tile,window", SHAPES)
def test_forward_and_the_three_gradients(tq, tk, tile, window, bthd,
                                         monkeypatch):
    monkeypatch.setattr(fa, "BLOCK_Q", tile)
    monkeypatch.setattr(fa, "BLOCK_K", tile)
    rng = np.random.default_rng(tq + tk + window)
    d = 128 if bthd else 8
    q = jnp.asarray(rng.normal(size=(1, 2, tq, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 2, tk, d)), jnp.float32)
            for _ in range(2))
    mask = jnp.asarray(dense_rule(tq, tk, window))
    swap = (lambda x: jnp.moveaxis(x, 1, 2)) if bthd else (lambda x: x)

    def kernel(q, k, v):
        out = fa.flash_attention(swap(q), swap(k), swap(v), causal=True,
                                 interpret=True, bthd=bthd, window=window)
        return jnp.sum(jnp.sin(swap(out))), swap(out)

    def dense(q, k, v):
        out = dense_attention(q, k, v, mask)
        return jnp.sum(jnp.sin(out)), out

    (_, got), got_g = jax.value_and_grad(kernel, (0, 1, 2), True)(q, k, v)
    (_, want), want_g = jax.value_and_grad(dense, (0, 1, 2), True)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for a, b, name in zip(got_g, want_g, "qkv"):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                   err_msg="d" + name)


def _runs(runs):
    return [t for lo, n in runs for t in range(int(lo), int(lo) + int(n))]


WALKS = SHAPES + [(512, 512, 64, 128), (200, 200, 32, 70),
                  (96, 160, 32, 64)]


@pytest.mark.parametrize("tq,tk,tile,window", WALKS)
def test_the_walks_visit_the_window_and_cut_it_into_whole_tiles_and_edges(
        tq, tk, tile, window):
    """What the three kernels loop over: whole runs hold only tiles that
    the dense rule allows whole with no padded key; whole runs and edges
    together are the tiles that hold an allowed pair, each once; the
    census is their count. ``tile_whole`` with a window is never true
    where the dense tile has a false entry."""
    mask = dense_rule(tq, tk, window)
    nq, nk = -(-tq // tile), -(-tk // tile)

    def cut(i, j):
        return mask[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile]

    def whole(i, j):
        c = cut(i, j)
        return c.shape[1] == tile and bool(c.all())

    offset = tk - tq
    visited = whole_count = 0
    for i in range(nq):
        for j in range(nk):
            if fa.tile_whole(i * tile, j * tile, tile, tk, True, offset,
                             window, tile):
                assert whole(i, j), (i, j)
        runs, edges, diag = fa._key_walk(i * tile, tile, tile, tq, tk, True,
                                         None, window)
        assert not diag
        assert all(whole(i, j) for j in _runs(runs))
        assert sorted(_runs(runs) + _runs(edges)) == [
            j for j in range(nk) if cut(i, j).any()]
        visited += len(_runs(runs) + _runs(edges))
        whole_count += len(_runs(runs))
    for j in range(nk):
        runs, edges, diag = fa._query_walk(j * tile, tile, tile, nq, tk, True,
                                           offset, None, window)
        assert not diag
        assert all(whole(i, j) for i in _runs(runs))
        assert sorted(_runs(runs) + _runs(edges)) == [
            i for i in range(nq) if cut(i, j).any()]
    assert fa.flash_tile_census(tq, tk, tile, tile, True, None, window) \
        == (visited, whole_count, 0)


def test_the_census_of_the_cell_from_the_walk_alone():
    """16,384 positions in tiles of 512, a window of 4096: a query tile
    visits at most 9 key tiles (a lower edge, seven whole, the diagonal),
    252 a head and sequence, 196 whole, where the causal walk visits
    528, 496 whole."""
    assert fa.flash_tile_census(16384, 16384, 512, 512, True, None,
                                4096) == (252, 196, 0)
    assert fa.flash_tile_census(16384, 16384, 512, 512, True) \
        == (528, 496, 0)
    # a window that holds every key walks as the causal mask does
    assert fa.flash_tile_census(2048, 2048, 512, 512, True, None, 4096) \
        == fa.flash_tile_census(2048, 2048, 512, 512, True)


@pytest.mark.parametrize("tq,tk,window", [(64, 64, 20), (48, 64, 16),
                                          (16384, 16384, 4096),
                                          (100, 100, 150)])
def test_the_work_is_the_allowed_pairs(tq, tk, window):
    """The FLOPs a call site notes: ``4 B H D`` (forward; 6 and 8 for the
    dq and dk/dv kernels) times the allowed pairs, whatever tiles are
    visited; ``W (W + 1) / 2 + (L - W) W`` at the cell's shape."""
    pairs = int(dense_rule(tq, tk, window).sum()) if tq < 1000 else \
        4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert fa.window_allowed_pairs(tq, tk, window) == pairs
    b, h, d = 2, 3, 8
    assert fa.flash_fwd_work(b, h, tq, tk, d, 2, True,
                             window=window)[0] == 4.0 * b * h * d * pairs
    assert fa.flash_bwd_work(b, h, tq, tk, d, 2, True, matmuls=3,
                             window=window)[0] == 6.0 * b * h * d * pairs
    assert fa.flash_bwd_work(b, h, tq, tk, d, 2, True, matmuls=4,
                             window=window)[0] == 8.0 * b * h * d * pairs
    assert fa.window_allowed_pairs(16384, 16384, 4096) == 58_722_304


def test_the_window_is_a_causal_masks():
    x = jnp.zeros((1, 1, 16, 8))
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(x, x, x, interpret=True, window=4)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(x, x, x, causal=True, interpret=True, window=0)
    with pytest.raises(ValueError, match="window"):
        maybe_flash_attention(x, x, x, window=4)
    with pytest.raises(ValueError, match="sliding_window"):
        nn.GroupedQueryAttention(32, 4, 2, 8, causal=False,
                                 sliding_window=4)


def test_the_kernels_are_named_after_the_window(monkeypatch):
    """``swa_flash_*`` under a window, ``flash_*`` where the window holds
    every key (it is the causal mask then)."""
    monkeypatch.setattr(fa, "BLOCK_Q", 16)
    monkeypatch.setattr(fa, "BLOCK_K", 16)

    def names(window):
        def loss(q):
            return jnp.sum(fa.flash_attention(q, q, q, causal=True,
                                              interpret=True, window=window))
        jaxpr = jax.make_jaxpr(jax.grad(loss))(jnp.zeros((1, 2, 64, 8)))
        return sorted(e.params["name"] for e in _eqns(jaxpr.jaxpr)
                      if e.primitive.name == "pallas_call")

    assert names(20) == ["swa_flash_bwd_dkv", "swa_flash_bwd_dq",
                         "swa_flash_fwd"]
    assert names(64) == names(None) == ["flash_bwd_dkv", "flash_bwd_dq",
                                        "flash_fwd"]


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_off_a_tpu_the_seam_runs_dense_masked_attention():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 24, 2, 8)), jnp.float32)
               for _ in range(3))
    got = maybe_flash_attention(q, k, v, scale=8 ** -0.5, causal=True,
                                layout="bthd", window=6)
    want = dense_attention(*(jnp.moveaxis(x, 1, 2) for x in (q, k, v)),
                           jnp.asarray(dense_rule(24, 24, 6)))
    np.testing.assert_allclose(jnp.moveaxis(got, 1, 2), want, rtol=1e-5,
                               atol=1e-5)


# -- the experts -------------------------------------------------------------

def test_the_router_reads_the_input_it_is_given():
    """``forward(x, router_input=r)``: the experts chosen (their load,
    the plan) follow ``r`` alone, and the result is the hand-written sum
    over the choice that ``r`` makes, applied to ``x``."""
    pt.seed(1)
    layer = nn.DroplessMoE(16, 12, 8, 3, score_func="softmax", gated=True,
                           gate_act="relu")
    params, buffers = layer.param_dict(), layer.buffer_dict()
    rng = np.random.default_rng(0)
    x, x2, r = (jnp.asarray(rng.normal(size=(2, 10, 16)), jnp.float32)
                for _ in range(3))
    with jax.default_matmul_precision("highest"):
        out, stats = functional_call(layer, params, buffers, x, r)
        out2, stats2 = functional_call(layer, params, buffers, x2, r)
        _, own = functional_call(layer, params, buffers, x)
        chosen, w = layer.route(r.reshape(-1, 16))
        tokens = x.reshape(-1, 16)
        want = jnp.zeros_like(tokens)
        for e in range(8):
            weight = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)
            h = tokens @ params["w_in"][e]
            want = want + weight[:, None] * (
                (jax.nn.relu(h[:, :12]) * h[:, 12:]) @ params["w_out"][e])
    assert np.array_equal(stats["expert_load"], stats2["expert_load"])
    assert np.array_equal(stats["expert_load"], np.bincount(
        np.asarray(chosen).ravel(), minlength=8))
    assert not np.array_equal(stats["expert_load"], own["expert_load"])
    np.testing.assert_allclose(out.reshape(-1, 16), want, rtol=1e-5,
                               atol=1e-6)


def test_a_relu_gate_is_reglu_and_an_unknown_gate_is_refused():
    pt.seed(2)
    layer = nn.DroplessMoE(16, 12, 4, 4, gated=True, gate_act="relu")
    h = jnp.asarray(np.random.default_rng(1).normal(size=(5, 24)),
                    jnp.float32)
    np.testing.assert_array_equal(
        layer._act(h), jax.nn.relu(h[:, :12]) * h[:, 12:])
    assert layer.gate_act == "relu"
    assert nn.DroplessMoE(16, 12, 4, 4, gated=True).gate_act == "silu"
    with pytest.raises(ValueError, match="gate_act"):
        nn.DroplessMoE(16, 12, 4, 4, gated=True, gate_act="gelu")


# -- the model against the reference --------------------------------------------

CFG = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rms_norm_eps=1e-6, rope_theta=1.5e6, rope_layout=[0, 1, 1, 1],
    sliding_window_layout=[0, 1, 1, 1], sliding_window_size=8,
    moe_num_primary_experts=4, moe_num_primary_experts_total=16,
    expert_offset=4, moe_num_active_primary_experts=3,
    moe_ffn_hidden_size=24, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True)
SEQ = 24


@pytest.fixture(autouse=True)
def _small_windows(monkeypatch):
    """Windows of the experts' sorted list of a few rows, so every test
    walks more than one."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)


def build(seed=0, **over):
    pt.seed(seed)
    model = SmallThinkerForCausalLM(SmallThinkerConfig(
        **{**CFG, **over}, initializer_range=0.2))
    # no gain at its initial 1: a norm that is left out must show
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if "norm" in name:
            p.value = p.value + jnp.asarray(
                rng.normal(0, 0.3, p.shape), p.value.dtype)
    return model


def batch(seed=1, rows=2, seq=SEQ):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, CFG["vocab_size"], (rows, seq + 1))
    return tokens[:, :-1].astype(np.int32), tokens[:, 1:].astype(np.int32)


def selection_bias(model, seed=3):
    """The model's buffers with a selection bias of softmax scores' size
    in every expert layer, so that the bias selects in the comparison."""
    rng = np.random.default_rng(seed)
    return {k: (v + jnp.asarray(rng.normal(0, 0.02, v.shape), v.dtype)
                if k.endswith("e_score_correction_bias") else v)
            for k, v in model.buffer_dict().items()}


def system(model, ids, labels, buffers):
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
    return jax.value_and_grad(
        lambda p: ref.loss(p, CFG, ids, labels, buffers))(params)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    off = np.linalg.norm(a - b)
    # a layer none of whose held experts was chosen has no gradient
    return 0.0 if off == 0 else off / np.linalg.norm(b)


def compare(model):
    ids, labels = batch()
    buffers = selection_bias(model)
    with jax.default_matmul_precision("highest"):
        out, loss, grads = system(model, ids, labels, buffers)
        ref_loss, ref_grads = _reference(model.param_dict(), buffers, ids,
                                         labels)
    assert abs(float(loss) - float(ref_loss)) < TOL * float(ref_loss)
    for name in ref_grads:          # every gradient
        assert rel(grads[name], ref_grads[name]) < TOL, name
    assert int(out.moe_pairs_dropped) == 0
    return out


@pytest.mark.parametrize("recompute", ["none", "layer"])
def test_loss_and_gradients_match_the_reference(recompute):
    out = compare(build(recompute=recompute))
    # four layers, 48 tokens, 3 choices each, a quarter of the experts
    assert 0 < int(out.moe_pairs_held) < 4 * 48 * 3
    assert out.moe_expert_load.shape == (4, 16)


@pytest.mark.parametrize("fault", sorted(drill.FAULTS))
def test_a_fault_fails_the_comparison(fault):
    """The benchmark's controls, planted in the float32 comparison: no
    window, half the window, rotary positions on the global layer, the
    router after attention, SwiGLU experts."""
    model = build()
    with drill.FAULTS[fault](None, model, None):
        with pytest.raises(AssertionError):
            compare(model)
    compare(model)          # and the fault is gone


def test_the_reference_follows_a_given_choice_and_counts_its_distance():
    """``loss_and_routing`` handed its own choice reads as without it, with
    no pair chosen otherwise; handed one pair moved to an expert outside
    the reference's top-k, it counts that pair in its layer, gives its
    shortfall, and the loss moves."""
    model = build()
    ids, labels = batch(rows=1)
    params, buffers = model.param_dict(), selection_bias(model)
    with jax.default_matmul_precision("highest"):
        own, (n, gap, chosen) = ref.loss_and_routing(params, CFG, ids,
                                                     labels, buffers)
        assert chosen.shape == (CFG["num_hidden_layers"], 1, SEQ,
                                CFG["moe_num_active_primary_experts"])
        assert not np.any(np.asarray(n)) and not np.any(np.asarray(gap))
        same, (n, gap, used) = ref.loss_and_routing(
            params, CFG, ids, labels, buffers, chosen)
        assert float(same) == pytest.approx(float(own), rel=1e-6)
        assert not np.any(np.asarray(n))
        assert np.array_equal(np.asarray(used), np.asarray(chosen))
        # token 0 of layer 2: its last choice swapped for a held expert
        # the reference would not choose, so that the result moves too
        moved = np.asarray(chosen).copy()
        held = range(CFG["expert_offset"], CFG["expert_offset"]
                     + CFG["moe_num_primary_experts"])
        outside = sorted(set(held) - set(moved[2, 0, 0].tolist()))[0]
        moved[2, 0, 0, -1] = outside
        other, (n, gap, _) = ref.loss_and_routing(
            params, CFG, ids, labels, buffers, jnp.asarray(moved))
    assert np.asarray(n).tolist() == [0, 0, 1, 0]
    gap = np.asarray(gap)
    assert gap[2] > 0 and not np.any(gap[[0, 1, 3]])
    assert float(other) != float(own)


def test_the_layers_are_read_from_the_published_lists():
    model = build()
    kinds = [(layer.self_attn.sliding_window, layer.self_attn.rope_theta)
             for layer in model.layers]
    assert kinds == [(None, None)] + [(8, 1.5e6)] * 3
    published = SmallThinkerConfig()
    assert published.rope_layout == published.sliding_window_layout \
        == [0, 1, 1, 1] * 13
    with pytest.raises(ValueError, match="rope_layout"):
        SmallThinkerConfig(**{**CFG, "rope_layout": [0, 1, 1]})
    with pytest.raises(ValueError, match="sliding_window_layout"):
        SmallThinkerConfig(**{**CFG, "sliding_window_layout": [0, 1, 2, 1]})


def test_the_four_shares_add_up_to_the_uncut_expert_layer():
    """64 experts as 4 ranks of 16 at a small width, routed on another
    tensor than the one the experts read: the ranks' parts, summed, are
    the uncut reference's expert layer."""
    cfg = dict(CFG, moe_num_primary_experts=64, expert_offset=0,
               moe_num_active_primary_experts=6)
    pt.seed(5)
    whole = nn.DroplessMoE(32, 24, 64, 6, score_func="softmax", gated=True,
                           gate_act="relu")
    params = {"mlp." + k: v for k, v in whole.param_dict().items()}
    rng = np.random.default_rng(0)
    y, x = (jnp.asarray(rng.normal(size=(2, SEQ, 32)), jnp.float32)
            for _ in range(2))
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(params, cfg, y.reshape(-1, 32),
                                  x.reshape(-1, 32))
    total, pairs = 0.0, 0
    for rank in range(4):
        share = nn.DroplessMoE(32, 24, 64, 6, experts_held=16,
                               expert_offset=16 * rank,
                               score_func="softmax", gated=True,
                               gate_act="relu")
        mine = {"router_weight": params["mlp.router_weight"],
                "w_in": params["mlp.w_in"][16 * rank:16 * rank + 16],
                "w_out": params["mlp.w_out"][16 * rank:16 * rank + 16]}
        with jax.default_matmul_precision("highest"):
            out, stats = functional_call(share, mine,
                                         share.buffer_dict(), y, x)
            alone = ref.routed_experts(
                {"mlp." + k: v for k, v in mine.items()}, cfg,
                y.reshape(-1, 32), x.reshape(-1, 32), 16, 16 * rank)
        assert rel(out.reshape(-1, 32), alone) < TOL
        total = total + out.reshape(-1, 32)
        pairs += int(stats["pairs_held"])
        assert int(stats["pairs_dropped"]) == 0
    assert rel(total, want) < TOL
    assert pairs == 2 * SEQ * 6, "every pair is someone's"


def test_the_selection_bias_selects_and_moves_a_step_at_a_time():
    """Fitted to a batch by ``balance_router_bias`` (the model's own
    schedule), the bias evens the experts' load; while training, every
    step moves it by the aux-loss-free rule."""
    model = build(recompute="layer", router_bias_update_rate=1e-3)
    ids, labels = batch(rows=4, seq=64)
    before = np.asarray(model(ids).moe_expert_load)
    models.balance_router_bias(model, ids)
    after = np.asarray(model(ids).moe_expert_load)
    spread = lambda load: (load.max(1) / load.mean(1)).max()  # noqa: E731
    assert spread(after) < spread(before)
    fitted = {k: np.asarray(v) for k, v in model.buffer_dict().items()}
    assert any(np.abs(v).max() > 0 for v in fitted.values())
    step = TrainStep(model, pt.optimizer.AdamW(1e-4), models.next_token_loss,
                     extra_metrics=models.routing_metrics())
    step(ids, labels=(labels,))
    moved = step.state["buffers"]
    for name, v in fitted.items():
        d = np.abs(np.asarray(moved[name]) - v)     # 0 at the mean load
        assert np.all(np.isclose(d, 0, atol=1e-7)
                      | np.isclose(d, 1e-3, atol=1e-7)), name
        assert d.max() > 0, name


def test_train_step_returns_the_counters_and_learns():
    step = TrainStep(build(recompute="layer"), pt.optimizer.AdamW(3e-3),
                     models.next_token_loss,
                     extra_metrics=models.routing_metrics())
    ids, labels = batch(rows=4)
    first = step(ids, labels=(labels,))
    for _ in range(14):
        last = step(ids, labels=(labels,))
    assert float(last["loss"]) < float(first["loss"]) - 0.3
    assert int(last["moe_pairs_dropped"]) == 0
    assert 0 < int(last["moe_pairs_held"]) < 4 * 4 * SEQ * 3


def test_a_step_on_the_kernels_publishes_its_window_sites_and_census(
        monkeypatch):
    """With the seam on the flash kernels (interpreted here), the step
    notes its three window layers (``pt_swa_attention_sites``), and the
    census gauges are the window walk's three times and the causal
    walk's once; each layer's forward kernel runs once, its result kept
    across the recomputation."""
    from paddle_tpu import kernels
    from paddle_tpu.observability import xprof
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    monkeypatch.setattr(fa, "BLOCK_Q", 16)
    monkeypatch.setattr(fa, "BLOCK_K", 16)
    obs.reset_all()
    floor = pt.get_flags(["flash_attention_min_seq_train"])
    pt.set_flags({"enable_metrics": True,
                  "flash_attention_min_seq_train": 16})
    try:
        step = TrainStep(build(recompute="layer", head_dim=128,
                               num_attention_heads=2, num_key_value_heads=1,
                               sliding_window_size=20),
                         pt.optimizer.AdamW(1e-3), models.next_token_loss,
                         extra_metrics=models.routing_metrics())
        ids, labels = batch(seq=64)
        assert np.isfinite(float(step(ids, labels=(labels,))["loss"]))
        fn = step._span_name
        assert obs.gauge("pt_swa_attention_sites").value(fn=fn) == 3
        window = fa.flash_tile_census(64, 64, 16, 16, True, None, 20)
        causal = fa.flash_tile_census(64, 64, 16, 16, True)
        for i, kind in enumerate(("visited", "whole", "diagonal")):
            assert obs.gauge("pt_flash_tiles_" + kind).value(fn=fn) \
                == 3 * window[i] + causal[i], kind
        names = sorted(n[0] for n in xprof.kernel_notes(fn))
        assert names == sorted(
            ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
            + ["swa_flash_bwd_dkv", "swa_flash_bwd_dq", "swa_flash_fwd"] * 3)
    finally:
        pt.set_flags({"enable_metrics": False, **floor})
        obs.reset_all()
