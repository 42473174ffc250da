"""The options this repo's attention and expert layers gained for
models trained by diffusion over blocks: rotary positions and the q/k
norm of ``nn.GroupedQueryAttention`` against their definitions, the
softmax score and the gated expert of ``nn.DroplessMoE`` against a loop
over experts; and, with every option off, both layers bitwise what they
were: outputs and gradients equal to those of a copy of the forward
passes as they stood before the options, kept here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.nn.layers import moe


@pytest.fixture(autouse=True)
def _small_windows(monkeypatch):
    monkeypatch.setattr(moe, "_ROW_TILE", 8)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# -- rotary positions and the q/k norm, by their definitions -------------------

def test_rotary_turns_each_pair_of_the_two_halves_by_its_angle():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 6, 3, 8))
    pos = np.array([[0, 1, 2, 0, 1, 2], [5, 5, 7, 9, 11, 400]])
    got = nn.rotate_half_rope(jnp.asarray(x, jnp.float32), pos, 1e6)
    want = np.empty_like(x)
    for b in range(2):
        for t in range(6):
            for i in range(4):
                angle = pos[b, t] * 1e6 ** (-2 * i / 8)
                c, s = np.cos(angle), np.sin(angle)
                want[b, t, :, i] = x[b, t, :, i] * c - x[b, t, :, i + 4] * s
                want[b, t, :, i + 4] = x[b, t, :, i + 4] * c \
                    + x[b, t, :, i] * s
    assert rel(got, want) < 1e-6
    # two positions of the input that share a position id turn alike,
    # and a [T] vector of ids is every row's
    same = nn.rotate_half_rope(jnp.asarray(x[:1], jnp.float32), pos[0],
                               1e6)
    assert np.array_equal(same, got[:1])
    assert rel(np.linalg.norm(got, axis=-1), np.linalg.norm(x, axis=-1)) \
        < 1e-6, "a turn keeps a head's length"


def _attention(seed=0, **options):
    pt.seed(seed)
    return nn.GroupedQueryAttention(32, 4, 2, 8, causal=False, **options)


def _plain_attention(p, x, pos, eps, theta, mask=None):
    """GQA by its definition, a loop over heads, in float64."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    b, t, _ = x.shape

    def heads(name, n):
        return (x @ p[name + ".weight"]).reshape(b, t, n, 8)

    def norm(h, g):
        return h / np.sqrt((h ** 2).mean(-1, keepdims=True) + eps) * g

    def turn(h):
        out = np.empty_like(h)
        for i in range(4):
            angle = pos * theta ** (-2 * i / 8)
            c, s = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
            out[..., i] = h[..., i] * c - h[..., i + 4] * s
            out[..., i + 4] = h[..., i + 4] * c + h[..., i] * s
        return out

    q = turn(norm(heads("q_proj", 4), p["q_norm.weight"]))
    k = turn(norm(heads("k_proj", 2), p["k_norm.weight"]))
    v = heads("v_proj", 2)
    out = np.empty((b, t, 4, 8))
    for h in range(4):
        s = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // 2]) / 8 ** .5
        if mask is not None:
            s = np.where(mask, s, -np.inf)
        w = np.exp(s - s.max(-1, keepdims=True))
        out[:, :, h] = np.einsum("bqk,bkd->bqd",
                                 w / w.sum(-1, keepdims=True),
                                 v[:, :, h // 2])
    return out.reshape(b, t, 32) @ p["o_proj.weight"]


@pytest.mark.parametrize("masked", [False, True],
                         ids=["no_mask", "block_diffusion"])
def test_attention_with_the_options_is_its_definition(masked):
    layer = _attention(qk_norm_eps=1e-6, rope_theta=1e4,
                       block_diffusion=4 if masked else None)
    rng = np.random.default_rng(1)
    params = {k: v + 0.2 * jnp.asarray(rng.normal(size=v.shape), v.dtype)
              if "norm" in k else v for k, v in layer.param_dict().items()}
    x = jnp.asarray(rng.normal(size=(2, 16, 32)), jnp.float32)
    pos = np.arange(16) % 8
    mask = None
    if masked:
        from benchmarks.references.sdar_30b_a3b import allowed
        mask = np.asarray(allowed(np.arange(16)[:, None],
                                  np.arange(16)[None, :], 8, 4))
    got = functional_call(layer, params, {}, x, pos)
    assert rel(got, _plain_attention(params, x, pos, 1e-6, 1e4, mask)) \
        < 1e-5
    # without ids the positions are 0 .. T-1
    assert rel(functional_call(layer, params, {}, x),
               _plain_attention(params, x, np.arange(16), 1e-6, 1e4, mask)) \
        < 1e-5


def test_the_mask_is_not_causals_companion():
    with pytest.raises(ValueError, match="causal=False"):
        nn.GroupedQueryAttention(32, 4, 2, 8, causal=True,
                                 block_diffusion=4)


# -- the softmax score and the gated expert, by a loop over experts ------------

def _loop_over_experts(p, bias, x, top_k, held, offset, score, gated,
                       normed):
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = x @ p["router_weight"]
    if score == "softmax":
        e = np.exp(logits - logits.max(-1, keepdims=True))
        g = e / e.sum(-1, keepdims=True)
    else:
        g = 1 / (1 + np.exp(-logits))
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        chosen = np.argsort(-(g[n] + bias), kind="stable")[:top_k]
        w = g[n, chosen] / (g[n, chosen].sum() if normed else 1.0)
        for e, w_e in zip(chosen, w):
            if not offset <= e < offset + held:
                continue
            h = x[n] @ p["w_in"][e - offset]
            if gated:
                width = h.shape[0] // 2
                h = h[:width] / (1 + np.exp(-h[:width])) * h[width:]
            else:
                h = np.maximum(h, 0) ** 2
            out[n] += w_e * (h @ p["w_out"][e - offset])
    return out


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
@pytest.mark.parametrize("normed", [True, False])
def test_experts_with_the_options_are_a_loop_over_experts(score, gated,
                                                          normed):
    pt.seed(4)
    layer = nn.DroplessMoE(32, 24, 16, 3, experts_held=8, expert_offset=4,
                           norm_topk_prob=normed, score_func=score,
                           gated=gated)
    assert layer.w_in.shape == (8, 32, 48 if gated else 24)
    rng = np.random.default_rng(3)
    bias = rng.normal(0, 0.01, 16)
    x = jnp.asarray(rng.normal(size=(2, 21, 32)), jnp.float32)
    params = layer.param_dict()
    out, stats = functional_call(
        layer, params, {"e_score_correction_bias": jnp.asarray(
            bias, jnp.float32)}, x)
    want = _loop_over_experts(params, bias, x, 3, 8, 4, score, gated,
                              normed)
    assert rel(out.reshape(-1, 32), want) < 1e-5
    assert int(stats["pairs_dropped"]) == 0
    assert 0 < int(stats["pairs_held"]) < 42 * 3


def test_a_gated_shared_expert_is_gated_alike():
    pt.seed(6)
    layer = nn.DroplessMoE(32, 24, 8, 2, d_shared=40, gated=True)
    assert layer.shared_in.weight.shape == (32, 80)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 9, 32)),
                    jnp.float32)
    bare = nn.DroplessMoE(32, 24, 8, 2, gated=True)
    params = layer.param_dict()
    routed, _ = functional_call(
        bare, {k: v for k, v in params.items() if "shared" not in k},
        bare.buffer_dict(), x)
    h = x.reshape(-1, 32) @ params["shared_in.weight"]
    shared = (jax.nn.silu(h[:, :40]) * h[:, 40:]) \
        @ params["shared_out.weight"]
    out, _ = layer(x)
    assert rel(out.reshape(-1, 32), routed.reshape(-1, 32) + shared) < 1e-5


def test_an_unknown_score_is_refused():
    with pytest.raises(ValueError, match="score_func"):
        nn.DroplessMoE(32, 24, 8, 2, score_func="tanh")


# -- with the options off, bitwise what the layers were ----------------------------

class AttentionAsItWas(nn.GroupedQueryAttention):
    """``forward`` as it stood before the options (PR 34)."""

    def forward(self, x):
        from paddle_tpu.kernels import maybe_flash_attention
        b, t, _ = x.shape
        rep = self.num_heads // self.num_kv_heads
        q = self.q_proj(x).reshape(b, t, self.num_heads, self.head_dim)

        def kv(proj):
            heads = proj(x).reshape(b, t, self.num_kv_heads,
                                    self.head_dim)
            return jnp.repeat(heads, rep, axis=2)

        out = maybe_flash_attention(
            q, kv(self.k_proj), kv(self.v_proj), causal=self.causal,
            scale=self.head_dim ** -0.5, training=self.training,
            layout="bthd")
        return self.o_proj(out.reshape(b, t, -1))


class ExpertsAsTheyWere(nn.DroplessMoE):
    """``route``, ``_window`` and ``forward`` as they stood before the
    options (PR 34); ``_routed``, the loop over windows and its
    hand-written gradient, is the layer's own and calls these."""

    def route(self, tokens):
        scores = jax.nn.sigmoid(jnp.matmul(
            tokens.astype(jnp.float32),
            self.router_weight.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(
            scores + self.e_score_correction_bias, self.top_k)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return chosen, w * self.routed_scaling_factor

    def _window(self, acc, tokens, weights, w_in, w_out, order, ends, lo,
                rows: int):
        from paddle_tpu.kernels import (maybe_group_tiles,
                                        maybe_grouped_matmul)
        with jax.named_scope("pt.moe_route"):
            pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
            token_of = pair // self.top_k
            inside = jnp.clip(ends, lo, lo + rows) - lo
            live = jnp.arange(rows) < inside[-1]
            sizes = jnp.diff(inside, prepend=0).at[-1].add(
                rows - inside[-1])
            rows_in = tokens[token_of]
            w = jnp.where(live, weights.reshape(-1)[pair], 0.0)
            tiles = maybe_group_tiles(sizes, rows)
        with jax.named_scope("pt.moe_experts"):
            hidden = jnp.square(jax.nn.relu(maybe_grouped_matmul(
                jnp.where(live[:, None], rows_in, 0), w_in, sizes, tiles)))
            out = (maybe_grouped_matmul(hidden, w_out, sizes, tiles)
                   * w[:, None].astype(hidden.dtype)).astype(jnp.float32)
        with jax.named_scope("pt.moe_route"):
            return acc.at[token_of].add(out)

    def forward(self, x):
        tokens = x.reshape(-1, x.shape[-1])
        n, held = tokens.shape[0], self.experts_held
        total = n * self.top_k
        rows = min(total, -(-moe.WINDOW_FACTOR * total * held
                            // (self.num_experts * moe._ROW_TILE))
                   * moe._ROW_TILE)
        windows = -(-total // rows)
        with jax.named_scope("pt.moe_route"):
            chosen, weights = self.route(tokens)
            local = chosen - self.expert_offset
            key = jnp.where((local >= 0) & (local < held), local,
                            held).reshape(-1)
            order = jnp.pad(jnp.argsort(key), (0, windows * rows - total))
            load = jnp.bincount(chosen.reshape(-1),
                                length=self.num_experts).astype(jnp.int32)
            held_load = load[self.expert_offset:self.expert_offset + held]
            ends = jnp.cumsum(held_load)
            pairs_held = ends[-1]
            windows_run = jnp.minimum(-(-pairs_held // rows), windows)
        routed = self._routed(tokens, weights, order, ends, rows,
                              windows_run)
        out = routed.astype(x.dtype)
        if self.has_shared:
            with jax.named_scope("pt.moe_shared"):
                out = out + self.shared_out(jnp.square(jax.nn.relu(
                    self.shared_in(tokens))))
        return out.reshape(x.shape), {"pairs_held": pairs_held}


def _bitwise(now, was, x, dtype):
    """Outputs and every gradient of the two layers on the same
    parameters, jitted as a model's step is."""
    params = {k: v.astype(dtype) for k, v in now.param_dict().items()}
    buffers = now.buffer_dict()
    x = x.astype(dtype)

    def run(layer):
        def loss(p, x):
            out = functional_call(layer, p, buffers, x)
            out = out[0] if isinstance(out, tuple) else out
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
            params, x)

    (_, out_now), g_now = run(now)
    (_, out_was), g_was = run(was)
    assert np.array_equal(np.asarray(out_now, np.float32),
                          np.asarray(out_was, np.float32))
    for a, b in zip(jax.tree.leaves(g_now), jax.tree.leaves(g_was)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [True, False])
def test_attention_without_the_options_is_bitwise_what_it_was(dtype,
                                                              causal):
    pt.seed(2)
    now = nn.GroupedQueryAttention(32, 4, 2, 8, causal=causal)
    pt.seed(2)
    was = AttentionAsItWas(32, 4, 2, 8, causal=causal)
    assert set(now.param_dict()) == set(was.param_dict()) == {
        "q_proj.weight", "k_proj.weight", "v_proj.weight", "o_proj.weight"}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 24, 32)),
                    jnp.float32)
    _bitwise(now, was, x, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d_shared", [0, 40])
def test_experts_without_the_options_are_bitwise_what_they_were(dtype,
                                                                d_shared):
    kw = dict(d_shared=d_shared, experts_held=8, expert_offset=4,
              routed_scaling_factor=2.5)
    pt.seed(3)
    now = nn.DroplessMoE(32, 24, 16, 3, **kw)
    pt.seed(3)
    was = ExpertsAsTheyWere(32, 24, 16, 3, **kw)
    assert {k: v.shape for k, v in now.param_dict().items()} \
        == {k: v.shape for k, v in was.param_dict().items()}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 21, 32)),
                    jnp.float32)
    _bitwise(now, was, x, dtype)
