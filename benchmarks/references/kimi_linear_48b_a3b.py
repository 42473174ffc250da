"""moonshotai Kimi-Linear-48B-A3B (``model_type: kimi_linear``), plain:
forward, next-token loss and, through ``jax.grad``, gradients, in
float32 at ``highest`` matmul precision. No kernel, no chunking of the
delta rule, and no import from the package under test.

Every layer is ``h + mixer(rmsnorm(h))`` then ``h + mlp(rmsnorm(h))``,
the mixer's kind read from ``linear_attn_config`` (1-indexed layers):

- KDA (Kimi Delta Attention): ``q, k, v = silu(conv4(x W))`` (causal,
  depthwise, no bias); q and k L2-normalised a head; ``a = -exp(A_log)
  softplus(x W_fa W_fb + dt_bias)`` a log-decay for every key channel;
  ``beta = sigmoid(x W_b)``; the recurrence written as the recurrence,
  a ``lax.scan`` over POSITIONS carrying the [heads, 128, 128] state:
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t / sqrt(128)``; ``y = rmsnorm_head(o)
  sigmoid(x W_ga W_gb + b_g)``, then ``y W_o``.
- MLA: ``q = x W_q`` (192 a head), ``[c ; k_pe] = x W_kva``, ``[k_nope ;
  v] = rmsnorm(c) W_kvb``, ``k = [k_nope ; k_pe]`` with ``k_pe`` shared
  by the heads and no rotation, causal softmax at ``192 ** -0.5``, a
  dense [rows, L] score block at a time.
- the first ``first_k_dense_replace`` layers: a dense SwiGLU; the rest:
  ``s = sigmoid(W_r u)`` over all ``num_experts_total``, the 8 largest
  of ``s + b`` chosen, ``w = 2.446 s / sum s``, gated experts summed
  densely over the experts HELD (``num_experts`` from ``expert_offset``:
  a pair on an absent expert adds nothing), plus the gated shared
  expert.

So that a sequence of 16,384 fits a chip, the recurrence runs in blocks
of ``SCAN_BLOCK`` positions, attention ``QUERY_BLOCK`` query rows at a
time, each layer as a whole, each recomputed in the backward pass
(``jax.checkpoint``): the recurrence's backward holds one state a block.
The arithmetic is unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# What the reference computes in. ``benchmarks/kimi_control_drill.py``
# loads a second copy of this module with bfloat16 and "default"
# (parameters, products, the recurrence's state and the loss one
# precision down).
PRECISION = "highest"
DTYPE = jnp.float32
SCAN_BLOCK = 256
QUERY_BLOCK = 2048


def _cast(params):
    return {k: jnp.asarray(v, DTYPE) for k, v in params.items()}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _conv(x, w):
    """Causal depthwise conv, x [L, C], w [K, C], no bias."""
    k, length = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(padded[i:i + length] * w[i] for i in range(k))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, a, beta):
    """q, k [L, H, K] normalised (q scaled), v [L, H, V], a [L, H, K]
    log-decay, beta [L, H] -> o [L, H, V], a position at a time."""
    length, h, dk = k.shape

    def step(state, inp):
        q_t, k_t, v_t, a_t, b_t = inp
        state = state * jnp.exp(a_t)[..., None]
        kv = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + jnp.einsum("hk,hv->hkv", k_t,
                                   b_t[:, None] * (v_t - kv))
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    pad = -length % SCAN_BLOCK

    def blocks(t):      # k = 0, beta = 0, a = 0: the state is unmoved
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape(-1, SCAN_BLOCK, *t.shape[1:])

    _, o = jax.lax.scan(block, jnp.zeros((h, dk, v.shape[-1]), v.dtype),
                        tuple(blocks(t) for t in (q, k, v, a, beta)))
    return o.reshape(-1, h, v.shape[-1])[:length]


def kda(p, pre, cfg, x):
    """x [L, hidden] -> [L, hidden]."""
    lac, eps = cfg["linear_attn_config"], cfg["rms_norm_eps"]
    h, d = lac["num_heads"], lac["head_dim"]
    length = x.shape[0]
    q, k, v = (jax.nn.silu(_conv(x @ p[pre + n + "_proj.weight"],
                                 p[pre + n + "_conv1d"])).reshape(
                                     length, h, d) for n in "qkv")
    f = (x @ p[pre + "f_a_proj.weight"]) @ p[pre + "f_b_proj.weight"]
    a = -jnp.exp(p[pre + "A_log"])[:, None] * jax.nn.softplus(
        f.reshape(length, h, d) + p[pre + "dt_bias"].reshape(h, d))
    beta = jax.nn.sigmoid(x @ p[pre + "b_proj.weight"])
    o = delta_rule(_l2(q) * d ** -0.5, _l2(k), v, a, beta)
    gate = (x @ p[pre + "g_a_proj.weight"]) @ p[pre + "g_b_proj.weight"] \
        + p[pre + "g_b_proj.bias"]
    y = _rms(o, p[pre + "o_norm_weight"], eps).reshape(length, h * d) \
        * jax.nn.sigmoid(gate)
    return y @ p[pre + "o_proj.weight"]


def mla(p, pre, cfg, x):
    """x [L, hidden] -> [L, hidden]: causal attention over the latent
    keys and values, query rows a block at a time."""
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope_w = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, length = cfg["v_head_dim"], x.shape[0]
    dqk = nope + rope_w
    q = (x @ p[pre + "q_proj.weight"]).reshape(length, h, dqk)
    ckv = x @ p[pre + "kv_a_proj_with_mqa.weight"]
    c, k_pe = ckv[:, :rank], ckv[:, rank:]
    kv = (_rms(c, p[pre + "kv_a_layernorm.weight"], cfg["rms_norm_eps"])
          @ p[pre + "kv_b_proj.weight"]).reshape(length, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None], (length, h, rope_w))], -1)
    v = kv[..., nope:]
    rows = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def some_rows(args):
        q_b, first = args
        causal = first + jnp.arange(rows)[:, None] >= keys
        s = jnp.einsum("qhd,khd->hqk", q_b, k) * dqk ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    n = length // rows
    out = jax.lax.map(some_rows, (q.reshape(n, rows, h, dqk),
                                  jnp.arange(n) * rows))
    return out.reshape(length, h * dv) @ p[pre + "o_proj.weight"]


def _swiglu(x, w_gate_up, w_down, width):
    hid = x @ w_gate_up
    return (jax.nn.silu(hid[:, :width]) * hid[:, width:]) @ w_down


def route(p, pre, cfg, x, bias):
    """(chosen [L, k], weights [L, k]) over every expert scored."""
    s = jax.nn.sigmoid(x @ p[pre + "router_weight"])
    _, chosen = jax.lax.top_k(s + bias, cfg["num_experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"]


def routed_experts(p, pre, cfg, x, bias, held=None, offset=None):
    """The part of the routed result that the experts ``[offset, offset
    + held)`` give (the configuration's by default); ``w_in`` / ``w_out``
    hold those experts, first to last, ``w_in`` as ``[W_gate | W_up]``."""
    held = cfg["num_experts"] if held is None else held
    offset = cfg["expert_offset"] if offset is None else offset
    chosen, w = route(p, pre, cfg, x, bias)
    out = jnp.zeros_like(x)
    for e in range(held):
        weight = jnp.sum(jnp.where(chosen == offset + e, w, 0.0), -1)
        out = out + weight[:, None] * _swiglu(
            x, p[pre + "w_in"][e], p[pre + "w_out"][e],
            cfg["moe_intermediate_size"])
    return out


def shared_expert(p, pre, cfg, x):
    return _swiglu(x, p[pre + "shared_in.weight"],
                   p[pre + "shared_out.weight"],
                   cfg["moe_intermediate_size"] * cfg["num_shared_experts"])


def dense_mlp(p, pre, x):
    return (jax.nn.silu(x @ p[pre + "gate_proj.weight"])
            * (x @ p[pre + "up_proj.weight"])) @ p[pre + "down_proj.weight"]


def hidden_states(p, cfg, ids, buffers=None):
    """ids [L] -> final-norm hidden states [L, hidden], float32."""
    eps = cfg["rms_norm_eps"]
    kda_layers = cfg["linear_attn_config"]["kda_layers"]
    x = p["embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layers.{i}."

        @jax.checkpoint
        def layer(x, pre=pre, i=i):
            mixer = kda if i + 1 in kda_layers else mla
            x = x + mixer(p, pre + "self_attn.", cfg, _rms(
                x, p[pre + "input_layernorm.weight"], eps))
            u = _rms(x, p[pre + "post_attention_layernorm.weight"], eps)
            if i < cfg["first_k_dense_replace"]:
                return x + dense_mlp(p, pre + "mlp.", u)
            bias = (buffers or {}).get(pre + "mlp.e_score_correction_bias",
                                       0.0)
            return x + routed_experts(p, pre + "mlp.", cfg, u, bias) \
                + shared_expert(p, pre + "mlp.", cfg, u)

        x = layer(x)
    return _rms(x, p["norm.weight"], eps)


def logits(params, cfg, ids, buffers=None):
    """ids [B, L] -> logits [B, L, vocabulary held], float32."""
    with jax.default_matmul_precision(PRECISION):
        p = _cast(params)
        return jnp.stack([hidden_states(p, cfg, row, buffers)
                          @ p["lm_head.weight"] for row in ids])


def loss(params, cfg, ids, labels, buffers=None):
    """Mean cross-entropy of ``labels`` [B, L] (the token after each
    position) over the vocabulary rows held."""
    logp = jax.nn.log_softmax(logits(params, cfg, ids, buffers), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1))
