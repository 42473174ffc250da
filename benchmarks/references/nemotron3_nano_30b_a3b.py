"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``), plain:
forward, next-token loss and, through ``jax.grad``, gradients, in
float32 at ``highest`` matmul precision. No kernel, and no import from
the package under test.

Follows HF ``modeling_nemotron_h.py``. Every layer is ``x + mixer(
rmsnorm(x))``, its kind read from ``hybrid_override_pattern``:

- ``M`` Mamba-2, with the recurrence written as the recurrence: a
  ``lax.scan`` over positions carrying the [heads, 64, 128] state
  (``h_t = a_t h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``).
- ``E`` sigmoid router over all ``n_routed_experts_total`` experts,
  the six largest of ``s + b`` chosen, weights ``2.5 s / (sum s +
  1e-20)``, non-gated ``relu(x)^2`` experts by a loop over the experts
  HELD (``n_routed_experts`` from ``expert_offset``; a pair that falls
  on an absent expert adds nothing), plus the shared expert.
- ``*`` causal attention, 32 query heads on 2 KV heads, no positional
  term (the source reads ``rope_theta`` nowhere).

So that one sequence of 8192 fits a chip, long loops are cut in blocks
whose inside is recomputed in the backward pass (``jax.checkpoint``):
the recurrence in runs of ``SCAN_BLOCK`` positions, attention a query
head at a time, each layer as a whole. The arithmetic is unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# What the reference computes in. ``benchmarks/control_drill.py`` loads a
# second copy of this module with bfloat16 and "default" (parameters,
# products, the scan's state and the loss one precision down) to show
# that the comparison's limits tell the two apart.
PRECISION = "highest"
DTYPE = jnp.float32
SCAN_BLOCK = 128


def _cast(params):
    return {k: jnp.asarray(v, DTYPE) for k, v in params.items()}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def recurrence(x, dt, a, b_mat, c_mat):
    """x [L, H, P], dt [L, H], a [H], b_mat / c_mat [L, H, N] (already
    spread from groups to heads) -> y [L, H, P], a step at a time."""
    length, h, p = x.shape
    n = b_mat.shape[-1]

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    pad = -length % SCAN_BLOCK

    def blocks(t):   # zero dt neither decays nor feeds the state
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape(-1, SCAN_BLOCK, *t.shape[1:])

    _, y = jax.lax.scan(block, jnp.zeros((h, p, n), x.dtype),
                        tuple(blocks(t) for t in (x, dt, b_mat, c_mat)))
    return y.reshape(-1, h, p)[:length]


def mamba2(p, pre, cfg, u):
    """u [L, hidden] -> [L, hidden]."""
    h, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    inner, length = h * hd, u.shape[0]
    proj = u @ p[pre + "in_proj.weight"]
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * g * n], axis=-1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = p[pre + "conv_bias"] + sum(
        padded[i:i + length] * p[pre + "conv_weight"][i] for i in range(k))
    xbc = jax.nn.silu(xbc)
    x, b_mat, c_mat = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(length, h, hd)
    spread = lambda t: jnp.repeat(t.reshape(length, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + p[pre + "dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p[pre + "A_log"]), spread(b_mat),
                   spread(c_mat))
    y = (y + p[pre + "D"][:, None] * x).reshape(length, inner)
    y = (y * jax.nn.silu(z)).reshape(length, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    y = y.reshape(length, inner) * p[pre + "norm.weight"]
    return y @ p[pre + "out_proj.weight"]


def route(p, pre, cfg, x, bias):
    """(chosen [L, k], weights [L, k]) over every expert the router
    scores."""
    s = jax.nn.sigmoid(x @ p[pre + "router_weight"])
    _, chosen = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * cfg["routed_scaling_factor"]


def routed_experts(p, pre, cfg, x, bias, held=None, offset=None):
    """The part of the routed result that the experts ``[offset,
    offset + held)`` give (the configuration's by default); ``w_in`` /
    ``w_out`` hold those experts, first to last."""
    held = cfg["n_routed_experts"] if held is None else held
    offset = cfg["expert_offset"] if offset is None else offset
    chosen, w = route(p, pre, cfg, x, bias)
    out = jnp.zeros_like(x)
    for e in range(held):
        gate = jnp.sum(jnp.where(chosen == offset + e, w, 0.0), -1)
        out = out + gate[:, None] * (
            _relu2(x @ p[pre + "w_in"][e]) @ p[pre + "w_out"][e])
    return out


def shared_expert(p, pre, x):
    return _relu2(x @ p[pre + "shared_in.weight"]) \
        @ p[pre + "shared_out.weight"]


def attention(p, pre, cfg, x):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, length = cfg["head_dim"], x.shape[0]
    q = (x @ p[pre + "q_proj.weight"]).reshape(length, heads, d)
    k = (x @ p[pre + "k_proj.weight"]).reshape(length, kv, d)
    v = (x @ p[pre + "v_proj.weight"]).reshape(length, kv, d)
    causal = jnp.tril(jnp.ones((length, length), bool))

    @jax.checkpoint
    def one_head(args):
        q_h, k_h, v_h = args
        s = jnp.where(causal, q_h @ k_h.T * d ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v_h

    rep = heads // kv
    out = jax.lax.map(one_head, (
        jnp.moveaxis(q, 1, 0), jnp.repeat(jnp.moveaxis(k, 1, 0), rep, 0),
        jnp.repeat(jnp.moveaxis(v, 1, 0), rep, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(length, heads * d) \
        @ p[pre + "o_proj.weight"]


def hidden_states(p, cfg, ids, buffers=None):
    """ids [L] -> final-norm hidden states [L, hidden], float32."""
    eps = cfg["layer_norm_epsilon"]
    x = p["embeddings.weight"][ids]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        pre = f"layers.{i}.mixer."

        @jax.checkpoint
        def layer(x, kind=kind, pre=pre, i=i):
            h = _rms(x, p[f"layers.{i}.norm.weight"], eps)
            if kind == "M":
                return x + mamba2(p, pre, cfg, h)
            if kind == "*":
                return x + attention(p, pre, cfg, h)
            bias = (buffers or {}).get(pre + "e_score_correction_bias",
                                       0.0)
            return x + routed_experts(p, pre, cfg, h, bias) \
                + shared_expert(p, pre, h)

        x = layer(x)
    return _rms(x, p["norm_f.weight"], eps)


def logits(params, cfg, ids, buffers=None):
    """ids [B, L] -> logits [B, L, vocabulary held], float32."""
    with jax.default_matmul_precision(PRECISION):
        p = _cast(params)
        return jnp.stack([hidden_states(p, cfg, row, buffers)
                          @ p["lm_head.weight"] for row in ids])


def loss(params, cfg, ids, labels, buffers=None):
    """Mean cross-entropy of ``labels`` [B, L] (the token after each
    position) over the vocabulary rows held, float32."""
    logp = jax.nn.log_softmax(logits(params, cfg, ids, buffers), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1))
