"""JetLM SDAR-30B-A3B-Chat (``model_type: sdar_moe``) trained by
diffusion over blocks, plain: forward, the weighted masked loss and,
through ``jax.grad``, gradients, in float32 at ``highest`` matmul
precision. No kernel, and no import from the package under test.

A sequence ``x0`` of ``L`` tokens is run as the ``2 L`` ids ``[xt ;
x0]`` (the noised copy, then the clean one), position ``p_i = i mod L``,
block ``beta(i) = (i mod L) // K``:

- every layer is ``h + attention(rmsnorm(h))`` then ``h + experts(
  rmsnorm(h))``;
- attention: 32 query heads on 4 KV heads of 128, an RMS norm with a
  gain over every q and k head, rotary positions (rotate-half, the
  whole head, theta 1e6), one softmax over the keys the block-diffusion
  rule allows a query: noisy to noisy in the same block, noisy to clean
  in earlier blocks, clean to clean in the same and earlier blocks,
  clean to noisy never. The mask is a boolean array built from the rule;
- experts: ``g = softmax(W_r u)`` over all ``num_experts_total``, the 8
  largest of ``g + b`` chosen (``b`` the selection bias, zero unless
  given), ``w = g[chosen] / sum g[chosen]``, gated experts ``W_down
  (silu(W_gate u) * W_up u)`` by a loop over the experts HELD
  (``num_experts`` from ``expert_offset``: a pair on an absent expert
  adds nothing); ``w_in`` holds ``[W_gate | W_up]`` side by side;
- final RMS norm, logits of the noisy positions over the vocabulary
  rows held, ``loss = (1 / (B L)) sum over masked i of CE(logits_i,
  x0_i) / t_i``.

So that 16,384 positions fit a chip, attention runs a query head and
``QUERY_BLOCK`` query rows at a time, each recomputed in the backward
pass (``jax.checkpoint``), as each layer is. The arithmetic is
unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# What the reference computes in. ``benchmarks/bd_control_drill.py``
# loads a second copy of this module with bfloat16 and "default".
PRECISION = "highest"
DTYPE = jnp.float32
QUERY_BLOCK = 2048


def _cast(params):
    return {k: jnp.asarray(v, DTYPE) for k, v in params.items()}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def positions(length):
    """p_i = i mod L for the 2 L positions."""
    return jnp.arange(2 * length) % length


def allowed(i, j, length, block):
    """The rule: may query position ``i`` see key position ``j``?"""
    bi, bj = (i % length) // block, (j % length) // block
    qn, kn = i < length, j < length
    return (qn & kn & (bi == bj)) | (qn & ~kn & (bj < bi)) \
        | (~qn & ~kn & (bj <= bi))


def rope(x, pos, theta):
    """x [T, H, D], pos [T]: rotate-half over the whole head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :].astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :].astype(x.dtype)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def attention(p, pre, cfg, x, pos):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, total = cfg["head_dim"], x.shape[0]
    length, eps = total // 2, cfg["rms_norm_eps"]
    q = (x @ p[pre + "q_proj.weight"]).reshape(total, heads, d)
    k = (x @ p[pre + "k_proj.weight"]).reshape(total, kv, d)
    v = (x @ p[pre + "v_proj.weight"]).reshape(total, kv, d)
    q = rope(_rms(q, p[pre + "q_norm.weight"], eps), pos, cfg["rope_theta"])
    k = rope(_rms(k, p[pre + "k_norm.weight"], eps), pos, cfg["rope_theta"])
    rows = QUERY_BLOCK if total % QUERY_BLOCK == 0 else total
    keys = jnp.arange(total)[None, :]

    @jax.checkpoint
    def some_rows(args):
        q_b, first, k_h, v_h = args
        mask = allowed(first + jnp.arange(rows)[:, None], keys, length,
                       cfg["block_length"])
        s = jnp.where(mask, q_b @ k_h.T * d ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ v_h

    def one_head(args):
        q_h, k_h, v_h = args
        n = total // rows
        out = jax.lax.map(some_rows, (
            q_h.reshape(n, rows, d), jnp.arange(n) * rows,
            jnp.broadcast_to(k_h, (n,) + k_h.shape),
            jnp.broadcast_to(v_h, (n,) + v_h.shape)))
        return out.reshape(total, d)

    rep = heads // kv
    out = jax.lax.map(one_head, (
        jnp.moveaxis(q, 1, 0), jnp.repeat(jnp.moveaxis(k, 1, 0), rep, 0),
        jnp.repeat(jnp.moveaxis(v, 1, 0), rep, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(total, heads * d) \
        @ p[pre + "o_proj.weight"]


def route(p, pre, cfg, x, bias):
    """(chosen [T, k], weights [T, k]) over every expert scored."""
    g = jax.nn.softmax(x @ p[pre + "router_weight"], axis=-1)
    _, chosen = jax.lax.top_k(g + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(g, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w


def routed_experts(p, pre, cfg, x, bias, held=None, offset=None):
    """The part of the routed result that the experts ``[offset, offset
    + held)`` give (the configuration's by default); ``w_in`` / ``w_out``
    hold those experts, first to last."""
    held = cfg["num_experts"] if held is None else held
    offset = cfg["expert_offset"] if offset is None else offset
    chosen, w = route(p, pre, cfg, x, bias)
    width = cfg["moe_intermediate_size"]
    out = jnp.zeros_like(x)
    for e in range(held):
        weight = jnp.sum(jnp.where(chosen == offset + e, w, 0.0), -1)
        h = x @ p[pre + "w_in"][e]
        out = out + weight[:, None] * (
            (jax.nn.silu(h[:, :width]) * h[:, width:]) @ p[pre + "w_out"][e])
    return out


def hidden_states(p, cfg, ids, buffers=None):
    """ids [2 L] -> final-norm hidden states of the NOISY half [L,
    hidden]."""
    eps, length = cfg["rms_norm_eps"], ids.shape[0] // 2
    pos = positions(length)
    x = p["embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layers.{i}."

        @jax.checkpoint
        def layer(x, pre=pre):
            x = x + attention(p, pre + "self_attn.", cfg, _rms(
                x, p[pre + "input_layernorm.weight"], eps), pos)
            bias = (buffers or {}).get(
                pre + "mlp.e_score_correction_bias", 0.0)
            return x + routed_experts(p, pre + "mlp.", cfg, _rms(
                x, p[pre + "post_attention_layernorm.weight"], eps), bias)

        x = layer(x)
    return _rms(x[:length], p["norm.weight"], eps)


def logits(params, cfg, ids, buffers=None):
    """ids [B, 2 L] -> logits of the noisy positions [B, L, vocabulary
    held]."""
    with jax.default_matmul_precision(PRECISION):
        p = _cast(params)
        return jnp.stack([hidden_states(p, cfg, row, buffers)
                          @ p["lm_head.weight"] for row in ids])


def loss(params, cfg, ids, labels, t, buffers=None):
    """``(1 / (B L)) sum over masked i of CE(logits_i, labels_i) /
    t_i``: ``ids`` [B, 2 L] the noised then the clean copy, ``labels``
    [B, L] the clean tokens, ``t`` [B, L] each position's noise level;
    a position is masked where its noised id is ``mask_token_id``."""
    length = labels.shape[1]
    logp = jax.nn.log_softmax(logits(params, cfg, ids, buffers), axis=-1)
    nll = -jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    masked = ids[:, :length] == cfg["mask_token_id"]
    weight = jnp.where(masked, 1.0 / t.astype(nll.dtype), 0.0)
    return jnp.sum(weight * nll) / labels.size
