"""PowerInfer SmallThinker-21BA3B (``model_type: smallthinker``), plain:
forward, next-token loss and, through ``jax.grad``, gradients, in float32
at ``highest`` matmul precision. No kernel, and no import from the
package under test.

Every layer, ``h`` its input and every norm an RMSNorm:

- ``x = norm_in(h)``; the router: ``s = softmax(x W_r)`` over all
  ``moe_num_primary_experts_total`` experts, the 6 largest of ``s + b``
  chosen (``b`` the selection bias, zero unless given), ``w = s[chosen]
  / sum s[chosen]``;
- attention: 28 query heads on 4 KV heads of 128 (query head ``i`` reads
  KV head ``i // 7``), no bias. A layer marked 1 in
  ``sliding_window_layout`` lets query ``t`` see the keys ``t - W < s <=
  t`` (``W = sliding_window_size``), one marked 0 every ``s <= t``; a
  layer marked 1 in ``rope_layout`` turns q and k by rotary positions
  (rotate-half over the whole head, ``rope_theta``), one marked 0 has no
  positional term. The masks are built from iotas; ``h' = h + o W_o``;
- experts on ``y = norm_post(h')``: ``h'' = h' + sum_e w_e W_down,e
  (relu(W_gate,e y) * W_up,e y)``, a loop over the experts HELD
  (``moe_num_primary_experts`` from ``expert_offset``: a pair on an
  absent expert adds nothing); ``w_in`` holds ``[W_gate | W_up]``;
- final norm, logits over the vocabulary rows held, the mean
  cross-entropy of the token after each position.

``loss_and_routing`` may be handed the experts another program chose,
layer by layer: the reference then sends every token to those experts
(their weights its own float32 scores, renormalised) and counts, a layer
at a time, the pairs its own top-k would not have chosen and the largest
distance by which such a pair falls short of its own k-th score. So a
choice that bfloat16 and float32 make otherwise at a near tie moves no
gradient, and the count and the distance say how near the tie was.

So that 16,384 positions fit a chip, attention runs a query head and
``QUERY_BLOCK`` query rows at a time and the loss ``LOSS_BLOCK`` tokens
at a time, each recomputed in the backward pass (``jax.checkpoint``), as
each layer is, and a layer's parameters are made float32 inside it.
Consecutive layers of one kind (window or global) run as one
``lax.scan`` over their stacked parameters: one function a kind to
compile, whatever the depth. The arithmetic is unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# What the reference computes in. ``benchmarks/smallthinker_control_
# drill.py`` loads a second copy of this module with bfloat16 and
# "default" (parameters, products and the loss one precision down).
PRECISION = "highest"
DTYPE = jnp.float32
# what the mean cross-entropy is taken in (``DTYPE`` where None)
LOSS_DTYPE = None
QUERY_BLOCK = 2048
LOSS_BLOCK = 2048

_LAYER_KEYS = ("input_layernorm.weight", "self_attn.q_proj.weight",
               "self_attn.k_proj.weight", "self_attn.v_proj.weight",
               "self_attn.o_proj.weight", "post_attention_layernorm.weight",
               "mlp.router_weight", "mlp.w_in", "mlp.w_out")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [T, H, D] at positions ``arange(T)``: rotate-half, whole head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :].astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :].astype(x.dtype)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def attention(p, cfg, x, window, theta):
    """x [T, hidden] -> [T, hidden]; ``window`` None for a global layer,
    ``theta`` None for no positional term."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, total = cfg["head_dim"], x.shape[0]
    q = (x @ p["self_attn.q_proj.weight"]).reshape(total, heads, d)
    k = (x @ p["self_attn.k_proj.weight"]).reshape(total, kv, d)
    v = (x @ p["self_attn.v_proj.weight"]).reshape(total, kv, d)
    if theta is not None:
        q, k = rope(q, theta), rope(k, theta)
    rows = QUERY_BLOCK if total % QUERY_BLOCK == 0 else total
    keys = jnp.arange(total)[None, :]

    @jax.checkpoint
    def some_rows(args):
        q_b, first, k_h, v_h = args
        pos = first + jnp.arange(rows)[:, None]
        mask = keys <= pos
        if window is not None:
            mask = mask & (keys > pos - window)
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
        @ p["self_attn.o_proj.weight"]


def route(p, cfg, x, bias=0.0, chosen=None):
    """(chosen [T, k], weights [T, k]) over every expert scored, and how
    far ``chosen``, where given, lies from the reference's own top-k:
    (pairs outside it, the largest shortfall of a chosen score below the
    k-th, in units of the score)."""
    logits = x @ p["mlp.router_weight"]
    s = jax.nn.softmax(logits, axis=-1) \
        if cfg["moe_primary_router_apply_softmax"] else jax.nn.sigmoid(logits)
    top, own = jax.lax.top_k(s + bias,
                             cfg["moe_num_active_primary_experts"])
    if chosen is None:
        chosen, otherwise, short = own, jnp.zeros((), jnp.int32), \
            jnp.zeros((), jnp.float32)
    else:
        picked = jax.lax.stop_gradient(
            jnp.take_along_axis(s + bias, chosen, axis=-1))
        below = jax.lax.stop_gradient(top[:, -1:]) - picked
        otherwise = jnp.sum(below > 0).astype(jnp.int32)
        short = jnp.max(jnp.maximum(below, 0.0)).astype(jnp.float32)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w, (otherwise, short)


def routed_experts(p, cfg, y, x, held=None, offset=None, bias=0.0,
                   chosen=None, routing=None):
    """The part of the result that the experts ``[offset, offset +
    held)`` give (the configuration's by default) to the tokens ``y``,
    routed on ``x`` (to ``chosen`` where given; ``route``'s count and
    shortfall and the experts chosen go to the list ``routing``); ``w_in``
    / ``w_out`` hold those experts, first to last."""
    held = cfg["moe_num_primary_experts"] if held is None else held
    offset = cfg["expert_offset"] if offset is None else offset
    chosen, w, far = route(p, cfg, x, bias, chosen)
    if routing is not None:
        routing.append(far + (chosen,))
    width = cfg["moe_ffn_hidden_size"]
    out = jnp.zeros_like(y)
    for e in range(held):
        weight = jnp.sum(jnp.where(chosen == offset + e, w, 0.0), -1)
        h = y @ p["mlp.w_in"][e]
        out = out + weight[:, None] * (
            (jax.nn.relu(h[:, :width]) * h[:, width:]) @ p["mlp.w_out"][e])
    return out


def layer(p, x, cfg, window, theta, chosen=None):
    """One layer: ``p`` its parameters (and its selection bias, float32)
    by their names inside it; ``chosen`` [T, k] the experts to send the
    tokens to, or None for its own choice. Returns the layer's output and
    (pairs outside its own top-k, the largest shortfall, the experts
    chosen)."""
    bias = p["mlp.e_score_correction_bias"]
    p = {k: v.astype(DTYPE) for k, v in p.items()}
    eps = cfg["rms_norm_eps"]
    h = _rms(x, p["input_layernorm.weight"], eps)
    x = x + attention(p, cfg, h, window, theta)
    far = []
    x = x + routed_experts(
        p, cfg, _rms(x, p["post_attention_layernorm.weight"], eps), h,
        bias=bias, chosen=chosen, routing=far)
    return x, far[0]


def hidden_states(params, cfg, ids, buffers=None, chosen=None):
    """ids [L] -> final-norm hidden states [L, hidden], and the routing's
    distance from the reference's own and the experts chosen, a layer
    each (``layer``); ``chosen`` [layers, L, k] or None."""
    scored = cfg["moe_num_primary_experts_total"]
    params = {**{f"layers.{n}.mlp.e_score_correction_bias":
                 jnp.zeros((scored,), jnp.float32)
                 for n in range(cfg["num_hidden_layers"])},
              **(buffers or {}), **params}
    x = params["embed_tokens.weight"][ids].astype(DTYPE)
    kinds = list(zip(cfg["sliding_window_layout"], cfg["rope_layout"]))
    far = []
    i = 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        body = jax.checkpoint(functools.partial(
            layer, cfg=cfg,
            window=cfg["sliding_window_size"] if kinds[i][0] else None,
            theta=cfg["rope_theta"] if kinds[i][1] else None))
        stacked = {k: jnp.stack([params[f"layers.{n}.{k}"]
                                 for n in range(i, j)])
                   for k in _LAYER_KEYS + ("mlp.e_score_correction_bias",)}
        if chosen is None:
            x, f = jax.lax.scan(lambda x, p: body(p, x), x, stacked)
        else:
            x, f = jax.lax.scan(lambda x, pc: body(pc[0], x, chosen=pc[1]),
                                x, (stacked, chosen[i:j]))
        far.append(f)
        i = j
    far = tuple(jnp.concatenate(parts) for parts in zip(*far))
    return _rms(x, params["norm.weight"].astype(DTYPE),
                cfg["rms_norm_eps"]), far


def loss_and_routing(params, cfg, ids, labels, buffers=None, chosen=None):
    """Mean cross-entropy of ``labels`` [B, L] (the token after each
    position) over the vocabulary rows held, and the routing: the pairs
    outside the reference's own top-k [layers] and the largest shortfall
    [layers], over the sequences, and the experts chosen [layers, B, L,
    k]. ``buffers`` hold the routers' selection biases (zero where not
    given); ``chosen`` [layers, B, L, k], where given, the experts every
    token is sent to."""
    with jax.default_matmul_precision(PRECISION):
        head = params["lm_head.weight"]
        exact = LOSS_DTYPE or DTYPE
        total = jnp.zeros((), exact)
        layers = cfg["num_hidden_layers"]
        otherwise = jnp.zeros((layers,), jnp.int32)
        short = jnp.zeros((layers,), jnp.float32)
        used = []
        for b, (row, label) in enumerate(zip(ids, labels)):
            h, (n, gap, c) = hidden_states(
                params, cfg, row, buffers,
                None if chosen is None else chosen[:, b])
            otherwise, short = otherwise + n, jnp.maximum(short, gap)
            used.append(c)
            rows = LOSS_BLOCK if h.shape[0] % LOSS_BLOCK == 0 \
                else h.shape[0]

            @jax.checkpoint
            def block_nll(args):
                h_b, y_b = args
                logp = jax.nn.log_softmax(
                    (h_b @ head.astype(DTYPE)).astype(exact), -1)
                return -jnp.sum(jnp.take_along_axis(
                    logp, y_b[:, None].astype(jnp.int32), -1))

            total = total + jnp.sum(jax.lax.map(block_nll, (
                h.reshape(-1, rows, h.shape[-1]), label.reshape(-1, rows))))
        return total / labels.size, (otherwise, short,
                                     jnp.stack(used, axis=1))


def loss(params, cfg, ids, labels, buffers=None, chosen=None):
    """``loss_and_routing``'s loss alone."""
    return loss_and_routing(params, cfg, ids, labels, buffers, chosen)[0]
