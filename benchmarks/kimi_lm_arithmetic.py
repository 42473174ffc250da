"""Model FLOPs of a Kimi Linear decoder (``model_type: kimi_linear``:
KDA and latent attention, a dense first layer, routed experts with a
shared expert) from its shapes alone: what the forward and backward
passes need, never what the compiler counts (recomputation is not model
work)."""

from __future__ import annotations

from typing import Dict


def kda_rule_flops_per_chunk(chunk: int, dk: int, dv: int) -> float:
    """The chunked gated delta rule's products for one chunk of
    ``chunk`` positions and one head, forward, 2 FLOPs a multiply-add,
    whole ``chunk x chunk`` blocks counted (the form computes them whole
    and masks; ``nn.layers.kda``):

    - ``A`` (key by key) and ``M`` (query by key) under the decay:
      ``2 C^2 K`` each;
    - the unit-triangular solve for ``W`` and ``U``: ``C^2 (K + V)``;
    - ``delta = U - W S``, the state's update ``(k exp(g_C - g))^T
      delta`` and the read-out ``Q~ S``: ``2 C K V`` each;
    - ``M delta``: ``2 C^2 V``."""
    c = chunk
    return 4.0 * c * c * dk + c * c * (dk + dv) + 6.0 * c * dk * dv \
        + 2.0 * c * c * dv


def mla_attention_flops_forward(cfg: Dict, batch: int, seq: int) -> float:
    """QK^T at the q/k width and PV at the value width over the causal
    pairs of one MLA layer: ``2 B H (L (L + 1) / 2) (d_qk + d_v)``, what
    the flash forward call site notes by its own count (half the square)
    to a rounding."""
    d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * batch * cfg["num_attention_heads"] * seq * (seq + 1) / 2 \
        * (d_qk + cfg["v_head_dim"])


def kimi_flops_per_step(cfg: Dict, batch: int, seq: int,
                        pairs_held: float) -> float:
    """FLOPs one training step of ``batch`` sequences of ``seq`` tokens
    needs, forward plus backward (three times the forward's), 2 FLOPs a
    multiply-add. Per token:

    - a KDA layer: q, k, v, o ``hidden x heads head_dim`` each, the
      low-rank f and g pairs ``(hidden + heads head_dim) x head_dim``
      each, beta ``hidden x heads``; and its chunked rule
      (``kda_rule_flops_per_chunk``) for every chunk and head;
    - an MLA layer: q ``hidden x heads (nope + rope)``, the latent
      ``hidden x (rank + rope)`` and ``rank x heads (nope + v)``, o
      ``heads v x hidden``; and attention over the causal pairs
      (``mla_attention_flops_forward``);
    - a dense layer's SwiGLU ``3 hidden x intermediate``; an expert
      layer's router ``hidden x experts scored`` and shared expert ``3
      hidden x shared width``, and the routed experts for
      ``pairs_held`` (token, choice) pairs A STEP over all the expert
      layers, only those that fell on experts held here, as counted by
      the step itself, at ``3 hidden x expert width`` each;
    - the head ``hidden x vocabulary rows held``; the embedding is a
      gather and counts nothing."""
    h, tokens = cfg["hidden_size"], batch * seq
    lac = cfg["linear_attn_config"]
    heads, d = lac["num_heads"], lac["head_dim"]
    width = heads * d
    kda = 4 * h * width + 2 * (h + width) * d + h * heads
    mh = cfg["num_attention_heads"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    mla = h * mh * (nope + rope) + h * (rank + rope) \
        + rank * mh * (nope + dv) + mh * dv * h
    layers = range(1, cfg["num_hidden_layers"] + 1)
    n_kda = sum(i in lac["kda_layers"] for i in layers)
    n_mla = sum(i in lac["full_attn_layers"] for i in layers)
    dense = cfg["first_k_dense_replace"]
    scored = cfg.get("num_experts_total") or cfg["num_experts"]
    expert = h * scored + 3 * h * cfg["moe_intermediate_size"] \
        * cfg["num_shared_experts"]
    per_token = n_kda * kda + n_mla * mla \
        + dense * 3 * h * cfg["intermediate_size"] \
        + (cfg["num_hidden_layers"] - dense) * expert + h * cfg["vocab_size"]
    chunk = cfg.get("chunk_size", 64)
    chunks = batch * -(-seq // chunk) * heads
    forward = 2.0 * (tokens * per_token + pairs_held * 3 * h
                     * cfg["moe_intermediate_size"]) \
        + n_kda * chunks * kda_rule_flops_per_chunk(chunk, d, d) \
        + n_mla * mla_attention_flops_forward(cfg, batch, seq)
    return 3.0 * forward
