"""Model FLOPs of a hybrid Mamba-2 / routed-experts / attention decoder
(``model_type: nemotron_h``) from its shapes alone: what the forward and
backward passes need, never what the compiler counts (recomputation is
not model work)."""

from __future__ import annotations

from typing import Dict


def nemotron_h_flops_per_step(cfg: Dict, batch: int, seq: int,
                              pairs_held: float) -> float:
    """FLOPs one training step of ``batch`` sequences of ``seq`` tokens
    needs, forward plus backward (three times the forward's matmuls).

    Per token and layer kind, 2 FLOPs a multiply-add:

    - ``M``: in-projection ``hidden x (2 inner + 2 groups state +
      heads)``, out-projection ``inner x hidden``; the chunked scan's
      four matmul families at chunk length ``Q``: ``C B^T`` (groups x Q
      x state), its product with ``x`` (heads x Q x head_dim), the
      chunk states and their read-out (heads x head_dim x state each).
      Full ``Q x Q`` blocks are counted: the kernel-free form computes
      them whole and masks.
    - ``E``: the router ``hidden x experts scored``, the shared expert
      ``2 hidden x shared width``, and the routed experts for
      ``pairs_held`` (token, choice) pairs A STEP over all ``E`` layers
      — only the pairs that fell on experts held here, as counted by
      the step itself — at ``2 hidden x expert width`` each.
    - ``*``: q, k, v, o projections, and causal attention ``2 x heads x
      head_dim x seq / 2`` a token for each of QK^T and PV.
    - the head ``hidden x vocabulary rows held``; the embedding is a
      gather and counts nothing."""
    h = cfg["hidden_size"]
    pattern = cfg["hybrid_override_pattern"]
    tokens = batch * seq
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    inner = heads * hd
    mamba = h * (2 * inner + 2 * g * n + heads) + inner * h \
        + g * q * n + heads * q * hd + 2 * heads * hd * n
    scored = cfg.get("n_routed_experts_total") or cfg["n_routed_experts"]
    experts = h * scored + 2 * h * cfg["moe_shared_expert_intermediate_size"]
    qh, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = h * d * (2 * qh + 2 * kv) + 2 * qh * d * seq / 2
    per_token = pattern.count("M") * mamba + pattern.count("E") * experts \
        + pattern.count("*") * attn + h * cfg["vocab_size"]
    routed = pairs_held * 2 * h * cfg["moe_intermediate_size"]
    return 3.0 * 2.0 * (tokens * per_token + routed)
