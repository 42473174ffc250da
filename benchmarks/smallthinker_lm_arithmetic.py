"""Model FLOPs of a SmallThinker decoder (``model_type: smallthinker``:
grouped-query attention, windowed or global, and softmax-routed ReGLU
experts) from its shapes alone: what the forward and backward passes
need, never what the compiler counts (recomputation is not model work).

At the cell's cut (hidden 2560, 28 query heads on 4 KV heads of 128,
16,384 positions, windows of 4096 on three layers of four, 16 of 64
experts of 768 held, 6 a token, 37,984 vocabulary rows), per token and
forward, at balanced routing: attention products 271.6 MFLOP (the global
layer 117.4, the three window layers 154.2), attention projections
167.8, the held experts 70.8, the routers 1.3, the head 194.5: 705.9
MFLOP, 34.7 TFLOP a step of 16,384 tokens forward and backward."""

from __future__ import annotations

from typing import Dict


def window_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal sliding window of ``window`` keys
    allows a sequence of ``seq``: ``W (W + 1) / 2 + (L - W) W`` for ``L
    >= W``, the causal ``L (L + 1) / 2`` below it."""
    if seq <= window:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops_forward(cfg: Dict, batch: int, seq: int) -> float:
    """QK^T and PV over the allowed pairs of every layer: ``4 B H D``
    times the causal pairs on a global layer and the window's on a
    window layer, what the flash forward call sites note."""
    per_pair = 4.0 * batch * cfg["num_attention_heads"] * cfg["head_dim"]
    pairs = sum(window_pairs(seq, cfg["sliding_window_size"]) if w
                else seq * (seq + 1) // 2
                for w in cfg["sliding_window_layout"])
    return per_pair * pairs


def smallthinker_flops_per_step(cfg: Dict, batch: int, seq: int,
                                pairs_held: float) -> float:
    """FLOPs one training step of ``batch`` sequences of ``seq`` tokens
    needs, forward plus backward (three times the forward's), 2 FLOPs a
    multiply-add. Per token and layer: q, k, v and o ``hidden x heads
    head_dim`` and ``hidden x kv_heads head_dim``; the router ``hidden x
    experts scored``; the held experts for ``pairs_held`` (token,
    choice) pairs A STEP over all the layers, only those that fell on
    experts held here, as counted by the step itself, at ``3 hidden x
    expert width`` each; the head ``hidden x vocabulary rows held`` (the
    embedding is a gather and counts nothing); and attention over its
    allowed pairs (``attention_flops_forward``)."""
    h, tokens = cfg["hidden_size"], batch * seq
    d = cfg["head_dim"]
    proj = 2 * h * cfg["num_attention_heads"] * d \
        + 2 * h * cfg["num_key_value_heads"] * d
    scored = cfg.get("moe_num_primary_experts_total") \
        or cfg["moe_num_primary_experts"]
    per_token = cfg["num_hidden_layers"] * (proj + h * scored) \
        + h * cfg["vocab_size"]
    forward = 2.0 * (tokens * per_token + pairs_held * 3 * h
                     * cfg["moe_ffn_hidden_size"]) \
        + attention_flops_forward(cfg, batch, seq)
    return 3.0 * forward
