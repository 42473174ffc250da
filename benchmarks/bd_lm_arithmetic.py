"""Model FLOPs of a routed-experts decoder trained by diffusion over
blocks (``model_type: sdar_moe``) from its shapes alone: what the
forward and backward passes need, never what the compiler counts
(recomputation is not model work) nor what a kernel visits (a tile's
masked pairs are not model work)."""

from __future__ import annotations

from typing import Dict


def allowed_pairs(seq: int, block: int) -> int:
    """(query, key) pairs the block-diffusion rule allows in one
    sequence of ``seq`` tokens run as ``2 seq`` positions, in closed
    form: noisy to noisy ``K L``, noisy to clean ``L (L - K) / 2``,
    clean to clean ``L (L + K) / 2``: ``L^2 + K L`` of the ``4 L^2`` in
    the rectangle."""
    return seq * seq + block * seq


def attention_flops_forward(cfg: Dict, batch: int, seq: int) -> float:
    """QK^T and PV of one layer over the allowed pairs: ``4 B H D (L^2 +
    K L)``, what every ``bd_flash_fwd`` call site notes."""
    return 4.0 * batch * cfg["num_attention_heads"] * cfg["head_dim"] \
        * allowed_pairs(seq, cfg["block_length"])


def sdar_flops_per_step(cfg: Dict, batch: int, seq: int,
                        pairs_held: float) -> float:
    """FLOPs one training step of ``batch`` sequences of ``seq`` tokens
    needs, forward plus backward (three times the forward's matmuls), 2
    FLOPs a multiply-add.

    - every one of the ``2 seq`` positions of a sequence, in every
      layer: the q, k, v, o projections ``hidden x head_dim x (2 heads +
      2 KV heads)`` and the router ``hidden x experts scored``;
    - attention over the allowed pairs (``attention_flops_forward``);
    - the routed experts for ``pairs_held`` (position, choice) pairs A
      STEP over all layers, only those that fell on experts held here,
      as counted by the step itself, at ``3 hidden x expert width`` each
      (gate, up and down);
    - the head over the noisy half, ``hidden x vocabulary rows held`` a
      token; the embedding is a gather and counts nothing."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    positions = 2 * batch * seq
    d = cfg["head_dim"]
    scored = cfg.get("num_experts_total") or cfg["num_experts"]
    per_position = h * d * (2 * cfg["num_attention_heads"]
                            + 2 * cfg["num_key_value_heads"]) + h * scored
    matmuls = 2.0 * (layers * positions * per_position
                     + pairs_held * 3 * h * cfg["moe_intermediate_size"]
                     + batch * seq * h * cfg["vocab_size"])
    return 3.0 * (matmuls + layers * attention_flops_forward(cfg, batch,
                                                             seq))
