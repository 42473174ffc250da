"""Metric arithmetic kept with the benchmark: the spread the bounds
are set from, the peaks table, and model FLOPs per
token computed from a configuration's shapes (never from the compiler's
``cost_analysis``, which counts recomputation)."""

from __future__ import annotations

import json
import os
import statistics
from typing import Dict, Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(n=4)`` — the
    spread the builder's contract sets bounds from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmarks/peaks.json (known: "
            f"{sorted(k for k in table if not k.startswith('_'))})")
    return table[device_kind]


def bert_flops_per_token(cfg: dict, seq: int, predicted: int) -> float:
    """Model FLOPs the forward and backward passes need per trained
    token of BERT pretraining, from shapes alone.

    Matrix-multiply parameters times 6 (2 forward, 4 backward). The MLM
    transform and the tied decoder run on ``predicted`` of ``seq``
    positions, the pooler and the NSP head on one; the embedding
    look-ups are gathers and count nothing. Attention adds
    ``12 * layers * seq * hidden`` per token (QK^T and PV, 2 FLOPs a
    multiply-add, forward plus twice that backward)."""
    h = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    per_layer = 4 * h * h + 2 * h * cfg["intermediate_size"]
    head = (h * h + h * cfg["vocab_size"]) * predicted / seq
    once = (h * h + 2 * h) / seq
    matmul_params = layers * per_layer + head + once
    return 6.0 * matmul_params + 12.0 * layers * seq * h


def mfu(tokens_per_s: float, flops_per_token: float, chips: int,
        device_kind: str) -> float:
    """Share of ``chips`` times the chip's bf16 peak that is model
    FLOPs, as a fraction."""
    peak = peaks_for(device_kind)["bf16_flops_per_s"]
    return tokens_per_s * flops_per_token / (chips * peak)
