"""Next-token training batches, drawn like ``generator.py``'s: from
``--seed`` through ``numpy``, every batch of one shape."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from .generator import seed_sequence


def next_token_batches(mix: Dict, vocab_size: int, batch: int, seed: int
                       ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``pool_batches`` distinct host batches ``(input_ids [B, S] int32,
    labels [B, S] int32)``: ``S + 1`` tokens a row, the label of a
    position the token that follows it, so every position trains.

    Token ids follow a Zipf law over a seeded ranking of the
    ``vocab_size`` rows the configuration holds (text does; the unigram
    frequencies are what a model learns first, so the loss falls within
    a window without a batch being seen twice)."""
    rng = np.random.default_rng(seed_sequence(seed, "next_token_batches"))
    seq = int(mix["seq_len"])
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(mix["zipf_s"]))
    cdf /= cdf[-1]
    ranking = rng.permutation(vocab_size)
    out = []
    for _ in range(int(mix["pool_batches"])):
        rows = ranking[np.searchsorted(cdf, rng.random((batch, seq + 1)))]
        rows = rows.astype(np.int32)
        out.append((np.ascontiguousarray(rows[:, :-1]),
                    np.ascontiguousarray(rows[:, 1:])))
    return out
