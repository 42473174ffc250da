"""Block-diffusion training batches, drawn like ``generator.py``'s: from
``--seed`` through ``numpy``, every batch of one shape. The noise is
drawn here, once, on the host: the system and the plain reference are
handed the same noised ids, clean ids and noise levels."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from .generator import seed_sequence


def block_diffusion_batches(mix: Dict, vocab_size: int, mask_id: int,
                            block: int, batch: int, seed: int
                            ) -> List[Tuple[np.ndarray, ...]]:
    """``pool_batches`` distinct host batches ``(input_ids [B, 2 L]
    int32, labels [B, L] int32, t [B, L] float32)``.

    ``labels`` are the clean tokens ``x0``: ids that follow a Zipf law
    over a seeded ranking of the rows the configuration holds, the
    ``mask_id`` row left out (the traffic never draws it as a token).
    For every block of ``block`` tokens ``t_b ~ U(t_min, 1]``; a token
    is masked with its block's probability, ``xt_i = mask_id if m_i
    else x0_i``; ``input_ids = [xt ; x0]``, the noised copy then the
    clean one; ``t`` gives each position its block's level (the loss
    weighs a masked position by ``1 / t``)."""
    rng = np.random.default_rng(
        seed_sequence(seed, "block_diffusion_batches"))
    seq = int(mix["seq_len"])
    if seq % block:
        raise ValueError(f"blocks of {block} do not divide seq_len {seq}")
    rows_held = np.array([r for r in range(vocab_size) if r != mask_id])
    ranks = np.arange(1, len(rows_held) + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(mix["zipf_s"]))
    cdf /= cdf[-1]
    ranking = rows_held[rng.permutation(len(rows_held))]
    t_min = float(mix["t_min"])
    out = []
    for _ in range(int(mix["pool_batches"])):
        x0 = ranking[np.searchsorted(cdf, rng.random((batch, seq)))]
        x0 = x0.astype(np.int32)
        # U(t_min, 1]: 1 - U[0, 1 - t_min)
        t_block = 1.0 - rng.random((batch, seq // block)) * (1.0 - t_min)
        t = np.repeat(t_block, block, axis=1).astype(np.float32)
        masked = rng.random((batch, seq)) < t
        xt = np.where(masked, np.int32(mask_id), x0)
        out.append((np.ascontiguousarray(np.concatenate([xt, x0], axis=1)),
                    x0, t))
    return out
