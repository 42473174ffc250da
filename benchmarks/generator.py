"""The one general traffic generator. A traffic mix is a data file,
``benchmarks/traffic/<mix>.json``; everything here is drawn from
``--seed`` through ``numpy`` (any whole number is accepted and folded),
so the same seed gives the same inputs and the program under test sees
only the generated arrays.

Every seed gets the same work: training batches all have one shape,
and only their contents differ."""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def seed_sequence(seed: int, stream: str) -> np.random.SeedSequence:
    """A named, independent stream of the run's seed. ``seed`` may be
    any whole number (the driver's exceed 32 bits); it is folded to 64
    bits, and the stream's name keeps data, weights and dropout apart."""
    folded = int(seed) % (1 << 64)
    return np.random.SeedSequence(
        [folded & 0xFFFFFFFF, folded >> 32,
         *[ord(c) for c in stream]])


def small_seed(seed: int, stream: str) -> int:
    """A 31-bit seed for program entry points that take an int32."""
    return int(seed_sequence(seed, stream).generate_state(1)[0] >> 1)


def _rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(seed_sequence(seed, stream))


# -- training ---------------------------------------------------------------

def pretraining_batches(mix: Dict, vocab_size: int, batch: int,
                        seed: int) -> List[Tuple[np.ndarray, ...]]:
    """``pool_batches`` distinct host batches of BERT pretraining
    input: ``(input_ids [B, S] int32, masked_positions [B, P] int32,
    mlm_labels [B, P] int64, nsp_labels [B] int64)``.

    Token ids follow a Zipf law over a seeded ranking of the vocabulary
    (text does; it also gives the loss something to learn that no
    memorised batch is needed for: the unigram frequencies). ``P``
    sorted distinct positions per row are predicted; the label is the
    original token and the input there is the mask id, a random token
    or the original, in the published 80/10/10 split."""
    rng = _rng(seed, "pretraining_batches")
    seq, pred = int(mix["seq"]), int(mix["predicted"])
    n = int(mix["pool_batches"])
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(mix["zipf_s"]))
    cdf /= cdf[-1]
    ranking = rng.permutation(vocab_size)

    def tokens(shape):
        return ranking[np.searchsorted(cdf, rng.random(shape))]

    out = []
    for _ in range(n):
        ids = tokens((batch, seq)).astype(np.int32)
        pos = np.sort(np.argsort(rng.random((batch, seq)),
                                 axis=1)[:, :pred], axis=1)
        labels = np.take_along_axis(ids, pos, axis=1).astype(np.int64)
        how = rng.random((batch, pred))
        repl = np.where(how < mix["p_mask"], mix["mask_id"],
                        np.where(how < mix["p_mask"] + mix["p_random"],
                                 tokens((batch, pred)), labels))
        np.put_along_axis(ids, pos, repl.astype(np.int32), axis=1)
        nsp = rng.integers(0, 2, (batch,)).astype(np.int64)
        out.append((ids, pos.astype(np.int32), labels, nsp))
    return out
