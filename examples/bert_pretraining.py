"""BERT pretraining — the flagship workload (BASELINE config 3).

Shows the full masked-LM data pipeline the way the reference trains
BERT (mask 15% of tokens, gather only those positions through the
vocab head — ref: bert_dygraph_model.py:327 mask_pos gather) and the
two ways to run the step:

- single device: ``static.TrainStep`` (donated-state XLA program)
- a mesh: ``parallel.ShardedTrainStep`` (same call, batch sharded over
  dp, megatron rules optional for mp)

On a v5e this is the model the ``bert_base_s512`` cells of
``benchmarks/run.py`` time; on CPU it runs a tiny config for the smoke
test. bf16 parameters with fp32
LN/softmax/loss reductions, per-leaf AdamW.
"""

from __future__ import annotations

import numpy as np


def make_mlm_batch(rng, batch: int, seq: int, vocab: int,
                   mask_rate: float = 0.15, mask_id: int = 103):
    """Synthetic masked-LM batch in the reference's layout: input ids
    with [MASK] substitutions, positions of the masked tokens, and the
    ORIGINAL token ids at those positions as labels (gathered — the
    head only projects these)."""
    n_masked = max(1, int(seq * mask_rate) // 8 * 8)  # MXU-friendly
    ids = rng.integers(200, vocab, (batch, seq)).astype(np.int32)
    pos = np.sort(rng.permuted(
        np.broadcast_to(np.arange(seq), (batch, seq)), axis=1)
        [:, :n_masked], axis=1).astype(np.int32)
    labels = np.take_along_axis(ids, pos, axis=1).astype(np.int64)
    masked_ids = ids.copy()
    np.put_along_axis(masked_ids, pos, mask_id, axis=1)
    nsp = rng.integers(0, 2, (batch,)).astype(np.int64)
    return masked_ids, pos, labels, nsp


def main(steps: int = 10, batch: int = 4, seq: int = 64,
         sharded: bool = False, verbose: bool = True):
    import paddle_tpu as pt
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   pretraining_loss)

    import jax
    on_accel = jax.default_backend() not in ("cpu",)
    config = BertConfig() if on_accel else BertConfig(
        num_hidden_layers=2, hidden_size=64, num_attention_heads=2,
        intermediate_size=128, vocab_size=1024,
        max_position_embeddings=seq)

    pt.seed(0)
    model = BertForPretraining(config)
    if on_accel:
        model.to(dtype="bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01)
    loss_fn = pretraining_loss

    if sharded:
        from paddle_tpu.parallel import (ShardedTrainStep,
                                         data_parallel_mesh)
        step = ShardedTrainStep(model, opt, loss_fn,
                                mesh=data_parallel_mesh())
    else:
        from paddle_tpu.static import TrainStep
        step = TrainStep(model, opt, loss_fn)

    rng = np.random.default_rng(0)
    ids, pos, labels, nsp = make_mlm_batch(
        rng, batch, seq, config.vocab_size)
    losses = []
    for i in range(steps):
        m = step(ids, labels=(labels, nsp), masked_positions=pos)
        losses.append(float(m["loss"]))
        if verbose and (i % 5 == 0 or i == steps - 1):
            print(f"step {i}: loss {losses[-1]:.4f}")
    return {"first_loss": losses[0], "last_loss": losses[-1]}


if __name__ == "__main__":
    main()
