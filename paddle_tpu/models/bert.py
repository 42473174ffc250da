"""BERT (base/large) encoder for pretraining.

Performance target model (BASELINE.json config 3: BERT-base pretraining,
fused attention + layer_norm + adam). Capability parity with the
reference's ERNIE/BERT path (its transformer ops: multihead_matmul fused
attention, fused_embedding_eltwise_layernorm — here the Pallas flash
attention + layer_norm kernels route in via nn.MultiHeadAttention/
nn.LayerNorm). bf16-friendly: keep LN/softmax fp32 via amp black list.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import nn


class MLMHeadOutput(NamedTuple):
    """Loss-region handoff for the fused MLM head
    (FLAGS_fused_softmax_xent): the transformed hidden states plus the
    tied decoder weight/bias instead of the materialized [B, P, V]
    logits — pretraining_loss feeds them to the fused projection+xent
    kernel so the logits never exist in HBM. A NamedTuple so it flows
    through functional_call/jit as a pytree."""
    hidden: jnp.ndarray
    weight: jnp.ndarray
    bias: jnp.ndarray


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2


def bert_base_config() -> BertConfig:
    return BertConfig()


def bert_large_config() -> BertConfig:
    return BertConfig(hidden_size=1024, num_hidden_layers=24,
                      num_attention_heads=16, intermediate_size=4096)


class BertEmbeddings(nn.Layer):
    """(capability ref: fused_embedding_eltwise_layernorm_op.cu — word +
    position + type embeddings + LN fused; XLA fuses the adds/LN here)."""

    def __init__(self, config: BertConfig) -> None:
        super().__init__()
        self.word_embeddings = nn.Embedding(config.vocab_size,
                                            config.hidden_size)
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size)
        self.token_type_embeddings = nn.Embedding(config.type_vocab_size,
                                                  config.hidden_size)
        self.layer_norm = nn.LayerNorm(config.hidden_size, epsilon=1e-12)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    # device scope (docs/observability.md): a profile reads the
    # look-ups, the embedding norm and their backward as one block
    @jax.named_scope("pt.embed")
    def forward(self, input_ids, token_type_ids=None):
        seq = input_ids.shape[1]
        pos_ids = jnp.arange(seq, dtype=jnp.int32)[None, :]
        emb = self.word_embeddings(input_ids) \
            + self.position_embeddings(pos_ids)
        if token_type_ids is not None:
            emb = emb + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(emb))


class BertEncoderLayer(nn.TransformerEncoderLayer):
    def __init__(self, config: BertConfig) -> None:
        super().__init__(
            d_model=config.hidden_size,
            nhead=config.num_attention_heads,
            dim_feedforward=config.intermediate_size,
            dropout=config.hidden_dropout_prob,
            activation=config.hidden_act,
            attn_dropout=config.attention_probs_dropout_prob,
            normalize_before=False)


class BertModel(nn.Layer):
    def __init__(self, config: Optional[BertConfig] = None) -> None:
        super().__init__()
        self.config = config = config or BertConfig()
        self.embeddings = BertEmbeddings(config)
        self.encoder = nn.TransformerEncoder(
            lambda: BertEncoderLayer(config), config.num_hidden_layers)
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)
        self.pooler_act = nn.Tanh()

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        emb = self.embeddings(input_ids, token_type_ids)
        mask = None
        if attention_mask is not None:
            # [B, T] keep-mask → additive [B, 1, 1, T]
            mask = (1.0 - attention_mask[:, None, None, :].astype(
                emb.dtype)) * jnp.finfo(jnp.float32).min
        seq_out = self.encoder(emb, src_mask=mask)
        with jax.named_scope("pt.head_loss"):
            pooled = self.pooler_act(self.pooler(seq_out[:, 0]))
        return seq_out, pooled


class BertPretrainingHeads(nn.Layer):
    def __init__(self, config: BertConfig) -> None:
        super().__init__()
        self.transform = nn.Linear(config.hidden_size, config.hidden_size)
        self.transform_act = nn.GELU()
        self.transform_norm = nn.LayerNorm(config.hidden_size,
                                           epsilon=1e-12)
        self.decoder_bias = nn.Parameter(
            jnp.zeros((config.vocab_size,), jnp.float32))
        self.seq_relationship = nn.Linear(config.hidden_size, 2)

    @jax.named_scope("pt.head_loss")
    def forward(self, sequence_output, pooled_output, word_embedding_weight):
        from ..kernels import fused_softmax_xent_enabled
        h = self.transform_norm(self.transform_act(
            self.transform(sequence_output)))
        nsp_logits = self.seq_relationship(pooled_output)
        if fused_softmax_xent_enabled():
            # defer the vocab projection into the loss region so the
            # fused kernel can stream it (pretraining_loss unpacks)
            return MLMHeadOutput(h, word_embedding_weight,
                                 self.decoder_bias), nsp_logits
        mlm_logits = h @ word_embedding_weight.T + self.decoder_bias
        return mlm_logits, nsp_logits


class BertForPretraining(nn.Layer):
    """MLM + NSP pretraining model (BASELINE config 3)."""

    def __init__(self, config: Optional[BertConfig] = None) -> None:
        super().__init__()
        self.config = config = config or BertConfig()
        self.bert = BertModel(config)
        self.cls = BertPretrainingHeads(config)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_positions=None):
        """``masked_positions`` [B, P] (per-row indices into the time
        axis) restricts the MLM head to the masked tokens, as the
        reference's BERT does (ref: python/paddle/fluid/tests/unittests/
        dygraph_to_static/bert_dygraph_model.py:327-335 gathers mask_pos
        from the flattened encoder output before the MLM transform) —
        the vocab-size projection is ~20% of step FLOPs at seq 512 and
        only ~15% of positions are masked. mlm_logits is then [B, P, V]
        and the MLM labels must be gathered the same way."""
        seq_out, pooled = self.bert(input_ids, token_type_ids,
                                    attention_mask)
        if masked_positions is not None:
            with jax.named_scope("pt.head_loss"):
                seq_out = jnp.take_along_axis(
                    seq_out,
                    masked_positions[:, :, None].astype(jnp.int32),
                    axis=1)
        return self.cls(seq_out, pooled,
                        self.bert.embeddings.word_embeddings.weight)


@jax.named_scope("pt.head_loss")
def pretraining_loss(outputs, mlm_labels, nsp_labels,
                     ignore_index: int = -100):
    """Masked-LM + next-sentence loss."""
    from ..ops import loss as L
    mlm_logits, nsp_logits = outputs
    if isinstance(mlm_logits, MLMHeadOutput):
        # fused loss region: per-position xent straight off the hidden
        # states; mean over all positions matches the reference
        # cross_entropy (ignored positions contribute exact zeros)
        from ..kernels import maybe_fused_linear_xent
        mlm = jnp.mean(maybe_fused_linear_xent(
            mlm_logits.hidden, mlm_logits.weight, mlm_logits.bias,
            mlm_labels, ignore_index=ignore_index))
    else:
        mlm = L.cross_entropy(mlm_logits, mlm_labels,
                              ignore_index=ignore_index, reduction="mean")
    nsp = L.cross_entropy(nsp_logits, nsp_labels, reduction="mean")
    return mlm + nsp
