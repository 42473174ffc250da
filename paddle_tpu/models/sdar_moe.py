"""SDAR-MoE: a pre-norm decoder of attention-then-routed-experts layers
that generates by diffusion over blocks (HF ``model_type: sdar_moe``;
JetLM/SDAR-30B-A3B-Chat is the published instance: 48 layers of hidden
size 2048, 32 query heads on 4 KV heads of 128 with an RMS norm on every
q and k head and rotary positions, a softmax router over 128 gated
experts of width 768, 8 a token, no shared expert).

Trained, a sequence ``x0`` of ``L`` tokens is run as ``2 L`` positions,
``[xt ; x0]``: the noised copy (a token replaced by ``mask_token_id``
with its block's probability ``t``) and the clean copy, position ``p_i =
i mod L``, under the block-diffusion mask of blocks of ``block_length``
(``kernels.flash_attention``; BD3-LMs, arXiv:2503.09573). The loss reads
the noisy half alone: ``(1 / (B L)) sum over masked i of CE(logits_i,
x0_i) / t_i``, the label at the same position (``block_diffusion_loss``).

``num_experts`` counts the experts this instance HOLDS; with
``num_experts_total`` larger it is one expert-parallel rank's share, as
``NemotronHConfig`` has it. ``vocab_size`` likewise is the rows held.

With ``recompute="layer"`` a training step keeps every layer's input,
its attention's flash result and its routing plan
(``nn.recompute_layer``), and makes the rest of the layer again in the
backward pass.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I
from . import nemotron_h

__all__ = ["SdarMoeConfig", "SdarMoeForCausalLM", "BlockDiffusionOutput",
           "block_diffusion_loss", "block_diffusion_metrics"]


@dataclass
class SdarMoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    num_experts: int = 128
    num_experts_total: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    # the selection bias's move a training step (``NemotronHConfig``)
    router_bias_update_rate: float = 0.0
    block_length: int = 4
    # the id a noised token is replaced by: the last row held
    mask_token_id: Optional[int] = None
    initializer_range: float = 0.02
    # "layer": while training, every layer is recomputed in the
    # backward pass from its input, but for the flash kernel's result
    # and the routing plan, which are kept (nn.recompute_layer)
    recompute: str = "none"

    def __post_init__(self) -> None:
        if self.recompute not in ("none", "layer"):
            raise ValueError(f"recompute {self.recompute!r}")
        if self.mask_token_id is None:
            self.mask_token_id = self.vocab_size - 1


class BlockDiffusionOutput(NamedTuple):
    """What the model hands its loss: the final hidden states of the
    NOISY half [B, L, hidden] and the head's weight instead of logits
    (``block_diffusion_loss`` streams the projection in blocks of
    tokens), which of those positions hold the mask token, and the
    step's counters as ``CausalLMOutput`` has them."""
    hidden: jnp.ndarray
    head_weight: jnp.ndarray
    masked: jnp.ndarray
    # loss positions of this call, counted by the step itself
    bd_masked_tokens: jnp.ndarray
    moe_pairs_held: jnp.ndarray
    moe_load_max_over_mean: jnp.ndarray
    moe_pairs_dropped: jnp.ndarray
    moe_expert_load: jnp.ndarray
    moe_windows_run: jnp.ndarray

    def logits(self):
        return jnp.matmul(self.hidden, self.head_weight)


class SdarMoeBlock(nn.Layer):
    """``h + attn(rmsnorm(h))`` then ``h + experts(rmsnorm(h))``."""

    def __init__(self, config: SdarMoeConfig) -> None:
        super().__init__()
        c = self.config = config
        w = I.Normal(0.0, c.initializer_range)
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = nn.GroupedQueryAttention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, causal=False, weight_attr=w, out_weight_attr=w,
            qk_norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
            block_diffusion=c.block_length)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.mlp = nn.DroplessMoE(
            c.hidden_size, c.moe_intermediate_size,
            c.num_experts_total or c.num_experts, c.num_experts_per_tok,
            experts_held=c.num_experts, expert_offset=c.expert_offset,
            norm_topk_prob=c.norm_topk_prob, weight_attr=w,
            out_weight_attr=w, score_func="softmax", gated=True)

    def forward(self, x, position_ids):
        with jax.named_scope("pt.attn"):
            x = x + self.self_attn(self.input_layernorm(x), position_ids)
        with jax.named_scope("pt.moe_route"):
            h = self.post_attention_layernorm(x)
        y, stats = self.mlp(h)
        with jax.named_scope("pt.moe_route"):
            return x + y, stats


class SdarMoeForCausalLM(nn.Layer):
    """Token embedding, the blocks, a final RMSNorm and an untied head;
    ``forward`` takes ``[xt ; x0]`` ids [B, 2 L]."""

    def __init__(self, config: Optional[SdarMoeConfig] = None) -> None:
        super().__init__()
        c = self.config = config or SdarMoeConfig()
        w = I.Normal(0.0, c.initializer_range)
        self.embed_tokens = nn.Embedding(c.vocab_size, c.hidden_size,
                                         weight_attr=w)
        self.layers = nn.LayerList([SdarMoeBlock(c) for _ in
                                    range(c.num_hidden_layers)])
        self.norm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.lm_head = nn.Linear(c.hidden_size, c.vocab_size,
                                 weight_attr=w, bias_attr=False)

    def router_bias_fit(self):
        """For ``balance_router_bias``, as ``NemotronHForCausalLM`` has
        it: the bias buffers' names, and 64 passes whose moves shrink
        from 4e-3 to 3e-5, sized for softmax scores over 128 experts
        (sigmoid scores lie a half apart, these a hundredth) and scaled
        for another count."""
        c = self.config
        scale = 128.0 / (c.num_experts_total or c.num_experts)
        names = [f"layers.{i}.mlp.e_score_correction_bias"
                 for i in range(len(self.layers))]
        return names, 64, 4e-3 * scale, 3e-5 * scale

    def forward(self, input_ids) -> BlockDiffusionOutput:
        c = self.config
        length = input_ids.shape[1] // 2
        if input_ids.shape[1] != 2 * length or length % c.block_length:
            raise ValueError(
                f"input_ids hold a noised and a clean copy of whole "
                f"blocks of {c.block_length}: got {input_ids.shape}")
        with jax.named_scope("pt.embed"):
            x = self.embed_tokens(input_ids)
            # the two copies of a token share its position
            position_ids = jnp.arange(2 * length, dtype=jnp.int32) % length
        remat = c.recompute == "layer" and self.training
        held = jnp.zeros((), jnp.int32)
        dropped = jnp.zeros((), jnp.int32)
        windows_run = jnp.zeros((), jnp.int32)
        ratio = jnp.zeros((), jnp.float32)
        loads = []
        rate = c.router_bias_update_rate if self.training else 0
        for layer in self.layers:
            x, stats = (nn.recompute_layer(layer) if remat
                        else layer)(x, position_ids)
            held = held + stats["pairs_held"]
            dropped = dropped + stats["pairs_dropped"]
            windows_run = windows_run + stats["windows_run"]
            ratio = jnp.maximum(ratio, stats["load_max_over_mean"])
            loads.append(stats["expert_load"])
            if rate:
                # outside the recomputed layer, where a buffer may be
                # written
                with jax.named_scope("pt.moe_route"):
                    layer.mlp.e_score_correction_bias = \
                        nemotron_h._balanced(
                            layer.mlp.e_score_correction_bias, loads[-1],
                            rate)
        with jax.named_scope("pt.head_loss"):
            masked = input_ids[:, :length] == c.mask_token_id
            x = self.norm(x[:, :length])
        return BlockDiffusionOutput(
            x, self.lm_head.weight, masked,
            jnp.sum(masked, dtype=jnp.int32), held, ratio, dropped,
            jnp.stack(loads), windows_run)


@jax.named_scope("pt.head_loss")
def block_diffusion_loss(out: BlockDiffusionOutput, labels, t):
    """``(1 / (B L)) sum over masked i of CE(logits_i, labels_i) /
    t_i``, float32: ``labels`` [B, L] the clean tokens (the same
    position: no shift), ``t`` [B, L] the noise level of each position's
    block. The projection and the softmax run over blocks of tokens,
    each recomputed in the backward pass, as ``next_token_loss`` does."""
    hidden = out.hidden.reshape(-1, out.hidden.shape[-1])
    labels = labels.reshape(-1).astype(jnp.int32)
    weight = jnp.where(out.masked.reshape(-1),
                       1.0 / t.reshape(-1).astype(jnp.float32), 0.0)
    n = hidden.shape[0]
    block = nemotron_h._LOSS_BLOCK if n % nemotron_h._LOSS_BLOCK == 0 else n

    @jax.checkpoint
    @jax.named_scope("pt.head_loss")    # a loop body's own name stack
    def block_nll(args):
        h, y, w = args
        logits = jnp.matmul(h, out.head_weight,
                            preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(w * (jax.nn.logsumexp(logits, axis=-1) - picked))

    nll = jax.lax.map(block_nll, (
        hidden.reshape(-1, block, hidden.shape[-1]),
        labels.reshape(-1, block), weight.reshape(-1, block)))
    return jnp.sum(nll) / n


def block_diffusion_metrics() -> Dict[str, Callable]:
    """``extra_metrics`` for ``static.TrainStep``: the routing counters
    of a step and the loss positions it counted."""
    return {**nemotron_h.routing_metrics(),
            "bd_masked_tokens": lambda out, *labels: out.bd_masked_tokens}
