"""SmallThinker: a pre-norm decoder whose layers are grouped-query
attention, windowed with rotary positions or global without a positional
term, each followed by softmax-routed ReGLU experts whose router reads
the layer's input before attention (HF ``model_type: smallthinker``;
PowerInfer/SmallThinker-21BA3B-Instruct is the published instance: 52
layers of hidden size 2560, 28 query heads on 4 KV heads of 128, windows
of 4096 keys on three layers of four, 64 experts of 768, 6 a token, no
shared expert).

One layer, ``h`` its input, every norm an RMSNorm:

- ``x = norm_in(h)``; the router scores ``softmax(x W_r)`` over every
  expert, chooses the ``moe_num_active_primary_experts`` largest and
  renormalises their weights (``norm_topk_prob``);
- ``h' = h + attn(x)``: a layer marked 1 in ``sliding_window_layout``
  sees the ``sliding_window_size`` last positions up to its own, one
  marked 0 every earlier one; a layer marked 1 in ``rope_layout`` turns
  q and k by rotary positions at ``rope_theta``, one marked 0 has no
  positional term;
- ``h'' = h' + sum_e w_e W_down,e (relu(W_gate,e y) * W_up,e y)``,
  ``y = norm_post(h')``: the experts read the stream after attention,
  the router the one before it.

``moe_num_primary_experts`` counts the experts this instance HOLDS; with
``moe_num_primary_experts_total`` larger it is one expert-parallel rank's
share (``nn.DroplessMoE``), as ``SdarMoeConfig`` has it. ``vocab_size``
likewise is the rows held. ``router_bias_update_rate`` moves every
expert layer's selection bias a training step by the aux-loss-free rule,
as ``NemotronHConfig`` has it (0: the source's router, which has none).

With ``recompute="layer"`` a training step keeps every layer's input,
its attention's flash result and its routing plan
(``nn.recompute_layer``), and makes the rest of the layer again in the
backward pass.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I
from . import nemotron_h
from .nemotron_h import CausalLMOutput

__all__ = ["SmallThinkerConfig", "SmallThinkerForCausalLM"]


def _published_layout() -> List[int]:
    return [0, 1, 1, 1] * 13


@dataclass
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    # a layer's 1: rotary positions / a window; 0: none / global
    rope_layout: List[int] = field(default_factory=_published_layout)
    sliding_window_layout: List[int] = field(
        default_factory=_published_layout)
    sliding_window_size: int = 4096
    moe_num_primary_experts: int = 64
    moe_num_primary_experts_total: Optional[int] = None
    expert_offset: int = 0
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    # the selection bias's move a training step (``NemotronHConfig``)
    router_bias_update_rate: float = 0.0
    initializer_range: float = 0.02
    # "layer": while training, every layer is recomputed in the
    # backward pass from its input, but for the flash kernel's result
    # and the routing plan, which are kept (nn.recompute_layer)
    recompute: str = "none"

    def __post_init__(self) -> None:
        if self.recompute not in ("none", "layer"):
            raise ValueError(f"recompute {self.recompute!r}")
        for name in ("rope_layout", "sliding_window_layout"):
            layout = getattr(self, name)
            if len(layout) != self.num_hidden_layers \
                    or set(layout) - {0, 1}:
                raise ValueError(f"{name} wants a 0 or 1 for each of "
                                 f"{self.num_hidden_layers} layers: "
                                 f"{layout}")


class SmallThinkerBlock(nn.Layer):
    """``h + attn(norm_in(h))``, then ``h + experts(norm_post(h))``
    routed on ``norm_in`` of the block's input; the attention's norm and
    residual are charged to ``pt.attn``, the experts' to
    ``pt.moe_route``."""

    def __init__(self, config: SmallThinkerConfig, index: int) -> None:
        super().__init__()
        c = self.config = config
        w = I.Normal(0.0, c.initializer_range)
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = nn.GroupedQueryAttention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, causal=True, weight_attr=w, out_weight_attr=w,
            rope_theta=c.rope_theta if c.rope_layout[index] else None,
            sliding_window=c.sliding_window_size
            if c.sliding_window_layout[index] else None)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.mlp = nn.DroplessMoE(
            c.hidden_size, c.moe_ffn_hidden_size,
            c.moe_num_primary_experts_total or c.moe_num_primary_experts,
            c.moe_num_active_primary_experts,
            experts_held=c.moe_num_primary_experts,
            expert_offset=c.expert_offset, norm_topk_prob=c.norm_topk_prob,
            weight_attr=w, out_weight_attr=w,
            score_func="softmax" if c.moe_primary_router_apply_softmax
            else "sigmoid", gated=True, gate_act="relu")

    def forward(self, x):
        with jax.named_scope("pt.attn"):
            h = self.input_layernorm(x)
            x = x + self.self_attn(h)
        with jax.named_scope("pt.moe_route"):
            u = self.post_attention_layernorm(x)
        y, stats = self.mlp(u, router_input=h)
        with jax.named_scope("pt.moe_route"):
            return x + y, stats


class SmallThinkerForCausalLM(nn.Layer):
    """Token embedding, the blocks, a final RMSNorm and an untied head;
    the loss is ``models.next_token_loss`` (the head in blocks of
    tokens) and the routing counters are ``models.routing_metrics``'."""

    def __init__(self, config: Optional[SmallThinkerConfig] = None) -> None:
        super().__init__()
        c = self.config = config or SmallThinkerConfig()
        w = I.Normal(0.0, c.initializer_range)
        self.embed_tokens = nn.Embedding(c.vocab_size, c.hidden_size,
                                         weight_attr=w)
        self.layers = nn.LayerList([SmallThinkerBlock(c, i) for i in
                                    range(c.num_hidden_layers)])
        self.norm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.lm_head = nn.Linear(c.hidden_size, c.vocab_size,
                                 weight_attr=w, bias_attr=False)

    def router_bias_fit(self):
        """For ``balance_router_bias``, as ``SdarMoeForCausalLM`` has it:
        the bias buffers' names, and 64 passes whose moves shrink from
        4e-3 to 3e-5 for softmax scores over 128 experts, scaled for the
        experts scored here."""
        c = self.config
        scale = 128.0 / (c.moe_num_primary_experts_total
                         or c.moe_num_primary_experts)
        names = [f"layers.{i}.mlp.e_score_correction_bias"
                 for i in range(len(self.layers))]
        return names, 64, 4e-3 * scale, 3e-5 * scale

    def forward(self, input_ids) -> CausalLMOutput:
        with jax.named_scope("pt.embed"):
            x = self.embed_tokens(input_ids)
        remat = self.config.recompute == "layer" and self.training
        rate = self.config.router_bias_update_rate if self.training else 0
        held = jnp.zeros((), jnp.int32)
        dropped = jnp.zeros((), jnp.int32)
        windows_run = jnp.zeros((), jnp.int32)
        ratio = jnp.zeros((), jnp.float32)
        loads = []
        for layer in self.layers:
            x, stats = (nn.recompute_layer(layer) if remat else layer)(x)
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
            x = self.norm(x)
        return CausalLMOutput(x, self.lm_head.weight, held, ratio, dropped,
                              jnp.stack(loads), windows_run)
