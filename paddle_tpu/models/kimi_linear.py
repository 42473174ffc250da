"""Kimi Linear: a pre-norm decoder whose layers mix by Kimi Delta
Attention (KDA, a gated delta rule with a decay for every key channel)
or by multi-head latent attention without a positional term (MLA), each
followed by a dense SwiGLU (the first ``first_k_dense_replace`` layers)
or by sigmoid-routed SwiGLU experts with a shared expert (HF
``model_type: kimi_linear``; moonshotai/Kimi-Linear-48B-A3B-Instruct is
the published instance: 27 layers of hidden size 2304, KDA and MLA at
3 : 1, 256 experts of 1024, 8 a token).

The kind of a layer is read from ``linear_attn_config``: its
``kda_layers`` and ``full_attn_layers`` are 1-indexed layer numbers, as
published. ``num_experts`` counts the experts this instance HOLDS; with
``num_experts_total`` larger it is one expert-parallel rank's share
(``nn.DroplessMoE``), as ``NemotronHConfig`` and ``SdarMoeConfig`` have
it. ``vocab_size`` likewise is the rows held.

With ``recompute="layer"`` a training step keeps every layer's input,
an MLA layer's flash result and an expert layer's routing plan
(``nn.recompute_layer``), and makes the rest of the layer again in the
backward pass.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import initializer as I
from . import nemotron_h
from .nemotron_h import CausalLMOutput

__all__ = ["KimiLinearConfig", "KimiLinearForCausalLM"]


def _published_layers() -> Dict:
    return {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}


@dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_hidden_layers: int = 27
    rms_norm_eps: float = 1e-5
    # MLA (q_lora_rank null: q is one projection; no rotation)
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # KDA, and which layer is which
    linear_attn_config: Dict = field(default_factory=_published_layers)
    # the gated delta rule's chunk (not in the source: flash-linear-
    # attention's chunk_kda walks chunks of 64)
    chunk_size: int = 64
    # experts
    first_k_dense_replace: int = 1
    num_experts: int = 256
    num_experts_total: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_token: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
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
        kinds = [self.layer_kind(i) for i in range(self.num_hidden_layers)]
        if None in kinds:
            raise ValueError(
                f"layer {kinds.index(None) + 1} is in neither kda_layers "
                f"nor full_attn_layers of {self.linear_attn_config}")

    def layer_kind(self, i: int) -> Optional[str]:
        """``"kda"`` or ``"mla"`` for the 0-based layer ``i``."""
        lac = self.linear_attn_config
        if i + 1 in lac["kda_layers"]:
            return "kda"
        return "mla" if i + 1 in lac["full_attn_layers"] else None


class _SwiGLU(nn.Layer):
    """The dense layers' MLP: ``W_down (silu(W_gate x) * W_up x)``."""

    def __init__(self, hidden: int, width: int, w, out_w) -> None:
        super().__init__()
        self.gate_proj = nn.Linear(hidden, width, w, bias_attr=False)
        self.up_proj = nn.Linear(hidden, width, w, bias_attr=False)
        self.down_proj = nn.Linear(width, hidden, out_w, bias_attr=False)

    def forward(self, x):
        return self.down_proj(jax.nn.silu(self.gate_proj(x))
                              * self.up_proj(x))


class KimiLinearBlock(nn.Layer):
    """``h + mixer(rmsnorm(h))`` then ``h + mlp(rmsnorm(h))``; a KDA
    block's norm and residual are charged to ``pt.kda_proj``, an MLA
    block's to ``pt.attn``, a dense MLP to ``pt.ffn`` and an expert
    layer's to ``pt.moe_route``."""

    def __init__(self, config: KimiLinearConfig, index: int) -> None:
        super().__init__()
        c = self.config = config
        w = I.Normal(0.0, c.initializer_range)
        self.kind = c.layer_kind(index)
        self.dense = index < c.first_k_dense_replace
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        if self.kind == "kda":
            lac = c.linear_attn_config
            self.self_attn = nn.KimiDeltaAttention(
                c.hidden_size, lac["num_heads"], lac["head_dim"],
                lac["short_conv_kernel_size"], c.chunk_size,
                c.rms_norm_eps, weight_attr=w, out_weight_attr=w)
        else:
            self.self_attn = nn.MultiHeadLatentAttention(
                c.hidden_size, c.num_attention_heads, c.kv_lora_rank,
                c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                c.rms_norm_eps, weight_attr=w, out_weight_attr=w)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        if self.dense:
            self.mlp = _SwiGLU(c.hidden_size, c.intermediate_size, w, w)
        else:
            self.mlp = nn.DroplessMoE(
                c.hidden_size, c.moe_intermediate_size,
                c.num_experts_total or c.num_experts,
                c.num_experts_per_token,
                d_shared=c.moe_intermediate_size * c.num_shared_experts,
                experts_held=c.num_experts, expert_offset=c.expert_offset,
                routed_scaling_factor=c.routed_scaling_factor,
                norm_topk_prob=c.moe_renormalize, weight_attr=w,
                out_weight_attr=w, score_func="sigmoid", gated=True)

    def forward(self, x):
        scope = "pt.kda_proj" if self.kind == "kda" else "pt.attn"
        with jax.named_scope(scope):
            h = self.input_layernorm(x)
        if self.kind == "kda":
            y = self.self_attn(h)
        else:
            with jax.named_scope(scope):
                y = self.self_attn(h)
        with jax.named_scope(scope):
            x = x + y
        if self.dense:
            with jax.named_scope("pt.ffn"):
                return x + self.mlp(self.post_attention_layernorm(x)), None
        with jax.named_scope("pt.moe_route"):
            h = self.post_attention_layernorm(x)
        y, stats = self.mlp(h)
        with jax.named_scope("pt.moe_route"):
            return x + y, stats


class KimiLinearForCausalLM(nn.Layer):
    """Token embedding, the blocks, a final RMSNorm and an untied head;
    the loss is ``models.next_token_loss`` (the head in blocks of
    tokens) and the routing counters are ``models.routing_metrics``'."""

    def __init__(self, config: Optional[KimiLinearConfig] = None) -> None:
        super().__init__()
        c = self.config = config or KimiLinearConfig()
        w = I.Normal(0.0, c.initializer_range)
        self.embed_tokens = nn.Embedding(c.vocab_size, c.hidden_size,
                                         weight_attr=w)
        self.layers = nn.LayerList([KimiLinearBlock(c, i) for i in
                                    range(c.num_hidden_layers)])
        self.norm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.lm_head = nn.Linear(c.hidden_size, c.vocab_size,
                                 weight_attr=w, bias_attr=False)

    def router_bias_fit(self):
        """For ``balance_router_bias``: the expert layers' bias buffers,
        and the hybrid decoder's schedule for sigmoid scores (48 passes,
        moves from 0.03 to 0.001)."""
        names = [f"layers.{i}.mlp.e_score_correction_bias"
                 for i, layer in enumerate(self.layers) if not layer.dense]
        return names, 48, 0.03, 0.001

    def forward(self, input_ids) -> CausalLMOutput:
        with jax.named_scope("pt.embed"):
            x = self.embed_tokens(input_ids)
        remat = self.config.recompute == "layer" and self.training
        held = jnp.zeros((), jnp.int32)
        dropped = jnp.zeros((), jnp.int32)
        windows_run = jnp.zeros((), jnp.int32)
        ratio = jnp.zeros((), jnp.float32)
        loads = []
        rate = self.config.router_bias_update_rate if self.training else 0
        for layer in self.layers:
            x, stats = (nn.recompute_layer(layer) if remat else layer)(x)
            if stats is None:
                continue
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
        return CausalLMOutput(
            x, self.lm_head.weight, held, ratio, dropped,
            jnp.stack(loads) if loads else jnp.zeros((0, 0), jnp.int32),
            windows_run)
