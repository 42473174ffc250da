"""Multi-head latent attention (MLA, DeepSeek-V2's attention; the global
layers of HF ``model_type: kimi_linear`` run it with ``q_lora_rank:
null`` and no positional term, ``mla_use_nope: true``).

Keys and values are made from one latent row a position: ``[c ; k_pe] =
x W_kva`` (c ``kv_lora_rank`` wide), ``[k_nope_h ; v_h] = rmsnorm(c)
W_kvb[h]``, ``k_h = [k_nope_h ; k_pe]`` with ``k_pe`` shared by every
head; ``q_h = x W_q[h]``, as wide as a key (``qk_nope + qk_rope``). Query
and key are wider than a value, so the flash kernels run with a value
width of their own (``kernels.flash_attention``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..layer import Layer
from .common import Linear
from .norm import RMSNorm

__all__ = ["MultiHeadLatentAttention"]


class MultiHeadLatentAttention(Layer):
    """``o_h = softmax(q_h k_h^T / sqrt(qk_nope + qk_rope) + causal)
    v_h``, ``out = concat_h(o_h) W_o``; no projection bias, no rotation
    of the ``qk_rope`` part (the parameters keep the HF names). The
    latent path, from ``W_kva`` to the keys and values of every head, is
    traced under ``pt.mla_latent``; the caller's scope (``pt.attn``)
    takes the rest.

    Where the flash kernel runs, its output and row statistics carry
    the names ``flash_out`` and ``flash_lse``, which ``nn.recompute_layer``
    keeps, as for ``nn.GroupedQueryAttention``."""

    def __init__(self, hidden_size: int, num_heads: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, norm_eps: float = 1e-5, weight_attr=None,
                 out_weight_attr=None) -> None:
        super().__init__()
        self.num_heads, self.kv_lora_rank = num_heads, kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_head_dim = qk_nope_head_dim + qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.q_proj = Linear(hidden_size, num_heads * self.qk_head_dim,
                             weight_attr, bias_attr=False)
        self.kv_a_proj_with_mqa = Linear(
            hidden_size, kv_lora_rank + qk_rope_head_dim, weight_attr,
            bias_attr=False)
        self.kv_a_layernorm = RMSNorm(kv_lora_rank, norm_eps)
        self.kv_b_proj = Linear(
            kv_lora_rank, num_heads * (qk_nope_head_dim + v_head_dim),
            weight_attr, bias_attr=False)
        self.o_proj = Linear(num_heads * v_head_dim, hidden_size,
                             out_weight_attr, bias_attr=False)

    def forward(self, x):
        from ...kernels import maybe_flash_attention
        b, t, _ = x.shape
        h = self.num_heads
        q = self.q_proj(x).reshape(b, t, h, self.qk_head_dim)
        with jax.named_scope("pt.mla_latent"):
            c, k_pe = jnp.split(self.kv_a_proj_with_mqa(x),
                                [self.kv_lora_rank], axis=-1)
            k_nope, v = jnp.split(
                self.kv_b_proj(self.kv_a_layernorm(c)).reshape(
                    b, t, h, self.qk_nope_head_dim + self.v_head_dim),
                [self.qk_nope_head_dim], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe[:, :, None], (b, t, h, k_pe.shape[-1]))], axis=-1)
        out = maybe_flash_attention(
            q, k, v, causal=True, scale=self.qk_head_dim ** -0.5,
            training=self.training, layout="bthd")
        return self.o_proj(out.reshape(b, t, h * self.v_head_dim))
