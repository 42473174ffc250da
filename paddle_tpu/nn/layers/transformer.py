"""Transformer layers.

TPU-native transformer stack. The reference's transformer support is
op-level fusions (fused/multihead_matmul_op.cu,
fused_embedding_eltwise_layernorm_op.cu, ir skip_layernorm_fuse_pass) used
by its BERT/ERNIE models; here the same capability is a first-class layer
family whose attention core routes through kernels.maybe_flash_attention
(Pallas on TPU). Shapes are [batch, seq, hidden] throughout; bf16-friendly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core.dtype import get_default_dtype
from ...ops import activation as A
from ...ops import nn_functional as F
from .. import initializer as I
from ..layer import Layer, LayerList, Parameter
from .common import Dropout, Linear
from .norm import LayerNorm


@jax.custom_vjp
def _qkv_linear(x, wq, bq, wk, bk, wv, bv):
    """Three ``F.linear`` projections of one input, values as three
    ``Linear`` calls give them. Differs only backward: with the weights
    split by columns over ``mp`` (megatron_param_rule) each of
    ``dQ Wq^T``, ``dK Wk^T``, ``dV Wv^T`` is a partial sum a chip, and
    autodiff adds them to the input's cotangent in an order that makes
    the partitioner all-reduce each product where its dot ends: three
    exchanges of ``[B, T, H]`` a layer. Written as one adjacent sum,
    XLA adds the partial products on the chip and exchanges once."""
    return F.linear(x, wq, bq), F.linear(x, wk, bk), F.linear(x, wv, bv)


def _qkv_linear_fwd(x, *wb):
    return _qkv_linear(x, *wb), (x, wb)


def _qkv_linear_bwd(res, cts):
    x, wb = res
    grads, dx = [], None
    for ct, w, b in zip(cts, wb[::2], wb[1::2]):
        # weight and bias gradients are F.linear's own
        _, pull = jax.vjp(lambda w, b: F.linear(x, w, b), w, b)
        grads += pull(ct)
        part = jnp.matmul(ct, w.T)
        dx = part if dx is None else dx + part
    return (dx.astype(x.dtype), *grads)


_qkv_linear.defvjp(_qkv_linear_fwd, _qkv_linear_bwd)


def _self_attention_projections(q_proj, k_proj, v_proj, x):
    """``q_proj(x), k_proj(x), v_proj(x)``. Under a mesh that splits
    the model over ``mp`` (the one in scope while a sharded step
    traces) the three go through ``_qkv_linear``, which sums their
    input gradients before the exchange; with no such axis these are
    the three ``Linear`` calls, and the program is what it was."""
    from ...parallel.mesh import MP, auto_axis_sizes
    if MP not in auto_axis_sizes():
        return q_proj(x), k_proj(x), v_proj(x)
    from ...observability.xprof import note_qkv_grad_summed
    note_qkv_grad_summed()
    return _qkv_linear(x, *(p for proj in (q_proj, k_proj, v_proj)
                            for p in (proj.weight, proj.bias)))


class MultiHeadAttention(Layer):
    """(capability ref: multihead_matmul_op.cu fused attention)."""

    def __init__(self, embed_dim: int, num_heads: int,
                 dropout: float = 0.0, kdim: Optional[int] = None,
                 vdim: Optional[int] = None, need_weights: bool = False,
                 weight_attr=None, bias_attr=None) -> None:
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        kdim = kdim or embed_dim
        vdim = vdim or embed_dim
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _split(self, x):
        b, t, _ = x.shape
        return jnp.moveaxis(
            x.reshape(b, t, self.num_heads, self.head_dim), 2, 1)

    def forward(self, query, key=None, value=None, attn_mask=None,
                causal: bool = False):
        # Layout: the projections go to attention in their NATIVE
        # [B, T, H, D] layout (layout="bthd") — the flash kernel gathers
        # heads inside its block DMA, so the routed path runs no
        # physical head transpose. The XLA fallback transposes to BHTD
        # internally.
        self_attention = key is None and value is None
        key = query if key is None else key
        value = key if value is None else value
        if self_attention and not self.need_weights:
            qp, kp, vp = _self_attention_projections(
                self.q_proj, self.k_proj, self.v_proj, query)
        else:
            qp = self.q_proj(query)
            kp = self.k_proj(key)
            vp = self.v_proj(value)
        if self.need_weights:
            # the reference returns (out, attention weights); weights
            # require materializing the [B, H, Tq, Tk] probs, so this
            # path stays on the XLA composition by construction
            from ...ops.attention import scaled_dot_product_attention
            out, weights = scaled_dot_product_attention(
                self._split(qp), self._split(kp), self._split(vp),
                mask=attn_mask, causal=causal,
                dropout_p=self.dropout, training=self.training,
                return_weights=True)
            b, h, t, d = out.shape
            out = jnp.moveaxis(out, 1, 2).reshape(b, t, h * d)
            out = self.out_proj(out)
            return out, weights
        from ...kernels import maybe_flash_attention

        def heads(x):
            b_, t_, _ = x.shape
            return x.reshape(b_, t_, self.num_heads, self.head_dim)

        out = maybe_flash_attention(
            heads(qp), heads(kp), heads(vp), mask=attn_mask,
            causal=causal, dropout_p=self.dropout,
            training=self.training, layout="bthd")
        b, t, h, d = out.shape
        return self.out_proj(out.reshape(b, t, h * d))


def rotate_half_rope(x, position_ids, theta: float):
    """Rotary positions over the whole head, rotate-half form: ``x`` [B,
    T, H, D] and ``position_ids`` [T] or [B, T] -> ``x cos + rotate_half(
    x) sin`` with frequencies ``theta ** (-2 i / D)`` and ``rotate_half(
    x) = [-x2, x1]`` over the head's two halves. Computed in float32 and
    returned in ``x``'s dtype."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.asarray(position_ids, jnp.float32)[..., None] * freq
    if angle.ndim == 2:
        angle = angle[None]
    cos = jnp.cos(angle)[:, :, None, :]                # [B | 1, T, 1, D/2]
    sin = jnp.sin(angle)[:, :, None, :]
    h = x.astype(jnp.float32)
    x1, x2 = h[..., :half], h[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


class GroupedQueryAttention(Layer):
    """Self-attention with ``num_heads`` query heads on ``num_kv_heads``
    key/value heads of ``head_dim`` (a KV head serves ``num_heads /
    num_kv_heads`` query heads), no projection bias, no positional term
    of its own, scale ``head_dim ** -0.5``. The KV heads are repeated
    to the query heads before the attention call, so the routed flash
    kernel sees plain multi-head operands in their native [B, T, H, D]
    layout; the gradient of the repeat sums a group's heads.

    Three options, each off by default (the layer then traces to what it
    always did): ``qk_norm_eps`` puts an RMS norm with a gain of its own
    over every q head and every k head (``q_norm`` / ``k_norm``);
    ``rope_theta`` turns q and k by rotary positions, from the
    ``position_ids`` the caller gives ``forward`` (two positions of the
    input may share one; ``arange(T)`` without them); ``block_diffusion``
    is the block length ``K`` of the block-diffusion training mask in
    ``causal``'s place: the input is a noised copy of ``T / 2`` tokens
    followed by the clean copy (``kernels.flash_attention``). The norm
    and the turn are traced under the scope ``pt.attn_qk``.

    Where the flash kernel runs, its output and row statistics carry the
    names ``flash_out`` and ``flash_lse``: inside ``nn.recompute_layer``
    the backward pass reads the kept pair and does not run the forward
    kernel again (the projections, the norm and the turn it recomputes)."""

    def __init__(self, hidden_size: int, num_heads: int,
                 num_kv_heads: int, head_dim: int, causal: bool = True,
                 weight_attr=None, out_weight_attr=None,
                 qk_norm_eps: Optional[float] = None,
                 rope_theta: Optional[float] = None,
                 block_diffusion: Optional[int] = None) -> None:
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_kv_heads} KV heads do not divide "
                             f"{num_heads} query heads")
        if block_diffusion is not None and causal:
            raise ValueError("block_diffusion is a mask of its own: "
                             "causal=False with it")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.causal = head_dim, causal
        self.rope_theta, self.block_diffusion = rope_theta, block_diffusion
        self.q_proj = Linear(hidden_size, num_heads * head_dim,
                             weight_attr, bias_attr=False)
        self.k_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, bias_attr=False)
        self.v_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             weight_attr, bias_attr=False)
        self.o_proj = Linear(num_heads * head_dim, hidden_size,
                             out_weight_attr, bias_attr=False)
        self.has_qk_norm = qk_norm_eps is not None
        if self.has_qk_norm:
            from .norm import RMSNorm
            self.q_norm = RMSNorm(head_dim, qk_norm_eps)
            self.k_norm = RMSNorm(head_dim, qk_norm_eps)

    def _qk(self, q, k, position_ids):
        """The options' work on q [B, T, H, D] and k [B, T, KV, D]."""
        if not (self.has_qk_norm or self.rope_theta is not None):
            return q, k
        with jax.named_scope("pt.attn_qk"):
            if self.has_qk_norm:
                q, k = self.q_norm(q), self.k_norm(k)
            if self.rope_theta is not None:
                if position_ids is None:
                    position_ids = jnp.arange(q.shape[1])
                q = rotate_half_rope(q, position_ids, self.rope_theta)
                k = rotate_half_rope(k, position_ids, self.rope_theta)
        return q, k

    def forward(self, x, position_ids=None):
        from ...kernels import maybe_flash_attention
        b, t, _ = x.shape
        rep = self.num_heads // self.num_kv_heads
        q = self.q_proj(x).reshape(b, t, self.num_heads, self.head_dim)

        def kv(proj):
            return proj(x).reshape(b, t, self.num_kv_heads, self.head_dim)

        q, k = self._qk(q, kv(self.k_proj), position_ids)
        mask = None if self.block_diffusion is None \
            else (t // 2, self.block_diffusion)
        out = maybe_flash_attention(
            q, jnp.repeat(k, rep, axis=2),
            jnp.repeat(kv(self.v_proj), rep, axis=2), causal=self.causal,
            scale=self.head_dim ** -0.5, training=self.training,
            layout="bthd", block_diffusion=mask)
        return self.o_proj(out.reshape(b, t, -1))


class TransformerEncoderLayer(Layer):
    def __init__(self, d_model: int, nhead: int, dim_feedforward: int,
                 dropout: float = 0.1, activation: str = "relu",
                 attn_dropout: Optional[float] = None,
                 act_dropout: Optional[float] = None,
                 normalize_before: bool = False) -> None:
        super().__init__()
        self.self_attn = MultiHeadAttention(
            d_model, nhead,
            dropout=attn_dropout if attn_dropout is not None else dropout)
        self.linear1 = Linear(d_model, dim_feedforward)
        self.linear2 = Linear(dim_feedforward, d_model)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.act_dropout = Dropout(
            act_dropout if act_dropout is not None else dropout)
        self.activation = getattr(A, activation)
        self.normalize_before = normalize_before

    def forward(self, src, src_mask=None):
        # the two device scopes a profile reads an encoder stack by
        # (docs/observability.md): each sub-block with its residual
        # add and norm, forward and backward alike
        with jax.named_scope("pt.attn"):
            residual = src
            if self.normalize_before:
                src = self.norm1(src)
            src = self.self_attn(src, attn_mask=src_mask)
            src = residual + self.dropout1(src)
            if not self.normalize_before:
                src = self.norm1(src)
        with jax.named_scope("pt.ffn"):
            residual = src
            if self.normalize_before:
                src = self.norm2(src)
            src = self.linear2(self.act_dropout(self.activation(
                self.linear1(src))))
            src = residual + self.dropout2(src)
            if not self.normalize_before:
                src = self.norm2(src)
        return src


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer_ctor, num_layers: int,
                 norm: Optional[Layer] = None) -> None:
        super().__init__()
        self.layers = LayerList([encoder_layer_ctor()
                                 for _ in range(num_layers)])
        if norm is not None:
            self.norm = norm
        self.has_norm = norm is not None

    def forward(self, src, src_mask=None):
        from ...flags import GLOBAL_FLAGS
        out = src
        remat = (GLOBAL_FLAGS.get("transformer_remat")
                 and self.training)
        for layer in self.layers:
            if remat:
                # per-layer rematerialization: the backward recomputes
                # this layer's activations instead of keeping them —
                # trades ~1/3 more FLOPs for O(layers) less activation
                # HBM (jax.checkpoint; traced RNG replays identically,
                # so dropout masks match between fwd and recompute)
                out = jax.checkpoint(
                    lambda s, m, _l=layer: _l(s, src_mask=m))(out, src_mask)
            else:
                out = layer(out, src_mask=src_mask)
        if self.has_norm:
            out = self.norm(out)
        return out


class TransformerDecoderLayer(Layer):
    def __init__(self, d_model: int, nhead: int, dim_feedforward: int,
                 dropout: float = 0.1, activation: str = "relu",
                 normalize_before: bool = False) -> None:
        super().__init__()
        self.self_attn = MultiHeadAttention(d_model, nhead, dropout=dropout)
        self.cross_attn = MultiHeadAttention(d_model, nhead, dropout=dropout)
        self.linear1 = Linear(d_model, dim_feedforward)
        self.linear2 = Linear(dim_feedforward, d_model)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(dropout)
        self.activation = getattr(A, activation)
        self.normalize_before = normalize_before

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        tgt = self.self_attn(tgt, attn_mask=tgt_mask, causal=tgt_mask is None)
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        tgt = self.cross_attn(tgt, memory, memory, attn_mask=memory_mask)
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.activation(self.linear1(tgt)))
        tgt = residual + self.dropout3(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        return tgt


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer_ctor, num_layers: int,
                 norm: Optional[Layer] = None) -> None:
        super().__init__()
        self.layers = LayerList([decoder_layer_ctor()
                                 for _ in range(num_layers)])
        if norm is not None:
            self.norm = norm
        self.has_norm = norm is not None

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None):
        out = tgt
        for layer in self.layers:
            out = layer(out, memory, tgt_mask, memory_mask)
        if self.has_norm:
            out = self.norm(out)
        return out


class Transformer(Layer):
    def __init__(self, d_model: int = 512, nhead: int = 8,
                 num_encoder_layers: int = 6, num_decoder_layers: int = 6,
                 dim_feedforward: int = 2048, dropout: float = 0.1,
                 activation: str = "relu",
                 normalize_before: bool = False) -> None:
        super().__init__()
        self.encoder = TransformerEncoder(
            lambda: TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                normalize_before=normalize_before), num_encoder_layers,
            LayerNorm(d_model) if normalize_before else None)
        self.decoder = TransformerDecoder(
            lambda: TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                normalize_before), num_decoder_layers,
            LayerNorm(d_model) if normalize_before else None)

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)
