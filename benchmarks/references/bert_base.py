"""BERT pretraining (MLM on the predicted positions + NSP), plain.

Follows Devlin et al. 2018 / google-research/bert ``modeling.py``:
post-norm encoder, exact (erf) GELU, tied MLM decoder. Departures, all
to match what ``paddle_tpu.models.BertForPretraining`` states it
computes: no token-type embedding is added when no segment ids are
given (the benchmark gives none); the encoder layers' layer norms use
epsilon 1e-5 (``nn.LayerNorm``'s default; the embedding and head norms
use the published 1e-12); no attention mask (all 512 positions are
real tokens). Dropout is off: the comparison is made in eval mode."""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISION = "highest"


def _f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _lin(p, name, x):
    return x @ p[name + ".weight"] + p[name + ".bias"]


def encode(p, cfg, ids):
    """ids [B, S] -> (sequence output [B, S, H], pooled [B, H])."""
    b, s = ids.shape
    heads = cfg["num_attention_heads"]
    x = p["bert.embeddings.word_embeddings.weight"][ids] \
        + p["bert.embeddings.position_embeddings.weight"][:s][None]
    x = _ln(x, p["bert.embeddings.layer_norm.weight"],
            p["bert.embeddings.layer_norm.bias"], 1e-12)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"bert.encoder.layers.{i}."
        d = x.shape[-1] // heads

        def split(t):
            return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

        q = split(_lin(p, pre + "self_attn.q_proj", x))
        k = split(_lin(p, pre + "self_attn.k_proj", x))
        v = split(_lin(p, pre + "self_attn.v_proj", x))
        w = jax.nn.softmax(q @ k.transpose(0, 1, 3, 2)
                           / jnp.sqrt(jnp.float32(d)), axis=-1)
        a = (w @ v).transpose(0, 2, 1, 3).reshape(b, s, heads * d)
        x = _ln(x + _lin(p, pre + "self_attn.out_proj", a),
                p[pre + "norm1.weight"], p[pre + "norm1.bias"], 1e-5)
        f = _lin(p, pre + "linear2", jax.nn.gelu(
            _lin(p, pre + "linear1", x), approximate=False))
        x = _ln(x + f, p[pre + "norm2.weight"], p[pre + "norm2.bias"],
                1e-5)
    pooled = jnp.tanh(_lin(p, "bert.pooler", x[:, 0]))
    return x, pooled


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def loss(params, cfg, ids, masked_positions, mlm_labels, nsp_labels):
    """Mean MLM cross-entropy over the predicted positions plus mean
    NSP cross-entropy, float32."""
    with jax.default_matmul_precision(PRECISION):
        p = _f32(params)
        x, pooled = encode(p, cfg, ids)
        x = jnp.take_along_axis(
            x, masked_positions[:, :, None].astype(jnp.int32), axis=1)
        h = _ln(jax.nn.gelu(_lin(p, "cls.transform", x),
                            approximate=False),
                p["cls.transform_norm.weight"],
                p["cls.transform_norm.bias"], 1e-12)
        mlm = h @ p["bert.embeddings.word_embeddings.weight"].T \
            + p["cls.decoder_bias"]
        nsp = _lin(p, "cls.seq_relationship", pooled)
        return _xent(mlm, mlm_labels.astype(jnp.int32)) \
            + _xent(nsp, nsp_labels.astype(jnp.int32))
