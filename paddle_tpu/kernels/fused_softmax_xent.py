"""Pallas fused MLM-head + softmax cross-entropy loss-region kernel.

TPU-native fusion of the hidden->vocab projection with the softmax
cross-entropy that consumes it (the "loss region" of a masked-LM step).
The reference fuses softmax+xent in softmax_with_cross_entropy_op.cu but
still materializes the [B, T, V] logits; for BERT's 30k vocab that
tensor is the biggest array in the step (~300 MB at b8 x s512 in fp32).
Following the blocked-primitive shape of "Tensor Processing Primitives"
(arxiv 2104.05755) and the flash-attention online-softmax idiom already
used by kernels/flash_attention.py, the forward streams the vocab
dimension through VMEM in chunks, carrying a running max ``m``, running
denominator ``s`` and the picked-label logit per row — the logits never
exist in HBM, only [N]-sized vectors leave the kernel:

    loss_i = logsumexp_j(h_i . w_j + b_j) - (h_i . w_label + b_label)

The backward recomputes each logits chunk in the same sweep and fuses
``dlogits = g * (softmax - onehot)`` directly into the two contractions
that consume it (``dh = dlogits @ W``, ``dW = dlogits^T @ h``,
``db = colsum(dlogits)``) — so the backward never materializes dlogits
either.  Two kernels because a Pallas output block is only resident
across the innermost grid dimension: ``dh`` accumulates over vocab
chunks (rows outer), ``dW``/``db`` accumulate over row blocks (vocab
outer).

Semantics match ops/loss.py softmax_with_cross_entropy's hard-label hot
path to fp32 tolerance (the online log-sum-exp rounds differently than
the two-pass jax.scipy logsumexp): f32 reductions regardless of input
dtype, ``ignore_index`` rows contribute exactly 0.0 loss and 0 gradient.
Routed via kernels.maybe_fused_linear_xent behind
FLAGS_fused_softmax_xent (off by default until a chip capture lands —
capture stages bert_b16_fusedloss / bert_b16_fusedloss_fusedadam).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.xprof import note_kernel

_ROW_BLOCK = 256     # row tile (second-to-minor: multiple of 8)
_VOCAB_BLOCK = 512   # vocab tile (minor: multiple of 128)
# finite -inf stand-in: exp(_NEG - m) underflows to exactly 0.0 and
# never produces the inf - inf = NaN a true -inf init would
_NEG = -1e30

# the inner grid dimension accumulates into the resident output block,
# so it must be sequential ("arbitrary"); rows/vocab-outer can go wide
_GRID_SEQ = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _ceil_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _chunk_logits(h_ref, w_ref, b_ref):
    """One (rows x vocab-chunk) logits tile in f32 on the MXU."""
    return jax.lax.dot_general(
        h_ref[:], w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + b_ref[:]


def _fwd_kernel(h_ref, w_ref, b_ref, lab_ref, m_ref, s_ref, pick_ref, *,
                block_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref[:], _NEG)
        s_ref[:] = jnp.zeros_like(s_ref[:])
        pick_ref[:] = jnp.zeros_like(pick_ref[:])

    logits = _chunk_logits(h_ref, w_ref, b_ref)
    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    s_ref[:] = s_ref[:] * jnp.exp(m_prev - m_new) \
        + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True)
    m_ref[:] = m_new
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    pick_ref[:] = pick_ref[:] + jnp.sum(
        jnp.where(lab_ref[:] == cols, logits, 0.0), axis=1,
        keepdims=True)


def _bwd_dh_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref, dh_ref,
                   *, block_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dh_ref[:] = jnp.zeros_like(dh_ref[:])

    logits = _chunk_logits(h_ref, w_ref, b_ref)
    p = jnp.exp(logits - lse_ref[:])
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    dlog = g_ref[:] * (p - (lab_ref[:] == cols).astype(jnp.float32))
    dh_ref[:] = dh_ref[:] + jax.lax.dot_general(
        dlog, w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_dw_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref, dw_ref,
                   db_ref, *, block_v: int):
    jv = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref[:])
        db_ref[:] = jnp.zeros_like(db_ref[:])

    logits = _chunk_logits(h_ref, w_ref, b_ref)
    p = jnp.exp(logits - lse_ref[:])
    cols = jv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1)
    dlog = g_ref[:] * (p - (lab_ref[:] == cols).astype(jnp.float32))
    dw_ref[:] = dw_ref[:] + jax.lax.dot_general(
        dlog, h_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db_ref[:] = db_ref[:] + jnp.sum(dlog, axis=0, keepdims=True)


def fused_xent_work(n: int, v: int, hd: int, matmuls: int = 1,
                    written: int = 0):
    """(FLOPs, HBM bytes) one call must do over ``n`` positions, a
    vocabulary of ``v`` and hidden width ``hd``, operands as padded.
    The forward runs the projection once (``2*N*V*H``); each backward
    kernel recomputes it and runs one more matmul (dh = dlogits W, or
    dW = dlogits^T h). Bytes: the f32 hidden rows, weight and bias read
    once (the logits never exist in HBM) and ``written`` f32 elements
    of result."""
    return (2.0 * matmuls * n * v * hd,
            float(4 * (n * hd + v * hd + v + written)))


def _padded_operands(h2, w, b2, lab, bn, bv):
    """Pad to tile multiples. Vocab padding gets bias _NEG so padded
    columns vanish from both the LSE (exp underflows to 0) and the
    backward softmax; padded rows get label -1 (matches nothing)."""
    n, hd = h2.shape
    v = w.shape[0]
    n_pad = _ceil_to(max(n, 1), bn)
    v_pad = _ceil_to(v, bv)
    h_pad = _ceil_to(hd, 128)
    hp = jnp.pad(h2, ((0, n_pad - n), (0, h_pad - hd)))
    wp = jnp.pad(w, ((0, v_pad - v), (0, h_pad - hd)))
    bp = jnp.pad(b2.astype(jnp.float32).reshape(1, v),
                 ((0, 0), (0, v_pad - v)), constant_values=_NEG)
    labp = jnp.pad(lab.reshape(n, 1), ((0, n_pad - n), (0, 0)),
                   constant_values=-1)
    return hp, wp, bp, labp, n_pad, v_pad, h_pad


def _forward(h2, w, b2, lab, ignore_index, bn, bv, interpret):
    n = h2.shape[0]
    hp, wp, bp, labp, n_pad, v_pad, h_pad = _padded_operands(
        h2, w, b2, lab, bn, bv)
    grid = (n_pad // bn, v_pad // bv)
    ms = {} if interpret else {"memory_space": pltpu.VMEM}
    row_spec = pl.BlockSpec((bn, 1), lambda i, j: (i, 0), **ms)
    m, s, picked = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=bv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, h_pad), lambda i, j: (i, 0), **ms),
            pl.BlockSpec((bv, h_pad), lambda i, j: (j, 0), **ms),
            pl.BlockSpec((1, bv), lambda i, j: (0, j), **ms),
            row_spec,
        ],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((n_pad, 1), jnp.float32)] * 3,
        compiler_params=_GRID_SEQ,
        interpret=interpret,
        name="fused_xent_fwd",
    )(hp, wp, bp, labp)
    note_kernel("fused_xent_fwd", *fused_xent_work(
        n_pad, v_pad, h_pad, written=3 * n_pad))
    lse = (m + jnp.log(s))[:n, 0]
    picked = picked[:n, 0]
    loss = jnp.where(lab != ignore_index, lse - picked, 0.0)
    return loss, lse


def _backward(res, g, ignore_index, bn, bv, interpret):
    h2, w, b2, lab, lse = res
    n, hd = h2.shape
    v = w.shape[0]
    hp, wp, bp, labp, n_pad, v_pad, h_pad = _padded_operands(
        h2, w, b2, lab, bn, bv)
    # padded rows get lse=+1e30 so their recomputed softmax underflows
    # to 0 (their h is zero-padded but the bias row is real-valued)
    lsep = jnp.pad(lse.reshape(n, 1), ((0, n_pad - n), (0, 0)),
                   constant_values=-_NEG)
    gv = jnp.where(lab != ignore_index, g.astype(jnp.float32), 0.0)
    gp = jnp.pad(gv.reshape(n, 1), ((0, n_pad - n), (0, 0)))
    ms = {} if interpret else {"memory_space": pltpu.VMEM}
    n_blocks, v_blocks = n_pad // bn, v_pad // bv
    row_spec = pl.BlockSpec((bn, 1), lambda i, j: (i, 0), **ms)
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, block_v=bv),
        grid=(n_blocks, v_blocks),
        in_specs=[
            pl.BlockSpec((bn, h_pad), lambda i, j: (i, 0), **ms),
            pl.BlockSpec((bv, h_pad), lambda i, j: (j, 0), **ms),
            pl.BlockSpec((1, bv), lambda i, j: (0, j), **ms),
            row_spec, row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((bn, h_pad), lambda i, j: (i, 0), **ms),
        out_shape=jax.ShapeDtypeStruct((n_pad, h_pad), jnp.float32),
        compiler_params=_GRID_SEQ,
        interpret=interpret,
        name="fused_xent_bwd_dh",
    )(hp, wp, bp, labp, lsep, gp)
    note_kernel("fused_xent_bwd_dh", *fused_xent_work(
        n_pad, v_pad, h_pad, matmuls=2, written=n_pad * h_pad))
    col_spec = pl.BlockSpec((bn, 1), lambda jv, i: (i, 0), **ms)
    dw, db = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, block_v=bv),
        grid=(v_blocks, n_blocks),
        in_specs=[
            pl.BlockSpec((bn, h_pad), lambda jv, i: (i, 0), **ms),
            pl.BlockSpec((bv, h_pad), lambda jv, i: (jv, 0), **ms),
            pl.BlockSpec((1, bv), lambda jv, i: (0, jv), **ms),
            col_spec, col_spec, col_spec,
        ],
        out_specs=[
            pl.BlockSpec((bv, h_pad), lambda jv, i: (jv, 0), **ms),
            pl.BlockSpec((1, bv), lambda jv, i: (0, jv), **ms),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((v_pad, h_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, v_pad), jnp.float32),
        ],
        compiler_params=_GRID_SEQ,
        interpret=interpret,
        name="fused_xent_bwd_dw",
    )(hp, wp, bp, labp, lsep, gp)
    note_kernel("fused_xent_bwd_dw", *fused_xent_work(
        n_pad, v_pad, h_pad, matmuls=2, written=v_pad * h_pad + v_pad))
    return dh[:n, :hd], dw[:v, :hd], db[0, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused_core(h2, w, b2, lab, ignore_index, bn, bv, interpret):
    loss, _ = _forward(h2, w, b2, lab, ignore_index, bn, bv, interpret)
    return loss


def _fused_core_fwd(h2, w, b2, lab, ignore_index, bn, bv, interpret):
    loss, lse = _forward(h2, w, b2, lab, ignore_index, bn, bv,
                         interpret)
    # residuals are the [N]-sized lse plus the operands the backward
    # recomputes from — never the [N, V] logits/softmax
    return loss, (h2, w, b2, lab, lse)


def _fused_core_bwd(ignore_index, bn, bv, interpret, res, g):
    dh, dw, db = _backward(res, g, ignore_index, bn, bv, interpret)
    h2, w, b2, lab, _ = res
    return (dh.astype(h2.dtype), dw.astype(w.dtype),
            db.astype(b2.dtype),
            np.zeros(lab.shape, jax.dtypes.float0))


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


def fused_linear_softmax_xent(hidden, weight, bias, labels,
                              ignore_index: int = -100,
                              block_rows: int = _ROW_BLOCK,
                              block_vocab: int = _VOCAB_BLOCK,
                              interpret: bool = False):
    """Per-position softmax cross-entropy of the never-materialized
    ``logits = hidden @ weight.T + bias``.

    hidden: [..., H]; weight: [V, H]; bias: [V] f32 or None;
    labels: [...] int (same leading shape as hidden). Returns f32 loss
    of labels' shape: ``lse - logit[label]``, 0.0 where
    ``label == ignore_index``. Differentiable w.r.t. hidden, weight and
    bias (custom_vjp; chunked recompute backward).
    """
    lead = hidden.shape[:-1]
    hd = hidden.shape[-1]
    n = int(np.prod(lead)) if lead else 1
    h2 = hidden.reshape(n, hd)
    lab = labels.reshape(n).astype(jnp.int32)
    v = weight.shape[0]
    b2 = jnp.zeros((v,), jnp.float32) if bias is None else bias
    bn = min(block_rows, _ceil_to(n, 8))
    bv = min(block_vocab, _ceil_to(v, 128))
    loss = _fused_core(h2, weight, b2, lab, int(ignore_index), bn, bv,
                       bool(interpret))
    return loss.reshape(lead)
