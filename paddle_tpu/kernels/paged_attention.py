"""Pallas ragged paged attention over the block-paged KV cache.

TPU-native decode attention for the LLM serving subsystem
(paddle_tpu/serving_llm): K/V live in fixed-size token blocks inside a
preallocated pool, and each sequence owns a block TABLE instead of a
contiguous cache (PAPERS.md "Ragged Paged Attention", arxiv
2604.15464). One query token per sequence attends over that sequence's
ragged context — continuous batching means every sequence in the batch
has a different length, so a dense [B, T_max, ...] cache would waste
HBM quadratically with pool churn.

Layout: q is [B, H, D] (the single new token per running sequence);
k_pool/v_pool are [N_blocks, block_size, H, D] — the pool layout the
engine writes token-by-token. block_tables is [B, max_blocks] int32
(entries past a sequence's block count are ignored; the host wrapper
clamps them in-range so the prefetched DMA stays legal), context_lens
is [B] int32 (valid tokens, INCLUDING the one at q's position).

Grid is (B, max_blocks) with the block scan sequential in the minor
dim: the block table rides pltpu.PrefetchScalarGridSpec as a
scalar-prefetch operand, so each program's K/V block DMA is indexed
``tables[b, j]`` — the gather happens in the BlockSpec index map, not
as a materialized jnp.take. The online-softmax carry (acc, m, l)
lives in scratch across the j scan, exactly like flash_attention's
fori_loop carry but spread over grid steps; ``pl.when(j*bs < ctx)``
skips whole blocks past a sequence's length, which is what makes the
ragged batch cost proportional to real tokens, not to max_blocks.

``interpret=True`` runs the same kernel under the Pallas interpreter
on CPU — tier-1's parity tests (vs dense attention, <=2e-6 fp32) and
the loopback serving tests ride that path.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.xprof import note_kernel

_NEG_INF = -1e30


def paged_attention_work(b: int, q_rows: int, h: int, d: int,
                         table_tokens: int, itemsize: int):
    """(FLOPs, HBM bytes) of one call, as an UPPER bound from shapes:
    every sequence attending its whole block table (``table_tokens`` =
    max_blocks * block_size). The live context lengths are runtime
    values; whole blocks past them are skipped, so the work done is
    this times the table's fill. QK^T and PV over ``q_rows`` query
    rows a sequence; K and V blocks, q and the output moved once."""
    return (4.0 * b * q_rows * h * table_tokens * d,
            float(b * h * d * itemsize * (2 * table_tokens
                                          + 2 * q_rows)))

# Grid dims: (sequence, kv-block scan). The scan dim carries the
# online-softmax state in scratch, so it MUST run sequentially;
# sequences are independent.
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _paged_attn_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, block_size: int,
                       scale: float):
    # tables_ref/lens_ref are the scalar-prefetch operands — already
    # consumed by the index maps; the kernel re-reads lens for masking.
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    ctx = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_size < ctx)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale     # [H, D]
        k = k_ref[0].astype(jnp.float32)             # [BS, H, D]
        v = v_ref[0].astype(jnp.float32)
        # head-batched q·k^T: batch H, contract D -> [H, BS]. The left
        # operand carries a unit row dim: Mosaic cannot express a
        # batched dot whose lhs has no free dimension.
        s = jax.lax.dot_general(
            q[:, None, :], k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)[:, 0, :]
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < ctx, s, _NEG_INF)        # ragged tail mask
        m_prev = m_ref[...][:, :1]                   # [H, 1]
        l_prev = l_ref[...][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # head-batched p·v: batch H, contract BS -> [H, D]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p[:, None, :], v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)[:, 0, :]
        # m/l replicate across the 128-lane minor dim (scratch keeps
        # the vector tiling happy; column 0 is the value)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nb - 1)
    def _finish():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    scale: Optional[float] = None,
                    interpret: bool = False):
    """Ragged paged decode attention.

    q: [B, H, D] — one query token per running sequence.
    k_pool/v_pool: [N_blocks, block_size, H, D] shared block pools.
    block_tables: [B, max_blocks] int — per-sequence pool indices;
        entries at/after ceil(ctx/block_size) are ignored.
    context_lens: [B] int — valid tokens per sequence (>= 1; the
        query's own K/V must already be written into the pool).

    Returns [B, H, D] attention outputs in q's dtype (fp32 math).

    Dispatches through a per-(scale, interpret) jitted wrapper (a
    nested jit inlines under an outer trace): the Pallas interpreter
    is orders of magnitude slower re-traced per eager call than
    compiled once per shape, and the serving decode loop calls this
    every step.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(int(q.shape[-1]))
    return _paged_attention_jitted(float(scale), bool(interpret))(
        q, k_pool, v_pool, block_tables, context_lens)


@functools.lru_cache(maxsize=None)
def _paged_attention_jitted(scale: float, interpret: bool):
    return jax.jit(functools.partial(_paged_attention_impl, scale=scale,
                                     interpret=interpret))


def _paged_attention_impl(q, k_pool, v_pool, block_tables, context_lens,
                          scale: Optional[float] = None,
                          interpret: bool = False):
    b, h, d = q.shape
    n_blocks, block_size = int(k_pool.shape[0]), int(k_pool.shape[1])
    max_blocks = int(block_tables.shape[1])
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # Past-end table entries may be garbage (freed/unassigned): clamp
    # in-range so the prefetched block DMA is always legal — the
    # in-kernel pl.when + position mask discard the fetched values.
    tables = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0,
                      n_blocks - 1)
    lens = jnp.asarray(context_lens, jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_blocks),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bi, j, tbl, ln: (bi, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_size, h, d),
                         lambda bi, j, tbl, ln: (tbl[bi, j], 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_size, h, d),
                         lambda bi, j, tbl, ln: (tbl[bi, j], 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, h, d),
                               lambda bi, j, tbl, ln: (bi, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),    # acc
            pltpu.VMEM((h, 128), jnp.float32),  # running max
            pltpu.VMEM((h, 128), jnp.float32),  # running denom
        ],
    )
    kernel = functools.partial(_paged_attn_kernel,
                               block_size=block_size, scale=scale)
    note_kernel("paged_decode", *paged_attention_work(
        b, 1, h, d, max_blocks * block_size, q.dtype.itemsize))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
        compiler_params=_GRID_SEMANTICS,
        name="paged_decode",
    )(tables, lens, q, k_pool, v_pool)


def _paged_attn_mq_kernel(tables_ref, lens_ref, qlens_ref, q_ref, k_ref,
                          v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                          block_size: int, scale: float):
    """Multi-query generalization of :func:`_paged_attn_kernel`: the
    block carries a whole ragged query WINDOW ([Qmax, H, D] per
    sequence) instead of one token. Query window position qi sits at
    absolute position ``ctx - q_len + qi`` and may attend keys
    [0, that position] — the causal mask of a speculative-decode
    verify window against its paged context. Padded window rows
    (qi >= q_len) attend the whole context (no NaN) and are discarded
    by the caller."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    ctx = lens_ref[b]
    qlen = qlens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_size < ctx)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale     # [Q, H, D]
        k = k_ref[0].astype(jnp.float32)             # [BS, H, D]
        v = v_ref[0].astype(jnp.float32)
        # head-batched q·k^T: batch H, contract D -> [H, Q, BS]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32)
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        qpos = ctx - qlen + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where((kpos <= qpos) & (kpos < ctx), s, _NEG_INF)
        m_prev = m_ref[...][:, :, :1]                # [H, Q, 1]
        l_prev = l_ref[...][:, :, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # head-batched p·v: batch H, contract BS -> [H, Q, D]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nb - 1)
    def _finish():
        l = l_ref[...][:, :, :1]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)   # [H, Q, D]
        o_ref[0] = jnp.transpose(out, (1, 0, 2)).astype(o_ref.dtype)


def paged_attention_multiquery(q, q_lens, k_pool, v_pool, block_tables,
                               context_lens,
                               scale: Optional[float] = None,
                               interpret: bool = False):
    """Ragged MULTI-QUERY paged attention — the speculative-decode
    verify step, where every sequence carries a short window of 1..k+1
    fresh query tokens over its paged context.

    q: [B, Qmax, H, D] — per-sequence query windows, right-padded to
        the batch max; rows at/after ``q_lens[b]`` are padding whose
        outputs the caller must ignore.
    q_lens: [B] int — valid window rows per sequence (1..Qmax).
    context_lens: [B] int — valid tokens per sequence INCLUDING the
        whole window (the window's K/V must already be written into
        the pool); requires ``context_lens >= q_lens``.
    Remaining arguments as :func:`paged_attention`.

    Returns [B, Qmax, H, D]. Window position qi attends key positions
    [0, ctx - q_len + qi] — exactly the causal continuation mask, so
    ``q_len == 1`` is today's single-token decode. A Qmax == 1 call
    routes through the existing single-query kernel unchanged
    (bit-compatible with the non-speculative decode path)."""
    if scale is None:
        scale = 1.0 / math.sqrt(int(q.shape[-1]))
    if int(q.shape[1]) == 1:
        out = _paged_attention_jitted(float(scale), bool(interpret))(
            q[:, 0], k_pool, v_pool, block_tables, context_lens)
        return out[:, None]
    return _paged_attention_mq_jitted(float(scale), bool(interpret))(
        q, q_lens, k_pool, v_pool, block_tables, context_lens)


@functools.lru_cache(maxsize=None)
def _paged_attention_mq_jitted(scale: float, interpret: bool):
    return jax.jit(functools.partial(_paged_attention_mq_impl,
                                     scale=scale, interpret=interpret))


def _paged_attention_mq_impl(q, q_lens, k_pool, v_pool, block_tables,
                             context_lens,
                             scale: Optional[float] = None,
                             interpret: bool = False):
    b, qmax, h, d = q.shape
    n_blocks, block_size = int(k_pool.shape[0]), int(k_pool.shape[1])
    max_blocks = int(block_tables.shape[1])
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    tables = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0,
                      n_blocks - 1)
    lens = jnp.asarray(context_lens, jnp.int32)
    qlens = jnp.asarray(q_lens, jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, max_blocks),
        in_specs=[
            pl.BlockSpec((1, qmax, h, d),
                         lambda bi, j, tbl, ln, ql: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_size, h, d),
                         lambda bi, j, tbl, ln, ql:
                         (tbl[bi, j], 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_size, h, d),
                         lambda bi, j, tbl, ln, ql:
                         (tbl[bi, j], 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, qmax, h, d),
                               lambda bi, j, tbl, ln, ql: (bi, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((h, qmax, d), jnp.float32),    # acc
            pltpu.VMEM((h, qmax, 128), jnp.float32),  # running max
            pltpu.VMEM((h, qmax, 128), jnp.float32),  # running denom
        ],
    )
    kernel = functools.partial(_paged_attn_mq_kernel,
                               block_size=block_size, scale=scale)
    note_kernel("paged_ragged", *paged_attention_work(
        b, qmax, h, d, max_blocks * block_size, q.dtype.itemsize))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, qmax, h, d), q.dtype),
        interpret=interpret,
        compiler_params=_GRID_SEMANTICS,
        name="paged_ragged",
    )(tables, lens, qlens, q, k_pool, v_pool)


def paged_attention_multiquery_reference(q, q_lens, k_pool, v_pool,
                                         block_tables, context_lens,
                                         scale: Optional[float] = None):
    """Dense XLA reference for the multi-query verify kernel: gather
    each sequence's blocks, apply the window-causal mask (window row
    qi attends keys [0, ctx - q_len + qi]), plain softmax attention.
    The parity oracle for the multi-query kernel tests."""
    b, qmax, h, d = q.shape
    block_size = int(k_pool.shape[1])
    max_blocks = int(block_tables.shape[1])
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(context_lens, jnp.int32)
    qlens = jnp.asarray(q_lens, jnp.int32)
    k = jnp.take(k_pool, tables, axis=0).reshape(
        b, max_blocks * block_size, h, d)
    v = jnp.take(v_pool, tables, axis=0).reshape(
        b, max_blocks * block_size, h, d)
    s = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    kpos = jnp.arange(max_blocks * block_size,
                      dtype=jnp.int32)[None, None, None, :]
    qpos = (lens - qlens)[:, None, None, None] + jnp.arange(
        qmax, dtype=jnp.int32)[None, None, :, None]
    mask = (kpos <= qpos) & (kpos < lens[:, None, None, None])
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqt,bthd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_reference(q, k_pool, v_pool, block_tables,
                              context_lens,
                              scale: Optional[float] = None):
    """Dense XLA reference: gather each sequence's blocks, run plain
    softmax attention. The parity oracle for the kernel tests and the
    numerics contract for anything routing around the kernel."""
    b, h, d = q.shape
    block_size = int(k_pool.shape[1])
    max_blocks = int(block_tables.shape[1])
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    tables = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(context_lens, jnp.int32)
    # [B, max_blocks*block_size, H, D] contiguous view of each table
    k = jnp.take(k_pool, tables, axis=0).reshape(
        b, max_blocks * block_size, h, d)
    v = jnp.take(v_pool, tables, axis=0).reshape(
        b, max_blocks * block_size, h, d)
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    pos = jnp.arange(max_blocks * block_size, dtype=jnp.int32)
    s = jnp.where(pos[None, None, :] < lens[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
