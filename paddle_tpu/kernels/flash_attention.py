"""Pallas flash attention (forward AND backward) with custom VJP.

TPU-native replacement for the reference's fused attention CUDA kernels
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu,
operators/math/bert_encoder_functor.cu MultiHeadGPUComputeFunctor). Those
kernels materialize the [T, T] score matrix in global memory; this kernel
uses the online-softmax blocked algorithm so scores never leave VMEM —
O(T) HBM traffic instead of O(T²), which is what makes long-context
feasible on TPU.

Layout: q, k, v are [B, H, T, D]. Grid is (B*H, Tq/BLOCK_Q); the kernel
scans K/V blocks with lax.fori_loop carrying (acc, row_max, row_sum).
Backward is the recompute-based flash backward as TWO Pallas kernels
(fwd saves only out + logsumexp; delta = rowsum(dO*O) is one cheap XLA
reduction): a dq kernel blocked over queries scanning K/V, and a dk/dv
kernel blocked over keys scanning Q/dO. Scores are recomputed blockwise
in VMEM, so the backward keeps the O(T) memory property too — the
previous XLA einsum backward materialized the full [B, H, T, T] scores
in fp32, which silently forfeited long-context training.

Masks: none, causal, causal within a sliding window of ``window`` keys
(``swa_flash_*``), and the block-diffusion rule (``bd_flash_*``). Each
kernel walks only the key (or query) tiles that hold an allowed pair,
and the tiles whose every pair is allowed without a mask.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.random import fmix32 as _fmix32
from ..observability import xprof
from ..observability.xprof import note_kernel

# 512 tiles measured fastest on chip (r5 d64 train sweep, v5e:
# 512-tile 1.18x/1.58x/2.08x vs XLA at seq 1k/2k/4k, dominating
# 256-tile 1.08x/1.36x/1.65x; 128-tile loses to XLA beyond 512).
BLOCK_Q = 512
BLOCK_K = 512
_NEG_INF = -1e30


def _block_sizes(tq: int, tk: int):
    """Kernel tile sizes, tunable per chip session via the
    flash_block_q/k flags (FLAGS_flash_block_q=... env works too) so a
    capture stage can sweep tiles without code edits. Flag value 0 (the
    default) means "use the module constants" — tests monkeypatch
    BLOCK_Q/BLOCK_K to force multi-block/tail paths and must keep
    working. Clamped to the sequence lengths."""
    bq, bk = 0, 0
    try:
        from ..flags import get_flags
        f = get_flags(["flash_block_q", "flash_block_k"])
        bq, bk = int(f["flash_block_q"]), int(f["flash_block_k"])
    # ptlint: disable=silent-failure -- kernels must stay importable standalone (no flags module); the compiled-in block defaults below apply
    except Exception:  # noqa: BLE001 — kernels stay importable alone
        pass
    bq, bk = bq or BLOCK_Q, bk or BLOCK_K
    return min(bq, tq), min(bk, tk)


def flash_fwd_work(b: int, h: int, tq: int, tk: int, d: int,
                   itemsize: int, causal: bool = False, bd=None,
                   d_v: Optional[int] = None,
                   window: Optional[int] = None):
    """(FLOPs, HBM bytes) one forward call must do: the two matmuls
    QK^T at the q/k width ``d`` and PV at the value width ``d_v`` (``d``
    unless given): ``2*B*H*Tq*Tk*(d + d_v)``, half of it under a causal
    mask, ``2*B*H*(d + d_v)*(L^2 + K*L)`` under the block-diffusion mask
    ``bd = (L, K)`` and ``2*B*H*(d + d_v)*window_allowed_pairs`` under a
    sliding window: the allowed pairs, never the tiles visited); q, k,
    v read and the output written once, plus the f32 logsumexp row."""
    d_v = d if d_v is None else d_v
    flops = 2.0 * b * h * tq * tk * (d + d_v)
    if bd is not None:
        flops = 2.0 * b * h * (d + d_v) * bd_allowed_pairs(*bd)
    elif window is not None:
        flops = 2.0 * b * h * (d + d_v) * window_allowed_pairs(tq, tk,
                                                               window)
    elif causal:
        flops /= 2
    return flops, float(b * h * ((tq + tk) * d + (tk + tq) * d_v)
                        * itemsize + 4 * b * h * tq)


def flash_bwd_work(b: int, h: int, tq: int, tk: int, d: int,
                   itemsize: int, causal: bool = False,
                   matmuls: int = 5, bd=None, d_v: Optional[int] = None,
                   window: Optional[int] = None):
    """(FLOPs, HBM bytes) one backward call must do. The recompute
    backward runs five matmuls — S = QK^T, dQ = dS K and dK = dS^T Q at
    the q/k width ``d``, dP = dO V^T and dV = P^T dO at the value width
    ``d_v`` (``d`` unless given): ``2*B*H*Tq*Tk*(3 d + 2 d_v)`` — in the
    fused single-block kernel; split in two, the dq kernel runs three of
    them (S, dP, dQ: ``2 d + d_v``) and the dk/dv kernel four (S, dP,
    dV, dK: ``2 d + 2 d_v``). Bytes: q, k, v, dO read, the two f32 row
    vectors (lse, delta), and the gradients this call writes."""
    d_v = d if d_v is None else d_v
    width = {5: 3 * d + 2 * d_v, 3: 2 * d + d_v, 4: 2 * d + 2 * d_v}
    flops = 2.0 * b * h * tq * tk * width[matmuls]
    if bd is not None:      # the allowed pairs, as in flash_fwd_work
        flops = 2.0 * b * h * width[matmuls] * bd_allowed_pairs(*bd)
    elif window is not None:
        flops = 2.0 * b * h * width[matmuls] * window_allowed_pairs(
            tq, tk, window)
    elif causal:
        flops /= 2
    written = {5: (tq + tk) * d + tk * d_v, 3: tq * d,
               4: tk * (d + d_v)}[matmuls]
    return flops, float(b * h * ((tq + tk) * d + (tk + tq) * d_v
                                 + written) * itemsize + 8 * b * h * tq)


def _heads_per_block(d: int, h: int) -> int:
    """How many heads share one program in the [B, T, H, D] layout.
    Mosaic requires the minor block dim be a multiple of 128 (or the
    whole array dim), so a d=64 head slab must ride as a head PAIR
    (128 lanes); d%128 heads ride alone. Callers gate unsupported
    combinations to the transpose path before reaching the kernel."""
    if not bthd_supported(d, h):
        raise ValueError(
            f"flash_attention bthd layout needs d%128==0 or (d%64==0 "
            f"and even heads); got d={d}, h={h} — route via the BHTD "
            "layout")
    return 1 if d % 128 == 0 else 2


def bthd_supported(d: int, h: int, d_v: Optional[int] = None) -> bool:
    """Whether the transpose-free [B, T, H, D] layout can ride the
    kernel for this geometry — the single home of the tiling rule
    (_heads_per_block gates on it). Heads share a program by the q/k
    width ``d``; the value width ``d_v`` (``d`` unless given) must then
    fill whole 128-lane blocks with the same heads."""
    if not (d % 128 == 0 or ((2 * d) % 128 == 0 and h % 2 == 0)):
        return False
    return d_v is None or (d_v * (1 if d % 128 == 0 else 2)) % 128 == 0


# Both grid dims of every flash kernel — (batch*heads, block index) —
# are independent: each program writes an exclusive output block and
# the sequential scan lives INSIDE the kernel (fori_loop). Telling
# Mosaic so lets it pipeline/parallelize grid iterations instead of the
# conservative sequential default. Pure scheduling hint: numerics are
# identical (interpret-mode tests + the compiled verify stage cover it).
_GRID_PARALLEL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"))


def _grid_params(*resident_bytes: int):
    """Every program keeps whole sequences resident in VMEM (the key
    and value, or the query and dO, of its head; double-buffered), and
    the compiler's scoped limit is 16 MiB of the chip's 128: at 8192
    positions of a 128-wide head the dk/dv kernel needs 25. Past a
    quarter of the limit the call asks for what its resident operands
    take plus room for a block's working set; below it (every shape
    the kernels ran at before) the parameters are the shared default
    and the compiled program is what it was."""
    resident = 2 * sum(resident_bytes)
    if resident <= 4 * 2 ** 20:
        return _GRID_PARALLEL
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=min(resident + 24 * 2 ** 20, 100 * 2 ** 20))


def _row_bytes(rows: int) -> int:
    """A [rows, heads-per-block] float32 column in VMEM: the minor
    dimension is padded to 128 lanes."""
    return rows * 128 * 4


# -- the block-diffusion mask ---------------------------------------------------
#
# A sequence of ``length`` tokens cut in blocks of ``block`` is run as
# ``2 * length`` positions: the noised copy (positions below ``length``)
# and the clean copy after it. A noisy query sees the noisy keys of its
# own block and the clean keys of earlier blocks; a clean query the clean
# keys of its own and earlier blocks; nobody sees a noisy key of another
# block (BD3-LMs, arXiv:2503.09573, the vectorised training form). The
# kernels compute the rule from iotas and walk only the tiles that hold
# an allowed pair: for a tile of queries (or, in the dk/dv kernel, of
# keys) these are one run of tiles in the noisy half and one in the
# clean half, which the two functions below give as (first, count) each.

def _bd_check(bd, tq: int, tk: int) -> None:
    length, block = bd
    if tq != 2 * length or tk != 2 * length or length % block:
        raise ValueError(
            f"block_diffusion=({length}, {block}) wants q and k of "
            f"{2 * length} positions and whole blocks; got {tq}, {tk}")


def _bd_sides(pos, length: int, block: int):
    """(noisy, block index) of the positions ``pos``."""
    noisy = pos < length
    local = jnp.where(noisy, pos, pos - length)
    if block & (block - 1) == 0:
        return noisy, jnp.right_shift(local, block.bit_length() - 1)
    return noisy, local // block


def bd_allowed(q_pos, k_pos, seq: int, length: int, block: int):
    """The rule on a tile: ``q_pos`` [BQ, 1] and ``k_pos`` [1, BK] int32
    positions -> [BQ, BK] bool. A position past ``seq`` (a padded tail)
    neither sees nor is seen. Two compares and an ``or`` on the tile;
    the rest is on its edges."""
    q_noisy, q_blk = _bd_sides(q_pos, length, block)
    k_noisy, k_blk = _bd_sides(k_pos, length, block)
    q_real, k_real = q_pos < seq, k_pos < seq
    # a noisy key is seen by the noisy queries of its block; a clean key
    # by the queries of later blocks, and by the clean ones of its own
    same = jnp.where(jnp.logical_and(q_noisy, q_real), q_blk, -1)
    upto = jnp.where(q_real, q_blk + jnp.where(q_noisy, 0, 1), -1)
    k_same = jnp.where(jnp.logical_and(k_noisy, k_real), k_blk, -2)
    k_before = jnp.where(jnp.logical_or(k_noisy, ~k_real), 2 ** 30, k_blk)
    return jnp.logical_or(k_same == same, k_before < upto)


def _tile_positions(r0, block_q: int, c0, block_k: int):
    """Full-tile (query, key) positions: what the plain and causal masks
    compare, and what the dropout hash counts by."""
    q_pos = r0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = c0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return q_pos, k_pos


def _tile_valid(r0, block_q: int, c0, block_k: int, seq_k: int,
                causal: bool, causal_offset: int, bd, window=None):
    """[BQ, BK] bool: the pairs of an edge tile that are allowed and
    real. The block-diffusion rule from the tile's edge vectors, the
    tail, the causal and the window masks from full-tile iotas."""
    if bd is not None:
        q_pos = r0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = c0 + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        return bd_allowed(q_pos, k_pos, seq_k, *bd)
    q_pos, k_pos = _tile_positions(r0, block_q, c0, block_k)
    valid = k_pos < seq_k                              # tail-block mask
    if causal:
        valid = jnp.logical_and(valid, q_pos + causal_offset >= k_pos)
    if window is not None:
        valid = jnp.logical_and(valid, k_pos > q_pos + causal_offset
                                - window)
    return valid


def bd_key_tiles(r0, block_q: int, block_k: int, length: int, block: int):
    """Key tiles of width ``block_k`` that hold a pair allowed to the
    queries ``[r0, r0 + block_q)``: ``(first noisy, count, first clean,
    count)``, the two runs disjoint."""
    r1 = jnp.minimum(r0 + block_q, 2 * length) - 1
    has_noisy = r0 < length
    rn1 = jnp.minimum(r1, length - 1)
    n_lo = (r0 // block * block) // block_k
    n_hi = (rn1 // block * block + block - 1) // block_k + 1
    n_cnt = jnp.where(has_noisy, n_hi - n_lo, 0)
    # the clean keys end (exclusive) at the last noisy row's block, or
    # past the last clean row's
    c_end = jnp.maximum(
        jnp.where(has_noisy, length + rn1 // block * block, 0),
        jnp.where(r1 >= length,
                  (r1 - length) // block * block + block + length, 0))
    c_lo = jnp.maximum(length // block_k, jnp.where(has_noisy, n_hi, 0))
    c_hi = jnp.where(c_end > length, (c_end - 1) // block_k + 1, 0)
    return n_lo, n_cnt, c_lo, jnp.maximum(c_hi - c_lo, 0)


def bd_query_tiles(c0, block_k: int, block_q: int, length: int,
                   block: int):
    """Query tiles of height ``block_q`` that hold a pair allowed to see
    the keys ``[c0, c0 + block_k)``: ``(first noisy, count, first clean,
    count)``, the two runs disjoint."""
    c1 = jnp.minimum(c0 + block_k, 2 * length) - 1
    has_nk, has_ck = c0 < length, c1 >= length
    cn1 = jnp.minimum(c1, length - 1)
    cc0 = jnp.maximum(c0, length) - length
    # noisy rows: the blocks of the noisy keys, and for the clean keys
    # every later block's rows (the two meet when the tile straddles)
    later = cc0 // block * block + block
    has_later = jnp.logical_and(has_ck, later < length)
    big = 4 * length
    first = jnp.minimum(jnp.where(has_nk, c0 // block * block, big),
                        jnp.where(has_later, later, big))
    last = jnp.maximum(
        jnp.where(has_nk, cn1 // block * block + block - 1, -1),
        jnp.where(has_later, length - 1, -1))
    any_noisy = jnp.logical_or(has_nk, has_later)
    n_lo = first // block_q
    n_hi = last // block_q + 1
    n_cnt = jnp.where(any_noisy, n_hi - n_lo, 0)
    # clean rows: from the first clean key's block to the end
    c_lo = jnp.maximum((length + cc0 // block * block) // block_q,
                       jnp.where(any_noisy, n_hi, 0))
    c_hi = (2 * length - 1) // block_q + 1
    return n_lo, n_cnt, c_lo, jnp.where(
        has_ck, jnp.maximum(c_hi - c_lo, 0), 0)


def bd_allowed_pairs(length: int, block: int) -> int:
    """Allowed (query, key) pairs of one sequence: ``L^2 + K L`` (noisy
    to noisy ``K L``, noisy to clean ``L (L - K) / 2``, clean to clean
    ``L (L + K) / 2``)."""
    return length * length + block * length


# -- the sliding window ---------------------------------------------------------
#
# Causal attention within a window of ``window`` keys: with the
# bottom-right alignment ``o = seq_k - seq_q``, query ``q`` sees the keys
# ``q + o - window < k <= q + o``, its own included. A query tile's keys
# are then one run of tiles: a lower edge where the window starts, whole
# tiles, and the causal edge; a key tile's queries one run likewise.

def window_allowed_pairs(tq: int, tk: int, window: int) -> int:
    """Allowed (query, key) pairs of one head and sequence under the
    window: ``W (W + 1) / 2 + (L - W) W`` for ``tq == tk == L >= W``."""
    import numpy as np
    q = np.arange(tq, dtype=np.int64) + (tk - tq)
    seen = np.minimum(q, tk - 1) - np.maximum(q - window + 1, 0) + 1
    return int(np.sum(np.clip(seen, 0, None)))


def _window_of(window: Optional[int], tk: int) -> Optional[int]:
    """The window a call runs under: none where it holds every key (the
    causal kernels then run, by their own names)."""
    if window is None or window >= tk:
        return None
    return int(window)


# -- a visited tile's kind ------------------------------------------------------
#
# A tile whose every (query, key) pair is allowed and real needs no mask:
# the loop body that walks it builds no position, compares nothing and
# selects nothing. The two predicates say so from scalars alone (Python
# ints for the census, int32 scalars in a kernel), and the walks below
# cut every run of visited tiles into its whole tiles and its edges. To
# call a whole tile an edge costs its mask; to call an edge whole is a
# wrong gradient: a run of whole tiles is one on whose two ends the
# predicate itself holds (`_cut_run`), whatever the closed form said.

def bd_tile_whole(r0, block_q: int, c0, block_k: int, seq: int,
                  length: int, block: int):
    """Whether the block-diffusion rule allows every pair of the tile of
    queries ``[r0, r0 + block_q)`` and keys ``[c0, c0 + block_k)``, all
    of them real: clean keys, queries of one half, and the keys' last
    block before the first query's ``upto`` (`bd_allowed`'s own terms,
    on the tile's corner)."""
    r1, c1 = r0 + block_q, c0 + block_k
    last = (c1 - 1 - length) // block           # the keys' last block
    noisy = (r1 <= length) & (last < r0 // block)
    clean = (r0 >= length) & (r1 <= seq) & (last <= (r0 - length) // block)
    return (c0 >= length) & (c1 <= seq) & (noisy | clean)


def tile_whole(r0, c0, block_k: int, seq_k: int, causal: bool,
               causal_offset: int = 0, window: Optional[int] = None,
               block_q: int = 0):
    """The same for the plain and the causal walks: no key of the tile
    past ``seq_k`` and, under the causal mask, its last key at or before
    its first query plus ``causal_offset``; under a ``window`` too, its
    first key inside the window of the tile's last query (``r0 +
    block_q - 1``, a padded row if the tile holds one: the stricter
    reading). (A padded query row is not masked by any walk: it is
    sliced off, and its dO is zero.)"""
    c1 = c0 + block_k
    if not causal:
        return c1 <= seq_k
    whole = (c1 <= seq_k) & (c1 - 1 <= r0 + causal_offset)
    if window is None:
        return whole
    return whole & (c0 > r0 + block_q - 1 + causal_offset - window)


def _cut_run(lo, cnt, w_lo, w_hi, whole):
    """The run of tiles ``[lo, lo + cnt)`` cut at the whole tiles
    ``[w_lo, w_hi)`` a closed form found in it: ``(whole run, [the
    edges before, the edges after])``, each ``(first, count)``. The
    whole tiles of a run are an interval (every term of the predicates
    is monotone along a run), so where ``whole(tile)`` holds on both
    ends it holds between them; where it does not, the run is all
    edges."""
    hi = lo + cnt
    w_lo = jnp.clip(w_lo, lo, hi)
    w_hi = jnp.clip(w_hi, w_lo, hi)
    ok = (w_hi > w_lo) & whole(w_lo) & whole(w_hi - 1)
    w_lo, w_hi = jnp.where(ok, w_lo, lo), jnp.where(ok, w_hi, lo)
    return (w_lo, w_hi - w_lo), [(lo, w_lo - lo), (w_hi, hi - w_hi)]


# the backward kernels walk the noisy diagonal in sub-tiles of this many
# rows and keys
_SUB = 128


def _bd_aligned(block_q: int, block_k: int, seq_k: int, bd) -> bool:
    """Square tiles, halves of whole tiles and tiles of whole blocks: a
    query tile then visits its own noisy tile, a run of whole clean
    tiles and one clean edge, and the counts are static."""
    length, block = bd
    return (block_q == block_k and length % block_q == 0
            and block_q % block == 0 and seq_k == 2 * length)


def _bd_subtiled(block_q: int, block_k: int, seq_k: int, bd) -> bool:
    """Whether the dq and dk/dv kernels walk the noisy diagonal tile as
    `_SUB` x `_SUB` problems on its own diagonal, a quarter of its work
    at tiles of 512: aligned tiles of whole sub-tiles, blocks that
    divide a sub-tile. (The forward kernel walks it whole under its
    mask: in sub-tiles its row statistics cost more than the tile,
    0.8 ms a call on the chip at PR 38.)"""
    return (_bd_aligned(block_q, block_k, seq_k, bd)
            and block_q % _SUB == 0 and block_q > _SUB
            and _SUB % bd[1] == 0)


def _window_key_walk(r0, block_q: int, block_k: int, seq_q: int,
                     seq_k: int, window: int):
    """`_key_walk` under the sliding window: the keys from the first
    query's window start to the last query's own key."""
    offset = seq_k - seq_q
    if offset == 0 and block_q == block_k and window % block_k == 0:
        # square tiles, a window of whole tiles: the tile the window
        # starts in (none for the first ones), whole tiles, the diagonal
        n, i = window // block_k, r0 // block_k
        return [(jnp.maximum(i - n + 1, 0), jnp.minimum(i, n - 1))], \
            [(jnp.maximum(i - n, 0), i >= n), (i, 1)], []
    num_k = -(-seq_k // block_k)
    first = jnp.clip((r0 + offset - window + 1) // block_k, 0, num_k)
    upper = jnp.clip((r0 + block_q - 1 + offset) // block_k + 1, first,
                     num_k)
    run, edges = _cut_run(
        first, upper - first,
        -(-(r0 + block_q + offset - window) // block_k),
        jnp.minimum(r0 + offset + 1, seq_k) // block_k,
        lambda j: tile_whole(r0, j * block_k, block_k, seq_k, True, offset,
                             window, block_q))
    return [run], edges, []


def _window_query_walk(c0, block_k: int, block_q: int, num_q: int,
                       seq_k: int, offset: int, window: int):
    """`_query_walk` under the sliding window: the queries from the one
    whose own key is the tile's first to the last whose window still
    reaches its last key."""
    if offset == 0 and block_q == block_k and window % block_q == 0:
        n, j = window // block_q, c0 // block_k
        return [(j + 1, jnp.clip(num_q - j - 1, 0, n - 1))], \
            [(j, 1), (jnp.minimum(j + n, num_q - 1), j + n < num_q)], []
    lower = jnp.clip((c0 - offset) // block_q, 0, num_q)
    upper = jnp.clip((c0 + block_k - 2 - offset + window) // block_q + 1,
                     lower, num_q)
    run, edges = _cut_run(
        lower, upper - lower,
        -(-(c0 + block_k - 1 - offset) // block_q),
        (c0 - offset + window) // block_q,
        lambda i: tile_whole(i * block_q, c0, block_k, seq_k, True, offset,
                             window, block_q))
    return [run], edges, []


def _key_walk(r0, block_q: int, block_k: int, seq_q: int, seq_k: int,
              causal: bool, bd, window: Optional[int] = None):
    """The key tiles that the queries ``[r0, r0 + block_q)`` visit, by
    kind: ``(whole, edges, diagonal)``, three lists of ``(first,
    count)`` runs; ``diagonal`` is the noisy diagonal tile under the
    aligned block-diffusion walk (`_bd_aligned`), its count a bool. The
    forward and the dq kernels walk it, and the census counts it. A
    count may be a traced bool: a tile that is walked or not."""
    if window is not None:
        return _window_key_walk(r0, block_q, block_k, seq_q, seq_k, window)
    if bd is not None and _bd_aligned(block_q, block_k, seq_k, bd):
        half = bd[0] // block_q           # tiles a half
        i = r0 // block_q
        own = jnp.where(i < half, i, i - half)
        # the clean tiles before its own whole, its own clean tile an
        # edge; a noisy query tile's noisy keys are its own tile
        return [(half, own)], [(half + own, 1)], [(i, i < half)]
    if bd is not None:
        length, block = bd
        n_lo, n_cnt, c_lo, c_cnt = bd_key_tiles(r0, block_q, block_k, *bd)
        # clean keys before the first query's `upto`, in whole tiles
        upto = jnp.where(r0 < length, r0 // block,
                         (r0 - length) // block + 1)
        run, edges = _cut_run(
            c_lo, c_cnt, -(-length // block_k),
            jnp.minimum(length + upto * block, seq_k) // block_k,
            lambda j: bd_tile_whole(r0, block_q, j * block_k, block_k,
                                    seq_k, length, block))
        return [run], [(n_lo, n_cnt)] + edges, []
    num_k = -(-seq_k // block_k)
    if not causal:           # static: every tile but one that holds a tail
        return [(0, seq_k // block_k)], [(seq_k // block_k,
                                          num_k - seq_k // block_k)], []
    # bottom-right alignment: query i sees the keys [0, i + offset]; only
    # the tiles that meet the block's visible range are visited
    offset = seq_k - seq_q
    if offset == 0 and block_q == block_k:
        # square tiles on the diagonal: one edge a query tile, its own
        return [(0, r0 // block_k)], [(r0 // block_k, 1)], []
    upper = jnp.clip((r0 + block_q - 1 + offset) // block_k + 1, 1, num_k)
    run, edges = _cut_run(
        0, upper, 0, jnp.minimum(r0 + offset + 1, seq_k) // block_k,
        lambda j: tile_whole(r0, j * block_k, block_k, seq_k, True, offset))
    return [run], edges, []


def _query_walk(c0, block_k: int, block_q: int, num_q: int, seq_k: int,
                causal: bool, offset: int, bd, window: Optional[int] = None):
    """The query tiles that see the keys ``[c0, c0 + block_k)``, by kind,
    as `_key_walk` gives the key tiles: the dk/dv kernel's walk over the
    ``num_q`` tiles of the padded queries. Where ``diagonal``'s count is
    true the keys are noisy, seen by their own query tile and no other,
    and the other runs are not to be walked."""
    if window is not None:
        return _window_query_walk(c0, block_k, block_q, num_q, seq_k,
                                  offset, window)
    if bd is not None and _bd_aligned(block_q, block_k, seq_k, bd):
        half = bd[0] // block_q           # tiles a half
        j = c0 // block_k
        own = j - half
        # a noisy key tile is seen by its own query tile alone (the
        # diagonal); a clean one by the noisy and the clean tile of its
        # index under a mask, and by every later tile of both halves whole
        later = half - own - 1
        return [(own + 1, later), (half + own + 1, later)], \
            [(own, 1), (half + own, 1)], [(j, j < half)]
    if bd is not None:
        length, block = bd
        n_lo, n_cnt, c_lo, c_cnt = bd_query_tiles(c0, block_k, block_q, *bd)
        last = (c0 + block_k - 1 - length) // block   # the keys' last block

        def whole(i):
            return bd_tile_whole(i * block_q, block_q, c0, block_k, seq_k,
                                 length, block)
        # the noisy rows of later blocks, the clean rows of its own and
        # later ones, each in whole tiles of one half
        noisy, n_edges = _cut_run(
            n_lo, n_cnt, ((last + 1) * block + block_q - 1) // block_q,
            length // block_q, whole)
        clean, c_edges = _cut_run(
            c_lo, c_cnt, (length + last * block + block_q - 1) // block_q,
            seq_k // block_q, whole)
        return [noisy, clean], n_edges + c_edges, []
    if not causal:
        if seq_k % block_k == 0:
            return [(0, num_q)], [], []
        tail = c0 + block_k > seq_k          # the last key tile's program
        return [(0, jnp.where(tail, 0, num_q))], \
            [(0, jnp.where(tail, num_q, 0))], []
    if offset == 0 and block_q == block_k:
        first = c0 // block_q
        return [(first + 1, num_q - first - 1)], [(first, 1)], []
    # first q block whose last visible key reaches this k block:
    # q_pos + offset >= c0  =>  q_pos >= c0 - offset
    lower = jnp.clip((c0 - offset) // block_q, 0, num_q)
    run, edges = _cut_run(
        lower, num_q - lower,
        (c0 + block_k - 1 - offset + block_q - 1) // block_q, num_q,
        lambda i: tile_whole(i * block_q, c0, block_k, seq_k, True, offset))
    return [run], edges, []


def _is_flag(cnt) -> bool:
    return getattr(cnt, "dtype", None) == jnp.bool_


def _walk(runs, body, carry):
    """``body(tile, carry)`` over the tiles of ``runs``, in their order:
    one ``fori_loop``; none where the runs are empty by their static
    counts, or single tiles by them (a loop round one tile costs a
    program half a tile's time)."""
    runs = [r for r in runs if not (isinstance(r[1], int) and r[1] == 0)]
    if not runs:
        return carry
    if any(_is_flag(cnt) for _, cnt in runs):
        # a tile walked or not (the window's lower edge): a cond, no loop
        for lo, cnt in runs:
            carry = jax.lax.cond(cnt, functools.partial(body, lo),
                                 lambda c: c, carry) if _is_flag(cnt) \
                else _walk([(lo, cnt)], body, carry)
        return carry
    if all(isinstance(cnt, int) and cnt == 1 for _, cnt in runs):
        for lo, _ in runs:                      # single tiles: no loop
            carry = body(lo, carry)
        return carry

    def tile_of(t):
        tile, base = runs[0][0] + t, runs[0][1]
        for lo, cnt in runs[1:]:      # the last run that starts at or before t
            tile = jnp.where(t < base, tile, lo + t - base)
            base = base + cnt
        return tile

    return jax.lax.fori_loop(0, sum(cnt for _, cnt in runs),
                             lambda t, c: body(tile_of(t), c), carry)


@functools.lru_cache(maxsize=None)
def flash_tile_census(tq: int, tk: int, bq: int, bk: int,
                      causal: bool = False, bd=None,
                      window: Optional[int] = None):
    """``(visited, whole, diagonal)``: the key tiles that one head and
    sequence's forward call visits, those of them walked without a mask,
    and those walked as noisy diagonal sub-tiles. From static shapes, by
    the walk the kernels themselves run."""
    visited = whole = diagonal = 0
    with jax.ensure_compile_time_eval():
        for r0 in range(0, tq, bq):
            runs, edges, diag = _key_walk(r0, bq, bk, tq, tk, causal, bd,
                                          window)
            whole += sum(int(cnt) for _, cnt in runs)
            diagonal += sum(int(cnt) for _, cnt in diag)
            visited += sum(int(cnt) for _, cnt in runs + edges + diag)
    if bd is None or not _bd_subtiled(bq, bk, tk, bd):
        diagonal = 0            # walked whole under its mask, as an edge
    return visited, whole, diagonal


def _dropout_keep(seed, g, q_pos, k_pos, dropout_p: float):
    """Counter-based keep mask: bits are a pure hash of (seed, head,
    global q/k position), so the SAME mask regenerates bitwise in the
    forward and in both recompute backward kernels — no PRNG state, and
    it runs identically under the Pallas interpreter on CPU."""
    h = _fmix32(seed.astype(jnp.uint32) ^
                _fmix32(jnp.uint32(g) + jnp.uint32(0x9E3779B9)))
    # mix the two coordinates through separate rounds (a single linear
    # q*T+k counter would alias positions once seq_q*seq_k > 2^32)
    u = _fmix32(q_pos.astype(jnp.uint32) + h)
    bits = _fmix32(u ^ (k_pos.astype(jnp.uint32)
                        * jnp.uint32(0x9E3779B9)))
    threshold = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return bits >= threshold


def _head_id(g, half: int, hpb: int, n_heads: int):
    """Global (batch*n_heads + head) counter for the dropout hash. With
    hpb == 1 this is exactly the grid index g (bitwise-identical masks
    to the historical single-head layout); with head pairs it
    reconstructs the same per-head counter from (pair, half)."""
    if hpb == 1:
        return g
    hg = n_heads // hpb
    return (g // hg) * n_heads + (g % hg) * hpb + half


def _flash_fwd_kernel(q_ref, k_ref, v_ref, seed_ref, bias_ref, o_ref,
                      lse_ref, *, scale: float, causal: bool,
                      block_k: int, seq_k: int, seq_q: int,
                      dropout_p: float, has_bias: bool, d_head: int,
                      hpb: int, n_heads: int, bd=None,
                      d_v: Optional[int] = None,
                      window: Optional[int] = None):
    # refs carry hpb heads side-by-side in the minor dim ([BQ, hpb*D]):
    # hpb == 1 is the classic one-head-per-program layout; hpb == 2
    # packs head PAIRS so the [B, T, H, D] layout's d=64 slabs form a
    # 128-lane block (Mosaic's minor-dim tiling floor). Each half is an
    # independent attention problem sharing the same K-scan. v and the
    # output carry the same heads at the value width d_v ([BK, hpb*d_v]).
    d_v = d_head if d_v is None else d_v
    q2 = q_ref[0].astype(jnp.float32) * scale        # [BQ, hpb*D]
    block_q = q2.shape[0]
    g = pl.program_id(0)
    r0 = pl.program_id(1) * block_q
    # bottom-right causal alignment (matches the XLA reference and the
    # backward): query i attends keys [0, i + seq_k - seq_q]
    causal_offset = seq_k - seq_q

    def body(j, carry, masked):
        accs, ms, ls = carry
        k2 = k_ref[0, pl.ds(j * block_k, block_k), :] \
            .astype(jnp.float32)
        v2 = v_ref[0, pl.ds(j * block_k, block_k), :] \
            .astype(jnp.float32)
        valid = _tile_valid(r0, block_q, j * block_k, block_k, seq_k,
                            causal, causal_offset, bd, window) \
            if masked else None
        if dropout_p > 0.0:                            # the hash's own
            q_pos, k_pos = _tile_positions(r0, block_q, j * block_k,
                                           block_k)
        bias = bias_ref[0, :, pl.ds(j * block_k, block_k)] \
            if has_bias else None
        new = ([], [], [])
        for half in range(hpb):
            sl = slice(half * d_head, (half + 1) * d_head)
            sl_v = slice(half * d_v, (half + 1) * d_v)
            s = jax.lax.dot_general(
                q2[:, sl], k2[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [BQ, BK]
            if has_bias:
                # [1, BK] additive key bias (this batch row) broadcasts
                s = s + bias
            if masked:
                s = jnp.where(valid, s, _NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)  # [BQ, 1]
            m_new = jnp.maximum(ms[half], m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(ms[half] - m_new)
            # l accumulates the full softmax denominator (undropped p);
            # dropout zeroes entries only in the numerator accumulator
            l_new = ls[half] * alpha + jnp.sum(p, axis=-1,
                                               keepdims=True)
            if dropout_p > 0.0:
                keep = _dropout_keep(
                    seed_ref[0, 0], _head_id(g, half, hpb, n_heads),
                    q_pos, k_pos, dropout_p)
                p = jnp.where(keep, p, 0.0)
            acc = accs[half] * alpha + jax.lax.dot_general(
                p, v2[:, sl_v], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            new[0].append(acc)
            new[1].append(m_new)
            new[2].append(l_new)
        return tuple(new[0]), tuple(new[1]), tuple(new[2])

    acc0 = tuple(jnp.zeros((block_q, d_v), jnp.float32)
                 for _ in range(hpb))
    m0 = tuple(jnp.full((block_q, 1), _NEG_INF, jnp.float32)
               for _ in range(hpb))
    l0 = tuple(jnp.zeros((block_q, 1), jnp.float32)
               for _ in range(hpb))
    # the noisy diagonal first (it makes the carry or leaves it), the
    # whole tiles without a mask, then the edges under theirs
    whole, edges, diag = _key_walk(r0, block_q, block_k, seq_q, seq_k,
                                   causal, bd, window)
    carry = (acc0, m0, l0)
    for tile, noisy in diag:
        carry = jax.lax.cond(
            noisy, functools.partial(body, tile, masked=True),
            lambda c: c, carry)
    carry = _walk(whole, functools.partial(body, masked=False), carry)
    accs, m_fin, l_fin = _walk(edges, functools.partial(body, masked=True),
                               carry)
    outs, lses = [], []
    for half in range(hpb):
        safe_l = jnp.maximum(l_fin[half], 1e-30)
        out = accs[half] / safe_l
        if dropout_p > 0.0:
            out = out / (1.0 - dropout_p)
        outs.append(out)
        lses.append(m_fin[half] + jnp.log(safe_l))
    o_ref[0] = jnp.concatenate(outs, axis=1).astype(o_ref.dtype) \
        if hpb > 1 else outs[0].astype(o_ref.dtype)
    lse_ref[0] = jnp.concatenate(lses, axis=1) if hpb > 1 else lses[0]


# the Mosaic calls under the block-diffusion mask and under a sliding
# window carry names of their own, so a metric that reads ``flash_*``
# goes on reading what it read
_BD_PREFIX = "bd_"
_SWA_PREFIX = "swa_"


def _prefix(bd, window) -> str:
    return _BD_PREFIX if bd is not None else \
        _SWA_PREFIX if window is not None else ""


def _seed_arr(seed):
    if seed is None:
        return jnp.zeros((1, 1), jnp.int32)
    return jnp.asarray(seed, jnp.int32).reshape(1, 1)


def _bias_arr(kv_bias, b, tk, tk_p):
    """[B, Tk] additive key bias -> padded [B, 1, tk_p] f32 (the middle
    unit dim satisfies Mosaic block tiling, like the lse layout)."""
    if kv_bias is None:
        return jnp.zeros((1, 1, tk_p), jnp.float32)
    bias = jnp.asarray(kv_bias, jnp.float32).reshape(b, 1, tk)
    if tk_p != tk:
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, tk_p - tk)))
    return bias


def _flash_forward(q, k, v, seed, scale: float, causal: bool,
                   dropout_p: float, interpret: bool = False,
                   kv_bias=None, bthd: bool = False, bd=None,
                   window: Optional[int] = None):
    """``bthd=False``: q/k/v are [B, H, T, D] (classic layout).
    ``bthd=True``: q/k/v are [B, T, H, D] — the layout attention
    projections produce naturally. The kernels are IDENTICAL in both
    modes: in bthd mode the arrays are viewed as [B, T, H*D] (a free
    reshape) and each program's BlockSpec index map selects its head's
    d-wide column slab, so the strided head gather happens inside the
    block DMA instead of as a physical [B,T,H,D]→[B,H,T,D] transpose —
    which the r5 BERT profile measured at ~2.2 ms/step of
    transpose_jvp ops plus their forward twins."""
    if bthd:
        b, tq, h, d = q.shape
        tk = k.shape[1]
    else:
        b, h, tq, d = q.shape
        tk = k.shape[2]
    d_v = v.shape[-1]       # the value and output width; q's and k's d
    if bd is not None:
        _bd_check(bd, tq, tk)
    window = _window_of(window, tk)
    prefix = _prefix(bd, window)
    bq, bk = _block_sizes(tq, tk)
    # pad sequences to block multiples: pl.ds on a short tail CLAMPS the
    # start index (shifting rows under the validity mask), so the buffers
    # must physically cover every block; the k_pos < seq_k mask in the
    # kernel discards the padded keys, and padded queries are sliced off
    # the output below.
    tq_p = pl.cdiv(tq, bq) * bq
    tk_p = pl.cdiv(tk, bk) * bk
    hpb = _heads_per_block(d, h) if bthd else 1
    hg = h // hpb                    # head-groups per batch element
    lead = b if bthd else b * h      # flat leading dim of the arrays

    def flat(x, t, tp):
        x = x.reshape(lead, t, -1)
        return jnp.pad(x, ((0, 0), (0, tp - t), (0, 0))) \
            if tp != t else x

    qr = flat(q, tq, tq_p)
    kr, vr = flat(k, tk, tk_p), flat(v, tk, tk_p)
    if bthd:
        # program g handles (batch g//hg, head-group g%hg): block index
        # g%hg on the H*D dim × block width hpb*d = this group's slab
        q_spec = pl.BlockSpec((1, bq, hpb * d),
                              lambda g, i: (g // hg, i, g % hg),
                              memory_space=pltpu.VMEM)
        kv_spec, v_spec, o_spec = (
            pl.BlockSpec((1, rows, hpb * w), imap, memory_space=pltpu.VMEM)
            for rows, w, imap in (
                (tk_p, d, lambda g, i: (g // hg, 0, g % hg)),
                (tk_p, d_v, lambda g, i: (g // hg, 0, g % hg)),
                (bq, d_v, lambda g, i: (g // hg, i, g % hg))))
        out_struct = jax.ShapeDtypeStruct((b, tq_p, h * d_v), q.dtype)
    else:
        q_spec = pl.BlockSpec((1, bq, d), lambda g, i: (g, i, 0),
                              memory_space=pltpu.VMEM)
        kv_spec, v_spec, o_spec = (
            pl.BlockSpec((1, rows, w), imap, memory_space=pltpu.VMEM)
            for rows, w, imap in (
                (tk_p, d, lambda g, i: (g, 0, 0)),
                (tk_p, d_v, lambda g, i: (g, 0, 0)),
                (bq, d_v, lambda g, i: (g, i, 0))))
        out_struct = jax.ShapeDtypeStruct((b * h, tq_p, d_v), q.dtype)
    grid = (b * hg, tq_p // bq)
    has_bias = kv_bias is not None
    # bias rows are per batch element: block index g // hg (hg static)
    bias_map = (lambda g, i: (g // hg, 0, 0)) if has_bias else \
        (lambda g, i: (0, 0, 0))
    kernel = functools.partial(_flash_fwd_kernel, scale=scale,
                               causal=causal, block_k=bk, seq_k=tk,
                               seq_q=tq, dropout_p=dropout_p,
                               has_bias=has_bias, d_head=d, hpb=hpb,
                               n_heads=h, bd=bd, d_v=d_v, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            q_spec,
            kv_spec,
            v_spec,
            pl.BlockSpec((1, 1), lambda g, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, tk_p), bias_map,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            o_spec,
            # lse as [b*hg, tq, hpb]: a trailing dim equal to the array
            # dim satisfies Mosaic's (8,128) block tiling rule, which a
            # 2-D (1, bq) block does not
            pl.BlockSpec((1, bq, hpb), lambda g, i: (g, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            out_struct,
            jax.ShapeDtypeStruct((b * hg, tq_p, hpb), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_grid_params(tk_p * hpb * d * k.dtype.itemsize,
                                     tk_p * hpb * d_v * v.dtype.itemsize),
        name="flash_fwd" if not prefix else prefix + "flash_fwd",
    )(qr, kr, vr, _seed_arr(seed), _bias_arr(kv_bias, b, tk, tk_p))
    note_kernel(prefix + "flash_fwd", *flash_fwd_work(
        b, h, tq, tk, d, q.dtype.itemsize, causal, bd=bd, d_v=d_v,
        window=window))
    xprof.note_flash_tiles(*flash_tile_census(tq, tk, bq, bk, causal, bd,
                                              window))
    # what `nn.recompute_layer` keeps of a recomputed layer: the backward
    # kernels read both, and making either again is this whole call. The
    # output is named as the kernel wrote it (kept under the caller's
    # [.., H, D] view, XLA copied it into that layout and out of it
    # again: 17 ms a step of the block-diffusion cell), the statistics
    # as [B, H, Tq] (the kernel's own array holds hpb lanes in 128).
    out = checkpoint_name(out, "flash_out")
    # lse -> [B, H, Tq]: head = group*hpb + half, so the trailing half
    # dim interleaves back via a (tiny, h*tq fp32) transpose
    lse_pub = lse[:, :tq, :].reshape(b, hg, tq, hpb)
    lse_pub = checkpoint_name(
        jnp.moveaxis(lse_pub, 3, 2).reshape(b, h, tq), "flash_lse")
    if bthd:
        return out[:, :tq].reshape(b, tq, h, d_v), lse_pub
    return out[:, :tq].reshape(b, h, tq, d_v), lse_pub


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 9, 10, 11))
def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    interpret: bool = False, dropout_p: float = 0.0,
                    seed=None, kv_bias=None, bthd: bool = False,
                    block_diffusion=None, window: Optional[int] = None):
    """Fused attention:
    dropout(softmax(QK^T * scale + kv_bias [+ causal mask])) V.

    ``dropout_p`` > 0 applies post-softmax dropout INSIDE the kernel
    (capability ref: multihead_matmul fused attention + the reference's
    attention dropout); the keep mask is a counter-based hash of
    (seed, head, position), regenerated bitwise in the recompute
    backward. ``seed``: int32 scalar/array; required when dropout_p > 0
    (a fixed implicit seed would silently drop the same entries every
    step).

    ``kv_bias``: [B, Tk] additive key bias (0 keep / large-negative
    masked) — the key-padding mask of variable-length batches. Treated
    as non-trainable: its cotangent is zero.

    ``bthd``: q/k/v (and the output + cotangents) are [B, T, H, D] —
    the projections' natural layout — instead of [B, H, T, D]. Same
    kernels; the head gather rides the block DMA, eliminating the
    physical transposes around attention (see _flash_forward).

    ``block_diffusion``: ``(length, block)`` static ints, the
    block-diffusion training mask in ``causal``'s place: q and k hold
    ``2 * length`` positions, a noised copy of a sequence then its clean
    copy, cut in blocks of ``block`` (``bd_allowed`` is the rule). One
    softmax over a query's whole key set, the rule computed in the
    kernels from iotas, no tile without an allowed pair visited,
    forward or backward; the Mosaic calls are named ``bd_flash_*``. Not
    with ``causal``, dropout or a key bias.

    ``window``: a static int ``W``, with ``causal``: query ``q`` sees the
    keys ``q + o - W < k <= q + o`` (``o = Tk - Tq``), ``W`` of them, its
    own included. The kernels visit only the tiles inside the window,
    forward and backward, and their Mosaic calls are named
    ``swa_flash_*``; a window that holds every key is the causal mask.
    """
    _check_mask(causal, dropout_p, kv_bias, block_diffusion, window)
    if dropout_p > 0.0 and seed is None:
        raise ValueError("flash_attention: dropout_p > 0 requires a "
                         "seed (vary it per step)")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    # the trace `nn.recompute_layer` makes of a layer is not the one a
    # gradient runs: that is `_fwd`'s, which notes the call
    with xprof.unnoted(when=xprof.in_layer_primal()):
        out, _ = _flash_forward(q, k, v, seed, scale, causal, dropout_p,
                                interpret, kv_bias, bthd, block_diffusion,
                                window)
    return out


def _check_mask(causal, dropout_p, kv_bias, block_diffusion,
                window=None) -> None:
    if block_diffusion is not None and (causal or dropout_p > 0.0
                                        or kv_bias is not None):
        raise ValueError("flash_attention: block_diffusion is a mask of "
                         "its own: no causal, dropout_p or kv_bias")
    if window is not None and (not causal or int(window) < 1):
        raise ValueError(f"flash_attention: window={window} is a causal "
                         "mask's: causal=True and at least one key")


def _fwd(q, k, v, causal, scale, interpret, dropout_p, seed, kv_bias,
         bthd, block_diffusion=None, window=None):
    _check_mask(causal, dropout_p, kv_bias, block_diffusion, window)
    if dropout_p > 0.0 and seed is None:
        raise ValueError("flash_attention: dropout_p > 0 requires a "
                         "seed (vary it per step)")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _flash_forward(q, k, v, seed, scale, causal, dropout_p,
                              interpret, kv_bias, bthd, block_diffusion,
                              window)
    return out, (q, k, v, seed, kv_bias, out, lse, scale)


def _grad_core(q_h, k_h, v_h, do_h, lse_col, delta_col, valid, bias,
               seed_ref, head_id, q_pos, k_pos, *, dropout_p: float,
               has_bias: bool):
    """The backward's shared per-head-slab math — ONE home for the
    s/bias/mask/p/dp/dropout/dsc chain so the scanning kernels and the
    fused single-block kernel cannot diverge. ``q_h`` is the query
    times ``scale`` in float32, as the forward made it, so ``s`` is the
    forward's bit for bit; ``valid`` is ``None`` on a whole tile.
    Returns ``(p_v, dsc)``: ``p_v`` is the dropped+rescaled probs (dv's
    operand), ``dsc`` the cotangent of ``s`` (dk's operand against the
    scaled query; dq's against k, its sum scaled once by the caller)."""
    s = jax.lax.dot_general(
        q_h, k_h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [BQ, BK]
    if has_bias:
        s = s + bias
    if valid is not None:
        s = jnp.where(valid, s, _NEG_INF)
    p = jnp.exp(s - lse_col)                             # probs, 0 at -inf
    dp = jax.lax.dot_general(
        do_h, v_h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [BQ, BK]
    if dropout_p > 0.0:
        # same mask as the forward: dP = keep * dp / (1-p_drop);
        # delta already equals rowsum(P_dropped * dp) via dO.O
        keep = _dropout_keep(seed_ref[0, 0], head_id, q_pos, k_pos,
                             dropout_p)
        inv = 1.0 - dropout_p
        p_v = jnp.where(keep, p / inv, 0.0)
        dp = jnp.where(keep, dp / inv, 0.0)
    else:
        p_v = p
    dsc = p * (dp - delta_col)
    return p_v, dsc


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   seed_ref, bias_ref, dq_ref, *, scale: float,
                   causal: bool, block_k: int, seq_k: int, seq_q: int,
                   dropout_p: float, has_bias: bool, d_head: int,
                   hpb: int, n_heads: int, bd=None,
                   d_v: Optional[int] = None,
                   window: Optional[int] = None):
    d_v = d_head if d_v is None else d_v               # v's and dO's
    q2 = q_ref[0].astype(jnp.float32) * scale          # [BQ, hpb*D]
    do2 = do_ref[0].astype(jnp.float32)                # [BQ, hpb*d_v]
    lse2 = lse_ref[0]                                  # [BQ, hpb] f32
    delta2 = delta_ref[0]                              # [BQ, hpb] f32
    block_q = q2.shape[0]
    g = pl.program_id(0)
    r0 = pl.program_id(1) * block_q
    causal_offset = seq_k - seq_q

    def body(j, dq_accs, masked):
        k2 = k_ref[0, pl.ds(j * block_k, block_k), :] \
            .astype(jnp.float32)
        v2 = v_ref[0, pl.ds(j * block_k, block_k), :] \
            .astype(jnp.float32)
        valid = _tile_valid(r0, block_q, j * block_k, block_k, seq_k,
                            causal, causal_offset, bd, window) \
            if masked else None
        q_pos, k_pos = _tile_positions(r0, block_q, j * block_k, block_k) \
            if dropout_p > 0.0 else (None, None)       # the hash's own
        bias = bias_ref[0, :, pl.ds(j * block_k, block_k)] \
            if has_bias else None
        out = []
        for half in range(hpb):
            sl = slice(half * d_head, (half + 1) * d_head)
            sl_v = slice(half * d_v, (half + 1) * d_v)
            _, dsc = _grad_core(
                q2[:, sl], k2[:, sl], v2[:, sl_v], do2[:, sl_v],
                lse2[:, half:half + 1], delta2[:, half:half + 1],
                valid, bias, seed_ref,
                _head_id(g, half, hpb, n_heads), q_pos, k_pos,
                dropout_p=dropout_p, has_bias=has_bias)
            out.append(dq_accs[half] + jax.lax.dot_general(
                dsc, k2[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        return tuple(out)

    def diagonal(j, dq_accs):
        """The noisy diagonal tile in sub-tiles (`_bd_subtiled`: one head
        a program): `_SUB` rows see `_SUB` keys, the tile's own
        diagonal."""
        new = []
        for lo in range(0, block_q, _SUB):
            rows = slice(lo, lo + _SUB)
            c0 = j * block_k + lo
            k2 = k_ref[0, pl.ds(c0, _SUB), :].astype(jnp.float32)
            v2 = v_ref[0, pl.ds(c0, _SUB), :].astype(jnp.float32)
            valid = _tile_valid(r0 + lo, _SUB, c0, _SUB, seq_k, False, 0,
                                bd)
            _, dsc = _grad_core(
                q2[rows], k2, v2, do2[rows], lse2[rows], delta2[rows],
                valid, None, seed_ref, None, None, None, dropout_p=0.0,
                has_bias=False)
            new.append(dq_accs[0][rows] + jax.lax.dot_general(
                dsc, k2, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        return (jnp.concatenate(new, axis=0),)

    dq0 = tuple(jnp.zeros((block_q, d_head), jnp.float32)
                for _ in range(hpb))
    whole, edges, diag = _key_walk(r0, block_q, block_k, seq_q, seq_k,
                                   causal, bd, window)
    dqs = dq0
    own = diagonal if bd is not None and _bd_subtiled(
        block_q, block_k, seq_k, bd) else functools.partial(body, masked=True)
    for tile, noisy in diag:     # first, as the forward's
        dqs = jax.lax.cond(noisy, functools.partial(own, tile),
                           lambda c: c, dqs)
    dqs = _walk(whole, functools.partial(body, masked=False), dqs)
    dqs = _walk(edges, functools.partial(body, masked=True), dqs)
    # s = (q * scale) k^T: the scale of dq, once on its [BQ, D] sum
    dq_ref[0] = ((jnp.concatenate(dqs, axis=1) if hpb > 1 else dqs[0])
                 * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    seed_ref, bias_ref, dk_ref, dv_ref, *, scale: float,
                    causal: bool, block_q: int, seq_k: int, seq_q: int,
                    dropout_p: float, has_bias: bool, d_head: int,
                    hpb: int, n_heads: int, bd=None,
                    d_v: Optional[int] = None,
                    window: Optional[int] = None):
    d_v = d_head if d_v is None else d_v               # v's, dO's, dv's
    # Padded-q correctness: dO and delta are zero-padded, so a padded
    # query row contributes p^T@dO = 0 to dv and p*(0-0) = 0 to dk —
    # no explicit q-validity mask is needed.
    k2 = k_ref[0].astype(jnp.float32)                  # [BK, hpb*D]
    v2 = v_ref[0].astype(jnp.float32)                  # [BK, hpb*d_v]
    block_k = k2.shape[0]
    g = pl.program_id(0)
    c0 = pl.program_id(1) * block_k
    num_q = q_ref.shape[1] // block_q
    causal_offset = seq_k - seq_q

    def body(i, carry, masked):
        dk_accs, dv_accs = carry
        # the query times scale, as the forward's: dk = dsc^T (q * scale)
        q2 = q_ref[0, pl.ds(i * block_q, block_q), :] \
            .astype(jnp.float32) * scale
        do2 = do_ref[0, pl.ds(i * block_q, block_q), :] \
            .astype(jnp.float32)
        lse2 = lse_ref[0, pl.ds(i * block_q, block_q), :]  # [BQ, hpb]
        delta2 = delta_ref[0, pl.ds(i * block_q, block_q), :]
        valid = _tile_valid(i * block_q, block_q, c0, block_k, seq_k,
                            causal, causal_offset, bd, window) \
            if masked else None
        q_pos, k_pos = _tile_positions(i * block_q, block_q, c0, block_k) \
            if dropout_p > 0.0 else (None, None)       # the hash's own
        new_dk, new_dv = [], []
        for half in range(hpb):
            sl = slice(half * d_head, (half + 1) * d_head)
            sl_v = slice(half * d_v, (half + 1) * d_v)
            # this kernel's k block is fixed, so the BlockSpec already
            # delivered exactly the [1, BK] bias slice for j_k
            p_v, dsc = _grad_core(
                q2[:, sl], k2[:, sl], v2[:, sl_v], do2[:, sl_v],
                lse2[:, half:half + 1], delta2[:, half:half + 1],
                valid, bias_ref[0] if has_bias else None, seed_ref,
                _head_id(g, half, hpb, n_heads), q_pos, k_pos,
                dropout_p=dropout_p, has_bias=has_bias)
            new_dv.append(dv_accs[half] + jax.lax.dot_general(
                p_v, do2[:, sl_v], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))        # [BK, d_v]
            new_dk.append(dk_accs[half] + jax.lax.dot_general(
                dsc, q2[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))        # [BK, D]
        return tuple(new_dk), tuple(new_dv)

    def diagonal(i, carry):
        """A noisy key tile's own query tile in sub-tiles: `_SUB` keys
        are seen by the `_SUB` queries on the tile's diagonal."""
        (dk,), (dv,) = carry
        new_dk, new_dv = [], []
        for lo in range(0, block_k, _SUB):
            keys = slice(lo, lo + _SUB)
            r0 = i * block_q + lo
            q2 = q_ref[0, pl.ds(r0, _SUB), :].astype(jnp.float32) * scale
            do2 = do_ref[0, pl.ds(r0, _SUB), :].astype(jnp.float32)
            valid = _tile_valid(r0, _SUB, c0 + lo, _SUB, seq_k, False, 0,
                                bd)
            p_v, dsc = _grad_core(
                q2, k2[keys], v2[keys], do2,
                lse_ref[0, pl.ds(r0, _SUB), :],
                delta_ref[0, pl.ds(r0, _SUB), :], valid, None, seed_ref,
                None, None, None, dropout_p=0.0, has_bias=False)
            new_dv.append(dv[keys] + jax.lax.dot_general(
                p_v, do2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            new_dk.append(dk[keys] + jax.lax.dot_general(
                dsc, q2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        return ((jnp.concatenate(new_dk, axis=0),),
                (jnp.concatenate(new_dv, axis=0),))

    zeros = tuple(jnp.zeros((block_k, d_head), jnp.float32)
                  for _ in range(hpb))
    zeros_v = zeros if d_v == d_head else tuple(
        jnp.zeros((block_k, d_v), jnp.float32) for _ in range(hpb))
    whole, edges, diag = _query_walk(c0, block_k, block_q, num_q, seq_k,
                                     causal, causal_offset, bd, window)

    def seen_by_others():
        carry = _walk(whole, functools.partial(body, masked=False),
                      (zeros, zeros_v))
        return _walk(edges, functools.partial(body, masked=True), carry)

    if diag:          # a noisy key tile: its own query tile and no other
        (tile, noisy), = diag
        own = diagonal if _bd_subtiled(block_q, block_k, seq_k, bd) \
            else functools.partial(body, masked=True)
        dks, dvs = jax.lax.cond(
            noisy, lambda: own(tile, (zeros, zeros_v)), seen_by_others)
    else:
        dks, dvs = seen_by_others()
    dk_ref[0] = (jnp.concatenate(dks, axis=1) if hpb > 1 else dks[0]) \
        .astype(dk_ref.dtype)
    dv_ref[0] = (jnp.concatenate(dvs, axis=1) if hpb > 1 else dvs[0]) \
        .astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      seed_ref, bias_ref, dq_ref, dk_ref, dv_ref, *,
                      scale: float, causal: bool, seq_k: int,
                      seq_q: int, dropout_p: float, has_bias: bool,
                      d_head: int, hpb: int, n_heads: int, bd=None,
                      d_v: Optional[int] = None,
                      window: Optional[int] = None):
    """Single-block backward: when BOTH padded sequences fit one tile
    (tq_p == bq and tk_p == bk — e.g. BERT's T=512 with 512-tiles),
    the dq and dkv kernels' scans each degenerate to one iteration
    that recomputes the SAME s/p/dp matrices. This kernel computes
    them once and emits dq, dk, dv together — one pallas_call, one
    set of DMAs, no duplicated softmax/mask/dropout work. The r5 b16
    profile put the flash custom-calls at 11.8 ms/step (20.6%), so
    the duplicated backward half is real step time."""
    d_v = d_head if d_v is None else d_v               # v's, dO's, dv's
    q2 = q_ref[0].astype(jnp.float32) * scale          # [BQ, hpb*D]
    k2 = k_ref[0].astype(jnp.float32)                  # [BK, hpb*D]
    v2 = v_ref[0].astype(jnp.float32)
    do2 = do_ref[0].astype(jnp.float32)
    lse2 = lse_ref[0]                                  # [BQ, hpb]
    delta2 = delta_ref[0]
    block_q, block_k = q2.shape[0], k2.shape[0]
    g = pl.program_id(0)
    # the one tile is whole, statically, where no key is padding and no
    # mask is asked for
    valid = None if bd is None and not causal and seq_k == block_k else \
        _tile_valid(0, block_q, 0, block_k, seq_k, causal, seq_k - seq_q,
                    bd, window)
    q_pos, k_pos = _tile_positions(0, block_q, 0, block_k) \
        if dropout_p > 0.0 else (None, None)           # the hash's own
    dqs, dks, dvs = [], [], []
    for half in range(hpb):
        sl = slice(half * d_head, (half + 1) * d_head)
        sl_v = slice(half * d_v, (half + 1) * d_v)
        p_v, dsc = _grad_core(
            q2[:, sl], k2[:, sl], v2[:, sl_v], do2[:, sl_v],
            lse2[:, half:half + 1], delta2[:, half:half + 1],
            valid, bias_ref[0] if has_bias else None, seed_ref,
            _head_id(g, half, hpb, n_heads), q_pos, k_pos,
            dropout_p=dropout_p, has_bias=has_bias)
        dvs.append(jax.lax.dot_general(
            p_v, do2[:, sl_v], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))           # [BK, d_v]
        dks.append(jax.lax.dot_general(
            dsc, q2[:, sl], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))           # [BK, D]
        dqs.append(jax.lax.dot_general(
            dsc, k2[:, sl], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale)   # [BQ, D]
    cat = (lambda xs: jnp.concatenate(xs, axis=1)) if hpb > 1 \
        else (lambda xs: xs[0])
    dq_ref[0] = cat(dqs).astype(dq_ref.dtype)
    dk_ref[0] = cat(dks).astype(dk_ref.dtype)
    dv_ref[0] = cat(dvs).astype(dv_ref.dtype)


def _flash_backward(q, k, v, seed, out, lse, g, scale: float,
                    causal: bool, dropout_p: float,
                    interpret: bool = False, dlse=None, kv_bias=None,
                    bthd: bool = False, bd=None,
                    window: Optional[int] = None):
    if bthd:
        b, tq, h, d = q.shape
        tk = k.shape[1]
    else:
        b, h, tq, d = q.shape
        tk = k.shape[2]
    d_v = v.shape[-1]       # v's, dO's and dv's width; q's and k's d
    window = _window_of(window, tk)
    bq, bk = _block_sizes(tq, tk)
    tq_p = pl.cdiv(tq, bq) * bq
    tk_p = pl.cdiv(tk, bk) * bk

    hpb = _heads_per_block(d, h) if bthd else 1
    hg = h // hpb
    if bthd:
        # [B, T, H, D] -> [B, T, H*D] view; head-group slabs are
        # selected by the BlockSpec index maps (see _flash_forward)
        def flat(x, t, tp):
            x = x.reshape(b, t, -1)
            return jnp.pad(x, ((0, 0), (0, tp - t), (0, 0))) \
                if tp != t else x

        def seq_spec(blk, imap, w=d):
            return pl.BlockSpec((1, blk, hpb * w), imap,
                                memory_space=pltpu.VMEM)

        q_map = lambda g_, i: (g_ // hg, i, g_ % hg)      # noqa: E731
        kv_map = lambda g_, i: (g_ // hg, 0, g_ % hg)     # noqa: E731
        kblk_map = lambda g_, j: (g_ // hg, j, g_ % hg)   # noqa: E731
        qfull_map = lambda g_, j: (g_ // hg, 0, g_ % hg)  # noqa: E731
        dq_struct = jax.ShapeDtypeStruct((b, tq_p, h * d), q.dtype)
        dk_struct = jax.ShapeDtypeStruct((b, tk_p, h * d), k.dtype)
        dv_struct = jax.ShapeDtypeStruct((b, tk_p, h * d_v), v.dtype)
        # delta/lse ride as [b*hg, tq, hpb] (head = group*hpb + half):
        # [b, tq, h] -> that layout is a tiny fp32 transpose
        # (b*h*tq elements), not activation-scale traffic
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                          # [b, tq, h]
        delta = jnp.moveaxis(delta.reshape(b, tq, hg, hpb), 2, 1) \
            .reshape(b * hg, tq, hpb)
    else:
        def flat(x, t, tp):
            x = x.reshape(b * h, t, -1)
            return jnp.pad(x, ((0, 0), (0, tp - t), (0, 0))) \
                if tp != t else x

        def seq_spec(blk, imap, w=d):
            return pl.BlockSpec((1, blk, w), imap,
                                memory_space=pltpu.VMEM)

        q_map = lambda g_, i: (g_, i, 0)                # noqa: E731
        kv_map = lambda g_, i: (g_, 0, 0)               # noqa: E731
        kblk_map = lambda g_, j: (g_, j, 0)             # noqa: E731
        qfull_map = lambda g_, j: (g_, 0, 0)            # noqa: E731
        dq_struct = jax.ShapeDtypeStruct((b * h, tq_p, d), q.dtype)
        dk_struct = jax.ShapeDtypeStruct((b * h, tk_p, d), k.dtype)
        dv_struct = jax.ShapeDtypeStruct((b * h, tk_p, d_v), v.dtype)
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1).reshape(b * h, tq, 1)

    qr, dor = flat(q, tq, tq_p), flat(g, tq, tq_p)
    kr, vr = flat(k, tk, tk_p), flat(v, tk, tk_p)

    def to_rows(x):
        """[B, H, Tq]-shaped values -> the kernels' row layout
        (b*hg, tq, hpb) with head = group*hpb + half (unpadded)."""
        x = x.reshape(b, hg, hpb, tq)
        return jnp.moveaxis(x, 2, 3).reshape(b * hg, tq, hpb)

    def pad_rows(x):
        return jnp.pad(x, ((0, 0), (0, tq_p - tq), (0, 0))) \
            if tq_p != tq else x

    # delta = rowsum(dO * O): one elementwise+reduce in XLA.
    # An lse cotangent folds in here: ds = p*(dP - (delta - dlse))*scale
    # (d lse_i/ds_ij = p_ij), so no kernel change is needed.
    if dlse is not None:
        delta = delta - to_rows(dlse.astype(jnp.float32))
    delta = pad_rows(delta)
    lse_r = pad_rows(to_rows(lse.astype(jnp.float32)))

    seed_a = _seed_arr(seed)
    has_bias = kv_bias is not None
    bias_a = _bias_arr(kv_bias, b, tk, tk_p)
    bias_map = (lambda g_, i: (g_ // hg, 0, 0)) if has_bias else \
        (lambda g_, i: (0, 0, 0))
    shape = (b, h, tq, tk, d, q.dtype.itemsize)     # for the work notes
    kernel_kw = dict(scale=scale, causal=causal, seq_k=tk, seq_q=tq,
                     dropout_p=dropout_p, has_bias=has_bias, d_head=d,
                     hpb=hpb, n_heads=h, bd=bd, d_v=d_v, window=window)
    prefix = _prefix(bd, window)
    row_spec = pl.BlockSpec((1, bq, hpb), lambda g_, i: (g_, i, 0),
                            memory_space=pltpu.VMEM)
    rowfull_spec = pl.BlockSpec((1, tq_p, hpb),
                                lambda g_, j: (g_, 0, 0),
                                memory_space=pltpu.VMEM)
    if tq_p == bq and tk_p == bk:
        # single-block fast path: dq/dk/dv from ONE kernel (see
        # _bwd_fused_kernel) — the two-kernel path would recompute
        # identical s/p/dp
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, **kernel_kw),
            grid=(b * hg, 1),
            in_specs=[
                seq_spec(bq, q_map),
                seq_spec(bk, kblk_map),
                seq_spec(bk, kblk_map, d_v),
                seq_spec(bq, q_map, d_v),
                row_spec,
                row_spec,
                pl.BlockSpec((1, 1), lambda g_, i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, 1, tk_p), bias_map,
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                seq_spec(bq, q_map),
                seq_spec(bk, kblk_map),
                seq_spec(bk, kblk_map, d_v),
            ],
            out_shape=[dq_struct, dk_struct, dv_struct],
            interpret=interpret,
            compiler_params=_GRID_PARALLEL,
            name="flash_bwd" if not prefix else prefix + "flash_bwd",
        )(qr, kr, vr, dor, lse_r, delta, seed_a, bias_a)
        note_kernel(prefix + "flash_bwd",
                    *flash_bwd_work(*shape, causal, bd=bd, d_v=d_v,
                                    window=window))
        return _from_flat(bthd, (b, h, tq, tk, d, d_v), dq, dk, dv)
    # one program's resident whole-sequence operands, for _grid_params
    k_bytes = tk_p * hpb * d * k.dtype.itemsize
    v_bytes = tk_p * hpb * d_v * v.dtype.itemsize
    q_bytes = tq_p * hpb * d * q.dtype.itemsize
    do_bytes = tq_p * hpb * d_v * g.dtype.itemsize
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=bk, **kernel_kw),
        grid=(b * hg, tq_p // bq),
        in_specs=[
            seq_spec(bq, q_map),
            seq_spec(tk_p, kv_map),
            seq_spec(tk_p, kv_map, d_v),
            seq_spec(bq, q_map, d_v),
            row_spec,
            row_spec,
            pl.BlockSpec((1, 1), lambda g_, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, tk_p), bias_map,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=seq_spec(bq, q_map),
        out_shape=dq_struct,
        interpret=interpret,
        compiler_params=_grid_params(k_bytes, v_bytes),
        name="flash_bwd_dq" if not prefix else prefix + "flash_bwd_dq",
    )(qr, kr, vr, dor, lse_r, delta, seed_a, bias_a)
    note_kernel(prefix + "flash_bwd_dq",
                *flash_bwd_work(*shape, causal, matmuls=3, bd=bd, d_v=d_v,
                                window=window))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, **kernel_kw),
        grid=(b * hg, tk_p // bk),
        in_specs=[
            seq_spec(tq_p, qfull_map),
            seq_spec(bk, kblk_map),
            seq_spec(bk, kblk_map, d_v),
            seq_spec(tq_p, qfull_map, d_v),
            rowfull_spec,
            rowfull_spec,
            pl.BlockSpec((1, 1), lambda g_, j: (0, 0),
                         memory_space=pltpu.SMEM),
            # this kernel's k block is fixed per program: deliver only
            # the bk-wide bias slice instead of the whole padded row
            pl.BlockSpec((1, 1, bk),
                         (lambda g_, j: (g_ // hg, 0, j)) if has_bias
                         else (lambda g_, j: (0, 0, 0)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            seq_spec(bk, kblk_map),
            seq_spec(bk, kblk_map, d_v),
        ],
        out_shape=[dk_struct, dv_struct],
        interpret=interpret,
        compiler_params=_grid_params(q_bytes, do_bytes, _row_bytes(tq_p),
                                     _row_bytes(tq_p)),
        name="flash_bwd_dkv" if not prefix else prefix + "flash_bwd_dkv",
    )(qr, kr, vr, dor, lse_r, delta, seed_a, bias_a)
    note_kernel(prefix + "flash_bwd_dkv",
                *flash_bwd_work(*shape, causal, matmuls=4, bd=bd, d_v=d_v,
                                window=window))
    return _from_flat(bthd, (b, h, tq, tk, d, d_v), dq, dk, dv)


def _from_flat(bthd: bool, dims, dq, dk, dv):
    """The backward kernels' padded flat results in the caller's
    layout: dq and dk at the q/k width, dv at the value width."""
    b, h, tq, tk, d, d_v = dims
    if bthd:
        return (dq[:, :tq].reshape(b, tq, h, d),
                dk[:, :tk].reshape(b, tk, h, d),
                dv[:, :tk].reshape(b, tk, h, d_v))
    return (dq[:, :tq].reshape(b, h, tq, d),
            dk[:, :tk].reshape(b, h, tk, d),
            dv[:, :tk].reshape(b, h, tk, d_v))


def _bwd(causal, scale_arg, interpret, dropout_p, bthd, block_diffusion,
         window, res, g):
    import numpy as np

    q, k, v, seed, kv_bias, out, lse, scale = res
    dq, dk, dv = _flash_backward(q, k, v, seed, out, lse, g, scale,
                                 causal, dropout_p, interpret,
                                 kv_bias=kv_bias, bthd=bthd,
                                 bd=block_diffusion, window=window)
    # seed is integer-valued: its cotangent is the symbolic-zero float0
    dseed = None if seed is None else \
        np.zeros(jnp.shape(jnp.asarray(seed)), jax.dtypes.float0)
    # the key bias is a mask, not a trainable input: zero cotangent
    dbias = None if kv_bias is None else jnp.zeros_like(kv_bias)
    return dq, dk, dv, dseed, dbias


flash_attention.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             interpret: bool = False):
    """Flash attention returning ``(out, lse)`` with BOTH outputs
    differentiable — the building block for combining partial-attention
    results over sharded K/V (ring attention): given per-chunk
    ``(o_i, lse_i)``, the exact full-attention output is
    ``sum(o_i * exp(lse_i - m)) / sum(exp(lse_i - m))``, and gradients
    flow through the lse weights.

    The lse cotangent needs NO extra kernel: ``d lse/ds = p`` folds into
    the backward's delta term, ``ds = p*(dP - (delta - dlse))*scale``.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _flash_forward(q, k, v, None, scale, causal, 0.0, interpret)


def _fwd_lse(q, k, v, causal, scale, interpret):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _flash_forward(q, k, v, None, scale, causal, 0.0,
                              interpret)
    return (out, lse), (q, k, v, out, lse, scale)


def _bwd_lse(causal, scale_arg, interpret, res, g):
    q, k, v, out, lse, scale = res
    do, dlse = g
    return _flash_backward(q, k, v, None, out, lse, do, scale, causal,
                           0.0, interpret, dlse=dlse)


flash_attention_with_lse.defvjp(_fwd_lse, _bwd_lse)
