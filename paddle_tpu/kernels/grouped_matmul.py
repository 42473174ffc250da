"""Grouped matmuls of the routed experts as Pallas kernels.

``nn.DroplessMoE`` sorts its (token, expert) pairs by expert and multiplies
the rows of group ``i`` with expert ``i``'s matrix. ``moe_gmm`` is that
product, ``[m, k] x [g, k, n] -> [m, n]`` (and ``dy x w^T`` for the input
gradients, read from the weights as they lie: no ``[g, n, k]`` copy goes
through HBM); ``moe_tgmm`` is the weight gradient, ``[m, k]^T x [m, n] ->
[g, k, n]`` by group. ``grouped_matmul`` ties them with a ``custom_vjp``.
XLA:TPU lowers ``jax.lax.ragged_dot`` to Mosaic calls tiled 512 x 128 x
128, which re-read their operands from HBM at ~100 FLOPs a byte against
the chip's ridge of 240: 13% of the MXU peak at the hybrid decoder's
widths (PERF.md section 6, PR 33).

The design is megablox's (``jax.experimental.pallas.ops.tpu.megablox``),
written for this repo's two shapes. Rows are cut into tiles of
``ROW_TILE``. ``group_tiles`` lists, from the group sizes and in plain
``jnp``, the (group, row tile) pairs the grid walks: a tile that holds
rows of two groups is visited once for each, under a row mask, and the
list is shared by every call of a window. It is padded to its static
bound, ``m / ROW_TILE + g - 1``, with copies of its last pair: such a
step moves no block and computes nothing.

**Tiles, and why** (measured on a v5e at 12288 x 2688 x 1856 with the
cell's groups, seven of ~770 rows and a last one of 6.9k; PERF.md
section 6, PR 33, has the table). ``moe_gmm`` keeps a group's whole
``[k, n]`` matrix in VMEM (10 MB of bf16, double-buffered) beside one
row tile of ``lhs``: a matrix is read from HBM once a group, not once a
row tile, and a grid step is one ``[ROW_TILE, k] x [k, n]`` product, cut
along ``n`` inside the kernel. **Rows in tiles of 256**: a tile two
groups share is computed once for each, so 8 groups cost up to 7 tiles
more: 31 visits for 24 tiles of 512 (a product alone reaches 81% of the
MXU peak there and the call 65%), 55 for 48 of 256 (70%), 103 for 96 of
128 (70%: shorter products run slower), 19 for 12 of 1024 (46%).
**Columns**: 1856 = 14.5 x 128 has no divisor that is a multiple of 128
but itself, so the 1856-wide side is always one whole-dimension block
and Mosaic pads its last half lane tile (1/30 of the passes on that
side; walking it as 1792 + 64 changed nothing); the 2688-wide side (21 x
128) is cut in 896 (7 x 128): inside ``moe_gmm`` the cut changes nothing
forward and 10% in the transposed form, and ``moe_tgmm``, which
accumulates one ``[896, 1856]`` (or ``[1856, 896]``) float32 block of a
group's gradient over the group's row tiles, is 8% slower with 384 and
10% with the whole 2688. A call whose ``rhs`` has 1856 as its minor
dimension (``w_in`` forward, ``w_in`` transposed) runs at 58-61% where
its twin on ``w_out`` runs at 70%, whichever side of the product the
1856 is on. The kernels ask for the VMEM they hold (``_params``), as the
flash kernels do.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.xprof import note_kernel

ROW_TILE = 256
# the 2688-wide side in blocks of 7 lane tiles; a side with no such
# divisor (1856) stays whole
_COLUMN_TILE = 896


class GroupTiles(NamedTuple):
    """What the grid of one window's grouped matmuls walks."""
    offsets: jax.Array      # [g + 1] the row each group starts at
    group_of: jax.Array     # [steps] the group of a grid step
    tile_of: jax.Array      # [steps] its row tile
    visits: jax.Array       # [1] the steps that hold work


def group_tiles(sizes, m: int, row_tile: int = ROW_TILE) -> GroupTiles:
    """The (group, row tile) pairs to visit for groups of ``sizes`` rows
    in ``m`` rows (a multiple of ``row_tile``), in the order the kernels
    need: by group, a tile two groups share twice in a row. An empty
    group visits one tile (``moe_tgmm`` has its zeros to write), and the
    rows past the last group are visited with it, under a mask that
    holds none of them, so every row of a result is written."""
    if m % row_tile:
        raise NotImplementedError(
            f"{m} rows are no multiple of the row tile {row_tile}")
    sizes = sizes.astype(jnp.int32)
    g, tiles = sizes.shape[0], m // row_tile
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    # for the walk alone, the last group reaches the last row
    walk_ends = ends.at[-1].set(m)
    walk_starts = jnp.concatenate([jnp.zeros(1, jnp.int32), walk_ends[:-1]])
    first = jnp.minimum(walk_starts // row_tile, tiles - 1)
    count = jnp.maximum(-(-walk_ends // row_tile) - first, 1)
    upto = jnp.cumsum(count)
    step = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    # the groups whose visits end at or before a step (no loop, as a
    # search would be inside the window loop's body)
    group_of = jnp.minimum(
        jnp.sum(step[:, None] >= upto[None, :], axis=1, dtype=jnp.int32),
        g - 1)
    tile_of = jnp.minimum(
        first[group_of] + step - (upto - count)[group_of], tiles - 1)
    return GroupTiles(offsets, group_of, tile_of, upto[-1:])


def gmm_work(m: int, k: int, n: int, groups: int, itemsize: int):
    """(FLOPs, HBM bytes) one grouped product must do, ``moe_gmm`` or
    ``moe_tgmm``: every row meets one ``[k, n]`` matrix, ``2 * m * k *
    n``; the two operands and the result cross HBM once."""
    return 2.0 * m * k * n, float((m * k + groups * k * n + m * n)
                                  * itemsize)


def _cost(work):
    """What XLA's scheduler is told a call costs: the work it notes."""
    return pl.CostEstimate(flops=int(work[0]), bytes_accessed=int(work[1]),
                           transcendentals=0)


def _params(semantics, *held_bytes: int):
    """The VMEM a call holds (its blocks twice, for the pipeline; its
    accumulator and a product's float32 value once) plus room for the
    compiler's own temporaries, under the chip's 128 MiB."""
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=int(min(sum(held_bytes) + 16 * 2 ** 20,
                                 100 * 2 ** 20)))


def _rows_of(tile, start, end, row_tile: int):
    """[row_tile, 1] mask of the tile's rows that lie in [start, end)."""
    rows = tile * row_tile + jax.lax.broadcasted_iota(
        jnp.int32, (row_tile, 1), 0)
    return (rows >= start) & (rows < end)


def _gmm_kernel(offsets, group_of, tile_of, visits, lhs_ref, rhs_ref,
                out_ref, *, row_tile: int, column_tile: int,
                transpose_rhs: bool):
    i = pl.program_id(0)

    @pl.when(i < visits[0])
    def _():
        group, tile = group_of[i], tile_of[i]
        start, end = offsets[group], offsets[group + 1]
        # the tile's first visit writes every row of it; a later one
        # (the next group's) keeps what the one before wrote
        first = jnp.logical_or(
            i == 0, tile_of[jnp.maximum(i - 1, 0)] != tile)
        mine = _rows_of(tile, start, end, row_tile)
        contract = (((1,), (1,)), ((), ())) if transpose_rhs \
            else (((1,), (0,)), ((), ()))

        @pl.when(end > start)
        def _():
            lhs = lhs_ref[...]
            for lo in range(0, out_ref.shape[1], column_tile):
                cols = slice(lo, lo + column_tile)
                rhs = rhs_ref[cols, :] if transpose_rhs else rhs_ref[:, cols]
                product = jax.lax.dot_general(
                    lhs, rhs, contract, preferred_element_type=jnp.float32)
                before = jnp.where(first, 0.0,
                                   out_ref[:, cols].astype(jnp.float32))
                out_ref[:, cols] = jnp.where(mine, product, before).astype(
                    out_ref.dtype)

        @pl.when(jnp.logical_and(end <= start, first))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)


def _row_tile(m: int, groups: int, tiles: GroupTiles) -> int:
    """The row tile ``tiles`` was made for: it has ``m / tile + groups -
    1`` steps."""
    return m // (tiles.group_of.shape[0] - groups + 1)


def moe_gmm(lhs, rhs, tiles: GroupTiles, *, transpose_rhs: bool = False,
            interpret: bool = False):
    """Rows of group ``i`` of ``lhs`` [m, k] times ``rhs[i]``: [m, n],
    in ``lhs``'s dtype, accumulated in float32. ``rhs`` is [g, k, n], or
    [g, n, k] with ``transpose_rhs`` (the product with each matrix's
    transpose). Rows past the last group are zeros."""
    (m, k), g = lhs.shape, rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if rhs.shape[2 if transpose_rhs else 1] != k:
        raise ValueError(f"lhs {lhs.shape} against rhs {rhs.shape}, "
                         f"transpose_rhs={transpose_rhs}")
    note_kernel("moe_gmm", *gmm_work(m, k, n, g, lhs.dtype.itemsize))
    return _gmm_call(lhs, rhs, tiles, transpose_rhs, _COLUMN_TILE,
                     interpret)


# jitted, so that a step's call sites (40 traced in the hybrid decoder's)
# share one trace of each kernel and one lowered function: tracing a
# pallas_call costs ~65 ms, and set-up traces the step three times
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gmm_call(lhs, rhs, tiles, transpose_rhs, column_tile, interpret):
    (m, k), g = lhs.shape, rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    ct = column_tile if n % column_tile == 0 else n
    steps, row_tile = tiles.group_of.shape[0], _row_tile(m, g, tiles)
    kernel = functools.partial(_gmm_kernel, row_tile=row_tile,
                               column_tile=ct, transpose_rhs=transpose_rhs)
    itemsize = lhs.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((row_tile, k),
                             lambda i, off, grp, til, vis: (til[i], 0)),
                pl.BlockSpec((None,) + rhs.shape[1:],
                             lambda i, off, grp, til, vis: (grp[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (row_tile, n), lambda i, off, grp, til, vis: (til[i], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        # a tile's second visit reads what its first wrote: in order
        compiler_params=_params(
            ("arbitrary",), 2 * row_tile * k * itemsize,
            2 * k * n * rhs.dtype.itemsize, 2 * row_tile * n * itemsize,
            2 * row_tile * ct * 4),
        cost_estimate=_cost(gmm_work(m, k, n, g, itemsize)),
        interpret=interpret,
        name="moe_gmm",
    )(*tiles, lhs, rhs)


def _tgmm_kernel(offsets, group_of, tile_of, visits, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, row_tile: int):
    i = pl.program_id(2)
    last = visits[0] - 1

    @pl.when(i <= last)
    def _():
        group, tile = group_of[i], tile_of[i]
        start, end = offsets[group], offsets[group + 1]

        @pl.when(jnp.logical_or(
            i == 0, group_of[jnp.maximum(i - 1, 0)] != group))
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(end > start)
        def _():
            mine = _rows_of(tile, start, end, row_tile)

            def own(ref):
                # a tile two groups share: the other's rows add nothing
                x = ref[...]
                return jnp.where(mine, x.astype(jnp.float32), 0.0).astype(
                    x.dtype)

            acc_ref[...] += jax.lax.dot_general(
                own(lhs_ref), own(rhs_ref), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(jnp.logical_or(
            i == last, group_of[jnp.minimum(i + 1, last)] != group))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def moe_tgmm(lhs, rhs, tiles: GroupTiles, groups: int, *, dtype=None,
             interpret: bool = False):
    """``lhs[rows of group i]^T x rhs[rows of group i]`` for every group:
    [groups, k, n] from ``lhs`` [m, k] and ``rhs`` [m, n], accumulated in
    float32 and written in ``dtype`` (``lhs``'s by default). A group
    without rows gets zeros."""
    (m, k), n = lhs.shape, rhs.shape[1]
    note_kernel("moe_tgmm", *gmm_work(m, k, n, groups, lhs.dtype.itemsize))
    return _tgmm_call(lhs, rhs, tiles, groups, jnp.dtype(dtype or lhs.dtype),
                      _COLUMN_TILE, interpret)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _tgmm_call(lhs, rhs, tiles, groups, dtype, column_tile, interpret):
    (m, k), n = lhs.shape, rhs.shape[1]
    # one side is cut, the one that has the divisor
    bk = column_tile if k % column_tile == 0 else k
    bn = column_tile if bk == k and n % column_tile == 0 else n
    steps, row_tile = tiles.group_of.shape[0], _row_tile(m, groups, tiles)
    itemsize = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, row_tile=row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // bk, n // bn, steps),
            in_specs=[
                pl.BlockSpec(
                    (row_tile, bk),
                    lambda a, b, i, off, grp, til, vis: (til[i], a)),
                pl.BlockSpec(
                    (row_tile, bn),
                    lambda a, b, i, off, grp, til, vis: (til[i], b)),
            ],
            out_specs=pl.BlockSpec(
                (None, bk, bn),
                lambda a, b, i, off, grp, til, vis: (grp[i], a, b)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        compiler_params=_params(
            ("parallel", "parallel", "arbitrary"),
            4 * row_tile * (bk + bn) * itemsize,    # blocks and masked
            2 * bk * bn * dtype.itemsize, 2 * bk * bn * 4),
        cost_estimate=_cost(gmm_work(m, k, n, groups, itemsize)),
        interpret=interpret,
        name="moe_tgmm",
    )(*tiles, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, tiles, interpret):
    return moe_gmm(lhs, rhs, tiles, interpret=interpret)


def _grouped_fwd(lhs, rhs, tiles, interpret):
    return moe_gmm(lhs, rhs, tiles, interpret=interpret), (lhs, rhs, tiles)


def _grouped_bwd(interpret, saved, g):
    lhs, rhs, tiles = saved
    g = g.astype(lhs.dtype)
    return (moe_gmm(g, rhs, tiles, transpose_rhs=True, interpret=interpret),
            moe_tgmm(lhs, g, tiles, rhs.shape[0], dtype=rhs.dtype,
                     interpret=interpret),
            None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, sizes, tiles: Optional[GroupTiles] = None, *,
                   interpret: bool = False):
    """``jax.lax.ragged_dot(lhs, rhs, sizes)`` by the kernels above,
    forward and both gradients: ``lhs`` [m, k] with ``m`` a multiple of
    ``ROW_TILE``, ``rhs`` [g, k, n], ``sizes`` [g]. ``tiles`` is
    ``group_tiles(sizes, m)`` where the caller has it already (the
    calls of one window share theirs). Unlike XLA:TPU's lowering, rows
    past the last group come out as zeros."""
    if tiles is None:
        tiles = group_tiles(sizes, lhs.shape[0])
    return _grouped(lhs, rhs, tiles, interpret)
