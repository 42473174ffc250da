"""The Mamba-2 chunked state-space-dual scan as a pair of Pallas kernels.

``nn.layers.ssm.ssd_chunked_scan`` is the scan as XLA sees it: the
cumulative log-decay, the ``[chunk, chunk]`` decay blocks, ``C B^T``,
``x dt`` in float32 and every chunk's state are arrays in HBM, 2.3 GB
read and written a sequence at the hybrid decoder's widths for 0.17 GB
of inputs and outputs, and a backward that recomputes the whole forward
because those arrays are too large to keep (PERF.md section 6, PR 34).
``ssd_fwd`` and ``ssd_bwd`` keep all of that in VMEM; ``ssd_scan`` ties
them with a ``custom_vjp`` whose residuals are the inputs and the
float32 state entering each chunk.

**The walk.** The grid is (sequence, group, chunk), the chunk axis in
order. A grid step holds one chunk of one group with time on the lanes:
x ``[R * P, chunk]`` for the group's ``R`` heads of ``P`` channels, B
and C ``[N, chunk]``, dt and ``dt * a`` ``[R, chunk]``, and the group's
state ``[R * P, N]`` float32 in scratch (zeroed at chunk 0). The
backward walks the chunks from the last to the first with the state's
cotangent in scratch, rebuilds ``C B^T`` and the decay blocks of its
chunk from the inputs and reads that chunk's entering state.

**Time on the lanes, and why.** A head is ``P`` whole sublanes of its
group's slab, so every per-head piece is an aligned slice and its
``[P, chunk] x [chunk, chunk]`` product streams ``P`` rows through the
MXU, not a lane tile padded to 128; dt and the cumulative log-decay of a
head are rows that spread over its sublanes for nothing, the sums over a
head's channels that the backward needs (the cotangents of dt and of the
log-decay) are sums over sublanes and come out as rows, and the state is
``[R * P, N]`` with no 64-wide minor dimension to pad. The prefix sum of
the log-decay is a float32 product with a triangle of ones, not a
``reduce_window``; the one thing a ``[chunk, chunk]`` block needs down
its sublanes, the log-decay as a column, comes from one ``[128, chunk]``
transpose a grid step. And it is the layout XLA keeps the Mamba-2
layer's activations in when left to itself (``{1,2,0}`` for ``[B, L,
channels]``: the gated group norm reduces over channels without a
relayout there): ``[B, channels, L]`` operands are bitcasts of those,
where ``[B, L, channels]`` operands cost the step 24.6 ms of float32
copies round the kernels (PERF.md section 6, PR 34).

**Precision** is ``ssd_chunked_scan``'s: dt, the cumulative log-decay
and the states in float32; matmul operands in the input's dtype with
float32 accumulation; the causal mask applied before ``exp``, so a used
exponent is never positive. A block's exponent ``cum_i - cum_j`` gives
its cotangent to position i and takes it from position j: both sums are
read off one float32 array, as autodiff reads them, so that what cancels
between them in the suffix sum over the chunk cancels exactly (a
cheaper identity through ``sum_p dy y`` does not, and loses a digit on
``A_log``'s gradient in bfloat16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.xprof import note_kernel
from .grouped_matmul import _cost, _params

LANES = 128
_NEG_INF = float("-inf")
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# the sequences and groups in any order, a group's chunks in theirs
_SEMANTICS = ("parallel", "parallel", "arbitrary")
# contracting dimensions of a product: a @ b^T, a^T @ b
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def supported(x_shape, group_shape, chunk: int) -> bool:
    """Whether the kernels take ``x`` [B, L, H, P] with B and C
    [B, L, G, N] at this chunk: whole chunks; the chunk and N whole lane
    tiles; a head a whole number of sublane tiles (16 rows in
    bfloat16)."""
    _, length, h, p = x_shape
    g, n = group_shape[2:]
    return (h % g == 0 and length % chunk == 0 and chunk % LANES == 0
            and n % LANES == 0 and p % 16 == 0 and h // g <= LANES)


def ssd_work(shape, group_shape, chunk: int, itemsize: int,
             backward: bool = False):
    """(FLOPs, HBM bytes) one call must do, ``ssd_fwd`` or ``ssd_bwd``:
    the matmuls of every (sequence, group, chunk), and each operand and
    result crossing HBM once, the float32 states entering the chunks
    among them (written forward, read backward)."""
    bsz, length, h, p = shape
    g, n = group_shape[2:]
    r, q = h // g, chunk
    cells = bsz * g * (length // q)
    block, wide = 2.0 * q * q, 2.0 * q * n * r * p
    wides, groups, rows = bsz * length * h * p, bsz * length * g * n, \
        4 * bsz * length * h
    if backward:
        # B C^T and the block's two gradients; a head's two [q, q]
        # products; C state, B dstate, the state's cotangent, and the
        # gradients of C and B through the state
        flops = cells * (3 * block * n + 2 * block * p * r + 5 * wide)
        moved = (3 * wides + 4 * groups) * itemsize + 4 * rows
    else:
        flops = cells * (block * n + block * p * r + 2 * wide)
        moved = (2 * wides + 2 * groups) * itemsize + 2 * rows
    return flops, float(moved + 4 * cells * n * r * p)


def _ones_where(shape, keep):
    """float32 0/1 of ``keep(row index, column index)``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return keep(rows, cols).astype(_F32)


def _log_decay(dta_ref, tile_ref):
    """The chunk's cumulative log-decay, inclusive, as rows [R, chunk]
    and as columns [chunk, 128], head ``h`` on lane ``h``."""
    r, q = dta_ref.shape
    cum = jnp.dot(dta_ref[...], _ones_where((q, q), lambda k, i: k <= i),
                  precision=_HIGHEST, preferred_element_type=_F32)
    tile_ref[0:r, :] = cum
    return cum, tile_ref[...].T


def _all(x):
    """The sum of a 2-D array, [1, 1]."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _along_lanes(value, n: int):
    """A [1, 1] value as a [1, n] row, through a select: Mosaic then
    holds a plain array, which can be spread over the sublanes again
    (it implements no broadcast along both at once)."""
    keep = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) >= 0
    return jnp.where(keep, value, 0.0)


def _fwd_kernel(x_ref, dt_ref, dta_ref, b_ref, c_ref, y_ref, entering_ref,
                state, tile, to_end_all, *, heads: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, n = x_ref.shape[1], b_ref.shape[0]
    p = x_ref.shape[0] // heads
    cd = x_ref.dtype
    cum_rows, cum_cols = _log_decay(dta_ref, tile)
    bt, ct = b_ref[...], c_ref[...]
    # the block of head h, [j, i]: (B_j . C_i) exp(cum_i - cum_j), j <= i
    cbt = jax.lax.dot_general(bt, ct, _TN, preferred_element_type=_F32)
    upper = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    entering_ref[...] = state[...]
    from_state = jnp.dot(state[...].astype(cd), ct,
                         preferred_element_type=_F32)        # [r * p, q]
    for h in range(heads):
        rows = slice(h * p, (h + 1) * p)
        cum = cum_rows[h:h + 1, :]
        last = cum[:, q - 1:q]
        xdt = x_ref[rows, :].astype(_F32) * dt_ref[h:h + 1, :]
        seg = cum - cum_cols[:, h:h + 1]
        block = (cbt * jnp.exp(jnp.where(upper, seg, _NEG_INF))).astype(cd)
        y_ref[rows, :] = (
            jnp.dot(xdt.astype(cd), block, preferred_element_type=_F32)
            + from_state[rows, :] * jnp.exp(cum)).astype(y_ref.dtype)
        # the state leaving the chunk: what entered, decayed over the
        # chunk, and every position's share decayed to the chunk's end
        to_end_all[rows, :] = (xdt * jnp.exp(last - cum)).astype(cd)
        state[rows, :] = state[rows, :] * _along_lanes(jnp.exp(last), n)
    state[...] += jax.lax.dot_general(to_end_all[...], bt, _NT,
                                      preferred_element_type=_F32)


def _bwd_kernel(x_ref, dt_ref, dta_ref, b_ref, c_ref, dy_ref, entering_ref,
                dx_ref, ddt_ref, ddta_ref, db_ref, dc_ref,
                dstate, tile, to_end_all, dz_all, *, heads: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    q, n = x_ref.shape[1], b_ref.shape[0]
    p = x_ref.shape[0] // heads
    cd = x_ref.dtype
    cum_rows, cum_cols = _log_decay(dta_ref, tile)
    bt, ct = b_ref[...], c_ref[...]
    # here a head's block lies [i, j], so that its x dt gradient is a
    # plain product with it
    cb = jax.lax.dot_general(ct, bt, _TN, preferred_element_type=_F32)
    lower = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, LANES), 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    entering = entering_ref[...]
    entering_c = entering.astype(cd)
    ds = dstate[...]
    ds_c = ds.astype(cd)
    from_state = jnp.dot(entering_c, ct, preferred_element_type=_F32)
    d_to_end = jnp.dot(ds_c, bt, preferred_element_type=_F32)
    dcb = jnp.zeros((q, q), _F32)
    at_i = jnp.zeros((q, LANES), _F32)
    for h in range(heads):
        rows = slice(h * p, (h + 1) * p)
        cum = cum_rows[h:h + 1, :]
        last = cum[:, q - 1:q]
        to_end = jnp.exp(last - cum)
        carry = _along_lanes(jnp.exp(last), n)
        dt = dt_ref[h:h + 1, :]
        xf = x_ref[rows, :].astype(_F32)
        xdt = xf * dt
        dyf = dy_ref[rows, :].astype(_F32)
        dy_c = dy_ref[rows, :]
        dz = dyf * jnp.exp(cum)
        dz_all[rows, :] = dz.astype(cd)
        to_end_all[rows, :] = (xdt * to_end).astype(cd)
        dxdt = d_to_end[rows, :] * to_end
        # the log-decay's cotangent, as rows: from the state's read; from
        # every position's decay to the chunk's end, minus at the
        # position, plus at the chunk's last, where the state's carry
        # arrives too
        through_end = jnp.sum(xdt * dxdt, axis=0, keepdims=True)
        at_last = _all(ds[rows, :] * entering[rows, :] * carry) \
            + jnp.sum(through_end, axis=1, keepdims=True)
        d_cum = jnp.sum(dz * from_state[rows, :], axis=0, keepdims=True) \
            - through_end + jnp.where(is_last, at_last, 0.0)
        decay = jnp.exp(jnp.where(
            lower, cum_cols[:, h:h + 1] - cum, _NEG_INF))        # [i, j]
        dxdt += jnp.dot(dy_c, (cb * decay).astype(cd),
                        preferred_element_type=_F32)
        d_decay = decay * jax.lax.dot_general(
            dy_c, xdt.astype(cd), _TN, preferred_element_type=_F32)
        dcb += d_decay
        # the block's own exponents, cum_i - cum_j: plus at i, minus at j
        # (a row already); both off one array, so that what cancels
        # between them cancels
        d_seg = d_decay * cb
        tile[h:h + 1, :] = d_cum - jnp.sum(d_seg, axis=0, keepdims=True)
        at_i += jnp.where(lane == h,
                          jnp.sum(d_seg, axis=1, keepdims=True), 0.0)
        dx_ref[rows, :] = (dxdt * dt).astype(dx_ref.dtype)
        ddt_ref[h:h + 1, :] = jnp.sum(dxdt * xf, axis=0, keepdims=True)
        dstate[rows, :] = ds[rows, :] * carry
    # dt a_k decays every position from k on
    ddta_ref[...] = jnp.dot(
        tile[0:heads, :] + at_i.T[0:heads],
        _ones_where((q, q), lambda i, at: i >= at),
        precision=_HIGHEST, preferred_element_type=_F32)
    dstate[...] += jax.lax.dot_general(dz_all[...], ct, _NT,
                                       preferred_element_type=_F32)
    dcb_c = dcb.astype(cd)
    dc_ref[...] = (
        jax.lax.dot_general(bt, dcb_c, _NT, preferred_element_type=_F32)
        + jax.lax.dot_general(entering_c, dz_all[...], _TN,
                              preferred_element_type=_F32)
    ).astype(dc_ref.dtype)
    db_ref[...] = (
        jnp.dot(ct, dcb_c, preferred_element_type=_F32)
        + jax.lax.dot_general(ds_c, to_end_all[...], _TN,
                              preferred_element_type=_F32)
    ).astype(db_ref.dtype)


def _time_minor(t):
    """[B, L, ...] -> [B, channels, L]: time on the lanes."""
    return jnp.swapaxes(t.reshape(t.shape[0], t.shape[1], -1), 1, 2)


def _by_group(t, groups: int):
    """[B, L, H] -> [B, G, R, L]: a group's heads one block, whatever
    their number."""
    bsz, length, h = t.shape
    return _time_minor(t).reshape(bsz, groups, h // groups, length)


def _time_major(t, like):
    """[B, channels, L] -> ``like``'s shape [B, L, ...]."""
    return jnp.swapaxes(t, 1, 2).reshape(like.shape)


def _block_specs(q: int, rp: int, r: int, n: int, at):
    """The blocks of x, of dt and dt a, of B and C, and of a chunk's
    entering state, at chunk ``at(c)``."""
    wide = pl.BlockSpec((None, rp, q), lambda b, g, c: (b, g, at(c)))
    row = pl.BlockSpec((None, None, r, q), lambda b, g, c: (b, g, 0, at(c)))
    group = pl.BlockSpec((None, n, q), lambda b, g, c: (b, g, at(c)))
    state = pl.BlockSpec((None, None, None, rp, n),
                         lambda b, g, c: (b, at(c), g, 0, 0))
    return wide, row, group, state


# jitted, so that the layers' call sites share one trace of each kernel
# (PERF.md section 6, PR 33: tracing a pallas_call costs ~65 ms a site)
@functools.partial(jax.jit, static_argnums=(5, 6))
@jax.named_scope("pt.ssm_scan")
def ssd_fwd(x, dt, dta, b_mat, c_mat, chunk: int, interpret: bool = False):
    """y [B, L, H, P] of the scan, and the float32 state entering every
    chunk, [B, L / chunk, G, R * P, N]. dt and ``dta = dt * a`` are
    [B, L, H] float32. The states are written whoever calls: under a
    layer's ``jax.checkpoint`` the forward pass runs the ``custom_vjp``'s
    forward rule as the recomputation does, so a variant without them
    would serve evaluation alone, at 4% of a call (PERF.md section 6,
    PR 34)."""
    bsz, length, h, p = x.shape
    g, n = b_mat.shape[2:]
    r, q, nc = h // g, chunk, length // chunk
    rp, size = r * p, x.dtype.itemsize
    wide, row, group, state = _block_specs(q, rp, r, n, lambda c: c)
    y, entering = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=r),
        grid=(bsz, g, nc),
        in_specs=[wide, row, row, group, group],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct((bsz, h * p, length), x.dtype),
                   jax.ShapeDtypeStruct((bsz, nc, g, rp, n), _F32)],
        scratch_shapes=[pltpu.VMEM((rp, n), _F32),
                        pltpu.VMEM((LANES, q), _F32),
                        pltpu.VMEM((rp, q), x.dtype)],
        compiler_params=_params(
            _SEMANTICS,
            5 * q * rp * size, 4 * q * n * size, 4 * r * q * 4,
            3 * n * rp * 4, 3 * q * rp * 4, 4 * q * q * 4),
        cost_estimate=_cost(ssd_work(x.shape, b_mat.shape, q, size)),
        interpret=interpret,
        name="ssd_fwd",
    )(_time_minor(x), _by_group(dt, g), _by_group(dta, g),
      _time_minor(b_mat), _time_minor(c_mat))
    return _time_major(y, x), entering


@functools.partial(jax.jit, static_argnums=(7, 8))
@jax.named_scope("pt.ssm_scan")
def ssd_bwd(x, dt, dta, b_mat, c_mat, dy, entering, chunk: int,
            interpret: bool = False):
    """The cotangents of x, dt (as a factor of ``x dt``), ``dta``, B and
    C for ``dy``, from the forward's inputs and entering states."""
    bsz, length, h, p = x.shape
    g, n = b_mat.shape[2:]
    r, q, nc = h // g, chunk, length // chunk
    rp, size = r * p, x.dtype.itemsize
    wide, row, group, state = _block_specs(q, rp, r, n,
                                           lambda c: nc - 1 - c)
    dx, ddt, ddta, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=r),
        grid=(bsz, g, nc),
        in_specs=[wide, row, row, group, group, wide, state],
        out_specs=[wide, row, row, group, group],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h * p, length), x.dtype),
            jax.ShapeDtypeStruct((bsz, g, r, length), _F32),
            jax.ShapeDtypeStruct((bsz, g, r, length), _F32),
            jax.ShapeDtypeStruct((bsz, g * n, length), b_mat.dtype),
            jax.ShapeDtypeStruct((bsz, g * n, length), c_mat.dtype)],
        scratch_shapes=[pltpu.VMEM((rp, n), _F32),
                        pltpu.VMEM((LANES, q), _F32),
                        pltpu.VMEM((rp, q), x.dtype),
                        pltpu.VMEM((rp, q), x.dtype)],
        compiler_params=_params(
            _SEMANTICS,
            8 * q * rp * size, 8 * q * n * size, 8 * r * q * 4,
            3 * n * rp * 4, 6 * q * rp * 4, 6 * q * q * 4),
        cost_estimate=_cost(ssd_work(x.shape, b_mat.shape, q, size, True)),
        interpret=interpret,
        name="ssd_bwd",
    )(_time_minor(x), _by_group(dt, g), _by_group(dta, g),
      _time_minor(b_mat), _time_minor(c_mat), _time_minor(dy), entering)
    heads = lambda t: _time_major(t.reshape(bsz, h, length), dt)
    return (_time_major(dx, x), heads(ddt), heads(ddta),
            _time_major(db, b_mat), _time_major(dc, c_mat))


def _forward(x, dt, b_mat, c_mat, a, chunk, interpret):
    if not supported(x.shape, b_mat.shape, chunk):
        raise NotImplementedError(
            f"ssd_scan takes whole chunks and whole lane tiles: x "
            f"{x.shape}, B {b_mat.shape}, chunk {chunk}")
    dt32 = dt.astype(_F32)
    note_kernel("ssd_fwd", *ssd_work(x.shape, b_mat.shape, chunk,
                                     x.dtype.itemsize))
    return ssd_fwd(x, dt32, dt32 * a.astype(_F32), b_mat, c_mat, chunk,
                   interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, dt, b_mat, c_mat, a, chunk, interpret):
    return _forward(x, dt, b_mat, c_mat, a, chunk, interpret)[0]


def _scan_fwd(x, dt, b_mat, c_mat, a, chunk, interpret):
    y, entering = _forward(x, dt, b_mat, c_mat, a, chunk, interpret)
    return y, (x, dt, b_mat, c_mat, a, entering)


def _scan_bwd(chunk, interpret, saved, dy):
    x, dt, b_mat, c_mat, a, entering = saved
    dt32, a32 = dt.astype(_F32), a.astype(_F32)
    note_kernel("ssd_bwd", *ssd_work(x.shape, b_mat.shape, chunk,
                                     x.dtype.itemsize, True))
    dx, ddt, ddta, db, dc = ssd_bwd(x, dt32, dt32 * a32, b_mat, c_mat,
                                    dy.astype(x.dtype), entering, chunk,
                                    interpret)
    with jax.named_scope("pt.ssm_scan"):
        return (dx, (ddt + ddta * a32).astype(dt.dtype), db, dc,
                jnp.sum(ddta * dt32, axis=(0, 1)).astype(a.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, b_mat, c_mat, a, chunk: int, *,
             interpret: bool = False):
    """``ssd_chunked_scan(x, dt, b_mat, c_mat, a, chunk)`` by the kernels
    above, with its gradient: x [B, L, H, P]; dt [B, L, H] float32, after
    softplus; a [H] float32, negative; b_mat, c_mat [B, L, G, N]. Raises
    ``NotImplementedError`` for shapes ``supported`` declines."""
    return _scan(x, dt, b_mat, c_mat, a, chunk, interpret)
