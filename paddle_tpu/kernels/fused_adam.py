"""Pallas fused Adam/AdamW kernel.

TPU-native replacement for the reference's fused optimizer CUDA kernels
(/root/reference/paddle/fluid/operators/optimizers/adam_op.h AdamFunctor +
the fuse_adam_op_pass that batches per-param launches,
framework/ir/fuse_optimizer_ops_pass/). Param, grad, m, v stream through
VMEM once; all four outputs are written in the same pass (XLA would also
fuse this well — the kernel exists to guarantee the single-pass schedule
and to fold bias correction + weight decay into the same sweep, and as the
registration point for a future multi-tensor horizontally-fused launch).

Operates on flat fp32 views; the optimizer flattens/unflattens around it.

``fused_adam_leaf`` is the newer LAYOUT-PRESERVING entry point
(FLAGS_fused_adam): it keeps each leaf's native 2-D tiling (collapsing
only leading dims) so no relayout copies are forced — the measured
regression that keeps the ravel-based FLAGS_use_pallas_adam path off —
and mirrors the unfused update's exact op order so results are BITWISE
identical to it (no reciprocal rewrite, same multiply/divide order).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.xprof import note_kernel

_BLOCK = 8 * 128 * 64  # elements per grid step (fits VMEM x4 buffers)


def fused_adam_work(n: int, param_itemsize: int = 4):
    """(FLOPs, HBM bytes) one fused Adam call must do over ``n``
    elements. The bytes bound it: parameter, gradient and both f32
    moments read, parameter and moments written; some twelve vector
    operations an element."""
    return 12.0 * n, float(n * (2 * param_itemsize + 20))


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                 p_out, m_out, v_out, *, beta1, beta2, eps, weight_decay):
    lr_c = sc_ref[0]          # bias-corrected lr
    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:].astype(jnp.float32)
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    update = m * pl.reciprocal(jnp.sqrt(v) + eps, approx=False)
    if weight_decay:
        update = update + (weight_decay / 1.0) * p  # decoupled decay term
    p_new = p - lr_c * update
    p_out[:] = p_new.astype(p_out.dtype)
    m_out[:] = m
    v_out[:] = v


def _adam_leaf_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                      p_out, m_out, v_out, *, beta1, beta2, eps):
    # EXACTLY the unfused Adam.update expression (optimizer/__init__.py)
    # in the same order — parity with it is bitwise, which is what the
    # skip-step guard / GradScaler interaction tests pin down
    g = g_ref[:]
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * jnp.square(g)
    p_out[:] = p_ref[:] - sc_ref[0] * m / (jnp.sqrt(v) + eps)
    m_out[:] = m
    v_out[:] = v


def _leaf_2d(x):
    """Native-layout 2-D view: collapse leading dims onto rows, keep
    the minor (lane) dim — a free reshape, unlike ravel on >=2-D."""
    if x.ndim >= 2:
        return x.reshape(-1, x.shape[-1])
    return x.reshape(1, -1)


def _round_up(n: int, mult: int) -> int:
    return max(mult, -(-n // mult) * mult)


def fused_adam_leaf(p, g, m, v, lr_corrected, beta1: float, beta2: float,
                    eps: float, interpret: bool = False):
    """One fused Adam step on a single fp32 leaf, layout preserved.

    Returns (p_new, m_new, v_new) with p's shape/dtype. lr_corrected
    already carries bias correction (caller folds it, same as the
    unfused path). Bitwise-identical to the unfused update.
    """
    shape = p.shape
    p2, g2, m2, v2 = (_leaf_2d(x) for x in (p, g, m, v))
    rows, cols = p2.shape
    bm = min(256, _round_up(rows, 8))
    bn = min(2048, _round_up(cols, 128))
    grid = (pl.cdiv(rows, bm), pl.cdiv(cols, bn))
    kernel = functools.partial(_adam_leaf_kernel, beta1=beta1,
                               beta2=beta2, eps=eps)
    sc = jnp.asarray(lr_corrected, jnp.float32).reshape(1)
    tile = pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                        memory_space=pltpu.VMEM)
    p_new, m_new, v_new = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile, tile, tile, tile,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, p.dtype),
            jax.ShapeDtypeStruct(m2.shape, jnp.float32),
            jax.ShapeDtypeStruct(v2.shape, jnp.float32),
        ],
        interpret=interpret,
        name="fused_adam_leaf",
    )(p2, g2, m2, v2, sc)
    note_kernel("fused_adam_leaf", *fused_adam_work(
        rows * cols, p.dtype.itemsize))
    return (p_new.reshape(shape), m_new.reshape(shape),
            v_new.reshape(shape))


def fused_adam_flat(p, g, m, v, lr_corrected, beta1: float, beta2: float,
                    eps: float, weight_decay: float = 0.0,
                    interpret: bool = False):
    """One fused Adam step on flat arrays. lr_corrected already includes
    bias correction (sqrt(1-b2^t)/(1-b1^t) folded in by the caller)."""
    n = p.shape[0]
    block = min(_BLOCK, n)
    grid = (pl.cdiv(n, block),)
    kernel = functools.partial(_adam_kernel, beta1=beta1, beta2=beta2,
                               eps=eps, weight_decay=weight_decay)
    sc = jnp.asarray(lr_corrected, jnp.float32).reshape(1)
    note_kernel("fused_adam_flat", *fused_adam_work(
        n, p.dtype.itemsize))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block,), lambda i: (i,),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        # no input_output_aliases: callers (e.g. AdamW's decoupled decay)
        # may reuse the old param after this call; XLA still schedules the
        # update in-place when the buffers are donated at the jit boundary
        interpret=interpret,
        name="fused_adam_flat",
    )(p, g, m, v, sc)
