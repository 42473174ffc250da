"""Pallas layer-norm kernel.

TPU-native replacement for the reference's fused LayerNorm CUDA kernels
(/root/reference/paddle/fluid/operators/layer_norm_op.cu and the
skip_layernorm/embedding_eltwise_layernorm fusions in operators/fused/).
One pass over rows resident in VMEM: mean/var/normalize/affine fused, no
HBM round-trips between the stages. Grid tiles the row dimension; the
feature dimension stays whole (lane-dim 128-aligned models: 768/1024/...).

Reverse mode: ``_ln_core`` is a ``jax.custom_vjp``. The backward recomputes
the per-row mean/rstd from the saved input (avoids 1-D tiled kernel outputs,
which Mosaic lays out incompatibly with XLA) and applies the standard fused
three-term formula in fp32 XLA ops — the stat recompute fuses into the same
HBM pass as the dx computation.

Under a mesh only the forward kernel runs per shard (``_per_shard``: rows
over ``dp``); the ``custom_vjp`` sits outside that ``shard_map``, so the
backward is ordinary XLA that the partitioner leaves local to each chip
(``dw``/``db`` summed over ``dp``). Inside the ``shard_map`` its transpose
would sum ``dx`` over ``mp``, where the rows are whole: an all-reduce of
two identical halves per norm site.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.xprof import note_kernel

_ROW_BLOCK = 256


def layer_norm_fwd_work(rows: int, cols: int, itemsize: int):
    """(FLOPs, HBM bytes) one forward call must do. The bytes bound it:
    every row read and written once, the two f32 affine vectors read;
    some eight vector operations an element, none of them a matmul."""
    return 8.0 * rows * cols, float(2 * rows * cols * itemsize
                                    + 8 * cols)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _ln_forward(x, w, b, eps: float, interpret: bool):
    rows, cols = x.shape
    block = min(_ROW_BLOCK, rows)
    grid = (pl.cdiv(rows, block),)
    kernel = functools.partial(_ln_kernel, eps=eps)
    ms = {} if interpret else {"memory_space": pltpu.VMEM}
    note_kernel("layer_norm_fwd", *layer_norm_fwd_work(
        rows, cols, x.dtype.itemsize))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, cols), lambda i: (i, 0), **ms),
            pl.BlockSpec((cols,), lambda i: (0,), **ms),
            pl.BlockSpec((cols,), lambda i: (0,), **ms),
        ],
        out_specs=pl.BlockSpec((block, cols), lambda i: (i, 0), **ms),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="layer_norm_fwd",
    )(x, w, b)


def _ln_forward_per_shard(x, w, b, eps: float, interpret: bool):
    from . import _per_shard
    from ..parallel.mesh import DP
    return _per_shard(
        lambda x, w, b, _shard: _ln_forward(x, w, b, eps, interpret),
        (x, w, b), ({0: DP}, {}, {}), {0: DP})


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln_core(x, w, b, eps: float, interpret: bool):
    return _ln_forward_per_shard(x, w, b, eps, interpret)


def _ln_fwd(x, w, b, eps, interpret):
    return _ln_forward_per_shard(x, w, b, eps, interpret), (x, w, b)


def _ln_bwd(eps, interpret, res, g):
    x, w, b = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    dy = gf * w.astype(jnp.float32)
    db = jnp.sum(gf, axis=0).astype(b.dtype)
    dw = jnp.sum(gf * xhat, axis=0).astype(w.dtype)
    m1 = jnp.mean(dy, axis=-1, keepdims=True)
    m2 = jnp.mean(dy * xhat, axis=-1, keepdims=True)
    dx = (rstd * (dy - m1 - xhat * m2)).astype(x.dtype)
    return dx, dw, db


_ln_core.defvjp(_ln_fwd, _ln_bwd)


def layer_norm_pallas(x, weight=None, bias=None, epsilon: float = 1e-5,
                      interpret: bool = False):
    """LayerNorm over the last dim. Falls back for rank!=2 by reshaping."""
    orig_shape = x.shape
    cols = orig_shape[-1]
    if cols % 128 != 0 or x.size // cols < 8:
        raise NotImplementedError("unaligned feature dim; use XLA path")
    x2 = x.reshape(-1, cols)
    w = weight.reshape(cols) if weight is not None \
        else jnp.ones((cols,), jnp.float32)
    b = bias.reshape(cols) if bias is not None \
        else jnp.zeros((cols,), jnp.float32)
    out = _ln_core(x2, w, b, epsilon, interpret)
    return out.reshape(orig_shape)
