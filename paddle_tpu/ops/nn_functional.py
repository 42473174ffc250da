"""Neural-network functional ops.

TPU-native lowerings for the reference's NN operator family
(/root/reference/paddle/fluid/operators/: conv_op.cc + conv_cudnn_op.cu,
conv_transpose_op.cc, pool_op.cc, batch_norm_op.cc, layer_norm_op.cc,
instance_norm_op.cc, group_norm_op.cc, data_norm_op.cc, dropout_op.cc,
lookup_table_v2_op.cc, one_hot_op.cc, interpolate_op.cc, unfold_op.cc,
grid_sampler_op.cc, lrn_op.cc, affine_channel_op.cc, ...).

Convs/matmuls lower to XLA conv_general_dilated / dot_general so they tile
onto the MXU; layout is NCHW at the API (reference parity) with XLA free to
re-layout internally. Norm ops return functional (out, new_stats) instead of
mutating buffers — the Layer wrappers thread stats through step state.
"""

from __future__ import annotations

import builtins
import functools
from math import prod as _prod
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..core import random as _random
from ..core.random import fmix32 as _fmix32, mix32 as _mix32
from ..flags import GLOBAL_FLAGS
from ..observability import xprof as _xprof

IntOrPair = Union[int, Sequence[int]]


def _pair(v: IntOrPair, n: int = 2) -> Tuple[int, ...]:
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


def _conv_padding(padding, spatial: int):
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * spatial
    padding = list(padding)
    if len(padding) == spatial:
        return [(p, p) for p in padding]
    if len(padding) == 2 * spatial:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(spatial)]
    raise ValueError(f"bad padding {padding}")


# ---------------------------------------------------------------------------
# convolution (ref: conv_op.cc, conv_cudnn_op.cu, depthwise_conv_op.cu)
# ---------------------------------------------------------------------------

def conv2d(x, weight, bias=None, stride: IntOrPair = 1,
           padding: Union[str, IntOrPair] = 0, dilation: IntOrPair = 1,
           groups: int = 1, data_format: str = "NCHW",
           weight_format: Optional[str] = None):
    """``weight_format`` defaults to the historical pairing (OIHW for
    NCHW activations, HWIO for NHWC); pass ``weight_format="OIHW"``
    with NHWC activations to run channels-last compute on the same
    parameter layout the nn layers store (checkpoints stay
    layout-independent — XLA transposes the small filter, not the
    activations)."""
    if weight_format is None:
        weight_format = "OIHW" if data_format == "NCHW" else "HWIO"
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape, (data_format, weight_format, data_format))
    out = lax.conv_general_dilated(
        x, weight, window_strides=_pair(stride),
        padding=_conv_padding(padding, 2),
        rhs_dilation=_pair(dilation), dimension_numbers=dn,
        feature_group_count=groups, precision=None)
    if bias is not None:
        shape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + bias.reshape(shape)
    return out


def conv3d(x, weight, bias=None, stride: IntOrPair = 1,
           padding: Union[str, IntOrPair] = 0, dilation: IntOrPair = 1,
           groups: int = 1, data_format: str = "NCDHW"):
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape,
        ("NCDHW", "OIDHW", "NCDHW") if data_format == "NCDHW"
        else ("NDHWC", "DHWIO", "NDHWC"))
    out = lax.conv_general_dilated(
        x, weight, window_strides=_pair(stride, 3),
        padding=_conv_padding(padding, 3),
        rhs_dilation=_pair(dilation, 3), dimension_numbers=dn,
        feature_group_count=groups)
    if bias is not None:
        shape = (1, -1, 1, 1, 1) if data_format == "NCDHW" else (1,) * 4 + (-1,)
        out = out + bias.reshape(shape)
    return out


def conv1d(x, weight, bias=None, stride: int = 1,
           padding: Union[str, int] = 0, dilation: int = 1, groups: int = 1):
    x4 = x[:, :, None, :]
    w4 = weight[:, :, None, :]
    pad = padding if isinstance(padding, str) else [0, padding]
    out = conv2d(x4, w4, bias, stride=[1, stride], padding=pad,
                 dilation=[1, dilation], groups=groups)
    return out[:, :, 0, :]


def depthwise_conv2d(x, weight, bias=None, stride: IntOrPair = 1,
                     padding: Union[str, IntOrPair] = 0,
                     dilation: IntOrPair = 1, data_format: str = "NCHW"):
    channels = x.shape[1] if data_format == "NCHW" else x.shape[-1]
    return conv2d(x, weight, bias, stride, padding, dilation,
                  groups=channels, data_format=data_format)


def conv2d_transpose(x, weight, bias=None, stride: IntOrPair = 1,
                     padding: IntOrPair = 0, output_padding: IntOrPair = 0,
                     dilation: IntOrPair = 1, groups: int = 1,
                     data_format: str = "NCHW"):
    """(ref: conv_transpose_op.cc). weight layout [in, out//groups, kh, kw]."""
    stride = _pair(stride)
    pad = _conv_padding(padding, 2)
    if isinstance(pad, str):
        raise ValueError("string padding unsupported for transpose conv")
    opad = _pair(output_padding)
    dilation = _pair(dilation)
    kh = (weight.shape[2] - 1) * dilation[0] + 1
    kw = (weight.shape[3] - 1) * dilation[1] + 1
    # Gradient-of-conv formulation: lhs_dilation=stride, flipped kernel.
    pad_t = (kh - 1 - pad[0][0], kh - 1 - pad[0][1] + opad[0])
    pad_l = (kw - 1 - pad[1][0], kw - 1 - pad[1][1] + opad[1])
    w = jnp.flip(weight, axis=(2, 3))  # [I, O/g, kh, kw]
    if groups > 1:
        i, og, khs, kws = w.shape
        w = w.reshape(groups, i // groups, og, khs, kws)
        w = jnp.swapaxes(w, 1, 2).reshape(groups * og, i // groups, khs, kws)
    else:
        w = jnp.swapaxes(w, 0, 1)  # [O, I, kh, kw]
    if data_format not in ("NCHW", "NHWC"):
        raise ValueError(f"conv2d_transpose: data_format must be NCHW "
                         f"or NHWC, got {data_format!r}")
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape, (data_format, "OIHW", data_format))
    out = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[pad_t, pad_l],
        lhs_dilation=stride, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups)
    if bias is not None:
        out = out + (bias.reshape(1, -1, 1, 1) if data_format == "NCHW"
                     else bias.reshape(1, 1, 1, -1))
    return out


def conv_shift(x, y):
    """(ref: conv_shift_op.cc) circular correlation of each row."""
    b, m = x.shape
    _, n = y.shape
    half = n // 2
    idx = (jnp.arange(m)[:, None] + jnp.arange(-half, half + 1)[None, :]) % m
    gathered = x[:, idx]  # [b, m, n]
    return jnp.einsum("bmn,bn->bm", gathered, y)


# ---------------------------------------------------------------------------
# pooling (ref: pool_op.cc, spp_op.cc, max_pool2d_with_index)
# ---------------------------------------------------------------------------

def _pool(x, kind: str, ksize: IntOrPair, stride: Optional[IntOrPair],
          padding: IntOrPair, ceil_mode: bool, exclusive: bool,
          spatial: int, global_pool: bool, channels_last: bool = False):
    sp0 = 1 if channels_last else 2  # first spatial dim index
    if global_pool:
        ksize = x.shape[sp0:sp0 + spatial]
        stride = ksize
        padding = 0
    ksize = _pair(ksize, spatial)
    stride = _pair(stride if stride is not None else ksize, spatial)
    pads = _conv_padding(padding, spatial)
    if channels_last:
        window = (1,) + ksize + (1,)
        strides = (1,) + stride + (1,)
    else:
        window = (1, 1) + ksize
        strides = (1, 1) + stride
    if isinstance(pads, str):
        padding_cfg = pads
    else:
        padding_cfg = [(0, 0)] + list(pads) + [(0, 0)] if channels_last \
            else [(0, 0), (0, 0)] + list(pads)
        if ceil_mode:
            spatial_dims = range(sp0, sp0 + spatial)
            padding_cfg = [
                (lo, hi + (s - 1)) if i in spatial_dims else (lo, hi)
                for i, ((lo, hi), s) in enumerate(zip(padding_cfg, strides))]
    if kind == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides,
                                 padding_cfg)
    summed = lax.reduce_window(x, 0.0, lax.add, window, strides, padding_cfg)
    if exclusive and (isinstance(padding_cfg, list)
                      and builtins.any(p != (0, 0) for p in padding_cfg)):
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides,
                                   padding_cfg)
        return summed / jnp.maximum(counts, 1.0)
    denom = 1.0
    for k in ksize:
        denom *= k
    return summed / denom


def max_pool2d(x, kernel_size: IntOrPair, stride: Optional[IntOrPair] = None,
               padding: IntOrPair = 0, ceil_mode: bool = False,
               data_format: str = "NCHW"):
    return _pool(x, "max", kernel_size, stride, padding, ceil_mode, True, 2,
                 False, channels_last=data_format == "NHWC")


def avg_pool2d(x, kernel_size: IntOrPair, stride: Optional[IntOrPair] = None,
               padding: IntOrPair = 0, ceil_mode: bool = False,
               exclusive: bool = True, data_format: str = "NCHW"):
    return _pool(x, "avg", kernel_size, stride, padding, ceil_mode,
                 exclusive, 2, False, channels_last=data_format == "NHWC")


def max_pool3d(x, kernel_size: IntOrPair, stride: Optional[IntOrPair] = None,
               padding: IntOrPair = 0, ceil_mode: bool = False):
    return _pool(x, "max", kernel_size, stride, padding, ceil_mode, True, 3,
                 False)


def avg_pool3d(x, kernel_size: IntOrPair, stride: Optional[IntOrPair] = None,
               padding: IntOrPair = 0, ceil_mode: bool = False,
               exclusive: bool = True):
    return _pool(x, "avg", kernel_size, stride, padding, ceil_mode,
                 exclusive, 3, False)


def pool2d(x, pool_size: IntOrPair = -1, pool_type: str = "max",
           pool_stride: IntOrPair = 1, pool_padding: IntOrPair = 0,
           global_pooling: bool = False, ceil_mode: bool = False,
           exclusive: bool = True, data_format: str = "NCHW"):
    """Legacy fluid.layers.pool2d signature (ref: pool_op.cc)."""
    return _pool(x, pool_type, pool_size, pool_stride, pool_padding,
                 ceil_mode, exclusive, 2, global_pooling,
                 channels_last=data_format == "NHWC")


def adaptive_avg_pool2d(x, output_size: IntOrPair,
                        data_format: str = "NCHW"):
    oh, ow = _pair(output_size)
    if data_format == "NHWC":
        n, h, w, c = x.shape
        if h % oh == 0 and w % ow == 0:
            return jnp.mean(x.reshape(n, oh, h // oh, ow, w // ow, c),
                            axis=(2, 4))
        # general case: compute channels-first, transpose back once
        out = adaptive_avg_pool2d(jnp.transpose(x, (0, 3, 1, 2)),
                                  output_size)
        return jnp.transpose(out, (0, 2, 3, 1))
    n, c, h, w = x.shape
    if h % oh == 0 and w % ow == 0:
        return jnp.mean(x.reshape(n, c, oh, h // oh, ow, w // ow),
                        axis=(3, 5))
    # General case: mean over variable windows. Reference bin math
    # (adaptive_pool: start=floor(i*H/out), end=ceil((i+1)*H/out)) —
    # bins are never empty, so output_size > input repeats values
    # instead of producing NaN means over empty slices.
    rows = [((h * i) // oh, -(-(h * (i + 1)) // oh))
            for i in range(oh)]
    cols = [((w * j) // ow, -(-(w * (j + 1)) // ow))
            for j in range(ow)]
    parts = []
    for r0, r1 in rows:
        row = []
        for c0, c1 in cols:
            row.append(jnp.mean(x[:, :, r0:r1, c0:c1], axis=(2, 3)))
        parts.append(jnp.stack(row, axis=-1))
    return jnp.stack(parts, axis=-2)


def adaptive_max_pool2d(x, output_size: IntOrPair):
    oh, ow = _pair(output_size)
    n, c, h, w = x.shape
    if h % oh == 0 and w % ow == 0:
        return jnp.max(x.reshape(n, c, oh, h // oh, ow, w // ow),
                       axis=(3, 5))
    # non-empty reference bins (floor/ceil), as in adaptive_avg_pool2d
    rows = [((h * i) // oh, -(-(h * (i + 1)) // oh)) for i in range(oh)]
    cols = [((w * j) // ow, -(-(w * (j + 1)) // ow)) for j in range(ow)]
    parts = []
    for r0, r1 in rows:
        row = []
        for c0, c1 in cols:
            row.append(jnp.max(x[:, :, r0:r1, c0:c1], axis=(2, 3)))
        parts.append(jnp.stack(row, axis=-1))
    return jnp.stack(parts, axis=-2)


def _max_pool_with_index(x, kernel_size, stride, padding, spatial: int):
    """Shared exact (value, flat-index) pair reduce_window for the
    2d/3d *_with_index pools: int32 indices (no f32 mantissa loss),
    deterministic ties toward the smaller index like the reference."""
    spatial_shape = x.shape[2:2 + spatial]
    size = 1
    for s in spatial_shape:
        size *= s
    ksize = _pair(kernel_size, spatial)
    strides_sp = _pair(stride if stride is not None else kernel_size,
                       spatial)
    pads = _conv_padding(padding, spatial)
    window = (1, 1) + ksize
    strides = (1, 1) + strides_sp
    padding_cfg = pads if isinstance(pads, str) else \
        [(0, 0), (0, 0)] + list(pads)
    idx = jnp.broadcast_to(
        jnp.arange(size, dtype=jnp.int32).reshape(
            (1, 1) + spatial_shape), x.shape)
    neg_inf = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
        jnp.iinfo(x.dtype).min

    def reducer(a, b):
        av, ai = a
        bv, bi = b
        take_a = (av > bv) | ((av == bv) & (ai < bi))
        return (jnp.where(take_a, av, bv), jnp.where(take_a, ai, bi))

    return lax.reduce_window(
        (x, idx), (jnp.asarray(neg_inf, x.dtype), jnp.int32(2**31 - 1)),
        reducer, window, strides, padding_cfg)


def max_pool2d_with_index(x, kernel_size: IntOrPair,
                          stride: Optional[IntOrPair] = None,
                          padding: IntOrPair = 0):
    """(ref: max_pool2d_with_index_op) returns (out, argmax flat indices)."""
    vals, idxs = _max_pool_with_index(x, kernel_size, stride, padding, 2)
    return vals, idxs.astype(jnp.int64)


def unpool(x, indices, kernel_size: IntOrPair, stride: IntOrPair = None,
           output_size: Optional[Sequence[int]] = None):
    """(ref: unpool_op.cc) scatter pooled values back by argmax index."""
    n, c, h, w = x.shape
    ksize = _pair(kernel_size)
    stride = _pair(stride if stride is not None else kernel_size)
    if output_size is None:
        oh = (h - 1) * stride[0] + ksize[0]
        ow = (w - 1) * stride[1] + ksize[1]
    else:
        oh, ow = output_size[-2:]
    out = jnp.zeros((n, c, oh * ow), dtype=x.dtype)
    flat_idx = indices.reshape(n, c, -1).astype(jnp.int32)
    vals = x.reshape(n, c, -1)
    out = jax.vmap(jax.vmap(lambda o, i, v: o.at[i].set(v)))(out, flat_idx,
                                                            vals)
    return out.reshape(n, c, oh, ow)


# ---------------------------------------------------------------------------
# normalization — functional, stats threaded (see module docstring)
# ---------------------------------------------------------------------------

def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training: bool = False, momentum: float = 0.9,
               epsilon: float = 1e-5, data_format: str = "NCHW"):
    """Returns (out, new_running_mean, new_running_var).

    (ref: batch_norm_op.cc; momentum semantics: new = m*old + (1-m)*batch)
    """
    if data_format in ("NCHW", "NCL", "NCDHW"):
        axes = (0,) + tuple(range(2, x.ndim))
        shape = (1, -1) + (1,) * (x.ndim - 2)
    else:
        axes = tuple(range(x.ndim - 1))
        shape = (1,) * (x.ndim - 1) + (-1,)
    if training:
        if GLOBAL_FLAGS.get("batch_norm_single_pass"):
            # E[x^2]-E[x]^2 with fp32 accumulation: the two reductions
            # read the same operand so XLA's multi-output fusion makes
            # them ONE pass over the activation, where mean-then-var is
            # two data-dependent passes (r5 ResNet profile: BN-stat
            # loop fusions are ~1/5 of the step). Cancellation is
            # bounded by fp32 accumulation + the clamp; BN inputs are
            # ~unit-scale so the classic failure mode doesn't apply.
            xf = x.astype(jnp.float32)
            mean32 = jnp.mean(xf, axis=axes)
            mean_sq = jnp.mean(jnp.square(xf), axis=axes)
            var32 = jnp.maximum(mean_sq - jnp.square(mean32), 0.0)
            mean = mean32.astype(x.dtype)
            var = var32.astype(x.dtype)
        else:
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
        n = x.size // x.shape[1 if data_format.startswith("NC") else -1]
        unbiased = var * n / builtins.max(n - 1, 1)
        new_mean = momentum * running_mean + (1 - momentum) * mean
        new_var = momentum * running_var + (1 - momentum) * unbiased
    else:
        mean, var = running_mean, running_var
        new_mean, new_var = running_mean, running_var
    inv = lax.rsqrt(var + epsilon)
    out = (x - mean.reshape(shape)) * inv.reshape(shape)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out, new_mean, new_var


def sync_batch_norm(x, running_mean, running_var, weight=None, bias=None,
                    training: bool = False, momentum: float = 0.9,
                    epsilon: float = 1e-5, data_format: str = "NCHW",
                    axis_name: Optional[str] = None):
    """(ref: sync_batch_norm_op.cc) — batch stats allreduced over the data
    axis when run inside shard_map/pmap with ``axis_name``."""
    if not training or axis_name is None:
        return batch_norm(x, running_mean, running_var, weight, bias,
                          training, momentum, epsilon, data_format)
    if data_format.startswith("NC"):
        axes = (0,) + tuple(range(2, x.ndim))
        shape = (1, -1) + (1,) * (x.ndim - 2)
    else:
        axes = tuple(range(x.ndim - 1))
        shape = (1,) * (x.ndim - 1) + (-1,)
    mean = lax.pmean(jnp.mean(x, axis=axes), axis_name)
    mean_sq = lax.pmean(jnp.mean(jnp.square(x), axis=axes), axis_name)
    var = mean_sq - jnp.square(mean)
    new_mean = momentum * running_mean + (1 - momentum) * mean
    new_var = momentum * running_var + (1 - momentum) * var
    inv = lax.rsqrt(var + epsilon)
    out = (x - mean.reshape(shape)) * inv.reshape(shape)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out, new_mean, new_var


def layer_norm(x, weight=None, bias=None, epsilon: float = 1e-5,
               begin_norm_axis: int = -1):
    """(ref: layer_norm_op.cc). Normalizes over dims [begin_norm_axis:)."""
    if begin_norm_axis < 0:
        begin_norm_axis = x.ndim + begin_norm_axis
    axes = tuple(range(begin_norm_axis, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + epsilon)
    norm_shape = x.shape[begin_norm_axis:]
    if weight is not None:
        out = out * weight.reshape(norm_shape)
    if bias is not None:
        out = out + bias.reshape(norm_shape)
    return out


def instance_norm(x, weight=None, bias=None, epsilon: float = 1e-5):
    """(ref: instance_norm_op.cc) NCHW; per-(n, c) spatial stats."""
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + epsilon)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def group_norm(x, groups: int, weight=None, bias=None,
               epsilon: float = 1e-5, data_format: str = "NCHW"):
    """(ref: group_norm_op.cc)."""
    if data_format != "NCHW":
        raise NotImplementedError("group_norm supports NCHW")
    n, c = x.shape[:2]
    spatial = x.shape[2:]
    g = x.reshape((n, groups, c // groups) + spatial)
    axes = tuple(range(2, g.ndim))
    mean = jnp.mean(g, axis=axes, keepdims=True)
    var = jnp.var(g, axis=axes, keepdims=True)
    out = ((g - mean) * lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def local_response_norm(x, size: int = 5, alpha: float = 1e-4,
                        beta: float = 0.75, k: float = 1.0):
    """(ref: lrn_op.cc) NCHW cross-channel LRN."""
    sq = jnp.square(x)
    half = size // 2
    padded = jnp.pad(sq, ((0, 0), (half, size - 1 - half), (0, 0), (0, 0)))
    window = jnp.stack([padded[:, i:i + x.shape[1]] for i in range(size)],
                       axis=0).sum(axis=0)
    return x / jnp.power(k + alpha * window, beta)


lrn = local_response_norm


def data_norm(x, batch_size, batch_sum, batch_square_sum,
              epsilon: float = 1e-4):
    """(ref: data_norm_op.cc) normalization by accumulated batch statistics."""
    mean = batch_sum / batch_size
    scale = lax.rsqrt(batch_square_sum / batch_size - jnp.square(mean)
                      + epsilon)
    return (x - mean) * scale


def affine_channel(x, scale, bias, data_format: str = "NCHW"):
    """(ref: affine_channel_op.cc)."""
    shape = (1, -1) + (1,) * (x.ndim - 2) if data_format == "NCHW" \
        else (1,) * (x.ndim - 1) + (-1,)
    return x * scale.reshape(shape) + bias.reshape(shape)


def spectral_norm(weight, u, v, power_iters: int = 1, epsilon: float = 1e-12,
                  dim: int = 0):
    """(ref: spectral_norm_op.cc) returns normalized weight."""
    w = jnp.moveaxis(weight, dim, 0)
    w_mat = w.reshape(w.shape[0], -1)

    def body(_, uv):
        u_, v_ = uv
        v_ = w_mat.T @ u_
        v_ = v_ / (jnp.linalg.norm(v_) + epsilon)
        u_ = w_mat @ v_
        u_ = u_ / (jnp.linalg.norm(u_) + epsilon)
        return (u_, v_)

    u, v = lax.fori_loop(0, power_iters, body, (u, v))
    sigma = u @ w_mat @ v
    return weight / sigma


# ---------------------------------------------------------------------------
# dropout & friends (ref: dropout_op.cc)
# ---------------------------------------------------------------------------

def _mask_seed(key):
    """One ``uint32`` from a PRNG key: a fold of the key's own words
    (two under threefry, four under rbg), so no random word is drawn.
    Keys from ``rng_scope`` streams are already hashes of (step key,
    call-site counter); an explicit ``jax.random.key(n)`` differs from
    ``key(m)`` in one word, which the rounds spread over all 32 bits."""
    words = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    h = jnp.uint32(0x9E3779B9)
    for i in range(words.shape[0]):
        h = _fmix32(h ^ words[i])
    return h


def _position(shape, lo: int, hi: int, start=None):
    """``start`` + the row-major position within dims ``lo:hi``, as
    ``uint32`` of the whole ``shape``: iotas over the GLOBAL shape, so a
    mesh that splits the array splits the iotas with it. ``start`` rides
    the slowest iota's term, which XLA keeps as a short vector: adding
    it there costs nothing an element."""
    terms, stride = [], 1
    for d in range(hi - 1, lo - 1, -1):
        if shape[d] > 1:
            term = lax.broadcasted_iota(jnp.uint32, shape, d)
            terms.append(term * jnp.uint32(stride) if stride > 1 else term)
        stride *= shape[d]
    if start is not None:       # on the slowest term, or alone
        terms.append(terms.pop() + start if terms
                     else jnp.full(shape, start, jnp.uint32))
    if not terms:
        return jnp.zeros(shape, jnp.uint32)
    return functools.reduce(jnp.add, reversed(terms))


def _hash_keep(seed, keep_prob: float, shape):
    """Bernoulli(keep_prob) over ``shape`` as a pure function of
    (``seed``, global position): a threshold on 32 hashed bits, by
    ``mix32`` (a threshold reads the high bits, which the two rounds
    have mixed: ``fmix32``'s last fold would buy nothing)."""
    shape = tuple(int(d) for d in shape)
    thresh = jnp.uint32(min(int(keep_prob * (2.0 ** 32)), 2 ** 32 - 1))
    # the scope's name must not start with "pt.": a block metric charges
    # an operation to the last pt. token of its op_name
    with jax.named_scope("dropout_mask"):
        split, tail = len(shape), 1
        while split and tail * shape[split - 1] <= 2 ** 32:
            split -= 1
            tail *= shape[split]
        if split == 0:
            # under 2^32 elements every position is its own counter:
            # one full mix each
            bits = _mix32(_position(shape, 0, len(shape), seed))
        else:
            # two coordinates, each through rounds of its own, as
            # kernels/flash_attention._dropout_keep: one linear counter
            # would alias positions 2^32 apart
            if _prod(shape[:split]) > 2 ** 32:
                raise ValueError(f"dropout mask of shape {shape}: no "
                                 "split into two 32-bit coordinates")
            bits = _mix32(_fmix32(_position(shape, 0, split, seed))
                          ^ (_position(shape, split, len(shape))
                             * jnp.uint32(0x9E3779B9)))
        return bits < thresh


def dropout_keep_mask(key, keep_prob: float, shape):
    """Bernoulli(keep_prob) mask of ``shape``: a counter hash of (a
    32-bit seed folded from ``key``, the element's global position)
    against the threshold ``floor(keep_prob * 2^32)``; every element
    has 32 bits of its own. It is integer arithmetic on iotas, which
    XLA fuses into whatever applies the mask: no random word is written
    to memory (a ``jax.random.bits`` draw of ``shape`` is a word per
    element written and read back). The same key gives the same mask,
    eagerly, under ``jit``, and under any mesh: positions are global,
    so GSPMD partitions the iotas and ``ShardedTrainStep`` draws the
    mask ``TrainStep`` draws."""
    _xprof.note_dropout_mask(_prod(shape))
    return _hash_keep(_mask_seed(key), keep_prob, shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dropout_apply(x, seed, keep_prob: float, upscale: bool):
    keep = _hash_keep(seed, keep_prob, x.shape)
    return jnp.where(keep, x / keep_prob if upscale else x,
                     0.0).astype(x.dtype)


def _dropout_apply_fwd(x, seed, keep_prob, upscale):
    # the seed is the only residual: the backward hashes the mask
    # again, as the flash kernels do, instead of keeping it
    return _dropout_apply(x, seed, keep_prob, upscale), seed


def _dropout_apply_bwd(keep_prob, upscale, seed, g):
    return _dropout_apply(g, seed, keep_prob, upscale), None


_dropout_apply.defvjp(_dropout_apply_fwd, _dropout_apply_bwd)


def dropout(x, p: float = 0.5, training: bool = True,
            mode: str = "upscale_in_train", key=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return x * (1.0 - p)
        return x
    if key is None:
        key = _random.next_key("dropout")
    _xprof.note_dropout_mask(_prod(x.shape))
    return _dropout_apply(x, _mask_seed(key), 1.0 - p,
                          mode == "upscale_in_train")


def dropout2d(x, p: float = 0.5, training: bool = True, key=None):
    if not training or p == 0.0:
        return x
    if key is None:
        key = _random.next_key("dropout")
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape[:2] + (1, 1))
    return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)


def alpha_dropout(x, p: float = 0.5, training: bool = True, key=None):
    if not training or p == 0.0:
        return x
    if key is None:
        key = _random.next_key("dropout")
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    a = (1.0 / ((1 - p) * (1 + p * alpha_p ** 2)) ** 0.5)
    b = -a * alpha_p * p
    return (a * jnp.where(keep, x, alpha_p) + b).astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding / one-hot (ref: lookup_table_v2_op.cc, one_hot_op.cc)
# ---------------------------------------------------------------------------

def embedding(ids, weight, padding_idx: Optional[int] = None):
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


lookup_table = embedding


def one_hot(x, num_classes: int, dtype="float32"):
    from ..core.dtype import convert_dtype
    return jax.nn.one_hot(x, num_classes, dtype=convert_dtype(dtype))


# ---------------------------------------------------------------------------
# linear / fc
# ---------------------------------------------------------------------------

def linear(x, weight, bias=None):
    """weight is [in, out] (reference fc convention, fc_op.cc)."""
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


fc = linear


# ---------------------------------------------------------------------------
# interpolate (ref: interpolate_op.cc: nearest/bilinear/bicubic/trilinear)
# ---------------------------------------------------------------------------

def interpolate(x, size: Optional[Sequence[int]] = None,
                scale_factor: Optional[Union[float, Sequence[float]]] = None,
                mode: str = "nearest", align_corners: bool = False,
                data_format: str = "NCHW"):
    if data_format not in ("NCHW", "NCDHW", "NCL"):
        raise NotImplementedError("interpolate supports channel-first")
    spatial_in = x.shape[2:]
    if size is None:
        if isinstance(scale_factor, (int, float)):
            scale_factor = [scale_factor] * len(spatial_in)
        size = [int(s * f) for s, f in zip(spatial_in, scale_factor)]
    size = tuple(int(s) for s in size)

    method = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
              "trilinear": "linear", "bicubic": "cubic",
              "area": "linear"}[mode]
    if align_corners and method != "nearest":
        # jax.image.resize has no align_corners; build index grid manually.
        return _resize_align_corners(x, size, method)
    out_shape = x.shape[:2] + size
    return jax.image.resize(x, out_shape, method=method)


def _resize_align_corners(x, size, method):
    spatial_in = x.shape[2:]
    coords = []
    for s_in, s_out in zip(spatial_in, size):
        if s_out == 1:
            coords.append(jnp.zeros((1,)))
        else:
            coords.append(jnp.linspace(0.0, s_in - 1, s_out))
    if len(size) == 1:
        coords = [jnp.zeros((1,)), coords[0]]
        x = x[:, :, None, :]
        out = _resize_align_corners(x, (1, size[0]), method)
        return out[:, :, 0, :]
    if len(size) == 2:
        h, w = coords
        if method == "nearest":
            hi = jnp.round(h).astype(jnp.int32)
            wi = jnp.round(w).astype(jnp.int32)
            return x[:, :, hi[:, None], wi[None, :]]
        h0 = jnp.floor(h).astype(jnp.int32)
        h1 = jnp.minimum(h0 + 1, spatial_in[0] - 1)
        w0 = jnp.floor(w).astype(jnp.int32)
        w1 = jnp.minimum(w0 + 1, spatial_in[1] - 1)
        fh2 = (h - h0)[None, None, :, None]
        fw2 = (w - w0)[None, None, None, :]
        tl = x[:, :, h0[:, None], w0[None, :]]
        tr = x[:, :, h0[:, None], w1[None, :]]
        bl = x[:, :, h1[:, None], w0[None, :]]
        br = x[:, :, h1[:, None], w1[None, :]]
        top = tl + (tr - tl) * fw2
        bot = bl + (br - bl) * fw2
        return top + (bot - top) * fh2
    if len(size) == 3:
        out_shape = x.shape[:2] + tuple(size)
        return jax.image.resize(x, out_shape, method=method)
    raise NotImplementedError


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False):
    return interpolate(x, size, scale_factor, mode, align_corners)


# ---------------------------------------------------------------------------
# unfold / grid sample / misc vision-adjacent
# ---------------------------------------------------------------------------

def unfold(x, kernel_sizes: IntOrPair, strides: IntOrPair = 1,
           paddings: IntOrPair = 0, dilations: IntOrPair = 1):
    """(ref: unfold_op.cc = im2col) NCHW → [N, C*kh*kw, L]."""
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    dh, dw = _pair(dilations)
    pads = _conv_padding(paddings, 2)
    n, c, h, w = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), pads[0], pads[1]))
    hp = x.shape[2]
    wp = x.shape[3]
    oh = (hp - (dh * (kh - 1) + 1)) // sh + 1
    ow = (wp - (dw * (kw - 1) + 1)) // sw + 1
    patches = []
    for i in range(kh):
        for j in range(kw):
            patch = x[:, :, i * dh:i * dh + oh * sh:sh,
                      j * dw:j * dw + ow * sw:sw]
            patches.append(patch)
    out = jnp.stack(patches, axis=2)  # [N, C, kh*kw, oh, ow]
    return out.reshape(n, c * kh * kw, oh * ow)


def fold(x, output_sizes: IntOrPair, kernel_sizes: IntOrPair,
         strides: IntOrPair = 1, paddings: IntOrPair = 0,
         dilations: IntOrPair = 1):
    oh_, ow_ = _pair(output_sizes)
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    dh, dw = _pair(dilations)
    pads = _conv_padding(paddings, 2)
    n, ckk, l = x.shape
    c = ckk // (kh * kw)
    hp = oh_ + pads[0][0] + pads[0][1]
    wp = ow_ + pads[1][0] + pads[1][1]
    oh = (hp - (dh * (kh - 1) + 1)) // sh + 1
    ow = (wp - (dw * (kw - 1) + 1)) // sw + 1
    cols = x.reshape(n, c, kh * kw, oh, ow)
    out = jnp.zeros((n, c, hp, wp), dtype=x.dtype)
    k = 0
    for i in range(kh):
        for j in range(kw):
            out = out.at[:, :, i * dh:i * dh + oh * sh:sh,
                         j * dw:j * dw + ow * sw:sw].add(cols[:, :, k])
            k += 1
    return out[:, :, pads[0][0]:hp - pads[0][1], pads[1][0]:wp - pads[1][1]]


def grid_sample(x, grid, mode: str = "bilinear",
                padding_mode: str = "zeros", align_corners: bool = True):
    """(ref: grid_sampler_op.cc) NCHW x, grid [N, Ho, Wo, 2] in [-1, 1]."""
    n, c, h, w = x.shape
    gx = grid[..., 0]
    gy = grid[..., 1]
    if align_corners:
        fx = (gx + 1) * (w - 1) / 2
        fy = (gy + 1) * (h - 1) / 2
    else:
        fx = ((gx + 1) * w - 1) / 2
        fy = ((gy + 1) * h - 1) / 2

    def sample(ix, iy):
        valid = (ix >= 0) & (ix < w) & (iy >= 0) & (iy < h)
        ix_c = jnp.clip(ix, 0, w - 1)
        iy_c = jnp.clip(iy, 0, h - 1)
        # batched gather: out[n, c, ho, wo] = x[n, c, iy[n,ho,wo], ix[n,ho,wo]]
        vals = jax.vmap(lambda img, yy, xx: img[:, yy, xx])(x, iy_c, ix_c)
        if padding_mode == "zeros":
            vals = vals * valid[:, None].astype(x.dtype)
        return vals

    if mode == "nearest":
        return sample(jnp.round(fx).astype(jnp.int32),
                      jnp.round(fy).astype(jnp.int32))
    x0 = jnp.floor(fx).astype(jnp.int32)
    y0 = jnp.floor(fy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wa = ((x1 - fx) * (y1 - fy))[:, None]
    wb = ((x1 - fx) * (fy - y0))[:, None]
    wc = ((fx - x0) * (y1 - fy))[:, None]
    wd = ((fx - x0) * (fy - y0))[:, None]
    return (sample(x0, y0) * wa + sample(x0, y1) * wb
            + sample(x1, y0) * wc + sample(x1, y1) * wd).astype(x.dtype)


def affine_grid(theta, out_shape: Sequence[int], align_corners: bool = True):
    """(ref: affine_grid_op.cc) theta [N,2,3] → grid [N,H,W,2]."""
    n, _, h, w = out_shape

    def linsp(num):
        if align_corners:
            return jnp.linspace(-1.0, 1.0, num)
        step = 2.0 / num
        return jnp.linspace(-1.0 + step / 2, 1.0 - step / 2, num)

    ys = linsp(h)
    xs = linsp(w)
    gx, gy = jnp.meshgrid(xs, ys)
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)  # [H, W, 3]
    return jnp.einsum("hwk,njk->nhwj", base, theta)


# ---------------------------------------------------------------------------
# misc nn ops
# ---------------------------------------------------------------------------

def cosine_similarity(x1, x2, axis: int = 1, eps: float = 1e-8):
    dot_ = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot_ / jnp.maximum(n1 * n2, eps)


def cos_sim(x, y):
    """(ref: cos_sim_op.cc)."""
    return cosine_similarity(x, y, axis=-1)[..., None]


def normalize(x, p: float = 2, axis: int = 1, epsilon: float = 1e-12):
    norm = jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis,
                             keepdims=True), 1.0 / p)
    return x / jnp.maximum(norm, epsilon)


def l2_normalize(x, axis: int = -1, epsilon: float = 1e-12):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True)
                         + epsilon)


def label_smooth(label, prior_dist=None, epsilon: float = 0.1):
    """(ref: label_smooth_op.cc)."""
    k = label.shape[-1]
    if prior_dist is None:
        return (1 - epsilon) * label + epsilon / k
    return (1 - epsilon) * label + epsilon * prior_dist


def pad2d(x, paddings, mode: str = "constant", pad_value: float = 0.0,
          data_format: str = "NCHW"):
    from .manipulation import pad as _pad
    return _pad(x, paddings, mode=mode, value=pad_value,
                data_format=data_format)


def npair_loss(anchor, positive, labels, l2_reg: float = 0.002):
    """(ref: npair_loss in layers/loss.py)."""
    reg = l2_reg * (jnp.sum(jnp.square(anchor), axis=1)
                    + jnp.sum(jnp.square(positive), axis=1)).mean() * 0.25
    logits = anchor @ positive.T
    labels = labels.reshape(-1)
    same = (labels[:, None] == labels[None, :]).astype(logits.dtype)
    prob = same / jnp.sum(same, axis=1, keepdims=True)
    xent = -jnp.sum(prob * jax.nn.log_softmax(logits, axis=1), axis=1)
    return jnp.mean(xent) + reg


def pool3d(x, pool_size=-1, pool_type: str = "max", pool_stride=1,
           pool_padding=0, global_pooling: bool = False,
           ceil_mode: bool = False, exclusive: bool = True):
    """NCDHW pooling (ref: pool_op.cc 3-D path)."""
    return _pool(x, pool_type, pool_size, pool_stride, pool_padding,
                 ceil_mode, exclusive, 3, global_pooling)


def adaptive_pool3d(x, output_size, pool_type: str = "avg"):
    """(ref: pool_op.cc adaptive 3-D). Exact when each spatial dim
    divides; general case composes interpolation-style bins."""
    od, oh, ow = _pair(output_size, 3)
    n, c, d, h, w = x.shape
    if d % od == 0 and h % oh == 0 and w % ow == 0:
        r = x.reshape(n, c, od, d // od, oh, h // oh, ow, w // ow)
        if pool_type == "avg":
            return jnp.mean(r, axis=(3, 5, 7))
        return jnp.max(r, axis=(3, 5, 7))
    # slice per output cell (static python loops: od/oh/ow are constants)
    cells = []
    for i in range(od):
        d0, d1 = (d * i) // od, (d * (i + 1) + od - 1) // od
        for j in range(oh):
            h0, h1 = (h * j) // oh, (h * (j + 1) + oh - 1) // oh
            for k in range(ow):
                w0, w1 = (w * k) // ow, (w * (k + 1) + ow - 1) // ow
                win = x[:, :, d0:d1, h0:h1, w0:w1]
                cells.append(jnp.mean(win, axis=(2, 3, 4))
                             if pool_type == "avg"
                             else jnp.max(win, axis=(2, 3, 4)))
    return jnp.stack(cells, axis=-1).reshape(n, c, od, oh, ow)


def add_position_encoding(x, alpha: float = 1.0, beta: float = 1.0):
    """(ref: add_position_encoding_op.cc) out = alpha*x + beta*PE with the
    transformer sinusoid table. x: [B, T, C]."""
    b, t, c = x.shape
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    half = c // 2
    div = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                  * (-jnp.log(10000.0) / jnp.maximum(half - 1, 1)))
    pe = jnp.concatenate([jnp.sin(pos * div), jnp.cos(pos * div)], axis=1)
    if pe.shape[1] < c:  # odd channel count
        pe = jnp.pad(pe, ((0, 0), (0, c - pe.shape[1])))
    return alpha * x + beta * pe[None].astype(x.dtype)


def similarity_focus(x, axis: int, indexes):
    """(ref: similarity_focus_op.cc) build a focus mask: for each selected
    index along `axis` of a [B, C, H, W]-like tensor, mark the argmax
    cell of every row and column of the remaining 2-D slice."""
    if axis != 1:
        x = jnp.moveaxis(x, axis, 1)
    b, c, h, w = x.shape
    mask = jnp.zeros_like(x)
    for idx in indexes:
        sl = x[:, idx]  # [B, H, W]
        row_best = jnp.argmax(sl, axis=2)  # [B, H]
        col_best = jnp.argmax(sl, axis=1)  # [B, W]
        m = jnp.zeros((b, h, w), x.dtype)
        m = m.at[jnp.arange(b)[:, None], jnp.arange(h)[None, :],
                 row_best].set(1.0)
        m = m.at[jnp.arange(b)[:, None], col_best,
                 jnp.arange(w)[None, :]].set(1.0)
        mask = mask.at[:, idx].set(m)
    if axis != 1:
        mask = jnp.moveaxis(mask, 1, axis)
    return mask


def random_crop(x, shape: Sequence[int], key=None):
    """(ref: random_crop_op.cc) random crop of the trailing dims to
    `shape`, with an INDEPENDENT offset per leading-dim sample (the
    reference draws per-instance; a shared window would collapse the
    augmentation)."""
    from ..core import random as _random
    if key is None:
        key = _random.next_key("random")
    lead_shape = x.shape[: x.ndim - len(shape)]
    tail_shape = x.shape[x.ndim - len(shape):]

    def crop_one(xi, k):
        ks = jax.random.split(k, len(shape))
        starts = [jax.random.randint(ks[i], (), 0, dim - out + 1)
                  for i, (dim, out) in enumerate(zip(tail_shape, shape))]
        return jax.lax.dynamic_slice(xi, starts, shape)

    if not lead_shape:
        return crop_one(x, key)
    n = 1
    for d in lead_shape:
        n *= d
    flat = x.reshape((n,) + tuple(tail_shape))
    keys = jax.random.split(key, n)
    out = jax.vmap(crop_one)(flat, keys)
    return out.reshape(tuple(lead_shape) + tuple(shape))


def inplace_abn(x, running_mean, running_var, weight=None, bias=None,
                training: bool = False, momentum: float = 0.9,
                epsilon: float = 1e-5, act: Optional[str] = None,
                act_alpha: float = 1.0):
    """(ref: inplace_abn_op.cc) batch norm + activation. "In-place" is a
    CUDA memory trick with no XLA meaning (buffer reuse is the
    compiler's job); semantics = batch_norm then act."""
    out = batch_norm(x, running_mean, running_var, weight, bias,
                     training=training, momentum=momentum, epsilon=epsilon)
    y = out[0] if isinstance(out, tuple) else out
    if act == "relu":
        y = jax.nn.relu(y)
    elif act == "leaky_relu":
        y = jax.nn.leaky_relu(y, act_alpha)
    elif act == "elu":
        y = jax.nn.elu(y, act_alpha)
    elif act is not None:
        raise ValueError(f"inplace_abn: unsupported act {act}")
    if isinstance(out, tuple):
        return (y,) + out[1:]
    return y


def continuous_value_model(input, cvm, use_cvm: bool = True):
    """(ref: cvm_op.cc; fluid signature (input, cvm, use_cvm)). input
    [B, D]: an embedding whose first two slots are show/click
    placeholders; cvm [B, 2]: the raw (show, click) counts. use_cvm
    replaces the placeholders with (log(show+1), log(click+1)-log(show+1));
    otherwise the two slots are stripped (output [B, D-2])."""
    cvm = jnp.asarray(cvm)
    show = jnp.log(cvm[:, 0:1] + 1.0)
    click = jnp.log(cvm[:, 1:2] + 1.0) - show
    rest = input[:, 2:]
    if use_cvm:
        return jnp.concatenate([show, click, rest], axis=1)
    return rest


def deformable_roi_pooling(feat, rois, trans, output_size,
                           roi_batch_idx=None, spatial_scale: float = 1.0,
                           trans_std: float = 0.1,
                           samples_per_bin: int = 2):
    """(ref: deformable_psroi_pooling_op.cu) ROI pooling with learned
    per-bin offsets. feat [B, C, H, W]; rois [R, 4]; trans
    [R, 2, PH, PW] bin offsets. Each (offset-shifted) bin averages a
    ``samples_per_bin`` x ``samples_per_bin`` grid of bilinear samples
    (the reference's sample_per_part grid)."""
    from .detection import _bilinear_sample
    ph, pw = (output_size, output_size) if isinstance(output_size, int) \
        else output_size
    feat = jnp.asarray(feat)   # indexed by traced batch ids under vmap
    rois = jnp.asarray(rois)
    trans = jnp.asarray(trans)
    b, c, h, w = feat.shape
    if roi_batch_idx is None:
        roi_batch_idx = jnp.zeros((rois.shape[0],), jnp.int32)

    def one_roi(roi, t, bidx):
        x1, y1, x2, y2 = roi * spatial_scale
        rw = jnp.maximum(x2 - x1, 1.0)
        rh = jnp.maximum(y2 - y1, 1.0)
        bin_w, bin_h = rw / pw, rh / ph
        fmap = feat[bidx]                    # [C, H, W]
        sp = samples_per_bin
        # sub-sample grid inside each bin: offsets (k+0.5)/sp of the bin
        sub = (jnp.arange(sp) + 0.5) / sp          # [sp]
        ys = y1 + (jnp.arange(ph)[:, None] + sub[None, :]) * bin_h
        xs = x1 + (jnp.arange(pw)[:, None] + sub[None, :]) * bin_w
        # [PH, PW, sp, sp] sample coordinates, offset-shifted per bin
        yy = ys[:, None, :, None] + (t[1] * trans_std * rh)[:, :, None,
                                                            None]
        xx = xs[None, :, None, :] + (t[0] * trans_std * rw)[:, :, None,
                                                            None]
        yy = jnp.clip(yy, 0.0, h - 1.0)
        xx = jnp.clip(xx, 0.0, w - 1.0)
        vals = _bilinear_sample(fmap, yy, xx)      # [C, PH, PW, sp, sp]
        return jnp.mean(vals, axis=(-2, -1))       # [C, PH, PW]

    return jax.vmap(one_roi)(rois, trans,
                             jnp.asarray(roi_batch_idx, jnp.int32))


def max_pool3d_with_index(x, kernel_size, stride=None, padding=0):
    """(ref: max_pool3d_with_index_op) values + flat argmax indices per
    window over NCDHW input.

    One variadic reduce_window over (value, flat-index) pairs — exact
    for arbitrary value magnitudes and spatial sizes (the previous
    value*size−index f32 packing silently corrupted indices once
    |value|*size left the 24-bit mantissa), ties toward the smaller
    index like the reference."""
    return _max_pool_with_index(x, kernel_size, stride, padding, 3)
