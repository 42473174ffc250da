"""Normalization layers.

TPU-native wrappers (reference: python/paddle/fluid/dygraph/nn.py BatchNorm
:1035, LayerNorm, GroupNorm, SpectralNorm; kernels batch_norm_op.cc,
layer_norm_op.cc, instance_norm_op.cc, group_norm_op.cc,
sync_batch_norm_op.cc). BatchNorm running stats are registered buffers;
under jit they are captured by Layer.bind and threaded through step state
(the reference instead mutates scope variables in-place).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core.dtype import get_default_dtype
from ...ops import nn_functional as F
from .. import initializer as I
from ..layer import Layer, Parameter


class _BatchNormBase(Layer):
    def __init__(self, num_features: int, momentum: float = 0.9,
                 epsilon: float = 1e-5, weight_attr=None, bias_attr=None,
                 data_format: str = "NCHW",
                 use_global_stats: Optional[bool] = None,
                 sync_axis: Optional[str] = None) -> None:
        super().__init__()
        self.num_features = num_features
        self.momentum = momentum
        self.epsilon = epsilon
        self.data_format = data_format
        self.use_global_stats = use_global_stats
        self.sync_axis = sync_axis
        dt = get_default_dtype()
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = I.make_param(weight_attr, I.Constant(1.0),
                             (num_features,), dt)
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = I.make_param(bias_attr, I.Constant(0.0),
                             (num_features,), dt)
        self.register_buffer("_mean", jnp.zeros((num_features,), dt))
        self.register_buffer("_variance", jnp.ones((num_features,), dt))

    def forward(self, x):
        training = self.training and not (self.use_global_stats is True)
        w = self.weight if "weight" in self._parameters else None
        b = self.bias if "bias" in self._parameters else None
        if self.sync_axis is not None:
            out, new_mean, new_var = F.sync_batch_norm(
                x, self._mean, self._variance, w, b, training,
                self.momentum, self.epsilon, self.data_format,
                axis_name=self.sync_axis)
        else:
            out, new_mean, new_var = F.batch_norm(
                x, self._mean, self._variance, w, b, training,
                self.momentum, self.epsilon, self.data_format)
        if training:
            self._mean = new_mean
            self._variance = new_var
        return out


class BatchNorm(_BatchNormBase):
    """Fluid-style BatchNorm (dygraph/nn.py:1035)."""


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, **kw):
        kw.setdefault("data_format", "NCL")
        super().__init__(num_features, **kw)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, **kw):
        kw.setdefault("data_format", "NCDHW")
        super().__init__(num_features, **kw)


class SyncBatchNorm(_BatchNormBase):
    """(ref: sync_batch_norm_op.cc) — set ``sync_axis`` to the data-parallel
    mesh axis name; stats are pmean-reduced when run under shard_map."""

    def __init__(self, num_features, sync_axis: str = "dp", **kw):
        super().__init__(num_features, sync_axis=sync_axis, **kw)

    @classmethod
    def convert_sync_batchnorm(cls, layer: Layer,
                               sync_axis: str = "dp") -> Layer:
        for _, sub in layer.named_sublayers(include_self=True):
            if isinstance(sub, _BatchNormBase):
                object.__setattr__(sub, "sync_axis", sync_axis)
        return layer


class LayerNorm(Layer):
    """(ref: layer_norm_op.cc). normalized_shape covers trailing dims."""

    def __init__(self, normalized_shape, epsilon: float = 1e-5,
                 weight_attr=None, bias_attr=None) -> None:
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.epsilon = epsilon
        dt = get_default_dtype()
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = I.make_param(weight_attr, I.Constant(1.0),
                             self.normalized_shape, dt)
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = I.make_param(bias_attr, I.Constant(0.0),
                             self.normalized_shape, dt)

    def forward(self, x):
        w = self.weight if "weight" in self._parameters else None
        b = self.bias if "bias" in self._parameters else None
        begin = x.ndim - len(self.normalized_shape)
        from ...kernels import maybe_layer_norm
        return maybe_layer_norm(x, w, b, self.epsilon, begin)


class RMSNorm(Layer):
    """``x / sqrt(mean(x^2) + epsilon) * weight`` over the last axis
    (no mean subtraction, no bias), computed in float32 and returned in
    the input's dtype. With ``num_groups`` the mean is taken inside
    each of that many equal groups of the last axis (the gated norm of
    a Mamba-2 mixer); the weight is one vector over the whole axis."""

    def __init__(self, hidden_size: int, epsilon: float = 1e-5,
                 num_groups: int = 1, weight_attr=None) -> None:
        super().__init__()
        if hidden_size % num_groups:
            raise ValueError(f"{num_groups} groups do not divide "
                             f"{hidden_size}")
        self.hidden_size = hidden_size
        self.epsilon = epsilon
        self.num_groups = num_groups
        self.weight = I.make_param(weight_attr, I.Constant(1.0),
                                   (hidden_size,), get_default_dtype())

    def forward(self, x):
        h = x.astype(jnp.float32)
        grouped = h.reshape(*h.shape[:-1], self.num_groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
            + self.epsilon)
        h = grouped.reshape(h.shape) * self.weight.astype(jnp.float32)
        return h.astype(x.dtype)


class InstanceNorm2D(Layer):
    def __init__(self, num_features: int, epsilon: float = 1e-5,
                 weight_attr=None, bias_attr=None) -> None:
        super().__init__()
        dt = get_default_dtype()
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = I.make_param(weight_attr, I.Constant(1.0),
                             (num_features,), dt)
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = I.make_param(bias_attr, I.Constant(0.0),
                             (num_features,), dt)
        self.epsilon = epsilon

    def forward(self, x):
        w = self.weight if "weight" in self._parameters else None
        b = self.bias if "bias" in self._parameters else None
        return F.instance_norm(x, w, b, self.epsilon)


InstanceNorm1D = InstanceNorm2D
InstanceNorm3D = InstanceNorm2D


class GroupNorm(Layer):
    def __init__(self, num_groups: int, num_channels: int,
                 epsilon: float = 1e-5, weight_attr=None,
                 bias_attr=None) -> None:
        super().__init__()
        self.num_groups = num_groups
        self.epsilon = epsilon
        dt = get_default_dtype()
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = I.make_param(weight_attr, I.Constant(1.0),
                             (num_channels,), dt)
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = I.make_param(bias_attr, I.Constant(0.0),
                             (num_channels,), dt)

    def forward(self, x):
        w = self.weight if "weight" in self._parameters else None
        b = self.bias if "bias" in self._parameters else None
        return F.group_norm(x, self.num_groups, w, b, self.epsilon)


class SpectralNorm(Layer):
    """(ref: spectral_norm_op.cc)."""

    def __init__(self, weight_shape, dim: int = 0,
                 power_iters: int = 1, epsilon: float = 1e-12) -> None:
        super().__init__()
        self.dim = dim
        self.power_iters = power_iters
        self.epsilon = epsilon
        import numpy as np
        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        from ...core import random as _random
        import jax
        self.register_buffer("weight_u", jax.random.normal(
            _random.next_key("init"), (h,)))
        self.register_buffer("weight_v", jax.random.normal(
            _random.next_key("init"), (w,)))

    def forward(self, weight):
        return F.spectral_norm(weight, self.weight_u, self.weight_v,
                               self.power_iters, self.epsilon, self.dim)


class LocalResponseNorm(Layer):
    def __init__(self, size: int = 5, alpha: float = 1e-4,
                 beta: float = 0.75, k: float = 1.0) -> None:
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta,
                                     self.k)
