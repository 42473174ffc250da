"""State-space layers: the Mamba-2 mixer (Dao & Gu 2024, "Transformers
are SSMs"; the layer of HF ``modeling_nemotron_h.py``'s ``M`` blocks).

Per head the recurrence is ``h_t = a_t h_{t-1} + dt_t x_t (x) B_t``,
``y_t = h_t C_t + D x_t`` with ``a_t = exp(dt_t A)``. It is computed in
the chunked state-space-dual form: inside a chunk the quadratic form as
matmuls on the MXU, between chunks a scan over the chunk states. ``dt``,
the cumulative log-decay and the states are float32 whatever the
parameters' dtype; matmul operands take the input's dtype and accumulate
in float32.

The form has two implementations. ``ssd_chunked_scan`` below is the one
XLA compiles, ``Mamba2Mixer`` mapping it over the sequences under a
``jax.checkpoint``; ``kernels/ssd_scan.py`` is the same mathematics as a
forward and a backward Pallas kernel that keep the decay blocks and the
chunk states in VMEM. ``kernels.maybe_ssd_scan`` chooses from what it
sees: the kernels on a TPU with no mesh in scope, for whole chunks and
whole tiles, the XLA form everywhere else.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core import random as _random
from ...core.dtype import get_default_dtype
from .. import initializer as I
from ..layer import Layer, Parameter
from .common import Linear
from .norm import RMSNorm

__all__ = ["Mamba2Mixer", "ssd_chunked_scan", "causal_depthwise_conv"]


def causal_depthwise_conv(x, weight, bias):
    """``y[t] = bias + sum_k weight[k] * x[t - (K-1) + k]`` per channel,
    zeros before the sequence starts. x [B, L, C], weight [K, C]."""
    k = weight.shape[0]
    length = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias
    for i in range(k):
        out = out + padded[:, i:i + length] * weight[i]
    return out


# The scope is entered inside the function and inside its loop body: a
# loop's body is lowered with its own name stack, so a scope entered
# round the loop does not reach the operations in it.
@jax.named_scope("pt.ssm_scan")
def ssd_chunked_scan(x, dt, b_mat, c_mat, a, chunk: int):
    """The state-space-dual scan, without the ``D x`` skip.

    x [B, L, H, P]; dt [B, L, H] float32, after softplus; a [H] float32,
    negative; b_mat, c_mat [B, L, G, N], a group serving H / G heads.
    Returns y [B, L, H, P] in x's dtype. L need not be a multiple of
    ``chunk``: the tail is padded with ``dt = 0``, which neither decays
    nor feeds a state."""
    bsz, length, h, p = x.shape
    g, n = b_mat.shape[2:]
    r = h // g
    pad = -length % chunk
    if pad:
        x, dt, b_mat, c_mat = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b_mat, c_mat))
    nc = (length + pad) // chunk
    cd = x.dtype
    f32 = jnp.float32
    dt = dt.astype(f32).reshape(bsz, nc, chunk, g, r)
    xdt = x.reshape(bsz, nc, chunk, g, r, p).astype(f32) * dt[..., None]
    bc = b_mat.reshape(bsz, nc, chunk, g, n)
    cc = c_mat.reshape(bsz, nc, chunk, g, n)
    # cumulative log-decay inside each chunk, inclusive: [B, c, Q, G, R]
    cum = jnp.cumsum(dt * a.astype(f32).reshape(g, r), axis=2)

    # inside a chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) xdt_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc,
                    preferred_element_type=f32)
    cum_h = jnp.moveaxis(cum, 2, -1)                  # [B, c, G, R, Q]
    seg = cum_h[..., :, None] - cum_h[..., None, :]   # [.., i, j]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    m = (cb[:, :, :, None] * decay).astype(cd)        # [B, c, G, R, i, j]
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xdt.astype(cd),
                   preferred_element_type=f32)

    # each chunk's own contribution to the state at its end
    last = cum[:, :, -1]                              # [B, c, G, R]
    to_end = jnp.exp(last[:, :, None] - cum)          # [B, c, Q, G, R]
    local = jnp.einsum("bcjgn,bcjgrp->bcgrpn", bc,
                       (xdt * to_end[..., None]).astype(cd),
                       preferred_element_type=f32)

    # between chunks: the state entering each chunk, float32 throughout
    @jax.named_scope("pt.ssm_scan")
    def carry_over(state, inp):
        chunk_decay, chunk_state = inp
        return state * chunk_decay[..., None, None] + chunk_state, state

    _, entering = jax.lax.scan(
        carry_over, jnp.zeros((bsz, g, r, p, n), f32),
        (jnp.moveaxis(jnp.exp(last), 1, 0), jnp.moveaxis(local, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)           # [B, c, G, R, P, N]
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", cc, entering.astype(cd),
                       preferred_element_type=f32) \
        * jnp.exp(cum)[..., None]
    y = y.reshape(bsz, nc * chunk, h, p)[:, :length]
    return y.astype(cd)


class Mamba2Mixer(Layer):
    """``[z | xBC | dt] = W_in u``; ``xBC = silu(conv1d(xBC))`` (causal,
    depthwise); the scan over ``x, B, C``; ``out = W_out
    group_rmsnorm((y + D x) * silu(z))``. No projection bias."""

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int,
                 state_size: int, n_groups: int, conv_kernel: int = 4,
                 chunk_size: int = 128, norm_eps: float = 1e-5,
                 time_step_min: float = 0.001, time_step_max: float = 0.1,
                 time_step_floor: float = 1e-4, weight_attr=None,
                 out_weight_attr=None) -> None:
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.state_size, self.n_groups = state_size, n_groups
        self.chunk_size = chunk_size
        self.inner = inner = num_heads * head_dim
        self.conv_dim = conv_dim = inner + 2 * n_groups * state_size
        dtype = get_default_dtype()
        self.in_proj = Linear(hidden_size, inner + conv_dim + num_heads,
                              weight_attr, bias_attr=False)
        bound = 1.0 / math.sqrt(conv_kernel)
        self.conv_weight = Parameter(I.Uniform(-bound, bound)(
            (conv_kernel, conv_dim), dtype))
        self.conv_bias = Parameter(jnp.zeros((conv_dim,), dtype))
        # dt_bias = softplus^-1(dt), dt log-uniform in [min, max] and
        # floored; A = -(1 .. H); D = 1 (the source's time_step_* keys)
        u = jax.random.uniform(_random.next_key("init"), (num_heads,))
        step = jnp.maximum(jnp.exp(
            u * (math.log(time_step_max) - math.log(time_step_min))
            + math.log(time_step_min)), time_step_floor)
        self.dt_bias = Parameter(
            (step + jnp.log(-jnp.expm1(-step))).astype(dtype))
        self.A_log = Parameter(jnp.log(jnp.arange(
            1, num_heads + 1, dtype=jnp.float32)).astype(dtype))
        self.D = Parameter(jnp.ones((num_heads,), dtype))
        self.norm = RMSNorm(inner, norm_eps, num_groups=n_groups)
        self.out_proj = Linear(inner, hidden_size, out_weight_attr,
                               bias_attr=False)

    def forward(self, u):
        from ...kernels import maybe_ssd_scan
        bsz, length, _ = u.shape
        h, p = self.num_heads, self.head_dim
        gn = self.n_groups * self.state_size
        with jax.named_scope("pt.ssm_proj"):
            z, xbc, dt = jnp.split(
                self.in_proj(u), [self.inner, self.inner + self.conv_dim],
                axis=-1)
        with jax.named_scope("pt.ssm_conv"):
            xbc = jax.nn.silu(causal_depthwise_conv(
                xbc, self.conv_weight, self.conv_bias))
        with jax.named_scope("pt.ssm_scan"):
            x, b_mat, c_mat = jnp.split(xbc, [self.inner, self.inner + gn],
                                        axis=-1)
            x = x.reshape(bsz, length, h, p)
            group = (bsz, length, self.n_groups, self.state_size)
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + self.dt_bias.astype(jnp.float32))
            a = -jnp.exp(self.A_log.astype(jnp.float32))
            scan_in = (x, dt, b_mat.reshape(group), c_mat.reshape(group))
            y = maybe_ssd_scan(*scan_in, a, self.chunk_size)
            if y is None:
                # the XLA form, a sequence at a time and recomputed in
                # the backward pass: its [chunk, chunk] temporaries are
                # most of the layer's memory (the kernels hold them in
                # VMEM, walk the sequences on their grid and keep the
                # chunk states for their own backward)
                y = jax.lax.map(
                    jax.checkpoint(lambda s: ssd_chunked_scan(
                        *(t[None] for t in s), a, self.chunk_size)[0]),
                    scan_in)
            y = y + x * self.D.astype(x.dtype)[:, None]
            y = self.norm(y.reshape(bsz, length, self.inner)
                          * jax.nn.silu(z))
        with jax.named_scope("pt.ssm_proj"):
            return self.out_proj(y)
