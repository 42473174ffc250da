"""Kimi Delta Attention (KDA): linear attention by a gated delta rule with
a decay for every key channel (the Kimi Linear technical report;
flash-linear-attention's ``fla/layers/kda.py``, HF ``model_type:
kimi_linear``).

Per head, with ``k`` and ``q`` L2-normalised, ``a_t`` the log-decay of
each of the K key channels (``a_t <= 0``) and ``beta_t`` a scalar:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

with ``S`` a [K, V] state. ``kda_chunked`` computes it in chunks of
``chunk`` positions. Write ``g_t`` for the log-decay summed from the
chunk's start to ``t`` inclusive, ``S`` for the state entering the
chunk, and ``delta_t = beta_t (v_t - (Diag(exp(a_t)) S_{t-1})^T k_t)``,
so that ``S_t = Diag(exp(a_t)) S_{t-1} + k_t delta_t^T``. Unrolled in the
chunk:

    delta = (I + A)^-1 (beta (V - K~ S)),  A_ts = beta_t sum_c k_tc k_sc
                                              exp(g_tc - g_sc), s < t
    O = Q~ S + M delta,                    M_ts = sum_c q_tc k_sc
                                              exp(g_tc - g_sc), s <= t
    S' = Diag(exp(g_C)) S + (k exp(g_C - g))^T delta

with ``K~ = k exp(g)`` and ``Q~ = q exp(g)`` (rows). ``W = (I + A)^-1
beta K~`` and ``U = (I + A)^-1 beta V`` (a unit lower-triangular solve in
float32, the UT/WY transform) make ``delta = U - W S``. The solve is an
explicit inverse, forward substitution inside sub-chunks and 2 x 2 block
products above them, applied by one product (``_unit_lower_solve``): on
a TPU v5e ``triangular_solve`` inverts the [C, C] block in a custom call
of 86 us for a chunk of 32 heads.

**Stability.** The decay is per channel, and ``A_log`` is initialised
at up to log 16: a chunk of 64 sums its log-decay to below -100 at a
modest gate, so ``exp(-g)`` overflows float32 and the factorisation
``(q exp(g)) (k exp(-g))^T`` of ``M`` and ``A`` is not usable. Every
exponent here is non-positive where it is used:

- ``Q~``, ``K~``, ``exp(g_C)`` and ``exp(g_C - g_s)``: ``g`` falls along
  the chunk, so each is at most 1.
- Between sub-chunks of ``sub`` positions: query sub-chunk ``i`` and
  every key before it share the reference point ``r_i = g`` at the last
  position of sub-chunk ``i - 1`` (0 for the first), and ``exp(g_t -
  g_s) = exp(g_t - r_i) exp(r_i - g_s)``: both factors are ``exp`` of a
  non-positive number, so the products are matmuls on the MXU.
- Within a sub-chunk the pairwise decay ``exp(g_t - g_s)`` is formed
  directly for ``s <= t`` (a ``[sub, sub, K]`` product reduced over the
  channels, as flash-linear-attention's ``chunk_kda`` does with
  sub-chunks of 16).

The rule walks the chunks in a ``lax.scan`` that carries the [K, V]
state, all sequences and heads at once, one chunk's intra-chunk work a
step; the backward pass walks them in reverse, making a chunk's work
again and taking its gradient (``_kda_bwd``). Computing the intra-chunk
work of all chunks at once, with only the state's carry in the scan,
held 0.74 GB more at a layer's recomputed forward (PERF.md section 6,
PR 39). Matmul operands are in the input's dtype with float32
accumulation; the log-decay, beta, the triangular solve (every product
of it at ``Precision.HIGHEST``) and the states are float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ...core import random as _random
from ...core.dtype import get_default_dtype
from ...observability import xprof
from .. import initializer as I
from ..layer import Layer, Parameter
from .common import Linear
from .ssm import causal_depthwise_conv

__all__ = ["KimiDeltaAttention", "kda_chunked"]

# a chunk's sub-chunks: their own pairwise decays are formed directly
SUB_CHUNK = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(x, y):
    """A float32 product at full precision (the chip's default is one
    bf16 pass)."""
    return jnp.matmul(x, y, precision=_HIGHEST)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [.., C, C] strictly lower, float32, C a
    multiple of ``min(SUB_CHUNK, C)``.

    The diagonal blocks of ``SUB_CHUNK`` rows are inverted by forward
    substitution, a row at a time for every block of every sequence and
    head at once. Then 2 x 2 block inversion doubles the blocks,

        [T11 0; T21 T22]^-1 = [X11 0; -X22 T21 X11, X22]

    for every pair of a level at once: with ``x`` the block-diagonal
    [C, C] of the inverses so far, ``x - x T21 x`` with ``T21`` the part
    of ``a`` between each pair's two halves, two products of [C, C]
    matrices a level. Every factor is an inverse of a diagonal block of
    ``I + a``, which the rule keeps bounded (Higham's block method). The
    nilpotent product ``prod (I + (-a)^(2^i))`` is not: with correlated
    keys the powers of ``a`` reach 1e13 to 1e17 and cancel
    (``tests/test_kimi_linear.py``). On a TPU v5e the substitution beat
    four more levels of products down to single rows: a layer's rule at
    the Kimi cell's shape took 64.9 against 70.4 ms forward and
    backward."""
    c = a.shape[-1]
    sub = min(SUB_CHUNK, c)
    n = c // sub
    blocks = jnp.stack([a[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
                        for i in range(n)], -3)          # [.., n, sub, sub]
    x = jnp.broadcast_to(jnp.eye(sub, dtype=a.dtype), blocks.shape)
    for t in range(1, sub):     # row t: e_t - sum_{s < t} T_ts x_s
        x = x.at[..., t, :].add(-jnp.sum(blocks[..., t, :, None] * x, -2))
    x = jnp.where(jnp.eye(n, dtype=bool)[:, None, :, None],
                  x[..., None, :], 0.0).reshape(a.shape)
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    s = sub
    while s < c:
        between = (row // (2 * s) == col // (2 * s)) & (row // s != col // s)
        x = x - _mm(x, _mm(jnp.where(between, a, 0.0), x))
        s *= 2
    return x


@jax.custom_vjp
def _unit_lower_solve(a, rhs):
    """``(I + a)^-1 rhs`` for ``a`` [.., C, C] strictly lower and ``rhs``
    [.., C, N], float32: the inverse (``_unit_lower_inverse``) applied by
    one product. Its gradient reuses the inverse: ``d_rhs = X^T dx`` and
    ``d_a = -strict_lower(d_rhs x^T)``."""
    return _unit_lower_solve_fwd(a, rhs)[0]


def _unit_lower_solve_fwd(a, rhs):
    inv = _unit_lower_inverse(a)
    x = _mm(inv, rhs)
    return x, (inv, x)


def _unit_lower_solve_bwd(res, dx):
    inv, x = res
    d_rhs = _mm(jnp.swapaxes(inv, -1, -2), dx)
    c = inv.shape[-1]
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    return jnp.where(strict, -_mm(d_rhs, jnp.swapaxes(x, -1, -2)),
                     0.0), d_rhs


_unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


@jax.named_scope("pt.kda_scan")     # a loop body's own name stack
def _intra(q, k, v, g, beta, scale: float):
    """One chunk: q, k [B, C, H, K], v [B, C, H, V], g [B, C, H, K] and
    beta [B, C, H] float32. Returns ``Q~`` and ``W`` [B, H, C, K], ``U``
    [B, H, C, V] float32, ``M`` [B, H, C, C] float32, ``k exp(g_C - g)``
    [B, H, C, K] and ``exp(g_C)`` [B, H, K] float32 (module docstring)."""
    f32, cd = jnp.float32, v.dtype
    bsz, c, h, dk = q.shape
    sub = min(SUB_CHUNK, c)
    ns = c // sub
    g = jnp.cumsum(g, axis=1)                          # inclusive
    qf, kf = q.astype(f32) * scale, k.astype(f32)

    def subs(x):                                       # [B, ns, sub, ..]
        return x.reshape(bsz, ns, sub, *x.shape[2:])

    qs, ks, gs = subs(qf), subs(kf), subs(g)
    # within a sub-chunk: the pairwise decay itself, s <= t
    incl = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(
        incl, gs[:, :, :, None] - gs[:, :, None], -jnp.inf))
    m_in = jnp.sum(qs[:, :, :, None] * ks[:, :, None] * decay, -1)
    a_in = jnp.sum(ks[:, :, :, None] * ks[:, :, None] * decay, -1)
    # between sub-chunks: query sub-chunk i against every earlier key
    # through r_i, the log-decay at the end of sub-chunk i - 1
    ref = jnp.concatenate([jnp.zeros_like(g[:, :1]),
                           g[:, sub - 1:c - 1:sub]], axis=1)
    to_ref = jnp.exp(gs - ref[:, :, None])             # exponent <= 0
    before = (jnp.arange(c)[None, :] < sub * jnp.arange(ns)[:, None]
              )[:, :, None, None]                      # [ns, C, 1, 1]
    from_ref = jnp.where(before, jnp.exp(jnp.where(
        before, ref[:, :, None] - g[:, None], 0.0)), 0.0)
    k_from = (kf[:, None] * from_ref).astype(cd)       # [B, ns, C, H, K]
    m_out = jnp.einsum("bnthk,bnshk->bhnts", (qs * to_ref).astype(cd),
                       k_from, preferred_element_type=f32)
    a_out = jnp.einsum("bnthk,bnshk->bhnts", (ks * to_ref).astype(cd),
                       k_from, preferred_element_type=f32)
    # [B, H, C, C]: the sub-chunks' own blocks on the diagonal
    eye = jnp.eye(ns, dtype=f32)

    def whole(inside, outside):
        blocks = jnp.einsum("bnhts,nm->bhntms", jnp.moveaxis(
            inside, 4, 2), eye).reshape(bsz, h, c, c)
        return blocks + outside.reshape(bsz, h, c, c)

    m = whole(m_in, m_out)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    b_t = jnp.moveaxis(beta, 2, 1)[..., None]          # [B, H, C, 1]
    a = jnp.where(strict, whole(a_in, a_out), 0.0) * b_t

    def heads_first(x):                                # [B, H, C, ..]
        return jnp.moveaxis(x, 2, 1)

    gh = heads_first(g)
    k_tilde = heads_first(kf) * jnp.exp(gh)
    rhs = jnp.concatenate([k_tilde, heads_first(v.astype(f32))], -1) * b_t
    wu = _unit_lower_solve(a, rhs)
    last = gh[:, :, -1:]
    k_end = heads_first(kf) * jnp.exp(last - gh)
    return ((heads_first(qf) * jnp.exp(gh)).astype(cd),
            wu[..., :dk].astype(cd), wu[..., dk:], m, k_end.astype(cd),
            jnp.exp(last[:, :, 0]))


def _read_out(q_t, w, u, m, state):
    """A chunk's outputs [.., H, C, V] float32 and ``delta = U - W S``
    from the state entering it (``state`` in the operands' dtype)."""
    f32, cd = jnp.float32, state.dtype
    delta = u - jnp.einsum("...hck,...hkv->...hcv", w, state,
                           preferred_element_type=f32)
    o = jnp.einsum("...hck,...hkv->...hcv", q_t, state,
                   preferred_element_type=f32) \
        + jnp.einsum("...hts,...hsv->...htv", m.astype(cd),
                     delta.astype(cd), preferred_element_type=f32)
    return o, delta


def _next_state(state, k_end, decay, delta):
    """The float32 state leaving a chunk."""
    return decay[..., None] * state + jnp.einsum(
        "...hck,...hcv->...hkv", k_end, delta.astype(k_end.dtype),
        preferred_element_type=jnp.float32)


def kda_chunked(q, k, v, g, beta, scale: float, chunk: int = 64):
    """The gated delta rule over a sequence (module docstring).

    q, k [B, L, H, K] L2-normalised; v [B, L, H, V]; g [B, L, H, K] the
    log-decay of every position and key channel, <= 0; beta [B, L, H].
    Returns o [B, L, H, V] in v's dtype, the state starting at zero. L
    need not be a multiple of ``chunk``: the tail is padded with ``k =
    0, beta = 0, g = 0``, which neither decays nor writes the state.

    Differentiated by hand (``_kda_bwd``): the forward pass keeps its
    inputs and the float32 state entering each chunk, and the backward
    pass walks the chunks last to first, making a chunk's intra-chunk
    work again and taking its gradient, a chunk at a time. XLA's own
    gradient held the chunks' products, decays and their cotangents at
    once: 2.7 GB for the rule at 16,384 positions, 7.85 GB for a layer's
    backward, and the cell's step did not fit the chip. A traced call
    notes that its chunks' solve is the block-product inverse
    (``pt_kda_inverse_sites``)."""
    if chunk % min(SUB_CHUNK, chunk):
        raise ValueError(f"sub-chunks of {SUB_CHUNK} do not divide {chunk}")
    xprof.note_kda_inverse()
    return _kda(q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32),
                float(scale), chunk)


def _chunked(x, chunk: int):
    """[B, L, ..] -> [n, B, C, ..], the tail padded with zeros."""
    bsz, length = x.shape[:2]
    pad = -length % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    n = (length + pad) // chunk
    return jnp.moveaxis(x.reshape(bsz, n, chunk, *x.shape[2:]), 1, 0)


def _unchunked(x, length: int):
    """[n, B, C, ..] -> [B, L, ..]."""
    n, bsz, chunk = x.shape[:3]
    return jnp.moveaxis(x, 0, 1).reshape(bsz, n * chunk,
                                         *x.shape[3:])[:, :length]


def _one_chunk(q, k, v, g, beta, state, scale: float):
    """One chunk of every sequence and head: q, k, v, g, beta [B, C, H,
    *] and the float32 state entering it [B, H, K, V] -> the chunk's
    outputs [B, H, C, V] float32 and the state leaving it."""
    q_t, w, u, m, k_end, decay = _intra(q, k, v, g, beta, scale)
    o, delta = _read_out(q_t, w, u, m, state.astype(v.dtype))
    return o, _next_state(state, k_end, decay, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, scale, chunk):
    return _kda_fwd(q, k, v, g, beta, scale, chunk)[0]


@jax.named_scope("pt.kda_scan")
def _kda_fwd(q, k, v, g, beta, scale, chunk):
    bsz, length, h, dk = q.shape
    xs = tuple(_chunked(x, chunk) for x in (q, k, v, g, beta))

    @jax.named_scope("pt.kda_scan")     # a loop body's own name stack
    def forward(state, chunk_in):
        o, leaving = _one_chunk(*chunk_in, state, scale)
        return leaving, (o.astype(v.dtype), state)

    _, (o, entering) = jax.lax.scan(
        forward, jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32), xs)
    # the inputs are kept as the chunks they were cut into
    return _unchunked(jnp.moveaxis(o, 3, 2), length), (xs, entering)


@jax.named_scope("pt.kda_scan")
def _kda_bwd(scale, chunk, res, do):
    xs, entering = res

    @jax.named_scope("pt.kda_scan")     # a loop body's own name stack
    def back(d_state, inp):
        *chunk_in, state, do_c = inp
        _, pull = jax.vjp(lambda *a: _one_chunk(*a, scale), *chunk_in,
                          state)
        *grads, d_entering = pull((jnp.moveaxis(do_c, 2, 1).astype(
            jnp.float32), d_state))
        return d_entering, tuple(grads)

    _, grads = jax.lax.scan(back, jnp.zeros_like(entering[0]),
                            xs + (entering, _chunked(do, chunk)),
                            reverse=True)
    return tuple(_unchunked(x, do.shape[1]).astype(y.dtype)
                 for x, y in zip(grads, xs))


_kda.defvjp(_kda_fwd, _kda_bwd)


def _l2_normalize(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


class KimiDeltaAttention(Layer):
    """The KDA mixer (HF ``KimiDeltaAttention``; the layer's parameters
    keep its names):

    - ``q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x
      W_v))``, each conv causal, depthwise over ``heads x head_dim``
      channels, width ``conv_kernel``, no bias;
    - ``a = -exp(A_log[h]) softplus(x W_fa W_fb + dt_bias)``: a log-decay
      for every key channel (``f`` a low-rank pair through
      ``head_dim``); ``beta = sigmoid(x W_b)``, one a head;
    - the gated delta rule over L2-normalised q and k at scale
      ``head_dim ** -0.5`` (``kda_chunked``);
    - ``y = rmsnorm_head(o) * sigmoid(x W_ga W_gb + b_g)`` (one norm
      weight of ``head_dim`` shared by the heads), then ``y W_o``.

    Scopes: ``pt.kda_proj`` the projections, ``pt.kda_conv`` the three
    convs and their SiLU, ``pt.kda_scan`` the L2 norms, the decay gate,
    beta, the chunked rule and the gated RMS norm. A traced call notes
    its chunks (``pt_kda_chunks``: sequences x chunks a sequence) and
    its solve (``pt_kda_inverse_sites``)."""

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int,
                 conv_kernel: int = 4, chunk_size: int = 64,
                 norm_eps: float = 1e-5, weight_attr=None,
                 out_weight_attr=None) -> None:
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.chunk_size, self.norm_eps = chunk_size, norm_eps
        width = num_heads * head_dim
        dtype = get_default_dtype()

        def linear(i, o, attr=weight_attr, bias=False):
            return Linear(i, o, attr, bias_attr=None if bias else False)

        self.q_proj = linear(hidden_size, width)
        self.k_proj = linear(hidden_size, width)
        self.v_proj = linear(hidden_size, width)
        bound = 1.0 / math.sqrt(conv_kernel)
        for name in ("q_conv1d", "k_conv1d", "v_conv1d"):
            setattr(self, name, Parameter(I.Uniform(-bound, bound)(
                (conv_kernel, width), dtype)))
        # A in U(1, 16) a head; dt log-uniform in [0.001, 0.1] a channel,
        # floored at 1e-4, dt_bias = softplus^-1(dt) (fla's init)
        a = jax.random.uniform(_random.next_key("init"), (num_heads,),
                               minval=1.0, maxval=16.0)
        self.A_log = Parameter(jnp.log(a).astype(dtype))
        u = jax.random.uniform(_random.next_key("init"), (width,))
        dt = jnp.maximum(jnp.exp(u * (math.log(0.1) - math.log(0.001))
                                 + math.log(0.001)), 1e-4)
        self.dt_bias = Parameter((dt + jnp.log(-jnp.expm1(-dt)))
                                 .astype(dtype))
        self.f_a_proj = linear(hidden_size, head_dim)
        self.f_b_proj = linear(head_dim, width)
        self.b_proj = linear(hidden_size, num_heads)
        self.g_a_proj = linear(hidden_size, head_dim)
        self.g_b_proj = linear(head_dim, width, bias=True)
        self.o_norm_weight = Parameter(jnp.ones((head_dim,), dtype))
        self.o_proj = Linear(width, hidden_size, out_weight_attr,
                             bias_attr=False)

    def forward(self, x):
        bsz, length, _ = x.shape
        xprof.note_kda_chunks(bsz * -(-length // self.chunk_size))
        with jax.named_scope("pt.kda_proj"):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            f = self.f_b_proj(self.f_a_proj(x))
            gate = self.g_b_proj(self.g_a_proj(x))
            b = self.b_proj(x)
        # the elementwise work on either side of the rule is made again
        # in the backward pass from the projections' bf16 results: kept,
        # its float32 intermediates and their cotangents were most of a
        # layer's backward memory
        q, k, v, a, beta = jax.checkpoint(self._rule_inputs)(
            q, k, v, f, b, self.q_conv1d, self.k_conv1d, self.v_conv1d,
            self.A_log, self.dt_bias)
        o = kda_chunked(q, k, v, a, beta, self.head_dim ** -0.5,
                        self.chunk_size)
        y = jax.checkpoint(self._gated_norm)(o, gate, self.o_norm_weight)
        with jax.named_scope("pt.kda_proj"):
            return self.o_proj(y)

    def _rule_inputs(self, q, k, v, f, b, q_conv, k_conv, v_conv, a_log,
                     dt_bias):
        """The convs and SiLU (``pt.kda_conv``); the L2 norms, the decay
        gate and beta (``pt.kda_scan``): q, k, v [B, L, H, D] in the
        projections' dtype, the log-decay [B, L, H, D] and beta [B, L,
        H] float32."""
        bsz, length, _ = q.shape
        h, d = self.num_heads, self.head_dim
        heads = (bsz, length, h, d)
        with jax.named_scope("pt.kda_conv"):
            q, k, v = (jax.nn.silu(causal_depthwise_conv(t, w)) for t, w in (
                (q, q_conv), (k, k_conv), (v, v_conv)))
        with jax.named_scope("pt.kda_scan"):
            cd = v.dtype
            q = _l2_normalize(q.reshape(heads)).astype(cd)
            k = _l2_normalize(k.reshape(heads)).astype(cd)
            a = -jnp.exp(a_log.astype(jnp.float32))[:, None] \
                * jax.nn.softplus(f.astype(jnp.float32).reshape(heads)
                                  + dt_bias.astype(jnp.float32).reshape(h, d))
            return q, k, v.reshape(heads), a, jax.nn.sigmoid(
                b.astype(jnp.float32))

    @jax.named_scope("pt.kda_scan")
    def _gated_norm(self, o, gate, weight):
        """``rmsnorm_head(o) * sigmoid(gate)``, float32, in the gate's
        dtype."""
        bsz, length, h, d = o.shape
        o = o.astype(jnp.float32)
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + self.norm_eps) * weight.astype(jnp.float32)
        return (o.reshape(bsz, length, h * d)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)
