"""Element-wise dropout's keep-mask is a counter hash of (seed folded from
the key, global position): ``ops.nn_functional.dropout_keep_mask``.

Statistics of the generator (rate, independence along every axis, between
call sites and between steps), its semantics (determinism per key, forward
and backward on the same bits, both modes, replay under ``jax.checkpoint``,
the same mask under any mesh), and that no random word per element is left
in the traced program. Every mask here is a pure function of a fixed key,
so the statistical bounds cannot flake.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.core import random as _random
from paddle_tpu.ops import nn_functional as F

CELL = (32, 512, 768)          # one 768-wide site of bert_base_s512
RAGGED = (7, 129, 53)


def _z_binomial(count: int, n: int, q: float) -> float:
    return (count - n * q) / math.sqrt(n * q * (1.0 - q))


def _both_kept_z(a, b, q: float) -> float:
    """z-score of the count of positions kept in both masks against
    Binomial(n, q^2): independent masks stay within a few sigma."""
    a, b = np.asarray(a), np.asarray(b)
    return _z_binomial(int(np.sum(a & b)), a.size, q * q)


@pytest.mark.parametrize("shape", [CELL, RAGGED, (100003,), (100003, 1)],
                         ids=["cell", "ragged", "flat", "column"])
@pytest.mark.parametrize("p", [0.1, 0.5])
def test_keep_rate_within_four_sigma(p, shape):
    for seed in (0, 1, 20260101):
        keep = np.asarray(F.dropout_keep_mask(jax.random.key(seed),
                                              1.0 - p, shape))
        assert keep.shape == shape and keep.dtype == np.bool_
        z = _z_binomial(int(keep.sum()), keep.size, 1.0 - p)
        assert abs(z) < 4.0, (seed, z)


@pytest.mark.parametrize("axis", [0, 1, 2])
@pytest.mark.parametrize("p", [0.1, 0.5])
def test_neighbours_along_each_axis_are_independent(p, axis):
    # disjoint pairs (2i, 2i+1) along the axis: both kept ~ Binomial(q^2)
    keep = np.asarray(F.dropout_keep_mask(jax.random.key(5), 1.0 - p, CELL))
    even = np.take(keep, range(0, CELL[axis] - 1, 2), axis=axis)
    odd = np.take(keep, range(1, CELL[axis], 2), axis=axis)
    assert abs(_both_kept_z(even, odd, 1.0 - p)) < 4.0


@pytest.mark.parametrize("p", [0.1, 0.5])
def test_call_sites_and_steps_are_independent(p):
    q = 1.0 - p
    rng = jax.random.key(11)
    masks = []
    for _ in range(2):                      # two steps, as TrainStep splits
        rng, step_key = jax.random.split(rng)
        with _random.rng_scope(default=step_key, dropout=step_key):
            masks.append([F.dropout_keep_mask(_random.next_key("dropout"),
                                              q, CELL) for _ in range(2)])
    (a0, a1), (b0, b1) = masks
    for x, y in [(a0, a1), (b0, b1), (a0, b0), (a1, b1), (a0, b1)]:
        assert abs(_both_kept_z(x, y, q)) < 4.0


def test_mask_has_no_product_structure():
    """A mask that were f(row) ^ g(column) at heart would pass every
    pair count and still repeat itself: rows equal or complementary,
    2 x 2 minors of even parity."""
    keep = np.asarray(F._hash_keep(jnp.uint32(123), 0.5, (2048, 2048)))
    minors = (keep[0::2, 0::2] ^ keep[0::2, 1::2]
              ^ keep[1::2, 0::2] ^ keep[1::2, 1::2])
    assert abs(_z_binomial(int(minors.sum()), minors.size, 0.5)) < 4.0
    agree = (keep[:1024] == keep[1024:]).mean(axis=1)
    assert abs(agree.mean() - 0.5) < 4.0 * 0.5 / math.sqrt(keep.size / 2)
    assert agree.std() < 1.3 * 0.5 / math.sqrt(2048)


def _mix_count(shape):
    """How many 32-bit mixing multiplies the mask of ``shape`` traces."""
    jaxpr = jax.make_jaxpr(lambda s: F._hash_keep(s, 0.9, shape))(
        jax.ShapeDtypeStruct((), jnp.uint32))
    assert jaxpr.out_avals[0].shape == shape
    consts = [getattr(e.invars[1], "val", None) for e in jaxpr.eqns
              if e.primitive.name == "mul"]
    return sum(c is not None and np.ndim(c) == 0
               and int(c) in (0x85EBCA6B, 0xC2B2AE35) for c in consts)


def test_shapes_over_2_32_elements_mix_two_coordinates():
    # under 2^32 elements: one mix (two multiplies) an element
    assert _mix_count(CELL) == 2
    assert _mix_count((32, 12, 512, 512)) == 2
    # over: the leading coordinate through a mix of its own first
    assert _mix_count((3, 2 ** 16, 2 ** 15)) == 4
    assert _mix_count((2 ** 20, 2 ** 13, 4)) == 4
    with pytest.raises(ValueError, match="32-bit coordinates"):
        _mix_count((2 ** 33, 2 ** 33))
    # the two-coordinate path itself, on a grid small enough to hold:
    # rows 2^32 / 4096 apart in a [.., 4096] array are 2^32 positions apart
    from paddle_tpu.core.random import fmix32, mix32
    hi = jnp.arange(512, dtype=jnp.uint32)[:, None] * jnp.uint32(2 ** 20)
    lo = jnp.arange(4096, dtype=jnp.uint32)[None, :]
    keep = np.asarray(
        mix32(fmix32(hi + jnp.uint32(123)) ^ (lo * jnp.uint32(0x9E3779B9)))
        < jnp.uint32(int(0.9 * 2 ** 32)))
    assert abs(_z_binomial(int(keep.sum()), keep.size, 0.9)) < 4.0
    assert abs(_both_kept_z(keep[0::2], keep[1::2], 0.9)) < 4.0
    assert abs(_both_kept_z(keep[:, 0::2], keep[:, 1::2], 0.9)) < 4.0
    # scalars and empty arrays have a mask too
    assert F._hash_keep(jnp.uint32(1), 0.5, ()).shape == ()
    assert F._hash_keep(jnp.uint32(1), 0.5, (0, 3)).shape == (0, 3)


def test_same_key_same_mask_eagerly_and_under_jit():
    key = jax.random.key(3)
    eager = F.dropout_keep_mask(key, 0.9, RAGGED)
    jitted = jax.jit(lambda k: F.dropout_keep_mask(k, 0.9, RAGGED))(key)
    np.testing.assert_array_equal(eager, jitted)
    np.testing.assert_array_equal(eager,
                                  F.dropout_keep_mask(key, 0.9, RAGGED))
    other = F.dropout_keep_mask(jax.random.key(4), 0.9, RAGGED)
    assert abs(_both_kept_z(eager, other, 0.9)) < 4.0
    # raw uint32 keys (jax.random.PRNGKey) fold like typed ones
    np.testing.assert_array_equal(
        eager, F.dropout_keep_mask(jax.random.PRNGKey(3), 0.9, RAGGED))


def test_layers_dropout_seed_is_deterministic():
    x = jnp.ones((64, 128), jnp.float32)
    a = pt.layers.dropout(x, 0.3, seed=7)
    np.testing.assert_array_equal(a, pt.layers.dropout(x, 0.3, seed=7))
    assert not np.array_equal(a, pt.layers.dropout(x, 0.3, seed=8))


@pytest.mark.parametrize("mode,kept_grad", [("upscale_in_train", 1 / 0.9),
                                            ("downscale_in_infer", 1.0)])
@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_forward_and_backward_masks_are_the_same_bits(mode, kept_grad, jit):
    key = jax.random.key(9)
    x = jnp.full(RAGGED, 2.0, jnp.float32)

    def f(x):
        return F.dropout(x, 0.1, mode=mode, key=key)

    def loss(x):
        return jnp.sum(f(x))

    out = (jax.jit(f) if jit else f)(x)
    grad = (jax.jit(jax.grad(loss)) if jit else jax.grad(loss))(x)
    out, grad = np.asarray(out), np.asarray(grad)
    kept = out != 0.0
    assert 0.85 < kept.mean() < 0.95
    np.testing.assert_array_equal(grad != 0.0, kept)
    np.testing.assert_allclose(grad[kept], kept_grad, rtol=1e-6)
    np.testing.assert_allclose(out[kept], 2.0 * kept_grad, rtol=1e-6)
    # eval: identity or the downscale, no mask
    ev = F.dropout(x, 0.1, training=False, mode=mode)
    np.testing.assert_allclose(
        ev, x * (0.9 if mode == "downscale_in_infer" else 1.0))


def test_dtype_is_kept_and_the_seed_is_the_only_residual():
    key = jax.random.key(2)
    x = jnp.ones((16, 256), jnp.bfloat16)
    out, vjp = jax.vjp(lambda x: F.dropout(x, 0.1, key=key), x)
    assert out.dtype == jnp.bfloat16
    (g,) = vjp(jnp.ones_like(out))
    assert g.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(g, np.float32) != 0,
                                  np.asarray(out, np.float32) != 0)
    # what the backward closes over: no array of the activation's size
    assert all(np.size(leaf) <= 4 for leaf in jax.tree.leaves(vjp))


def test_checkpoint_round_a_layer_replays_the_mask():
    w = jnp.linspace(-1.0, 1.0, 64 * 64, dtype=jnp.float32).reshape(64, 64)
    x = jnp.ones((32, 64), jnp.float32)

    def layer(w, x):
        return F.dropout(jnp.tanh(x @ w), 0.5)

    def loss(layer_fn, w, key):
        with _random.rng_scope(dropout=key):
            return jnp.sum(layer_fn(w, x) ** 2)

    key = jax.random.key(21)
    plain = jax.jit(jax.value_and_grad(lambda w: loss(layer, w, key)))(w)
    remat = jax.jit(jax.value_and_grad(
        lambda w: loss(jax.checkpoint(layer), w, key)))(w)
    np.testing.assert_allclose(plain[0], remat[0], rtol=1e-6)
    np.testing.assert_allclose(plain[1], remat[1], rtol=1e-5, atol=1e-6)


def test_mask_does_not_depend_on_the_mesh():
    from paddle_tpu.parallel.mesh import create_mesh
    mesh = create_mesh({"dp": 2, "mp": 2}, allow_submesh=True)
    shape, key = (8, 64, 256), jax.random.key(13)
    x = jnp.arange(math.prod(shape), dtype=jnp.float32).reshape(shape) + 1.0
    whole = F.dropout(x, 0.1, key=key)
    for spec in (P("dp", None, "mp"), P("mp", "dp", None)):
        sharding = NamedSharding(mesh, spec)
        split = jax.jit(lambda x, k: F.dropout(x, 0.1, key=k),
                        out_shardings=sharding)(
            jax.device_put(x, sharding), key)
        assert split.sharding.is_equivalent_to(sharding, x.ndim)
        # the same positions; the kept values to a rounding of x / 0.9
        np.testing.assert_array_equal(np.asarray(whole) != 0.0,
                                      np.asarray(split) != 0.0)
        np.testing.assert_allclose(whole, split, rtol=1e-6)
        mask = jax.jit(lambda k: F.dropout_keep_mask(k, 0.9, shape),
                       out_shardings=sharding)(key)
        np.testing.assert_array_equal(mask, np.asarray(whole) != 0.0)


# -- the words are gone from the program ------------------------------------

_WORD_DRAWS = ("random_bits", "rng_bit_generator", "threefry2x32")


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("flash", [False, True],
                         ids=["xla_attention", "flash_routed"])
def test_encoder_layer_draws_no_word_per_element(flash, monkeypatch):
    """One BertEncoderLayer forward and backward at the cell's shape,
    traced from abstract values: the only random words left are the
    flash kernel's one seed a site."""
    from paddle_tpu import kernels
    from paddle_tpu.models.bert import BertConfig, BertEncoderLayer
    from paddle_tpu.nn.layer import functional_call
    monkeypatch.setattr(kernels, "_on_tpu", lambda: flash)
    layer = BertEncoderLayer(BertConfig())
    layer.to(dtype="bfloat16")
    layer.train()
    params, buffers = layer.param_dict(), layer.buffer_dict()

    def loss(p, x, key):
        with _random.rng_scope(default=key, dropout=key):
            y = functional_call(layer, p, buffers, x)
        return jnp.sum(y.astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     params),
        jax.ShapeDtypeStruct(CELL, jnp.bfloat16), jax.random.key(0))
    eqns = list(_equations(jaxpr.jaxpr))
    assert any(e.primitive.name == "iota" for e in eqns)
    draws = [e for e in eqns if e.primitive.name in _WORD_DRAWS]
    for e in draws:
        assert all(math.prod(v.aval.shape) <= 1 for v in e.outvars), e
    assert bool(draws) == flash


def test_counter_reads_the_cells_sites_and_elements(monkeypatch):
    """bert_base at b32 x s512, from abstract shapes: 1 embedding site +
    12 x (dropout1, act_dropout, dropout2), attention's own mask being
    the flash kernel's."""
    from paddle_tpu import kernels
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   pretraining_loss)
    from paddle_tpu.static import TrainStep
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    b, s, pred = 32, 512, 80
    was = obs.enabled()
    pt.set_flags({"enable_metrics": True})
    try:
        step = TrainStep(BertForPretraining(BertConfig()),
                         pt.optimizer.AdamW(1e-5), pretraining_loss)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        state = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), step.state)
        batch = step._make_batch((i32(b, s),), (i32(b, pred), i32(b)),
                                 {"masked_positions": i32(b, pred)})
        for _ in range(2):      # a retrace replaces, it does not add
            step._jitted.trace(state, batch)
            jax.clear_caches()
        sites = obs.gauge("pt_dropout_mask_sites").value(fn=step._span_name)
        elements = obs.gauge("pt_dropout_mask_elements").value(
            fn=step._span_name)
        summed = obs.gauge("pt_qkv_grad_summed_sites").value(
            fn=step._span_name)
    finally:
        pt.set_flags({"enable_metrics": was})
    assert sites == 37
    assert summed == 0      # one chip: no mp axis, three Linear calls
    assert elements == 918_552_576 == \
        b * s * (768 + 12 * (768 + 3072 + 768))
    # outside a tracked entry point nothing is counted
    F.dropout(jnp.ones((4, 4)), 0.5)
    assert obs.gauge("pt_dropout_mask_sites").value(fn=step._span_name) == 37
