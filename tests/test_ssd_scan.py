"""The fused Mamba-2 scan kernels (``kernels/ssd_scan.py``) against the
XLA form they stand in for (``nn.layers.ssm.ssd_chunked_scan``) and
against the position-by-position recurrence of ``benchmarks/references``,
under the Pallas interpreter on the CPU, and the seam that chooses
between the two forms. Everything is float32 here unless a test says
otherwise, so the forms differ by the order of their sums alone: 1e-4
relative, as in ``tests/test_nemotron_h.py``. What Mosaic makes of the
kernels is ``tests/test_tpu_compile.py``'s to say."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmarks.references import nemotron3_nano_30b_a3b as ref
from paddle_tpu import kernels
from paddle_tpu.kernels import ssd_scan as K
from paddle_tpu.nn.layer import functional_call
from paddle_tpu.nn.layers import ssm

TOL = 1e-4
CHUNK, STATE = 128, 128
# (heads, head width, groups): the hybrid decoder's heads of 64, narrower
# and wider ones, one head a group
SHAPES = [(4, 64, 2), (8, 32, 2), (2, 128, 2)]
NAMES = ("x", "dt", "b_mat", "c_mat", "a")


def rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def inputs(seed, heads, width, groups, rows=2, length=3 * CHUNK,
           dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    scale = 3 / np.sqrt(STATE)
    return (
        jnp.asarray(rng.normal(size=(rows, length, heads, width)), dtype),
        jnp.asarray(rng.uniform(0.001, 0.1, (rows, length, heads)),
                    jnp.float32),
        jnp.asarray(rng.normal(size=(rows, length, groups, STATE)) * scale,
                    dtype),
        jnp.asarray(rng.normal(size=(rows, length, groups, STATE)) * scale,
                    dtype),
        -jnp.asarray(rng.uniform(1.0, 16.0, (heads,)), jnp.float32))


def fused(*args):
    return K.ssd_scan(*args, CHUNK, interpret=True)


def xla_form(*args):
    return ssm.ssd_chunked_scan(*args, CHUNK)


def recurrence(x, dt, b_mat, c_mat, a):
    spread = lambda t: jnp.repeat(t, x.shape[2] // t.shape[2], axis=2)
    return jnp.stack([
        ref.recurrence(x[i], dt[i], a, spread(b_mat)[i], spread(c_mat)[i])
        for i in range(x.shape[0])])


def value_and_gradients(form, args, seed=7):
    """The result and the gradients of all five inputs under a fixed
    random cotangent."""
    weight = jnp.asarray(np.random.default_rng(seed).normal(
        size=args[0].shape), jnp.float32)
    loss = lambda *a: jnp.sum(form(*a).astype(jnp.float32) * weight)
    return form(*args), jax.grad(loss, argnums=range(5))(*args)


@pytest.mark.parametrize("form", [xla_form, recurrence],
                         ids=["xla_form", "recurrence"])
@pytest.mark.parametrize("heads,width,groups", SHAPES)
def test_values_and_gradients_match(heads, width, groups, form):
    """Two sequences, two groups, one to four heads a group, three
    chunks: a zero entering state at chunk 0 and a carried one after."""
    args = inputs(heads, heads, width, groups)
    got, got_grads = value_and_gradients(fused, args)
    want, want_grads = value_and_gradients(form, args)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel(got, want) < TOL
    for name, g, w in zip(NAMES, got_grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert rel(g, w) < TOL, name


def test_a_sequence_starts_from_nothing_and_a_chunk_from_the_one_before():
    args = inputs(3, 4, 64, 2)
    whole = fused(*args)
    cut = lambda rows, span: tuple(t[rows, span] for t in args[:4]) \
        + (args[4],)
    # the second sequence alone: the grid's state is zeroed where a
    # sequence (and a group) starts
    assert rel(fused(*cut(slice(1, 2), slice(None)))[0], whole[1]) < 1e-6
    # the first chunk alone is the first chunk; the second alone is not
    # the second, which the first one's state reaches
    first, second = slice(0, CHUNK), slice(CHUNK, 2 * CHUNK)
    assert rel(fused(*cut(slice(None), first)), whole[:, first]) < 1e-6
    assert rel(fused(*cut(slice(None), second)), whole[:, second]) > 1e-2


def test_the_steepest_decay_the_model_can_form_is_finite_and_equal():
    """``A = -64`` with dt 0.1 at every position: exponents to -800
    inside a chunk. The mask goes on before ``exp``, so no exponent used
    is positive, and nothing overflows on the way to a zero. The
    gradient of ``a`` here is what is left of terms a thousand times its
    size (``sum dt d(dt a)``: 0.005 from terms of 1 to 10), and both
    forms sit 1e-3 to 2e-3 from the recurrence's: held to 1e-2."""
    x, dt, b_mat, c_mat, a = inputs(5, 4, 64, 2)
    args = (x, jnp.full_like(dt, 0.1), b_mat, c_mat, jnp.full_like(a, -64.0))
    got, got_grads = value_and_gradients(fused, args)
    want, want_grads = value_and_gradients(xla_form, args)
    assert np.isfinite(np.asarray(got)).all()
    assert rel(got, want) < TOL
    for name, g, w in zip(NAMES, got_grads, want_grads):
        assert np.isfinite(np.asarray(g)).all(), name
        assert rel(g, w) < (1e-2 if name == "a" else TOL), name


def test_in_bfloat16_the_kernels_keep_the_precision_the_xla_form_keeps():
    """Against the float32 result, the kernels' bfloat16 result and
    gradients are as close as the XLA form's: the same operands are
    rounded (the decay block times ``C B^T``, ``x dt``, the states as a
    matmul reads them), nothing more."""
    exact = inputs(11, 4, 64, 2)
    low = tuple(t.astype(jnp.bfloat16) if i in (0, 2, 3) else t
                for i, t in enumerate(exact))
    # what rounding the inputs alone costs is not the forms' doing
    rounded = tuple(t.astype(jnp.float32) for t in low)
    want, want_grads = value_and_gradients(xla_form, rounded)
    got, got_grads = value_and_gradients(fused, low)
    xla, xla_grads = value_and_gradients(xla_form, low)
    assert got.dtype == jnp.bfloat16
    assert rel(got, want) < 1.25 * rel(xla, want)
    for name, g, x, w in zip(NAMES, got_grads, xla_grads, want_grads):
        assert g.dtype == x.dtype, name
        assert rel(g, w) < 1.25 * rel(x, w), name


# -- the seam -----------------------------------------------------------------

@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The seam as a TPU would see it, its kernels under the
    interpreter."""
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(K, "ssd_scan",
                        functools.partial(K.ssd_scan, interpret=True))


def test_the_seam_declines_off_a_tpu():
    args = inputs(0, 4, 64, 2, length=CHUNK)
    assert kernels.maybe_ssd_scan(*args, CHUNK) is None


def test_the_seam_takes_whole_chunks_and_whole_tiles(as_on_a_tpu):
    args = inputs(0, 4, 64, 2, length=2 * CHUNK)
    got = kernels.maybe_ssd_scan(*args, CHUNK)
    assert got is not None
    assert rel(got, xla_form(*args)) < TOL


def test_the_seam_declines_under_a_mesh(as_on_a_tpu):
    from paddle_tpu.parallel.mesh import create_mesh
    args = inputs(0, 4, 64, 2, length=CHUNK)
    with jax.sharding.set_mesh(create_mesh({"dp": -1})):
        assert kernels.maybe_ssd_scan(*args, CHUNK) is None


@pytest.mark.parametrize("length,chunk,width,state", [
    (CHUNK + 8, CHUNK, 64, STATE),      # a length the chunk does not divide
    (CHUNK, 8, 64, STATE),              # a chunk of 8
    (CHUNK, CHUNK, 24, STATE),          # heads of no whole sublane tiles
    (CHUNK, CHUNK, 64, 16),             # a state of 16
], ids=["ragged_length", "chunk_8", "head_24", "state_16"])
def test_the_seam_declines_what_is_no_whole_tile(as_on_a_tpu, length, chunk,
                                                 width, state):
    x, dt, b_mat, c_mat, a = inputs(0, 4, width, 2, length=length)
    assert kernels.maybe_ssd_scan(x, dt, b_mat[..., :state],
                                  c_mat[..., :state], a, chunk) is None
    with pytest.raises(NotImplementedError):
        K.ssd_scan(x, dt, b_mat[..., :state], c_mat[..., :state], a, chunk,
                   interpret=True)


def _mixer(chunk):
    pt.seed(0)
    return pt.nn.Mamba2Mixer(32, 4, 64, STATE, 2, chunk_size=chunk)


def _as_before_the_seam(mixer, params, u):
    """``Mamba2Mixer.forward`` as it was before the seam: the XLA form,
    a sequence at a time under a checkpoint."""
    bsz, length, _ = u.shape
    h, p, g, n = (mixer.num_heads, mixer.head_dim, mixer.n_groups,
                  mixer.state_size)
    f32 = jnp.float32
    z, xbc, dt = jnp.split(u @ params["in_proj.weight"],
                           [mixer.inner, mixer.inner + mixer.conv_dim], -1)
    xbc = jax.nn.silu(ssm.causal_depthwise_conv(
        xbc, params["conv_weight"], params["conv_bias"]))
    x, b_mat, c_mat = jnp.split(xbc, [mixer.inner, mixer.inner + g * n], -1)
    x = x.reshape(bsz, length, h, p)
    dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))
    a = -jnp.exp(params["A_log"].astype(f32))
    y = jax.lax.map(
        jax.checkpoint(lambda s: ssm.ssd_chunked_scan(
            *(t[None] for t in s), a, mixer.chunk_size)[0]),
        (x, dt, b_mat.reshape(bsz, length, g, n),
         c_mat.reshape(bsz, length, g, n)))
    y = y + x * params["D"].astype(x.dtype)[:, None]
    y = functional_call(mixer.norm, {"weight": params["norm.weight"]}, {},
                        y.reshape(bsz, length, mixer.inner)
                        * jax.nn.silu(z))
    return y @ params["out_proj.weight"]


@pytest.mark.parametrize("chunk,length,on_tpu", [
    (8, 24, False), (8, 24, True), (CHUNK, CHUNK + 8, True),
    (CHUNK, CHUNK, False)],
    ids=["cpu_chunk_8", "tpu_chunk_8", "tpu_ragged_length", "cpu_chunk_128"])
def test_where_the_seam_declines_the_mixer_is_todays_bit_for_bit(
        chunk, length, on_tpu, monkeypatch):
    if on_tpu:
        monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    mixer = _mixer(chunk)
    params = mixer.param_dict()
    u = jnp.asarray(np.random.default_rng(2).normal(size=(2, length, 32)),
                    jnp.float32)

    def run(form):
        loss = lambda p: jnp.sum(jnp.square(form(p)))
        return jax.jit(jax.value_and_grad(loss))(params)

    got, got_grads = run(lambda p: functional_call(mixer, p, {}, u))
    want, want_grads = run(lambda p: _as_before_the_seam(mixer, p, u))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    for name in params:
        assert np.array_equal(np.asarray(got_grads[name]),
                              np.asarray(want_grads[name])), name


def test_where_the_seam_engages_the_mixer_agrees_with_the_xla_form(
        as_on_a_tpu, monkeypatch):
    mixer = _mixer(CHUNK)
    params = mixer.param_dict()
    u = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 2 * CHUNK, 32)), jnp.float32)
    loss = lambda p: jnp.sum(jnp.square(functional_call(mixer, p, {}, u)))
    got, got_grads = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(kernels, "_on_tpu", lambda: False)
    want, want_grads = jax.value_and_grad(loss)(params)
    assert rel(got, want) < TOL
    for name in params:
        assert rel(got_grads[name], want_grads[name]) < TOL, name


def test_the_work_a_call_notes_is_the_cells_arithmetic():
    """At the hybrid decoder's shapes one sequence's forward is the 28.6
    GFLOP XLA's cost analysis gave the XLA form, less its elementwise
    work, and what crosses HBM is the inputs and the result once (0.17
    GB a sequence) and the states entering the chunks (0.13 GB)."""
    x, group = (2, 8192, 64, 64), (2, 8192, 8, 128)
    states = 2 * 64 * 64 * 64 * 128 * 4
    flops, moved = K.ssd_work(x, group, 128, 2)
    assert 27e9 < flops / 2 < 29e9
    assert 0.17e9 < (moved - states) / 2 < 0.18e9
    back, back_moved = K.ssd_work(x, group, 128, 2, backward=True)
    assert 2 * flops < back < 3 * flops
    assert back_moved > moved
