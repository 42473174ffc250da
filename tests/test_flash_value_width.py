"""The flash kernels with values narrower than their keys: q and k at 192
(a pair of heads is 384 lanes), v and the output at 128, as latent
attention runs them (``nn.MultiHeadLatentAttention``). Interpret mode on
the CPU against dense float32 attention. ``tests/test_tpu_compile.py``
lowers them for a described v5e, and holds the accepted cells' kernels,
where v is as wide as q, to the text they had before."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import flash_attention as fa


def _dense(q, k, v, scale, causal):
    """float32 attention over [B, T, H, D] q/k and [B, T, H, Dv] v."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("layout", ["bthd", "bhtd"])
@pytest.mark.parametrize("blocks", [64, 256], ids=["tiled", "one_tile"])
def test_value_width_matches_dense_attention(layout, blocks):
    """out, dq, dk, dv at d_qk 192 and d_v 128, causal: several tiles a
    sequence (the dq and dk/dv kernels) and one tile (the fused
    backward), in both layouts."""
    b, t, h, d, dv = 1, 256, 2, 192, 128
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(x, (b, t, h, d), jnp.float32)
            for x in keys[:2])
    v = jax.random.normal(keys[2], (b, t, h, dv), jnp.float32)
    g = jax.random.normal(keys[3], (b, t, h, dv), jnp.float32)
    scale = d ** -0.5
    bthd = layout == "bthd"

    def flash(q, k, v):
        if bthd:
            return fa.flash_attention(q, k, v, causal=True, scale=scale,
                                      interpret=True, bthd=True)
        out = fa.flash_attention(
            *(jnp.moveaxis(x, 2, 1) for x in (q, k, v)), causal=True,
            scale=scale, interpret=True)
        return jnp.moveaxis(out, 1, 2)

    orig = fa.BLOCK_Q, fa.BLOCK_K
    fa.BLOCK_Q = fa.BLOCK_K = blocks
    try:
        got, pull = jax.vjp(flash, q, k, v)
        grads = pull(g)
    finally:
        fa.BLOCK_Q, fa.BLOCK_K = orig
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(lambda *a: _dense(*a, scale, True), q, k, v)
        want_grads = pull(g)
    assert got.shape == (b, t, h, dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    for name, x, y in zip(("dq", "dk", "dv"), grads, want_grads):
        assert x.shape == y.shape, name
        np.testing.assert_allclose(x, y, atol=1e-4, rtol=1e-4,
                                   err_msg=name)


def test_xla_path_takes_a_narrower_value():
    """Off a TPU ``maybe_flash_attention`` runs XLA attention, which must
    give the same result for a narrower v."""
    from paddle_tpu.kernels import maybe_flash_attention
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k = (jax.random.normal(x, (2, 16, 4, 24)) for x in keys[:2])
    v = jax.random.normal(keys[2], (2, 16, 4, 8))
    got = maybe_flash_attention(q, k, v, causal=True, scale=24 ** -0.5,
                                layout="bthd")
    with jax.default_matmul_precision("highest"):
        want = _dense(q, k, v, 24 ** -0.5, True)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_work_counts_the_value_width():
    """Without ``d_v`` the notes count what they counted; with it the
    products at the value width count d_v."""
    b, h, t, d, dv = 2, 4, 1024, 192, 128
    pairs = 2.0 * b * h * t * t
    assert fa.flash_fwd_work(b, h, t, t, 128, 2) == (
        4.0 * b * h * t * t * 128, float(b * h * 4 * t * 128 * 2
                                         + 4 * b * h * t))
    assert fa.flash_fwd_work(b, h, t, t, d, 2, causal=True, d_v=dv)[0] \
        == pairs * (d + dv) / 2
    assert fa.flash_fwd_work(b, h, t, t, d, 2, d_v=dv)[1] == float(
        b * h * (2 * t * d + 2 * t * dv) * 2 + 4 * b * h * t)
    for m, width in ((5, 3 * d + 2 * dv), (3, 2 * d + dv),
                     (4, 2 * d + 2 * dv)):
        assert fa.flash_bwd_work(b, h, t, t, 128, 2, matmuls=m)[0] == \
            2.0 * m * b * h * t * t * 128
        assert fa.flash_bwd_work(b, h, t, t, d, 2, matmuls=m,
                                 d_v=dv)[0] == pairs * width
    # at d_v == d the bytes are the old formula's
    assert fa.flash_bwd_work(b, h, t, t, 128, 2, matmuls=4) == \
        fa.flash_bwd_work(b, h, t, t, 128, 2, matmuls=4, d_v=128)
    assert fa.flash_bwd_work(b, h, t, t, 128, 2, matmuls=4)[1] == float(
        b * h * (2 * t + 2 * t + 2 * t) * 128 * 2 + 8 * b * h * t)


def test_tiling_rule_takes_the_value_width():
    assert fa.bthd_supported(192, 32, 128)
    assert fa.bthd_supported(128, 3, 128)
    assert not fa.bthd_supported(192, 32, 96)     # a pair is 192 lanes
    assert not fa.bthd_supported(192, 3, 128)     # no pair of heads
