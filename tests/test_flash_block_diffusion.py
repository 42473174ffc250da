"""The block-diffusion mask as a mode of the flash kernels, in interpret
mode on the CPU, against dense masked attention written here: forward
and the three gradients, at lengths that are and are not whole tiles, at
block lengths 4 and 32; the tiles the kernels walk against the tiles
that hold an allowed pair; the FLOPs a call site notes against a count
of the dense rule's true entries and the benchmark's closed form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmarks import bd_lm_arithmetic
from paddle_tpu import observability as obs
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import maybe_flash_attention
from paddle_tpu.observability import xprof


def dense_rule(length, block):
    """[2 L, 2 L] bool, from the words of the rule and no kernel code."""
    mask = np.zeros((2 * length, 2 * length), bool)
    for i in range(2 * length):
        for j in range(2 * length):
            bi, bj = (i % length) // block, (j % length) // block
            if i < length and j < length:
                mask[i, j] = bi == bj
            elif i < length:
                mask[i, j] = bj < bi
            elif j >= length:
                mask[i, j] = bj <= bi
    return mask


def dense_attention(q, k, v, mask):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# (L, K, tile): whole tiles, a length that is none, a tile that
# straddles the two copies, one tile for everything (the fused backward)
SHAPES = [(16, 4, 8), (32, 4, 16), (20, 4, 16), (36, 4, 16), (32, 32, 16),
          (96, 32, 64), (24, 4, 512)]


@pytest.mark.parametrize("length,block,tile", SHAPES)
def test_the_rule_in_the_kernel_is_the_dense_rule(length, block, tile):
    pos = jnp.arange(2 * length + 3)     # a padded tail sees nothing
    got = np.asarray(fa.bd_allowed(pos[:, None], pos[None, :], 2 * length,
                                   length, block))
    want = dense_rule(length, block)
    assert np.array_equal(got[:2 * length, :2 * length], want)
    assert not got[2 * length:].any() and not got[:, 2 * length:].any()
    assert want.sum() == fa.bd_allowed_pairs(length, block) \
        == bd_lm_arithmetic.allowed_pairs(length, block)


@pytest.mark.parametrize("length,block,tile", SHAPES)
def test_no_tile_without_an_allowed_pair_is_walked_and_none_is_missed(
        length, block, tile):
    mask, total = dense_rule(length, block), 2 * length
    n_tiles = -(-total // tile)
    for first in range(0, total, tile):
        lo, n, c_lo, c = (int(x) for x in fa.bd_key_tiles(
            first, tile, tile, length, block))
        walked = [*range(lo, lo + n), *range(c_lo, c_lo + c)]
        assert walked == [j for j in range(n_tiles) if mask[
            first:first + tile, j * tile:(j + 1) * tile].any()]
        lo, n, c_lo, c = (int(x) for x in fa.bd_query_tiles(
            first, tile, tile, length, block))
        walked = [*range(lo, lo + n), *range(c_lo, c_lo + c)]
        assert walked == [i for i in range(n_tiles) if mask[
            i * tile:(i + 1) * tile, first:first + tile].any()]


@pytest.mark.parametrize("bthd", [False, True], ids=["bhtd", "bthd"])
@pytest.mark.parametrize("length,block,tile", SHAPES)
def test_forward_and_the_three_gradients(length, block, tile, bthd,
                                         monkeypatch):
    monkeypatch.setattr(fa, "BLOCK_Q", tile)
    monkeypatch.setattr(fa, "BLOCK_K", tile)
    rng = np.random.default_rng(length + block)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 2, 2 * length, 128
                                            if bthd else 8)),
                           jnp.float32) for _ in range(3))
    mask = jnp.asarray(dense_rule(length, block))
    swap = (lambda x: jnp.moveaxis(x, 1, 2)) if bthd else (lambda x: x)

    def kernel(q, k, v):
        out = fa.flash_attention(swap(q), swap(k), swap(v), interpret=True,
                                 bthd=bthd, block_diffusion=(length, block))
        return jnp.sum(jnp.sin(swap(out))), swap(out)

    def dense(q, k, v):
        out = dense_attention(q, k, v, mask)
        return jnp.sum(jnp.sin(out)), out

    (_, got), got_g = jax.value_and_grad(kernel, (0, 1, 2), True)(q, k, v)
    (_, want), want_g = jax.value_and_grad(dense, (0, 1, 2), True)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for a, b, name in zip(got_g, want_g, "qkv"):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                   err_msg="d" + name)


def test_the_mask_is_a_mode_of_its_own():
    x = jnp.zeros((1, 1, 16, 8))
    with pytest.raises(ValueError, match="block_diffusion"):
        fa.flash_attention(x, x, x, True, None, True, 0.0, None, None,
                           False, (8, 4))
    with pytest.raises(ValueError, match="whole blocks"):
        fa.flash_attention(x, x, x, interpret=True, block_diffusion=(8, 3))
    with pytest.raises(ValueError, match="positions"):
        fa.flash_attention(x, x, x, interpret=True, block_diffusion=(6, 2))


@pytest.mark.parametrize("length,block", [(16, 4), (64, 32)])
def test_the_noted_flops_are_the_allowed_pairs(length, block, monkeypatch):
    """Every ``bd_flash_*`` call site notes ``4 B H D`` (forward; 6 and
    8 for the dq and dk/dv kernels) times the count of the dense rule's
    true entries, whatever tiles it visits: at tile 8 the kernels visit
    far more."""
    monkeypatch.setattr(fa, "BLOCK_Q", 8)
    monkeypatch.setattr(fa, "BLOCK_K", 8)
    b, h, d = 2, 3, 8
    pairs = int(dense_rule(length, block).sum())
    obs.reset_all()
    pt.set_flags({"enable_metrics": True})
    try:
        def loss(q):
            return jnp.sum(fa.flash_attention(
                q, q, q, interpret=True, block_diffusion=(length, block)))
        with xprof.tracing("probe"):
            jax.make_jaxpr(jax.grad(loss))(
                jnp.zeros((b, h, 2 * length, d)))
        notes = {n[0]: n[1] for n in xprof.kernel_notes("probe")}
    finally:
        pt.set_flags({"enable_metrics": False})
        obs.reset_all()
    assert notes == {"bd_flash_fwd": 4.0 * b * h * d * pairs,
                     "bd_flash_bwd_dq": 6.0 * b * h * d * pairs,
                     "bd_flash_bwd_dkv": 8.0 * b * h * d * pairs}
    cfg = {"num_attention_heads": h, "head_dim": d, "block_length": block}
    assert notes["bd_flash_fwd"] == \
        bd_lm_arithmetic.attention_flops_forward(cfg, b, length)


def test_off_a_tpu_the_seam_runs_dense_masked_attention():
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 24, 2, 8)), jnp.float32)
               for _ in range(3))
    got = maybe_flash_attention(q, k, v, scale=8 ** -0.5, layout="bthd",
                                block_diffusion=(12, 4))
    want = dense_attention(*(jnp.moveaxis(x, 1, 2) for x in (q, k, v)),
                           jnp.asarray(dense_rule(12, 4)))
    np.testing.assert_allclose(jnp.moveaxis(got, 1, 2), want, rtol=1e-5,
                               atol=1e-5)
