"""The block-diffusion mask as a mode of the flash kernels, in interpret
mode on the CPU, against dense masked attention written here: forward
and the three gradients, at lengths that are and are not whole tiles, at
block lengths 4 and 32; the tiles the kernels walk against the tiles
that hold an allowed pair, and the tiles they walk without a mask
against the tiles the dense rule allows whole; the FLOPs a call site
notes against a count of the dense rule's true entries and the
benchmark's closed form."""

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


# blocks that straddle tiles, and the geometry of the noisy diagonal's
# sub-tiles (tiles of 512, a block that divides 128)
WALKS = SHAPES + [(64, 4, 16), (48, 6, 16), (1024, 4, 512)]


def _tiles(mask, tile):
    """{(query tile, key tile): its [tile, tile] cut of the dense rule}"""
    n = -(-mask.shape[0] // tile)
    return {(i, j): mask[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile]
            for i in range(n) for j in range(n)}


def _whole(cut, tile):
    return cut.shape == (tile, tile) and bool(cut.all())


@pytest.mark.parametrize("length,block,tile", WALKS)
def test_a_whole_tile_is_one_the_dense_rule_allows_whole(length, block,
                                                         tile):
    """Never true where the dense tile has a false entry or a padded
    position; and where the halves are whole tiles, true on every tile
    of clean keys the rule allows whole (a tile of noisy keys inside one
    block is walked as an edge)."""
    mask, total = dense_rule(length, block), 2 * length
    for (i, j), cut in _tiles(mask, tile).items():
        got = bool(fa.bd_tile_whole(i * tile, tile, j * tile, tile, total,
                                    length, block))
        assert not got or _whole(cut, tile), (i, j)
        if length % tile == 0 and j * tile >= length:
            assert got == _whole(cut, tile), (i, j)


def _runs(runs):
    return [t for lo, n in runs for t in range(int(lo), int(lo) + int(n))]


@pytest.mark.parametrize("length,block,tile", WALKS)
def test_the_walks_cut_their_runs_into_whole_tiles_and_edges(length, block,
                                                             tile):
    """What the three kernels loop over: the whole runs hold only tiles
    the dense rule allows whole, whole runs and edges together are the
    tiles that hold an allowed pair, each once; the census is their
    count."""
    mask, total = dense_rule(length, block), 2 * length
    tiles, n = _tiles(mask, tile), -(-total // tile)
    visited = whole_count = diagonal = 0
    for t in range(n):
        whole, edges, diag = fa._key_walk(t * tile, tile, tile, total, total,
                                    False, (length, block))
        assert all(_whole(tiles[t, j], tile) for j in _runs(whole))
        assert sorted(_runs(whole) + _runs(edges) + _runs(diag)) == [
            j for j in range(n) if tiles[t, j].any()]
        assert all(j == t and t * tile < length for j in _runs(diag))
        visited += len(_runs(whole) + _runs(edges) + _runs(diag))
        whole_count += len(_runs(whole))
        diagonal += len(_runs(diag))
        if length % tile == 0:  # no whole clean tile is walked as an edge
            assert not any(_whole(tiles[t, j], tile) for j in _runs(edges)
                           if j * tile >= length)
        whole, edges, diag = fa._query_walk(t * tile, tile, tile, n, total,
                                            False, 0, (length, block))
        seers = [i for i in range(n) if tiles[i, t].any()]
        if _runs(diag):     # a noisy key tile of the aligned walk: its own
            assert _runs(diag) == seers == [t] and t * tile < length
            continue        # query tile and no other; the rest is not run
        assert all(_whole(tiles[i, t], tile) for i in _runs(whole))
        assert sorted(_runs(whole) + _runs(edges)) == seers
        if length % tile == 0 and t * tile >= length:
            assert not any(_whole(tiles[i, t], tile) for i in _runs(edges))
    got = fa.flash_tile_census(total, total, min(tile, total),
                               min(tile, total), False, (length, block))
    # the noisy diagonal is a kind of its own where the walk is aligned
    # (halves of whole tiles, tiles of whole blocks); the census counts
    # it where it is walked in sub-tiles (tiles of 512, a block that
    # divides 128)
    assert diagonal == (length // tile if length % tile == 0
                        and tile % block == 0 else 0)
    assert got == (visited, whole_count,
                   diagonal if tile == 512 and length >= 512 else 0)


def test_the_census_of_the_cell_from_the_closed_walk_alone():
    """2 x 8192 positions in tiles of 512, blocks of 4: a head and
    sequence visits 288 tiles, 240 of them whole (no 16,384^2 array
    here)."""
    assert fa.flash_tile_census(16384, 16384, 512, 512, False,
                                (8192, 4)) == (288, 240, 16)
    # the hybrid decoder's causal walk, and BERT's one tile
    assert fa.flash_tile_census(8192, 8192, 512, 512, True) == (136, 120, 0)
    assert fa.flash_tile_census(512, 512, 512, 512) == (1, 1, 0)


@pytest.mark.parametrize("bthd", [False, True], ids=["bhtd", "bthd"])
@pytest.mark.parametrize("length,block,tile",
                         SHAPES + [(64, 4, 16), (1024, 4, 512)])
def test_forward_and_the_three_gradients(length, block, tile, bthd,
                                         monkeypatch):
    monkeypatch.setattr(fa, "BLOCK_Q", tile)
    monkeypatch.setattr(fa, "BLOCK_K", tile)
    rng = np.random.default_rng(length + block)
    batch = 1 if length > 512 else 2         # the interpreter's time
    q, k, v = (jnp.asarray(rng.normal(size=(batch, 2, 2 * length, 128
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
