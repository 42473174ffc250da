"""The grouped-matmul kernels (kernels/grouped_matmul.py) against
``jax.lax.ragged_dot``, under the Pallas interpreter at small sizes:
``moe_gmm``, its transposed-``rhs`` form, ``moe_tgmm`` and the
``custom_vjp`` that ties them. What Mosaic makes of them at the hybrid
decoder's widths is tests/test_tpu_compile.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import grouped_matmul as G

ROWS, TILE = 64, 16

# rows a group, of 64 rows in tiles of 16
ROUTINGS = {
    "balanced": [16, 16, 16, 16],
    "empty_first": [0, 20, 20, 24],
    "empty_middle": [20, 0, 0, 44],
    "empty_last": [30, 34, 0, 0],
    "one_holds_all": [0, 64, 0, 0],
    "ends_inside_tiles": [5, 13, 21, 25],
    "tile_shared_by_three": [14, 1, 1, 48],
    "rows_past_the_last_group": [7, 0, 20, 10],
}
DTYPES = {"f32": (jnp.float32, 1e-5), "bf16": (jnp.bfloat16, 2e-2)}


def rel(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def _operands(dtype, k=48, n=40, groups=4, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(ROWS, k)), dtype),
            jnp.asarray(rng.normal(size=(groups, k, n)), dtype),
            jnp.asarray(rng.normal(size=(ROWS, n)), dtype))


def _weight_gradient(lhs, dy, sizes, groups, dtype):
    """``ragged_dot``'s own, the statement ``moe_tgmm`` is held to."""
    k, n = lhs.shape[1], dy.shape[1]
    return jax.grad(lambda w: jnp.sum(
        jax.lax.ragged_dot(lhs, w, sizes).astype(jnp.float32)
        * dy.astype(jnp.float32)))(jnp.zeros((groups, k, n), dtype))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_moe_gmm_is_ragged_dot(routing, dtype):
    dtype, tol = DTYPES[dtype]
    lhs, rhs, dy = _operands(dtype)
    sizes = jnp.asarray(ROUTINGS[routing], jnp.int32)
    tiles = G.group_tiles(sizes, ROWS, TILE)
    got = G.moe_gmm(lhs, rhs, tiles, interpret=True)
    assert got.dtype == lhs.dtype
    assert rel(got, jax.lax.ragged_dot(lhs, rhs, sizes)) < tol
    # every row is written: those past the last group are zeros
    held = int(sizes.sum())
    assert not np.any(np.asarray(got[held:], np.float32))
    # dy x w^T from the weights as they lie
    got = G.moe_gmm(dy, rhs, tiles, transpose_rhs=True, interpret=True)
    assert rel(got, jax.lax.ragged_dot(
        dy, jnp.swapaxes(rhs, 1, 2), sizes)) < tol


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_moe_tgmm_is_ragged_dots_weight_gradient(routing, dtype):
    dtype, tol = DTYPES[dtype]
    lhs, rhs, dy = _operands(dtype)
    sizes = jnp.asarray(ROUTINGS[routing], jnp.int32)
    tiles = G.group_tiles(sizes, ROWS, TILE)
    got = G.moe_tgmm(lhs, dy, tiles, rhs.shape[0], interpret=True)
    assert got.shape == rhs.shape and got.dtype == lhs.dtype
    assert rel(got, _weight_gradient(lhs, dy, sizes, 4, dtype)) < tol
    # a group with no rows gets zeros, not what the buffer held
    for group, size in enumerate(ROUTINGS[routing]):
        if size == 0:
            assert not np.any(np.asarray(got[group], np.float32)), group


@pytest.mark.parametrize("k, n", [
    (48, 232),      # n like 1856: no multiple of 128
    (200, 256),     # k leaves a remainder against the tile: whole
    (256, 232),     # k cut in two, n whole
    (232, 256),     # k whole, n cut in two
    (256, 384),     # both have the divisor: k is the side cut
], ids=["n232", "k200_n256", "k256_n232", "k232_n256", "k256_n384"])
def test_widths_that_the_column_tile_divides_or_not(k, n, monkeypatch):
    """The 2688-wide side is cut in 896 and the 1856-wide one is not;
    here a tile of 128 against widths of its kind."""
    monkeypatch.setattr(G, "_COLUMN_TILE", 128)
    lhs, rhs, dy = _operands(jnp.float32, k, n)
    sizes = jnp.asarray(ROUTINGS["ends_inside_tiles"], jnp.int32)
    tiles = G.group_tiles(sizes, ROWS, TILE)
    kw = dict(interpret=True)
    assert rel(G.moe_gmm(lhs, rhs, tiles, **kw),
               jax.lax.ragged_dot(lhs, rhs, sizes)) < 1e-5
    assert rel(G.moe_gmm(dy, rhs, tiles, transpose_rhs=True, **kw),
               jax.lax.ragged_dot(dy, jnp.swapaxes(rhs, 1, 2),
                                  sizes)) < 1e-5
    assert rel(G.moe_tgmm(lhs, dy, tiles, 4, **kw),
               _weight_gradient(lhs, dy, sizes, 4, jnp.float32)) < 1e-5


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("routing", ["balanced", "empty_middle",
                                     "ends_inside_tiles"])
def test_gradients_are_those_of_the_ragged_dot_composition(routing, dtype):
    """Two products with ``relu^2`` between them, as a window of
    ``DroplessMoE`` runs them, through the ``custom_vjp``."""
    dtype, tol = DTYPES[dtype]
    lhs, w_in, _ = _operands(dtype, 48, 40)
    w_out = _operands(dtype, 40, 48, seed=1)[1]
    sizes = jnp.asarray(ROUTINGS[routing], jnp.int32)
    tiles = G.group_tiles(sizes, ROWS, TILE)

    def loss(product):
        def run(x, w1, w2):
            hidden = jnp.square(jax.nn.relu(product(x, w1)))
            return jnp.sum(jnp.square(product(hidden, w2)
                                      .astype(jnp.float32)))
        return jax.grad(run, argnums=(0, 1, 2))

    got = loss(lambda x, w: G.grouped_matmul(
        x, w, sizes, tiles, interpret=True))(lhs, w_in, w_out)
    want = loss(lambda x, w: jax.lax.ragged_dot(x, w, sizes))(
        lhs, w_in, w_out)
    for name, g, w in zip(("lhs", "w_in", "w_out"), got, want):
        assert g.dtype == w.dtype and rel(g, w) < 2 * tol, name


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_walk_visits_every_tile_and_every_group(routing):
    """``group_tiles``: a static number of steps, ``tiles + groups - 1``;
    the live ones walk the groups in order, every group at least once
    and every row tile at least once, a tile two groups share once for
    each, and the padding repeats the last pair (no block moves)."""
    sizes = np.asarray(ROUTINGS[routing])
    offsets, group_of, tile_of, visits = (np.asarray(a) for a in
                                          G.group_tiles(
        jnp.asarray(sizes, jnp.int32), ROWS, TILE))
    tiles, groups = ROWS // TILE, len(sizes)
    assert group_of.shape == tile_of.shape == (tiles + groups - 1,)
    assert list(offsets) == [0] + list(np.cumsum(sizes))
    live = int(visits[0])
    assert tiles <= live <= tiles + groups - 1
    assert np.all(np.diff(group_of) >= 0) and np.all(np.diff(tile_of) >= 0)
    assert set(group_of[:live]) == set(range(groups))
    assert set(tile_of[:live]) == set(range(tiles))
    assert np.all(group_of[live:] == group_of[live - 1])
    assert np.all(tile_of[live:] == tile_of[live - 1])
    # a group's visits cover its rows
    for group in range(groups):
        mine = tile_of[:live][group_of[:live] == group]
        for row in range(offsets[group], offsets[group + 1]):
            assert row // TILE in mine, (group, row)


def test_rows_the_tile_does_not_divide_are_refused():
    with pytest.raises(NotImplementedError):
        G.group_tiles(jnp.asarray([3, 4], jnp.int32), 20, 16)


def test_the_work_functions_count_a_product_once():
    flops, bytes_ = G.gmm_work(12288, 2688, 1856, 8, 2)
    assert flops == 2.0 * 12288 * 2688 * 1856
    assert bytes_ == 2.0 * (12288 * 2688 + 8 * 2688 * 1856 + 12288 * 1856)


def test_the_roofline_share_is_data_for_the_reader_that_is_there():
    """``train.moe_gmm_roofline`` is data alone: the reader the flash
    kernels' share uses, both of these kernels, the MXU's peak."""
    from benchmarks import manifest as mf
    from benchmarks.readers import kernels

    manifest = mf.Manifest()
    spec = manifest.metric_file("train.moe_gmm_roofline")
    accepted = manifest.metric_file("train.flash_fwd_roofline")
    assert mf.resolve(spec["reader"]) is kernels.kernel_peak_pct_per_event
    assert spec["args"] == dict(accepted["args"],
                                kernels=["moe_gmm", "moe_tgmm"])
    for name in spec["args"]["kernels"]:
        assert callable(getattr(G, name))
    entry = [m for m in manifest.doc["per_layer"]
             if m["name"] == spec["name"]]
    # the cells whose experts run the kernels
    assert entry[0]["workloads"] == ["nemotron3_nano_ep16_s8k",
                                     "sdar_30b_a3b_ep8_s8k",
                                     "kimi_linear_48b_a3b_ep32_s16k",
                                     "smallthinker_21b_a3b_ep4_s16k"]
