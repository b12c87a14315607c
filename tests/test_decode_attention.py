"""``ops.decode_attention``: the decode step's attention as one Mosaic call
that walks each slot's tiles of the cache up to its newest row (interpreted
here), against the masked product over every row that
``transformer._attention_step`` keeps for ``use_flash=False``."""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import decode_attention

module = importlib.import_module("ray_tpu.ops.decode_attention")

HEADS = {"28_over_4x128": (28, 4, 128), "32_over_8x64": (32, 8, 64)}


def _spread(q, G):
    """``q`` [S, H, D] laid over the K/V heads' lanes, as ``_attention_step``
    lays it."""
    S, H, D = q.shape
    own = (jnp.arange(H)[:, None] // (H // G)
           == jnp.arange(G)[None])[None, :, :, None]
    return jnp.where(own, q[:, :, None, :], 0).reshape(S, H, G * D)


def _masked(spread, k, v, layer, newest, scale):
    T = k.shape[2]
    s = jnp.einsum("shc,stc->sht", spread, k[layer],
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(T)[None] <= newest[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
    # a dead row's weight is an exact 0: leave its v out as the kernel does
    return jnp.einsum("sht,stc->shc", p.astype(v.dtype),
                      jnp.where(seen[:, :, None], v[layer], 0))


def _case(heads, S, T, n=2, dtype=jnp.float32, seed=0):
    H, G, D = HEADS[heads]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (_spread(jax.random.normal(ks[0], (S, H, D), dtype), G),
            jax.random.normal(ks[1], (n, S, T, G * D), dtype),
            jax.random.normal(ks[2], (n, S, T, G * D), dtype),
            1 / math.sqrt(D))


def _newest(lengths, T):
    return jnp.minimum(jnp.asarray(lengths, jnp.int32), T - 1)


@pytest.fixture
def tiles_of(monkeypatch):
    def set_rows(rows):
        monkeypatch.setattr(module, "_ROWS", rows)
    return set_rows


def test_a_tile_is_a_divisor_of_the_cache_in_eights_of_rows():
    assert module.tile_rows(13312) == 512 == module.tile_rows(4096)
    assert module.tile_rows(1408) == 352           # 4 x 352: no power of two
    assert module.tile_rows(40) == 40 and module.tile_rows(13000) == 200
    for T in (13312, 1408, 13000, 48, 24):
        assert T % module.tile_rows(T) == 0 == module.tile_rows(T) % 8
    # a slot reads the tiles up to its newest row's, the first at the least
    newest = np.array([0, 351, 352, 1407])
    np.testing.assert_array_equal(module.read_rows(newest, 1408),
                                  [352, 352, 704, 1408])
    assert ((newest + 1 <= module.read_rows(newest, 1408))
            & (module.read_rows(newest, 1408) <= 1408)).all()
    # rows that are no multiple of 8 cannot be cut into tiles: all are read
    assert module.tile_rows(7) is None and module.tile_rows(1001) is None
    assert module.read_rows(3, 1001) == 1001


def test_without_the_kernel_or_without_a_tile_it_is_the_masked_product():
    """``use_kernel=False``, and a cache of 7 rows under ``use_kernel=True``,
    take no Mosaic call and give the masked product."""
    for T, use_kernel in ((64, False), (7, True)):
        spread, k, v, scale = _case("32_over_8x64", 3, T)
        newest = _newest([0, 3, T + 2], T)

        def attend(l):
            return decode_attention(spread, k, v, l, newest, scale,
                                    use_kernel=use_kernel)

        assert "pallas_call" not in str(jax.make_jaxpr(attend)(1))
        np.testing.assert_allclose(
            attend(1), _masked(spread, k, v, 1, newest, scale), atol=2e-5)


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_the_walk_is_the_masked_product_at_every_length(heads, tiles_of):
    """Lengths 0, 1, one under a tile's edge, on it, one over, the cache's
    last row and past it (a ring that has filled): a slot each, layer 1 of
    two, the index traced."""
    tiles_of(16)
    T = 64
    lengths = [0, 1, 14, 15, 16, T - 1, T + 5]
    spread, k, v, scale = _case(heads, len(lengths), T)
    assert module.tile_rows(T) == 16
    newest = _newest(lengths, T)
    got = jax.jit(lambda l: decode_attention(spread, k, v, l, newest,
                                             scale))(1)
    assert got.shape == spread.shape and got.dtype == spread.dtype
    np.testing.assert_allclose(got, _masked(spread, k, v, 1, newest, scale),
                               atol=2e-4)
    # layer 0 holds other rows
    assert float(jnp.abs(got - _masked(spread, k, v, 0, newest,
                                       scale)).max()) > 1e-2


def test_a_tile_that_is_no_power_of_two(tiles_of):
    """352 rows in tiles of 88, as 1,408 are walked in tiles of 352."""
    tiles_of(100)
    T = 352
    assert module.tile_rows(T) == 88
    lengths = [0, 87, 88, 200, T - 1]
    spread, k, v, scale = _case("32_over_8x64", len(lengths), T, n=1)
    newest = _newest(lengths, T)
    np.testing.assert_allclose(
        decode_attention(spread, k, v, 0, newest, scale),
        _masked(spread, k, v, 0, newest, scale), atol=2e-4)


def test_bfloat16_rounds_where_the_masked_product_rounds(tiles_of):
    """Operands as they are, float32 scores and sums, ``p`` rounded before
    ``p V``: within bfloat16's step of the masked product in bfloat16, and
    of the float32 result."""
    tiles_of(16)
    T = 64
    lengths = [3, 16, 40, T + 1]
    spread, k, v, scale = _case("28_over_4x128", len(lengths), T,
                                dtype=jnp.bfloat16)
    newest = _newest(lengths, T)
    got = decode_attention(spread, k, v, 1, newest, scale)
    assert got.dtype == jnp.bfloat16
    exact = _masked(*(a.astype(jnp.float32) for a in (spread, k, v)), 1,
                    newest, scale)
    np.testing.assert_allclose(got.astype(jnp.float32), exact, atol=3e-2)
    np.testing.assert_allclose(
        got.astype(jnp.float32),
        _masked(spread, k, v, 1, newest, scale).astype(jnp.float32),
        atol=3e-2)


def test_a_slot_reads_its_own_rows_whatever_its_neighbours_hold(tiles_of):
    """The middle slot's output is the same to the bit when its neighbours'
    lengths change, and nothing a row past a slot's length holds reaches its
    output: not a NaN in K or in V, in the last live tile or in a dead
    one."""
    tiles_of(16)
    T = 64
    spread, k, v, scale = _case("32_over_8x64", 3, T)
    mine = 20
    outs = [decode_attention(spread, k, v, 1, _newest(lengths, T), scale)
            for lengths in ([0, mine, 63], [40, mine, 5], [15, mine, 16])]
    for other in outs[1:]:
        np.testing.assert_array_equal(outs[0][1], other[1])
    assert float(jnp.abs(outs[0][0] - outs[1][0]).max()) > 1e-3
    newest = _newest([5, mine, 31], T)
    dead = jnp.arange(T)[None, :, None] > newest[:, None, None]  # [S, T, 1]
    poisoned = decode_attention(
        spread, jnp.where(dead[None], jnp.nan, k),
        jnp.where(dead[None], jnp.nan, v), 1, newest, scale)
    assert bool(jnp.all(jnp.isfinite(poisoned)))
    np.testing.assert_array_equal(
        poisoned, decode_attention(spread, k, v, 1, newest, scale))
