"""``ops/block_attention.py:selected_block_attention`` alone, through the
interpreter at toy sizes (blocks and sub-tiles of 32), against a plain
float32 softmax over each query's chosen positions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import block_attention

H, R, DN, DR, DV, S, BS = 2, 24, 16, 8, 16, 256, 32
SCALE = 0.37


@pytest.fixture(autouse=True)
def blocks_of_32(monkeypatch):
    monkeypatch.setattr(block_attention, "SELECTED_POSITIONS", BS)


def _inputs(T, seed=0, dtype=jnp.float32, S=S):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)  # noqa: E731
    return (draw(T, H, DN + DR), draw(R, H, DN + DV) * R ** -0.5,
            draw(2, 1, 1, R + DR, S))


def _causal(start, T, width, rng, share=0.4):
    """A choice as an indexer's: causal, every row at least one position."""
    pos = start + np.arange(T)[:, None]
    seen = np.arange(width)[None, :] <= pos
    picked = seen & (rng.random((T, width)) < share)
    picked[np.arange(T), np.minimum(pos[:, 0], width - 1)] |= ~picked.any(1)
    return picked


def _plain(q, up, cache, picked, layer):
    """Float32 all the way: a head's keys and values of every position, the
    masked softmax over them."""
    q, up = np.asarray(q, np.float32), np.asarray(up, np.float32)
    rows = np.asarray(cache, np.float32)[layer, 0, 0]
    width = picked.shape[1]
    c, kr = rows[:R, :width], rows[R:, :width]
    kn = np.einsum("rhd,rs->hds", up[..., :DN], c)
    v = np.einsum("rhd,rs->hds", up[..., DN:], c)
    sc = (np.einsum("thd,hds->hts", q[..., :DN], kn)
          + np.einsum("thd,ds->hts", q[..., DN:], kr)) * SCALE
    sc = np.where(picked[None], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    return np.einsum("hts,hds->thd", p / p.sum(-1, keepdims=True), v)


def _kernel(q, up, cache, picked, layer, start):
    return np.asarray(block_attention.selected_block_attention(
        q, up, cache, jnp.asarray(picked), jnp.int32(layer),
        jnp.int32(start), scale=SCALE,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan")))


# T of every ratio to the block (under it, one sub-tile, two, four; 48: no
# whole sub-tiles, so all rows at once), ``start`` on a block's edge and off
@pytest.mark.parametrize("T, start, width", [
    (16, 0, 64), (16, 40, 64), (32, 32, 64), (32, 7, 128), (48, 16, 64),
    (64, 64, 128), (64, 50, 128), (128, 0, 128), (128, 96, 256),
    (128, 77, 256), (128, 128, 256)])
def test_a_chunk_attends_what_each_token_chose(T, start, width):
    q, up, cache = _inputs(T, seed=T + start)
    picked = _causal(start, T, width, np.random.default_rng(start))
    got = _kernel(q, up, cache, picked, 1, start)
    np.testing.assert_allclose(
        got, _plain(q, up, cache, picked, 1), rtol=2e-5, atol=2e-5)


def test_bfloat16_rounds_keys_values_and_probabilities_and_nothing_else():
    T, start, width = 128, 64, 256
    q, up, cache = _inputs(T, seed=3, dtype=jnp.bfloat16)
    picked = _causal(start, T, width, np.random.default_rng(3))
    got = _kernel(q, up, cache, picked, 0, start).astype(np.float32)
    want = _plain(q, up, cache, picked, 0)
    assert np.abs(got - want).max() < 0.03 * np.abs(want).max()


@pytest.mark.parametrize("start", [0, 64, 90])
def test_a_row_whose_first_choice_lies_in_its_last_block(start):
    """Every block before it is all masked for the row: the 1s those blocks
    add (``exp(NEG_INF - NEG_INF)``) are wiped when the first chosen
    position comes; its neighbours choose early ones."""
    T, width = 64, 256
    q, up, cache = _inputs(T, seed=5)
    picked = _causal(start, T, width, np.random.default_rng(5))
    for t in (0, 31, 32, 63):
        picked[t] = False
        picked[t, (start + t) // BS * BS:start + t + 1] = True
    got = _kernel(q, up, cache, picked, 1, start)
    np.testing.assert_allclose(
        got, _plain(q, up, cache, picked, 1), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("start", [0, 96])
def test_a_row_that_chose_its_own_position_alone_reads_its_own_value(start):
    T, width = 64, 256
    q, up, cache = _inputs(T, seed=6)
    picked = _causal(start, T, width, np.random.default_rng(6))
    picked[[3, 40]] = False
    picked[[3, 40], [start + 3, start + 40]] = True
    got = _kernel(q, up, cache, picked, 0, start)
    rows = np.asarray(cache, np.float32)[0, 0, 0, :R]
    for t in (3, 40):
        np.testing.assert_allclose(got[t], np.einsum(
            "rhd,r->hd", np.asarray(up)[..., DN:], rows[:, start + t]),
            rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        got, _plain(q, up, cache, picked, 0), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T, real", [(64, 40), (128, 65), (16, 9)])
def test_a_padded_row_with_nothing_chosen_is_finite_and_moves_no_other(
        T, real):
    start, width = 32, 256
    q, up, cache = _inputs(T, seed=7)
    picked = _causal(start, T, width, np.random.default_rng(7))
    whole = _kernel(q, up, cache, picked, 1, start)
    picked[real:] = False
    got = _kernel(q, up, cache, picked, 1, start)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[:real], whole[:real])


@pytest.mark.parametrize("T, start", [(128, 0), (128, 64), (64, 32)])
def test_a_sub_tile_above_the_diagonal_is_not_computed(T, start):
    """A choice planted where no row of a sub-tile may see (which an
    indexer's never holds) changes nothing: those tiles are skipped, not
    masked."""
    width = 256
    q, up, cache = _inputs(T, seed=8)
    picked = _causal(start, T, width, np.random.default_rng(8))
    want = _kernel(q, up, cache, picked, 0, start)
    last = start + (np.arange(T) // BS + 1) * BS - 1   # a sub-tile's last row
    block = np.arange(width) // BS * BS                # a block's first
    planted = picked | (block[None, :] > last[:, None])
    assert planted.sum() > picked.sum()
    np.testing.assert_array_equal(
        _kernel(q, up, cache, planted, 0, start), want)


@pytest.mark.parametrize("width", [32, 64, 128, 256])
def test_every_width_that_holds_the_chunk_gives_the_same_rows(width):
    """``kv_cache._attend_chosen``'s branches: the grid covers the choice's
    width, a block past the chunk's last is not visited."""
    T, start = 16, 8
    q, up, cache = _inputs(T, seed=9)
    picked = _causal(start, T, 256, np.random.default_rng(9))
    got = _kernel(q, up, cache, picked[:, :width], 1, start)
    np.testing.assert_allclose(
        got, _plain(q, up, cache, picked, 1), rtol=2e-5, atol=2e-5)


def test_a_chunk_that_reaches_the_caches_end():
    T, start = 64, S - 64
    q, up, cache = _inputs(T, seed=10)
    picked = _causal(start, T, S, np.random.default_rng(10))
    np.testing.assert_allclose(
        _kernel(q, up, cache, picked, 0, start),
        _plain(q, up, cache, picked, 0), rtol=2e-5, atol=2e-5)


def test_a_choice_of_no_whole_blocks_and_a_ragged_chunk_are_refused():
    q, up, cache = _inputs(16)
    with pytest.raises(ValueError, match="whole blocks"):
        _kernel(q, up, cache, np.ones((16, 48), bool), 0, 0)
    with pytest.raises(ValueError, match="XLA path"):
        _kernel(q[:8], up, cache, np.ones((8, 64), bool), 0, 0)


@pytest.mark.parametrize("start, T, S, want", [
    # a first chunk: 4 + 3 + 2 + 1 of 16
    (0, 2048, 33280, (16, 10)),
    # the median prompt's last chunk: 24 blocks, the last four a diagonal
    (10240, 2048, 33280, (96, 90)),
    # a bucket of 1,024 on a block's edge, and off it (the first sub-tile
    # ends 508 positions into block 9 and sees nothing of block 10)
    (4096, 1024, 33280, (20, 19)), (4100, 1024, 33280, (22, 21)),
    # one sub-tile: every visit is computed
    (2048, 16, 33280, (5, 5)),
    # the cache's end
    (32768, 512, 33280, (65, 65))])
def test_the_tiles_a_chunk_computes_counted_on_the_host(
        monkeypatch, start, T, S, want):
    monkeypatch.setattr(block_attention, "SELECTED_POSITIONS", 512)
    assert block_attention.selected_tiles(start, T, S) == want
