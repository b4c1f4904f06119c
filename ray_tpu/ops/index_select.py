"""A lightning indexer's two steps (DeepSeek-V3.2-Exp's sparse attention):
score every cached position for a query, then choose EXACTLY the ``kept``
largest. Attention over the choice is the latent layer's own
(``models/kv_cache.py:attend_latent``, which takes the choice as a mask).

``I[t, s] = sum_h w[t, h] x ReLU(q[t, h] . k[s])``: ``Hi`` heads of ``Di``
channels against ONE key a position that the heads share, the weights a
token and head in float32. The products' sums are float32 and so are the
scores: the choice is made on float32 numbers, as the plain reference makes
it.

The choice is exact and never a sort. A float32 score is mapped to an
unsigned integer of the same order, and the ``kept``-th largest of a row is
found digit by digit from the top (``BITS`` bits a pass: 2^BITS - 1
candidate thresholds, a count of the row's keys at or above each). A row's
choice is then every key above that value and, of the keys equal to it, the
lowest positions that fill the count: a tie goes to the lower position.
``jax.lax.approx_max_k`` is an approximate choice and a different result;
``jax.lax.top_k`` of 2,048 among tens of thousands is a sort on the chip.

``scores`` and ``chosen`` are XLA's: the full forward's, any backend's but
the TPU, and the oracle of the two kernels below, which are what a cached
forward runs on the chip (``models/kv_cache.py`` decides, by the platform
alone). ``scores_in_place`` keeps what is a head wide in VMEM and visits only
blocks of the ``"index"`` leaf that hold a key a query may see, read where
they lie: a live slot's filled blocks in a decode step, a tile of queries'
visible blocks in a chunk. What no step visited holds NOTHING (it may hold
a NaN): every reader masks by what a row sees before it compares.
``kth_largest`` keeps a tile of rows' ordered keys in VMEM while it finds
each row's ``kept``-th largest a bit at a time, counting up to the tile's
last visible block; ``chosen`` then makes its set from that threshold, so
the set is the one it finds alone, bit for bit.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.decode_attention import TILE

# bits of the key decided a pass of ``chosen``: 32 / BITS passes, each
# 2^BITS - 1 compares an element
BITS = 4
# ``scores_in_place``: positions a block of keys holds against a chunk's tile
# of ``QUERIES`` tokens (their [QUERIES x Hi, POSITIONS] float32 products are
# what VMEM holds a head wide), and against a decode step's one query a slot
# (a grid step that visits nothing still costs its third of a microsecond,
# and a slot has S / STEP_POSITIONS of them)
POSITIONS = 512
QUERIES = 64
STEP_POSITIONS = 8192
# ``kth_largest``: rows of a tile (their keys, [ROWS, width] int32, stay in
# VMEM through the 32 counts) and positions counted a turn of its loop. On
# the chip a turn costs some hundred cycles whatever it holds (PERF.md, PR
# 58: 2,048 rows over 16,384 in 8.9 ms at 16 x 512, 1.1 ms at 128 x 4096)
ROWS = 128
COUNTED = 4096


class Indexed(NamedTuple):
    """A latent layer's lightning indexer at the tokens of one call: the
    index queries [B, T, Hi, Di], their weights a head [B, T, Hi] float32,
    the tokens' own keys [B, T, Di] as the cache holds them (rotated), and
    how many positions a query's attention keeps."""
    q: jax.Array
    weights: jax.Array
    key: jax.Array
    kept: int


def _ordered(x):
    """float32 -> uint32 in the same order (-0.0 as 0.0; no NaN)."""
    x = jnp.where(x == 0, 0.0, x).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(
        key ^ jnp.int32(-0x80000000), jnp.uint32)


def chosen(scores, visible, kept: int, prefix=None):
    """``scores`` [.., S] float32, ``visible`` [.., S] bool (the positions a
    row's query may see: at least one) -> [.., S] bool, True at the
    ``min(kept, visible positions)`` largest visible scores of each row, a
    tie to the lower position. Exact: the count of True in a row is that
    minimum, no more and no fewer. ``prefix`` [.., 1] uint32: each row's
    ``kept``-th largest key where ``kth_largest`` found it already (None:
    found here, ``BITS`` bits a pass over the whole row)."""
    with jax.named_scope("mla.select"):
        u = jnp.where(visible, _ordered(scores), jnp.uint32(0))
        k = jnp.minimum(visible.sum(-1, dtype=jnp.int32), kept)[..., None]
        if prefix is None:
            prefix = jnp.zeros(u.shape[:-1] + (1,), jnp.uint32)
            digits = jnp.arange(1, 1 << BITS, dtype=jnp.uint32)
            for shift in range(32 - BITS, -1, -BITS):
                # the row's keys at or above each candidate [.., 2^BITS - 1]:
                # the largest digit that still leaves k of them
                candidates = prefix | (digits << shift)
                counts = (u[..., None, :] >= candidates[..., None]).sum(
                    -1, dtype=jnp.int32)
                digit = (counts >= k).sum(-1, dtype=jnp.uint32)[..., None]
                prefix = prefix | (digit << shift)
        above = u > prefix
        ties = (u == prefix) & visible
        room = k - above.sum(-1, dtype=jnp.int32, keepdims=True)

        def lowest(_):      # the first ``room`` of a row's ties, by position
            return ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room)

        # the ties of nearly every row are the one key itself
        ties = jax.lax.cond(
            jnp.any(ties.sum(-1, dtype=jnp.int32, keepdims=True) > room),
            lowest, lambda _: ties, None)
        return above | ties


def _weighted(weights, s):
    """``sum_h w[.., h] x ReLU(s[.., h, :])``, on the vector unit in
    float32: as a product it would round both to bfloat16 on the chip."""
    return (weights[..., None] * jax.nn.relu(s)).sum(-2)


def scores(q, weights, keys):
    """The index scores of a few tokens a slot against ALL of its keys: q
    [B, T, Hi, Di], ``weights`` [B, T, Hi] float32, ``keys`` [B, S, Di] ->
    [B, T, S] float32. XLA's, and the oracle's."""
    with jax.named_scope("mla.index"):
        s = jnp.einsum("bthd,bsd->bths", q, keys.astype(q.dtype),
                       preferred_element_type=jnp.float32)
        return _weighted(weights, s)


# -------------------------------------------------- the scores, inside VMEM
def block_of(S: int, most: int) -> int:
    """The longest block of whole lane tiles, ``most`` positions at most,
    that divides a cache of S (a test's ``most`` under a tile: of that; S
    itself where none does)."""
    step = min(TILE, most)
    return max([n for n in range(step, most + 1, step) if S % n == 0] or [S])


def _scores_kernel(at_ref, slot_ref, tile_ref, count_ref, q_ref, w_ref,
                   k_ref, o_ref, *, heads: int):
    """One tile's queries [tq x Hi, Di] against one block of keys [bs, Di]:
    the products, their ReLU, the weights and the sum over the heads, all
    here; [tq, bs] leaves. A grid row past the working ones, and a step past
    the row's last block, computes nothing."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i < at_ref[1]) & (j < count_ref[i]))
    def _():
        q = q_ref[...]
        s = jax.lax.dot_general(
            q, k_ref[...].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [tq x Hi, bs]
        s = w_ref[...] * jnp.maximum(s, 0.0)
        o_ref[...] = s.reshape(-1, heads, s.shape[-1]).sum(1)


def scores_in_place(q, weights, leaf, layer, slot, tile, count, working, *,
                    block: int, width=None, interpret: bool = False):
    """Tiles of queries, each against its own slot's first blocks of keys of
    layer ``layer`` of ``leaf`` [L, B, S, Di], read where they lie: q [N, tq,
    Hi, Di], ``weights`` [N, tq, Hi] float32 -> [N, tq, width] float32 (None:
    S). Grid row i of N: tile ``tile[i]`` of q and of the result, the keys of
    slot ``slot[i]``, its first ``count[i]`` blocks of ``block`` positions;
    only the first ``working`` rows compute. The index maps hold a step that
    visits nothing on the block the step before it held, so it moves nothing:
    give the rows past ``working`` the last working row's numbers. What no
    step visited is NOT written."""
    N, tq, Hi, Di = q.shape
    S, bs = leaf.shape[-2], block
    nk = (width or S) // bs

    def held(i, j, at, count):
        last = jnp.maximum(count[i], 1) - 1
        return jnp.where(i < at[1], jnp.minimum(j, last), last)

    rows = lambda last: pl.BlockSpec(                        # noqa: E731
        (None, tq * Hi, last),
        lambda i, j, at, slot, tile, count: (tile[i], 0, 0))
    call = pl.pallas_call(
        functools.partial(_scores_kernel, heads=Hi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N, nk),
            in_specs=[
                rows(Di), rows(1),
                pl.BlockSpec(
                    (None, None, bs, Di),
                    lambda i, j, at, slot, tile, count: (
                        at[0], slot[i], held(i, j, at, count), 0))],
            out_specs=pl.BlockSpec(
                (None, tq, bs), lambda i, j, at, slot, tile, count: (
                    tile[i], 0, held(i, j, at, count))),
        ),
        out_shape=jax.ShapeDtypeStruct((N, tq, nk * bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the products and their weighted ReLU, the keys and the result
            # twice, the queries and their weights (a lane each) twice
            vmem_limit_bytes=3 * tq * Hi * bs * 4 + 4 * bs * Di * 4
            + 4 * tq * Hi * (Di + TILE) * 4 + (16 << 20)),
        name="index_scores",
        interpret=interpret,
    )
    with jax.named_scope("mla.index"):
        return call(
            jnp.stack([jnp.asarray(layer, jnp.int32).reshape(()),
                       jnp.asarray(working, jnp.int32).reshape(())]),
            slot.astype(jnp.int32), tile.astype(jnp.int32),
            count.astype(jnp.int32), q.reshape(N, tq * Hi, Di),
            weights.astype(jnp.float32).reshape(N, tq * Hi, 1), leaf)


def scores_of_step(q, weights, leaf, layer, lens, live, *,
                   interpret: bool = False):
    """A decode step's scores: each LIVE slot's one query against the keys
    it has filled (positions ``< lens[b]``) of layer ``layer`` of ``leaf``
    [L, B, S, Di]: q [B, Hi, Di], ``weights`` [B, Hi] float32, ``live``
    ``decode_attention.live_slots``' [B + 1] -> [B, S] float32. A slot that
    is not live and a block past a slot's length cost no read and no
    product, and their places hold nothing."""
    B, S = leaf.shape[1:3]
    bs = block_of(S, STEP_POSITIONS)
    v = jnp.arange(B)
    row = jnp.clip(jnp.minimum(v, live[B] - 1), 0)
    slot = live[row]
    return scores_in_place(
        q[:, None], weights[:, None], leaf, layer, slot, slot,
        jnp.minimum((lens[slot] + bs - 1) // bs, S // bs), live[B],
        block=bs, interpret=interpret)[:, 0]


def _tiling(T: int, S: int):
    """(queries a tile, positions a block) of a chunk of T tokens against a
    cache of S."""
    return (next(n for n in (QUERIES, 16, 8, T) if T % n == 0),
            block_of(S, POSITIONS))


def scores_of_block(q, weights, leaf, layer, start, *, width=None,
                    interpret: bool = False):
    """The index scores of one slot's block of tokens at positions ``start
    ..`` against the keys of layer ``layer`` of ``leaf`` [L, 1, S, Di] that
    each tile of ``QUERIES`` tokens (or as many as divide T) sees, their own
    among them: q [T, Hi, Di], ``weights`` [T, Hi] float32 -> [T, width]
    float32 (None: S; a multiple of ``POSITIONS`` for a caller that knows
    ``start + T`` lies within it). The positions past a tile's last visible
    block hold nothing."""
    T, Hi, Di = q.shape
    S = leaf.shape[-2]
    tq, bs = _tiling(T, S)
    N = T // tq
    tile = jnp.arange(N)
    last = start + (tile + 1) * tq - 1      # a tile's last token's position
    return scores_in_place(
        q.reshape(N, tq, Hi, Di), weights.reshape(N, tq, Hi), leaf, layer,
        jnp.zeros_like(tile), tile,
        jnp.minimum(last // bs + 1, (width or S) // bs), N, block=bs,
        width=width, interpret=interpret).reshape(T, -1)


# ------------------------------------------- the kept-th largest, inside VMEM
def _kth_kernel(count_ref, s_ref, pos_ref, o_ref, u_sc, *, kept: int,
                n: int):
    """One tile of rows: their scores become ordered keys in ``u_sc`` (the
    positions a row does not see the least key of all), then the largest
    threshold that ``min(kept, visible)`` of a row's keys reach is built
    from the top bit down, each bit one count over the tile's first
    ``count`` blocks of ``n`` positions."""
    rows, width = s_ref.shape
    blocks = count_ref[pl.program_id(0)]
    pos = pos_ref[...]                                          # [rows, 1]
    low = jnp.int32(-0x80000000)

    def keyed(b, _):
        at = pl.ds(pl.multiple_of(b * n, n), n)
        x = s_ref[:, at]
        bits = pltpu.bitcast(jnp.where(x == 0, 0.0, x), jnp.int32)
        # signed, in the scores' order: ``_ordered`` with the top bit turned
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
        col = b * n + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
        u_sc[:, at] = jnp.where(col <= pos, key, low)

    jax.lax.fori_loop(0, blocks, keyed, None)
    k = jnp.minimum(jnp.minimum(pos + 1, width), kept)

    def decided(step, prefix):
        # as unsigned numbers; compared as signed ones with the top bit turned
        candidate = prefix | jnp.left_shift(jnp.int32(1), 31 - step)
        least = candidate ^ low

        def counted(b, total):
            at = pl.ds(pl.multiple_of(b * n, n), n)
            reach = (u_sc[:, at] >= least).astype(jnp.int32)
            # a block's lane tiles onto one, pair by pair: a long block a
            # turn and still a carry of one tile a row
            tiles = [reach[:, c:c + TILE] for c in range(0, n, TILE)]
            while len(tiles) > 1:
                tiles = [a + b for a, b in zip(tiles[::2], tiles[1::2])
                         ] + tiles[len(tiles) & ~1:]
            return total + tiles[0]

        reach = jax.lax.fori_loop(
            0, blocks, counted, jnp.zeros((rows, min(n, TILE)), jnp.int32)
        ).sum(-1, keepdims=True)
        return jnp.where(reach >= k, candidate, prefix)

    o_ref[...] = jax.lax.fori_loop(
        0, 32, decided, jnp.zeros((rows, 1), jnp.int32))


def kth_largest(scores, last, kept: int, *, interpret: bool = False):
    """Each row's ``min(kept, visible)``-th largest key among the positions
    ``<= last[row]`` it sees: ``scores`` [R, W] float32 (what lies past a
    row's ``last`` is never compared: it may hold anything), ``last`` [R]
    int32 -> [R, 1] uint32, ``chosen``'s ``prefix``. ``ROWS`` rows at a time
    (or as many as divide R) hold their keys in VMEM through all 32
    counts, each over the blocks of ``COUNTED`` positions up to the one that
    holds the tile's largest ``last``."""
    R, W = scores.shape
    rows = next(n for n in (ROWS, 8, R) if R % n == 0)
    n = block_of(W, COUNTED)
    pos = jnp.minimum(last.astype(jnp.int32), W - 1)
    call = pl.pallas_call(
        functools.partial(_kth_kernel, kept=kept, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R // rows,),
            in_specs=[pl.BlockSpec((rows, W), lambda i, count: (i, 0)),
                      pl.BlockSpec((rows, 1), lambda i, count: (i, 0))],
            out_specs=pl.BlockSpec((rows, 1), lambda i, count: (i, 0)),
            scratch_shapes=[pltpu.VMEM((rows, W), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=3 * rows * W * 4 + (16 << 20)),
        name="index_kth_largest",
        interpret=interpret,
    )
    with jax.named_scope("mla.select"):
        return jax.lax.bitcast_convert_type(call(
            pos.reshape(-1, rows).max(-1) // n + 1, scores, pos[:, None]),
            jnp.uint32)


def chosen_up_to(scores, last, kept: int, *, interpret: bool = False):
    """``chosen`` for rows that each see the positions ``<= last[row]``
    (``scores`` [R, W], ``last`` [R]), the threshold from ``kth_largest``."""
    visible = jnp.arange(scores.shape[-1])[None, :] <= last[:, None]
    return chosen(scores, visible, kept,
                  kth_largest(scores, last, kept, interpret=interpret))


def positions_read(start: int, T: int, S: int) -> int:
    """Positions of a slot's keys that the kernels visit for T tokens at
    ``start ..`` in a cache of S, on the host: whole blocks, a tile of
    queries' counted once a query (a decode step, T = 1: the filled blocks
    and the token's own key beside them)."""
    if T == 1:
        bs = block_of(S, STEP_POSITIONS)
        return min(-(-start // bs) * bs, S) + 1
    tq, bs = _tiling(T, S)
    return sum(tq * min(((start + first + tq - 1) // bs + 1) * bs, S)
               for first in range(0, T, tq))
