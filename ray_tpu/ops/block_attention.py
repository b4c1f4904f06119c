"""A block of tokens' attention over the cache as one pallas TPU kernel.

A prefill chunk (T tokens of one slot, at positions ``start .. start + T -
1``) attends the positions of its cache ``[L, 1, KV, D, S]``
(``models/kv_cache.py``) that are filled, its own columns among them: the
caller has put those in place (``kv_cache._place``), so every key comes from
the cache as it lies, position-minor. ``block_attention`` reads the WHOLE
cache, as ``ops/decode_attention.py`` does and for its reason (an operand of
a custom call is a whole array: a layer's slice cut by the scan would be
copied for it); the layer index and ``start`` ride as scalar-prefetch
arguments and steer which blocks of positions the pipeline fetches.

A tile is ``tq`` tokens of one kv head with the G query heads that share it,
``G x tq`` rows, against one block of ``bs`` positions: ``q [G tq, D] @ k [D,
bs]``, online softmax in float32, ``p [G tq, bs]`` against ``v [D, bs]``
contracted over positions, both products accumulated in float32 and the
probabilities in the cache's dtype for the second. A tile visits only the
blocks that hold a position one of its tokens may see: ``[0, start + t]`` in
a full layer; in a window layer (the cache a ring of S positions, position p
at ``p mod S``) the ``window - 1`` before each token and itself, round the
ring from the block that holds the first token's first position. A grid step
past a tile's last block fetches nothing (its block index is the last one's)
and computes nothing. Masks are position compares inside the kernel: no
``[.., T, S]`` array exists outside it. A ring's block met twice (the window's
two ends in it) is masked each time to its own end, which is sound where the
ring holds the window and the block, ``S >= window + T``: a slot ``p mod S``
then holds position p for every p a token of the block may see.

``selected_block_attention`` is the chunk of a LATENT layer whose tokens
each chose what they attend (``models/kv_cache.py:_attend_chosen``): one head
a grid row, all T queries against one block of the cache's rows a step, the
block up-projected to the head's keys and values in VMEM, the choice an int8
tile beside it. Every row of a visible block is read and up-projected; a
position a query did not choose is masked out of its softmax; and of a
block in the chunk's own span only the sub-tiles of queries that can see it
are computed (``selected_tiles`` counts both on the host).

Tile sizes follow the shapes given (``tiles``, ``_selected_tiling``), nothing
else; S is a multiple of the 128 lanes and T of ``TOKENS``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.decode_attention import NEG_INF, TILE

TOKENS = 16             # a tile's fewest tokens: a bfloat16 tile's sublanes
ROWS = 2048             # query rows of a tile: tokens x the heads that share
BLOCK_BYTES = 1 << 18   # of one K (or V) block of positions in VMEM
SCORES = 1 << 21        # float32 scores of a tile against a block (8 MiB)


def tiles(T: int, G: int, D: int, S: int, itemsize: int):
    """(tokens a tile, positions a block) for T tokens of G query heads a
    kv head against a cache ``[.., D, S]``: blocks as long as a head's fit
    ``BLOCK_BYTES``, tiles of up to ``ROWS`` rows whose scores against a
    block fit ``SCORES``; both powers of two (a row's token is read off its
    index by a mask, a position's block by a shift). Swept on the chip at
    the serving cells' shapes (PERF.md, PR 48): longer blocks and taller
    tiles were faster or the same everywhere, up to what VMEM holds."""
    bs = TILE
    while S % (2 * bs) == 0 and D * 2 * bs * itemsize <= BLOCK_BYTES:
        bs *= 2
    tq = TOKENS
    while T % (2 * tq) == 0 and G * 2 * tq <= min(ROWS, SCORES // bs):
        tq *= 2
    return tq, bs


def _visits(start, i, *, tq: int, bs: int, S: int, window):
    """Of tile ``i`` of a block of tokens that starts at ``start``: (the
    first block of positions it visits, counted from position 0; how many
    blocks it visits)."""
    p0 = start + i * tq
    p1 = p0 + tq - 1
    # x >> shift is x // bs: a floor division costs the scalar core, and
    # the lowering of each index map at every start-up, several times this
    shift = bs.bit_length() - 1
    if window is None:        # a token past the end sees the whole cache
        return 0, (jnp.minimum(p1, S - 1) >> shift) + 1
    first = jnp.maximum(p0 - (window - 1), 0) >> shift
    return first, (p1 >> shift) - first + 1


def _kernel(layer_ref, start_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc,
            acc_sc, *, tq: int, bs: int, S: int, scale: float, window):
    i, j = pl.program_id(1), pl.program_id(2)
    first, count = _visits(start_ref[0], i, tq=tq, bs=bs, S=S, window=window)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def attend(masked: bool):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        kt = jnp.promote_types(q.dtype, k.dtype)
        sc = jnp.dot(q.astype(kt), k.astype(kt),
                     preferred_element_type=jnp.float32) * scale  # [rows, bs]
        if masked:
            # a row is (head, token): its token is its index's low bits
            row = jax.lax.broadcasted_iota(jnp.int32, (sc.shape[0], 1), 0)
            pos = p0 + (row & (tq - 1))
            key = key0 + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
            seen = key <= pos
            if window is not None:
                seen &= key > pos - window
            sc = jnp.where(seen, sc, NEG_INF)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        # a row that has seen nothing yet adds 1s here (NEG_INF - NEG_INF);
        # the block that holds its own position wipes them out (alpha = 0)
        p = jnp.exp(sc - m_next)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=-1, keepdims=True)
        vt = jnp.promote_types(q.dtype, v.dtype)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
            p.astype(q.dtype).astype(vt), v.astype(vt),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[...] = m_next

    # positions are compared only in a block that some token of the tile
    # sees in part: the one its own positions lie in, the window's far end
    p0 = start_ref[0] + i * tq
    key0 = (first + j) * bs
    partly = key0 + bs - 1 > p0
    if window is not None:
        partly |= key0 <= p0 + tq - 1 - window
    pl.when((j < count) & partly)(lambda: attend(True))
    pl.when((j < count) & ~partly)(lambda: attend(False))

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def block_attention(q, k_cache, v_cache, layer, start, *, window=None,
                    interpret: bool = False):
    """q [1, T, KV, D] or [1, T, KV, G, D], the tokens at positions ``start
    [1] + t``, attends layer ``layer`` of ``k_cache`` / ``v_cache`` [L, 1,
    KV, D, S], which hold the tokens' own columns already -> what q's shape
    is. A token sees the positions up to its own; with ``window`` the caches
    are rings (position p at ``p mod S``, ``S >= window + T``) and it sees
    the ``window - 1`` before it and itself."""
    shape = q.shape
    T, KV, D = shape[1], shape[2], shape[-1]
    G = q.size // (T * KV * D)
    S = k_cache.shape[-1]
    cdt = k_cache.dtype
    if shape[0] != 1 or S % TILE or T % TOKENS:
        raise ValueError(
            f"one slot's block of tokens (a multiple of {TOKENS}) against "
            f"whole tiles of {TILE} positions: {shape} against {S} is the "
            f"XLA path's (models/kv_cache.py:attend)")
    if window is not None and not 0 < window <= S - T:
        raise ValueError(
            f"a ring of {S} positions holds no window of {window} beside a "
            f"block of {T}")
    tq, bs = tiles(T, G, D, S, cdt.itemsize)
    nq, rows = T // tq, G * tq
    # blocks a tile may visit: the whole of a full layer; of a ring the
    # most that the window and the tile's tokens span, wherever they start
    nk = S // bs if window is None else (window + tq - 2) // bs + 2
    out_dtype = jnp.promote_types(q.dtype, cdt)

    # [1, T, KV, G, D] -> [KV, tiles x (G, tq), D]: a tile's rows together
    def folded(x):
        x = x.reshape(nq, tq, KV, G, D).transpose(2, 0, 3, 1, 4)
        return x.reshape(KV, nq * rows, D)

    def unfolded(o):
        o = o.reshape(KV, nq, G, tq, D).transpose(1, 3, 0, 2, 4)
        return o.reshape(shape)

    def block(h, i, j, layer_ref, start_ref):
        first, count = _visits(
            start_ref[0], i, tq=tq, bs=bs, S=S, window=window)
        at = first + jnp.minimum(j, count - 1)   # past the last: the last
        if window is not None:
            at = jax.lax.rem(at, S // bs)
        return layer_ref[0], 0, h, 0, at

    tile = pl.BlockSpec((None, rows, D), lambda h, i, j, *_: (h, i, 0))
    cache = pl.BlockSpec((None, None, None, D, bs), block)
    block_bytes = D * bs * cdt.itemsize
    o = pl.pallas_call(
        functools.partial(_kernel, tq=tq, bs=bs, S=S, scale=D ** -0.5,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(KV, nq, nk),
            in_specs=[tile, cache, cache],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((KV, nq * rows, D), out_dtype),
        # two blocks of K and of V in flight, a tile's scores and their
        # passes in float32, the tiles of q and of the result twice
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=4 * block_bytes + 8 * rows * bs * 4
            + 8 * rows * max(D, TILE) * 4 + (16 << 20)),
        name="block_attention",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), start.astype(jnp.int32),
      folded(q), k_cache, v_cache)
    return unfolded(o)


# ------------------------------------------------ a latent layer's chosen rows
# positions of a latent cache a step of ``selected_block_attention`` holds,
# and the queries of one of its sub-tiles where they divide a chunk
SELECTED_POSITIONS = 512


def _selected_tiling(T: int, S: int):
    """(queries a sub-tile, positions a block) of a chunk of T tokens
    against a cache of S positions: blocks of ``SELECTED_POSITIONS`` or the
    longest shorter power of two that divides S, sub-tiles of a block's
    length (a tile above the diagonal is then a whole one) or, where that
    does not divide T, all T queries."""
    bs = next(n for n in (SELECTED_POSITIONS, 256, TILE) if S % n == 0)
    return (bs if T % bs == 0 else T), bs


def selected_tiles(start: int, T: int, S: int):
    """(sub-tile x block visits a chunk of T tokens at ``start ..`` would
    make with every query attending every block up to its last token's, the
    visits ``selected_block_attention`` computes: a sub-tile whose last
    query lies before a block's first position sees nothing of it), on the
    host, one head and layer."""
    tr, bs = _selected_tiling(T, S)
    blocks = min(start + T - 1, S - 1) // bs + 1
    return blocks * (T // tr), sum(
        T // tr - max(j * bs - start, 0) // tr for j in range(blocks))


def _selected_kernel(layer_ref, start_ref, q_ref, up_ref, c_ref, mask_ref,
                     o_ref, m_sc, l_sc, acc_sc, *, tr: int, bs: int, S: int,
                     scale: float, rank: int, Dn: int):
    """One head's T queries against one block of ``bs`` positions of a
    latent cache: the block's rows ``[rank + Dr, bs]`` are up-projected to
    the head's keys and values here (``up [Dn + Dv, rank]``), the keys
    stacked over the block's rotated rows, so that a score is one product
    ``q [., Dn + Dr] @ k [Dn + Dr, bs]``; then, ``tr`` queries at a time,
    scored, masked by the choice and folded into the queries' running
    softmax: the scale inside the exponential's argument, a query's sum
    kept a lane apart (``l [T, lanes]``: the lanes are added once, at the
    end, where a sum across them in every step held the step up). A step
    past the block that holds the last token's position computes nothing,
    nor does a sub-tile whose last query lies before the block's first
    position."""
    j = pl.program_id(1)
    T = q_ref.shape[0]
    n, lanes = T // tr, l_sc.shape[1]
    shift = bs.bit_length() - 1
    start = start_ref[0]
    count = (jnp.minimum(start + T - 1, S - 1) >> shift) + 1
    dtype = q_ref.dtype

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def up_projected(rows, c):
        """The head's keys or values of the block, rounded as a product's
        result in the activations' dtype is."""
        return jnp.dot(up_ref[rows, :], c,
                       preferred_element_type=jnp.float32).astype(dtype)

    def scores(i, k):
        own = slice(i * tr, (i + 1) * tr)
        sc = jnp.dot(q_ref[own, :], k, preferred_element_type=jnp.float32)
        return jnp.where(mask_ref[own, :] != 0, sc, NEG_INF)     # [tr, bs]

    def fold(i, sc, v):
        own = slice(i * tr, (i + 1) * tr)
        m_prev = m_sc[own, :]
        m_next = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp((m_prev - m_next) * scale)
        # a query that has met no chosen position yet adds 1s here (NEG_INF
        # - NEG_INF); the first block that holds one wipes them out (alpha
        # = 0), and a masked score's exponential is 0 from then on
        p = jnp.exp((sc - m_next) * scale)
        l_sc[own, :] = alpha * l_sc[own, :] + sum(
            p[:, at:at + lanes] for at in range(0, bs, lanes))
        acc_sc[own, :] = alpha * acc_sc[own, :] + jax.lax.dot_general(
            p.astype(dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[own, :] = m_next

    def walk(first: int):
        """The sub-tiles from ``first`` on in ONE straight line, in the
        order the units should meet them: a sub-tile's scores are asked for
        before the one before it is folded (and the first one's before the
        values are up-projected), so that the products run beside the
        softmax's passes: the compiler keeps to the order it is given."""
        block = c_ref[...].astype(dtype)                  # [rank + Dr, bs]
        c = block[:rank]
        k = jnp.concatenate(
            [up_projected(slice(0, Dn), c), block[rank:]], axis=0)
        sc = scores(first, k)
        v = up_projected(slice(Dn, None), c)
        for i in range(first, n):
            ahead = scores(i + 1, k) if i + 1 < n else None
            fold(i, sc, v)
            sc = ahead

    # a variant a first sub-tile whose last query sees the block's first
    # position: 0 for every block before the chunk's own span
    first = jnp.maximum((j << shift) - start, 0) >> (
        tr.bit_length() - 1) if n > 1 else 0
    for f in range(n):
        pl.when((j < count) & (first == f))(functools.partial(walk, f))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_sc[...] / jnp.sum(
            l_sc[...], axis=-1, keepdims=True)).astype(o_ref.dtype)


def selected_block_attention(q, up, cache, picked, layer, start, *,
                             scale: float, interpret: bool = False):
    """A block of one slot's tokens over the rows of a LATENT cache that
    each token CHOSE: q [T, H, Dn + Dr] at positions ``start [1] + t``, ``up``
    [R, H, Dn + Dv] (a head's ``[Wuk | Wuv]``), ``cache`` [L, 1, 1, R + Dr,
    S] with the tokens' own rows in place, ``picked`` [T, width] bool (causal
    already: an indexer's choice; ``width`` whole blocks, up to S, with
    ``start + T`` inside them) -> [T, H, Dv]. One head at a time holds all T
    queries and walks the blocks of ``SELECTED_POSITIONS`` positions up to
    the one with the last token's: each block's rows are read where they
    lie and up-projected in VMEM, so no up-projected key, no score and no
    probability is ever in HBM. Every row of a visible block is read and an
    unchosen one enters no softmax; a sub-tile of queries above the diagonal
    (its last query before the block's first position) is not computed."""
    T, H, Dq = q.shape
    R = up.shape[0]
    D, S = cache.shape[-2:]
    Dn = Dq - (D - R)
    Dv = up.shape[-1] - Dn
    width = picked.shape[1]
    if S % TILE or T % TOKENS:
        raise ValueError(
            f"a block of tokens (a multiple of {TOKENS}) against whole tiles "
            f"of {TILE} positions: {T} against {S} is the XLA path's "
            f"(models/kv_cache.py:attend_latent)")
    tr, bs = _selected_tiling(T, S)
    if width % bs or width > S:
        raise ValueError(
            f"a choice over {width} of {S} positions: no whole blocks of {bs}")
    shift = bs.bit_length() - 1

    def block(j, start_ref):
        count = (jnp.minimum(start_ref[0] + T - 1, S - 1) >> shift) + 1
        return jnp.minimum(j, count - 1)       # past the last: the last

    head = lambda *shape: pl.BlockSpec(                      # noqa: E731
        (None, *shape), lambda h, j, *_: (h, 0, 0))
    o = pl.pallas_call(
        functools.partial(_selected_kernel, tr=tr, bs=bs, S=S, scale=scale,
                          rank=R, Dn=Dn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H, width // bs),
            in_specs=[
                head(T, Dq), head(Dn + Dv, R),
                pl.BlockSpec(
                    (None, None, None, D, bs),
                    lambda h, j, layer_ref, start_ref: (
                        layer_ref[0], 0, 0, 0, block(j, start_ref))),
                pl.BlockSpec(
                    (T, bs), lambda h, j, layer_ref, start_ref: (
                        0, block(j, start_ref)))],
            out_specs=head(T, Dv),
            scratch_shapes=[
                pltpu.VMEM((T, 1), jnp.float32),
                pltpu.VMEM((T, min(TILE, bs)), jnp.float32),
                pltpu.VMEM((T, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((H, T, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=4 * D * bs * cache.dtype.itemsize
            + 10 * T * bs * 4 + 8 * T * max(Dq, TILE) * 4 + (16 << 20)),
        name="selected_block_attention",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(start, (1,)).astype(jnp.int32),
      jnp.moveaxis(q, 1, 0), jnp.transpose(up.astype(q.dtype), (1, 2, 0)),
      cache, picked.astype(jnp.int8))
    return jnp.moveaxis(o, 0, 1)
