"""One decode step's cache access as one pallas TPU kernel.

A decode step (one new token a slot) needs, for each slot, the K and V
columns that are filled and one new column written. ``decode_attention``
does both over the WHOLE cache ``[L, B, KV, D, S]`` (``models/kv_cache.py``),
aliased to its results: an operand of a custom call is a whole array, so a
layer's slice cut by the scan would be copied for it. The cache stays in
HBM; the layer index, ``lens`` and the live slots ride as scalar-prefetch
arguments and steer the kernel's own DMAs. The kernel visits the slots it is
told decode (``live``: their indices, ascending, then their count), in that
order, and no other:

- read: live slot by live slot (and group of kv heads), chunk by chunk of
  positions, only the chunks that hold positions ``< lens[b]`` (one at
  least: it holds the tile to write). Two buffers: a chunk is in flight
  while the one before is computed on, across the slots' edges too. Online
  softmax in float32, products accumulated in float32. The new column never
  comes from the cache: its score and value open the running softmax.
- write: the one 128-position tile that holds position ``lens[b]``: the old
  tile out of the chunk already in VMEM, the new column selected in, sent
  back. A position past the end selects nothing.
- a slot that is not live starts no DMA in either direction: its K and V
  leave the call as they entered, and its row of the result is zeros. No
  live slot at all is no DMA at all.

Chunk sizes follow the shapes given (``_blocks``), nothing else; S is a
multiple of the 128 lanes (the DMAs move whole tiles). What is
small (the queries, the new columns, the result) sits in VMEM whole.

With ``window`` the cache is a ring of S positions (``models/kv_cache.py``:
position p at ``p mod S``, S at least the window) and a slot attends its new
column and the ``window - 1`` positions before it: the visit reads the
chunks that hold those positions and no other, from the one that holds
position ``lens[b] - window + 1`` round the ring to the one that holds
``lens[b] mod S``, the tile it writes. Where those are one and the same
chunk of the ring (the window's two ends in it), it is read twice, each
time masked to its own end.

``latent_decode_attention`` is the same visit for a LATENT cache
``[L, B, 1, D, S]`` (multi-head latent attention with the up-projection
absorbed; ``models/kv_cache.py:attend_latent``): one row a position that
every query head scores, whose first ``values`` channels are the values too,
so a chunk is read once and enters both products. No ring and no group of
heads; its own chunk length (``LATENT_BLOCK_BYTES``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
TILE = 128              # the TPU's lane width: the finest write there is
BLOCK_BYTES = 1 << 19   # of one K (or V) chunk in VMEM; two of each are held


def _blocks(KV: int, D: int, S: int, itemsize: int):
    """(kv heads a chunk, positions a chunk) for a cache ``[.., KV, D, S]``:
    chunks as long as all heads fit ``BLOCK_BYTES``, then as many heads as
    do."""
    bs = TILE
    while S % (2 * bs) == 0 and KV * D * 2 * bs * itemsize <= BLOCK_BYTES:
        bs *= 2
    hb = max(h for h in range(1, KV + 1)
             if KV % h == 0 and (h == 1 or h * D * bs * itemsize <= BLOCK_BYTES))
    return hb, bs


def _kernel(layer_ref, lens_ref, live_ref, q_ref, kn_row_ref, vn_row_ref,
            kn_col_ref, vn_col_ref, k_hbm, v_hbm, o_ref, ko_hbm, vo_hbm, kbuf,
            vbuf, ktile, vtile, read_sem, write_sem, m_sc, l_sc, acc_sc, *,
            hb: int, bs: int, scale: float, window):
    B, KV = q_ref.shape[:2]
    S = k_hbm.shape[-1]
    groups = KV // hb
    # a visit: one live slot, one group of heads; ``live_ref`` holds the
    # live slots' indices and, behind them, their count
    visits = live_ref[B] * groups
    layer = layer_ref[0]

    def slot(v):
        return live_ref[v // groups]

    def span(i, size):
        return pl.ds(pl.multiple_of(i * size, size), size)

    def reach(b):
        """Of slot ``b``: (the first position it attends, the chunk that
        holds it counted from position 0, the place its new column lands
        on). Without a window: 0, 0 and the last place for a column past
        the end, which is then dropped."""
        n = lens_ref[b]
        if window is None:
            return 0, 0, jnp.minimum(n, S - 1)
        first = jnp.maximum(n - (window - 1), 0)
        return first, first // bs, n % S

    def read(v, c, buf):
        """The DMAs of chunk ``c`` of visit ``v`` into buffer ``buf``."""
        b, heads = slot(v), pl.ds((v % groups) * hb, hb)
        at = span(c if window is None
                  else (reach(b)[1] + c) % (S // bs), bs)
        return [pltpu.make_async_copy(
            hbm.at[layer, b, heads, :, at], dst.at[buf], read_sem.at[i, buf])
            for i, (hbm, dst) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]

    def write(v, buf):
        """The DMAs of visit ``v``'s tile out of buffer ``buf``."""
        b, heads = slot(v), pl.ds((v % groups) * hb, hb)
        at = span(reach(b)[2] // TILE, TILE)
        return [pltpu.make_async_copy(
            src.at[buf], hbm.at[layer, b, heads, :, at], write_sem.at[i, buf])
            for i, (src, hbm) in enumerate(((ktile, ko_hbm), (vtile, vo_hbm)))]

    def visit(v, buf):
        """Visit ``v``, whose first chunk is on its way into ``buf`` -> the
        buffer the next visit's first chunk is on its way into."""
        b, g = slot(v), v % groups
        heads = pl.ds(g * hb, hb)
        n = lens_ref[b]                   # the new column's position
        first, chunk0, place = reach(b)
        # past the end of a cache that is no ring it is dropped
        kept = n < S if window is None else True
        # chunks this visit reads: up to the one that holds ``place``
        count = (place // bs + 1 if window is None
                 else n // bs - chunk0 + 1)
        q = q_ref[b, heads]               # [hb, G, D]
        # the running softmax opens on the new column alone (p = 1)
        s_new = jnp.sum(
            q.astype(jnp.float32) * kn_row_ref[b, heads].astype(jnp.float32),
            axis=-1, keepdims=True) * scale                  # [hb, G, 1]
        m_sc[...] = jnp.where(kept, s_new, NEG_INF)
        l_sc[...] = jnp.where(kept, jnp.ones_like(s_new), 0.0)
        acc_sc[...] = jnp.where(kept, jnp.broadcast_to(
            vn_row_ref[b, heads].astype(jnp.float32), acc_sc.shape), 0.0)

        def chunk(c, buf):
            # the next chunk sets out before this one is computed on: this
            # visit's next or, behind its last, the next visit's first
            @pl.when(c + 1 < count)
            def _():
                for dma in read(v, c + 1, 1 - buf):
                    dma.start()

            @pl.when((c + 1 == count) & (v + 1 < visits))
            def _():
                for dma in read(v + 1, 0, 1 - buf):
                    dma.start()

            for dma in read(v, c, buf):
                dma.wait()

            if window is not None:
                c = chunk0 + c            # counted from position 0

            @pl.when(c * bs < n)          # a filled position among them:
            def _():                      # a slot's first column has none
                attend(c, kbuf[buf], vbuf[buf])
            return 1 - buf

        def attend(c, k, v_):             # [hb, D, bs]
            kt = jnp.promote_types(q.dtype, k.dtype)
            sc = jnp.einsum("hgd,hds->hgs", q.astype(kt), k.astype(kt),
                            preferred_element_type=jnp.float32) * scale
            pos = c * bs + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 2)
            old = pos < n                 # the filled positions it sees
            if window is not None:
                old &= pos >= first
            sc = jnp.where(old, sc, NEG_INF)
            m_prev = m_sc[...]
            m_next = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.where(old, jnp.exp(sc - m_next), 0.0)
            l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=-1, keepdims=True)
            vt = jnp.promote_types(q.dtype, v_.dtype)
            acc_sc[...] = alpha * acc_sc[...] + jnp.einsum(
                "hgs,hds->hgd", p.astype(q.dtype).astype(vt), v_.astype(vt),
                preferred_element_type=jnp.float32)
            m_sc[...] = m_next

        after = jax.lax.fori_loop(0, count, chunk, buf)
        o_ref[b, heads] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)

        # the tile that holds position n: out of the last chunk, the new
        # column selected in. n == S matches no lane: the tile as it was.
        held, out = 1 - after, v % 2      # the last chunk's buffer; the tile's

        @pl.when(v >= 2)
        def _():                          # the tile sent two visits ago
            for dma in write(v - 2, out):
                dma.wait()

        within = span((place % bs) // TILE, TILE)
        lane = (place // TILE) * TILE + jax.lax.broadcasted_iota(
            jnp.int32, (1, TILE), 1)
        if window is None:
            place = n                     # past the end: no lane is n
        for new_ref, chunk_ref, tile_ref in ((kn_col_ref, kbuf, ktile),
                                             (vn_col_ref, vbuf, vtile)):
            new = new_ref[b, g]                              # [D, hb]
            for h in range(hb):
                tile_ref[out, h] = jnp.where(
                    lane == place, new[:, h:h + 1],
                    chunk_ref[held, h, :, within])
        for dma in write(v, out):
            dma.start()
        return after

    # what no visit writes, a slot that is not live, leaves as zeros
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(visits > 0)
    def _():
        for dma in read(0, 0, 0):
            dma.start()

    jax.lax.fori_loop(0, visits, visit, 0)
    for back in (2, 1):                   # the last two visits' tiles

        @pl.when(visits >= back)
        def _():
            for dma in write(visits - back, (visits - back) % 2):
                dma.wait()


def live_slots(live: jax.Array) -> jax.Array:
    """``live`` [B] bool -> [B + 1] int32 as the kernel takes it: the indices
    of the slots that are live, ascending, and in the last place how many
    they are (what lies between is not read)."""
    B = live.shape[0]
    order, = jnp.nonzero(live, size=B, fill_value=0)
    return jnp.append(order, live.sum()).astype(jnp.int32)


def decode_attention(q, k_new, v_new, k_cache, v_cache, layer, lens, *,
                     live=None, window=None, interpret: bool = False):
    """q [B, KV, G, D] attends layer ``layer``'s filled positions
    (``< lens[b]``) of ``k_cache`` / ``v_cache`` [L, B, KV, D, S] and the
    new column ``k_new`` / ``v_new`` [B, KV, D], which is written to position
    ``lens[b]`` (dropped where that is S) -> (out [B, KV, G, D], k_cache,
    v_cache): the caches are the operands' own buffers. With ``window`` the
    caches are rings (position p at ``p mod S``), the filled positions
    attended are the ``window - 1`` before ``lens[b]``, and the new column
    is written to ``lens[b] mod S``. ``live`` (``live_slots``' [B + 1];
    None: every slot) names the slots this holds for: any other slot's cache
    is left as it is and its row of ``out`` is zeros."""
    B, KV, G, D = q.shape
    S = k_cache.shape[-1]
    cdt = k_cache.dtype
    if S % TILE:
        raise ValueError(
            f"the kernel moves whole tiles of {TILE} positions: a cache of "
            f"{S} is the XLA path's (models/kv_cache.py:attend)")
    if window is not None and not 0 < window <= S:
        raise ValueError(f"a ring of {S} positions holds no window of {window}")
    hb, bs = _blocks(KV, D, S, cdt.itemsize)
    if live is None:                      # slots 0 .. B - 1, and B of them
        live = jnp.arange(B + 1, dtype=jnp.int32)
    k_new, v_new = k_new.astype(cdt), v_new.astype(cdt)
    out_dtype = jnp.promote_types(q.dtype, cdt)

    def columns(new):                     # [B, KV, D] -> [B, KV // hb, D, hb]
        return new.reshape(B, KV // hb, hb, D).swapaxes(2, 3)

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    cache_shape = jax.ShapeDtypeStruct(k_cache.shape, cdt)
    chunk_bytes = hb * D * bs * cdt.itemsize
    o, k_cache, v_cache = pl.pallas_call(
        functools.partial(_kernel, hb=hb, bs=bs, scale=D ** -0.5,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[vmem, vmem, vmem, vmem, vmem, hbm, hbm],
            out_specs=[vmem, hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, hb, D, bs), cdt),
                pltpu.VMEM((2, hb, D, bs), cdt),
                pltpu.VMEM((2, hb, D, TILE), cdt),
                pltpu.VMEM((2, hb, D, TILE), cdt),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hb, G, 1), jnp.float32),
                pltpu.VMEM((hb, G, 1), jnp.float32),
                pltpu.VMEM((hb, G, D), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, D), out_dtype),
                   cache_shape, cache_shape],
        # operands count from the scalar-prefetch arguments on
        input_output_aliases={8: 1, 9: 2},
        # four chunks and four tiles held, the chunk's temporaries, and the
        # small operands, whose rows pad to whole tiles
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=8 * chunk_bytes + (32 << 20)),
        # a trace's reader tells the two kinds of cache apart by the name
        name="decode_attention" if window is None
        else "decode_attention_window",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens.astype(jnp.int32),
      live.astype(jnp.int32), q, k_new[:, :, None, :], v_new[:, :, None, :],
      columns(k_new), columns(v_new), k_cache, v_cache)
    return o, k_cache, v_cache


# of one chunk of a latent cache in VMEM (two are held): 576 rows of 512
# positions in bfloat16
LATENT_BLOCK_BYTES = 5 << 17


def _latent_kernel(layer_ref, lens_ref, live_ref, q_ref, new_row_ref,
                   new_col_ref, *rest, bs: int, scale: float, values: int,
                   selected: bool):
    """``_kernel`` for a cache of ONE row a position and no head axis,
    whose first ``values`` channels are also the values: a chunk is read
    once and enters both products. No ring, no group of heads; the visits,
    the two buffers and the tile written are ``_kernel``'s. ``selected``:
    one operand more, ``chosen_ref`` [B, 1, S] float32 in VMEM, above
    0 at the positions a slot's query reads (its own new row's among them,
    at ``lens[b]``): every other position is read and left out of the
    softmax."""
    chosen_ref = rest[0] if selected else None
    (c_hbm, o_ref, co_hbm, cbuf, ctile, read_sem, write_sem, m_sc, l_sc,
     acc_sc) = rest[selected:]
    B = q_ref.shape[0]
    S = c_hbm.shape[-1]
    visits = live_ref[B]
    layer = layer_ref[0]

    def span(i, size):
        return pl.ds(pl.multiple_of(i * size, size), size)

    def place_of(b):    # a column past the end lands nowhere
        return jnp.minimum(lens_ref[b], S - 1)

    def read(v, c, buf):
        return pltpu.make_async_copy(
            c_hbm.at[layer, live_ref[v], 0, :, span(c, bs)], cbuf.at[buf],
            read_sem.at[buf])

    def write(v, buf):
        return pltpu.make_async_copy(
            ctile.at[buf],
            co_hbm.at[layer, live_ref[v], 0, :,
                      span(place_of(live_ref[v]) // TILE, TILE)],
            write_sem.at[buf])

    def visit(v, buf):
        b = live_ref[v]
        n = lens_ref[b]                   # the new column's position
        place = place_of(b)
        kept = n < S
        if selected:    # the new row's own place among the chosen
            at = (place // TILE) * TILE
            lane = at + jax.lax.broadcasted_iota(jnp.int32, (1, TILE), 1)
            own = chosen_ref[b, :, span(place // TILE, TILE)]
            kept &= jnp.sum(jnp.where(lane == n, own, 0.0)) > 0
        count = place // bs + 1
        q = q_ref[b]                      # [H, D]
        new = new_row_ref[b]              # [1, D]
        s_new = jnp.sum(q.astype(jnp.float32) * new.astype(jnp.float32),
                        axis=-1, keepdims=True) * scale          # [H, 1]
        m_sc[...] = jnp.where(kept, s_new, NEG_INF)
        l_sc[...] = jnp.where(kept, jnp.ones_like(s_new), 0.0)
        acc_sc[...] = jnp.where(kept, jnp.broadcast_to(
            new[:, :values].astype(jnp.float32), acc_sc.shape), 0.0)

        def chunk(c, buf):
            @pl.when(c + 1 < count)
            def _():
                read(v, c + 1, 1 - buf).start()

            @pl.when((c + 1 == count) & (v + 1 < visits))
            def _():
                read(v + 1, 0, 1 - buf).start()

            read(v, c, buf).wait()

            @pl.when(c * bs < n)          # a filled position among them
            def _():
                rows = cbuf[buf]                                 # [D, bs]
                kt = jnp.promote_types(q.dtype, rows.dtype)
                sc = jnp.einsum("hd,ds->hs", q.astype(kt), rows.astype(kt),
                                preferred_element_type=jnp.float32) * scale
                pos = c * bs + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
                old = pos < n
                if selected:
                    old &= chosen_ref[b, :, span(c, bs)] > 0
                sc = jnp.where(old, sc, NEG_INF)
                m_prev = m_sc[...]
                m_next = jnp.maximum(
                    m_prev, jnp.max(sc, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.where(old, jnp.exp(sc - m_next), 0.0)
                l_sc[...] = alpha * l_sc[...] + jnp.sum(
                    p, axis=-1, keepdims=True)
                # the values are the chunk's first rows: nothing else is read
                acc_sc[...] = alpha * acc_sc[...] + jnp.einsum(
                    "hs,rs->hr", p.astype(q.dtype).astype(kt),
                    rows[:values].astype(kt),
                    preferred_element_type=jnp.float32)
                m_sc[...] = m_next
            return 1 - buf

        after = jax.lax.fori_loop(0, count, chunk, buf)
        o_ref[b] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)

        held, out = 1 - after, v % 2

        @pl.when(v >= 2)
        def _():                          # the tile sent two visits ago
            write(v - 2, out).wait()

        within = span((place % bs) // TILE, TILE)
        lane = (place // TILE) * TILE + jax.lax.broadcasted_iota(
            jnp.int32, (1, TILE), 1)
        # n == S matches no lane: the tile as it was
        ctile[out] = jnp.where(lane == n, new_col_ref[b],
                               cbuf[held, :, within])
        write(v, out).start()
        return after

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(visits > 0)
    def _():
        read(0, 0, 0).start()

    jax.lax.fori_loop(0, visits, visit, 0)
    for back in (2, 1):

        @pl.when(visits >= back)
        def _():
            write(visits - back, (visits - back) % 2).wait()


def latent_decode_attention(q, new, cache, layer, lens, *, values: int,
                            scale: float, live=None, chosen=None,
                            interpret: bool = False):
    """A decode step over a LATENT cache ``[L, B, 1, D, S]``: one row of D
    channels a position, which every query head shares, and whose first
    ``values`` channels are the values too (multi-head latent attention with
    the up-projection absorbed into the queries). q [B, H, D] attends layer
    ``layer``'s filled positions (``< lens[b]``) and the new row ``new``
    [B, D], which is written to position ``lens[b]`` (dropped where that is
    S) -> (out [B, H, values], cache: the operand's own buffer). Scores are
    ``q . row x scale``. ``live`` as ``decode_attention``'s. Each chunk of
    the cache is read once, for both products. ``chosen`` [B, S] bool (None:
    every filled position): the positions a slot's softmax runs over, its
    new row's own place among them; the others are read and masked, so a
    step costs what it cost and the result is the selected attention's."""
    B, H, D = q.shape
    S = cache.shape[-1]
    cdt = cache.dtype
    if S % TILE:
        raise ValueError(
            f"the kernel moves whole tiles of {TILE} positions: a cache of "
            f"{S} is the XLA path's (models/kv_cache.py:attend_latent)")
    bs = TILE
    while S % (2 * bs) == 0 and D * 2 * bs * cdt.itemsize <= LATENT_BLOCK_BYTES:
        bs *= 2
    if live is None:
        live = jnp.arange(B + 1, dtype=jnp.int32)
    new = new.astype(cdt)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    selected = chosen is not None
    marks = (chosen[:, None, :].astype(jnp.float32),) if selected else ()
    o, cache = pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, scale=scale, values=values,
                          selected=selected),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[vmem, vmem, vmem, *(vmem,) * selected, hbm],
            out_specs=[vmem, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, D, bs), cdt),
                pltpu.VMEM((2, D, TILE), cdt),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, values), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, values),
                                 jnp.promote_types(q.dtype, cdt)),
            jax.ShapeDtypeStruct(cache.shape, cdt)],
        input_output_aliases={6 + selected: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=8 * D * bs * cdt.itemsize + (48 << 20)
            + selected * 8 * B * S * 4),
        name="latent_decode_attention",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens.astype(jnp.int32),
      live.astype(jnp.int32), q, new[:, None, :], new[:, :, None], *marks,
      cache)
    return o, cache
