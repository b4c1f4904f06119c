"""The KV cache of incremental decoding: one contract for every family.

``{"k", "v"}``, each ``[L, B, KV, D, S]``: layer, slot, kv head, head
size, position. The engine sees an opaque pytree with the slot on axis 1;
only ``forward_cached`` of a model module reads the other axes.

Position is the minor axis because of how the TPU stores an array: it tiles
the two minor dimensions (8, 128), so ``[.., S, KV, D]`` with (25, 64) minor
would pad 2.4x, the compiler stores it position-minor instead and transposes
every layer's slice on its way to the attention products and back. Held
position-minor to begin with, the slice feeds both products as it lies.

``attend`` is a layer's whole access: place the new tokens' columns, attend
over what is filled. Which path it takes is parted by static shapes and the
platform alone (``_impl``). On a TPU, S a multiple of the chip's 128 lanes,
a kernel over the whole cache does it. A decode step (T == 1) is
``ops/decode_attention.py``, which reads the filled positions and writes one
tile a slot, of the slots it is told decode (``Step.live``) and of no other:
a slot that does not decode keeps its cache as it is and gets zeros for its
row. A block of tokens of one slot (a prefill chunk: B = 1, whole tiles of 16
tokens) has its T columns put where they lie by one update of T columns
(``_place``; a second one round a ring's end) and then attends through
``ops/block_attention.py``, which reads the blocks of positions that some
token of a tile may see and no other, and keeps its scores in VMEM: no
``[.., T, S]`` array, mask or score, is made. Everything else is XLA's
(``_attend_xla``): any other backend, a cache whose length the lanes do not
divide, a few tokens at every slot (speculation's verify), a ring too short
for the block beside the window. It takes the layer's slice out, scores the
block against all of it, writes it whole and puts it back; the kernels are
held to it by the tests.

A model with window layers (attention over the token and the ``window - 1``
before it) holds a second pair in the same pytree, ``{"k_window",
"v_window"}``, each ``[Lw, B, KV, D, R]``: a ring, position ``p`` at
``p mod R``. ``R`` (``ring_length``) is the window and the longest block of
tokens one call writes: a block's last token then never lands on a position
its first token still sees, nor a rejected draft on one that the token it is
rolled back to sees. A ring slot's position follows from the last position
written, so nothing beside the arrays is kept. The full layers keep ``{"k",
"v"}`` and count their own layers, the window layers theirs.

A model with state layers (a recurrence in place of attention: Mamba-2's,
``ops/ssm.py``, or the gated delta rule's, ``ops/delta_rule.py``) holds a
third pair, ``{"ssm", "conv"}``: ``[Ls, B, H, P, N]`` float32, the state a
head (the family's ``state_leaves`` says of what shape: Mamba-2's channels x
states, the delta rule's keys x values), and ``[Ls, B, (K - 1) C]``, the last ``K - 1`` rows that
entered the layer's convolution, one after another (flat: with ``[.., K - 1,
C]`` or ``[.., C, K - 1]`` minor the chip pads three rows to a tile of 8 or
128, and its compiler repacks the whole leaf around every layer's write, 0.75
ms a tick at 48 slots). Slot on axis 1 like the rest, and nothing
in them grows with the position. ``recur`` is such a layer's whole access,
as ``attend`` is an attention layer's, and the one place the two
recurrences share what they share: a block of tokens takes the layer's
state out, scans from it and puts back the state after the block's last REAL
token (``Step.real``); a decode step on a TPU is one kernel over the whole
state that reads and writes the slots that decode and no other.

A model with latent-attention layers (DeepSeek-V2's multi-head latent
attention) holds ONE leaf more, ``"latent"`` ``[Ll, B, 1, R + Dr, S]``: a
position's row is the normed latent ``c`` [R] and the rotated key ``kr``
[Dr] that every head shares, and nothing a head: K and V are both
up-projections of ``c``, so the rows are the keys' and the values' at once
(576 values a position where 32 heads of 128 would hold 8,192). Laid out as
one kv head of ``R + Dr`` channels, position minor, so that ``_place``,
``_write`` and the decode kernel's tiles are the other leaves' own.
``attend_latent`` is such a layer's whole access, by two routes that must
agree: a decode step ABSORBS the up-projection (``q_nope Wuk^T`` [H, R]
against the rows, the probabilities' sum over ``c`` [H, R] through ``Wuv``),
on a TPU through ``ops/decode_attention.py:latent_decode_attention``, which
reads each filled chunk once for both products; a block of tokens
UP-PROJECTS the rows it sees, its own and the earlier chunks', a block of
positions at a time, and attends as H heads of ``Dn + Dr`` / ``Dv``
(``_latent_blocks``: XLA, a running softmax over the filled blocks alone).

A latent layer with a lightning indexer (DeepSeek-V3.2-Exp's sparse
attention; ``decoder.Layer.index``) keeps TWO rows a position: beside the
latent row the indexer's rotated key, leaf ``"index"`` ``[Ll, B, S, Di]``,
position MAJOR: a key is the 128 lanes of one row, so the leaf is tiled as
it lies, a step writes its key as one row, and the one product that reads
all of a slot's keys (``q . K``) contracts their minor axis. (Held position
minor like the rest, the compiler turned the whole leaf into this order at
a decode program's start and back at its end, 2 x 0.34 GB a tick at 8
slots of 33k.) The latent rows keep their order because what reads them is
still the routes above. A chunk and a decode step write both rows of their
positions; the queries are then scored against every cached key they may
see (``ops/index_select.py``), EXACTLY the ``kept`` largest positions of each
query are chosen, and the softmax runs over those alone. Where a kernel
attends, two kernels score and search: ``index_scores`` reads the leaf where
it lies, a live slot's filled blocks in a decode step and a tile of queries'
visible blocks in a chunk, and keeps what is a head wide in VMEM;
``index_kth_largest`` finds each row's threshold with the row's keys held
in VMEM, and ``index_select.chosen`` makes the set from it. XLA's ``scores``
and ``chosen`` over the whole leaf are every other route's (and the full
forward's), and the kernels' oracle. The choice enters
every route as a mask, so every visible row is read and an unchosen one adds
nothing: the decode kernel takes it beside the cache, a chunk attends
through ``ops/block_attention.py:selected_block_attention`` (a head's
queries against blocks of rows up-projected in VMEM; what lies above the
chunk's own diagonal is left out by sub-tiles of queries), the XLA route
masks its whole scores.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import index_select, ssm
from ray_tpu.ops.attention import heads_in
from ray_tpu.ops.block_attention import (
    TOKENS, block_attention, selected_block_attention,
)
from ray_tpu.ops.decode_attention import (
    TILE,
    decode_attention,
    latent_decode_attention,
    live_slots,
)


FULL, WINDOW = ("k", "v"), ("k_window", "v_window")
STATE = ("ssm", "conv")
LATENT = "latent"
INDEX = "index"


Indexed = index_select.Indexed


def ring_length(window: int, block: int, max_len: int) -> int:
    """Positions a window layer's ring holds where one call writes at most
    ``block`` tokens: whole lane tiles where it has one (the decode kernel
    moves tiles), and never more than a full layer would hold."""
    ring = window + block
    if ring >= TILE:
        ring = -(-ring // TILE) * TILE
    return min(ring, max_len)


def positions_seen(start: int, T: int, length: int,
                   window: Optional[int] = None) -> int:
    """Key positions that T tokens at ``start .. start + T - 1`` see between
    them in a cache of ``length``: up to the last token's own; in a ring the
    ``window - 1`` before the first token (those there are) and the tokens'."""
    first = 0 if window is None else max(start - (window - 1), 0)
    return min(start + T - first, length)


def init_kv_cache(num_layers: int, batch: int, kv_heads: int, head_dim: int,
                  max_len: int, dtype, window_layers: int = 0,
                  ring: int = 0, state_layers: int = 0,
                  state=None, latent_layers: int = 0,
                  latent_dim: int = 0,
                  index_dim: int = 0) -> Dict[str, jax.Array]:
    """``num_layers`` full layers of ``max_len`` positions,
    ``window_layers`` rings of ``ring``, ``state_layers`` states (``state``
    names each of their leaves' (shape a slot, dtype)) and ``latent_layers``
    layers of one row of ``latent_dim`` channels a position, beside it an
    indexer's key of ``index_dim`` channels where they have one."""
    cache = {name: jnp.zeros((state_layers, batch, *shape), kind)
             for name, (shape, kind) in (state or {}).items()
             if state_layers}
    if latent_layers:
        cache[LATENT] = jnp.zeros(
            (latent_layers, batch, 1, latent_dim, max_len), dtype)
        if index_dim:
            cache[INDEX] = jnp.zeros(
                (latent_layers, batch, max_len, index_dim), dtype)
    for names, layers, length in ((FULL, num_layers, max_len),
                                  (WINDOW, window_layers, ring)):
        if layers or (names is FULL and not window_layers
                      and not latent_layers):
            shape = (layers, batch, kv_heads, head_dim, length)
            cache.update({n: jnp.zeros(shape, dtype) for n in names})
    return cache


class Step(NamedTuple):
    """What every layer of one ``forward_cached`` shares: where each slot's
    tokens start and, where a layer attends in XLA, which positions each
    token sees and which it lands on, in the full layers' cache and in the
    window layers' ring (None where the model has no such layer, and where
    a kernel attends: it compares positions itself). ``live``: the slots
    whose tokens are tokens, as the decode kernel takes them (``live_slots``;
    None: every slot). What the caller says of a slot, never read off its
    length. The kernel leaves any other slot alone; the XLA path computes
    every slot and the caller drops the rows it did not ask for. ``real``:
    how many of a slot's T tokens are tokens (None: all), for a state layer,
    which must not step on the rest."""
    start: jax.Array   # [B] int32
    mask: Optional[jax.Array]    # [B, T, S] bool
    hit: Optional[jax.Array]     # [B, T, S] bool
    window: Optional[int] = None
    ring_mask: Optional[jax.Array] = None   # [B, T, R] bool
    ring_hit: Optional[jax.Array] = None    # [B, T, R] bool
    live: Optional[jax.Array] = None        # [B + 1] int32
    real: Optional[jax.Array] = None        # [B] int32


def step(start: jax.Array, T: int, cache: Dict[str, jax.Array],
         window: Optional[int] = None,
         live: Optional[jax.Array] = None,
         real: Optional[jax.Array] = None) -> Step:
    """Token t of slot b sits at position ``start[b] + t``, sees the keys up
    to itself and lands on its own position. A position past the end marks
    nothing, so such a token is dropped (a ``dynamic_update_slice`` would
    move the whole write back over valid rows). In a ring it lands on its
    position ``mod R``; once the block is in, slot s holds the last position
    congruent to s that was written, and a token sees the slots whose
    position is its own or one of the ``window - 1`` before it. ``live``
    [B] bool: the slots that decode (None: every slot), compacted here, once
    for every layer."""
    B = start.shape[0]
    pos = (start[:, None] + jnp.arange(T)[None, :])[:, :, None]
    mask = hit = ring_mask = ring_hit = None
    # a latent layer's rows lie as a full layer's columns do
    whole = next((cache[n] for n in (FULL[0], LATENT) if n in cache), None)
    if whole is not None and _impl(B, T, whole.shape[-1]) == "xla":
        key_pos = jnp.arange(whole.shape[-1])[None, None, :]
        mask, hit = key_pos <= pos, pos == key_pos
    if WINDOW[0] in cache and _impl(
            B, T, cache[WINDOW[0]].shape[-1], window) == "xla":
        R = cache[WINDOW[0]].shape[-1]
        slot = jnp.arange(R)[None, None, :]
        last = pos[:, -1:, :]
        held = last - (last - slot) % R
        ring_mask = (held >= 0) & (held <= pos) & (held > pos - window)
        ring_hit = pos % R == slot
    return Step(start, mask, hit, window, ring_mask, ring_hit,
                None if live is None else live_slots(live), real)


def _decode_impl() -> str:
    """How a layer's access runs where a kernel can take it, by the platform
    alone: the kernel on a TPU (one that fails to lower there raises, it
    never gives way), XLA elsewhere. ``pallas_interpret`` is the tests'."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _impl(B: int, T: int, length: int, window: Optional[int] = None) -> str:
    """How B slots' T tokens attend a cache of ``length`` positions (a ring
    where ``window`` is given), by static shapes and the platform alone.
    The kernels move whole lane tiles of positions: a cache whose length
    they do not divide (the chip's compiler refuses a slice of it) is XLA's.
    A block of tokens is the kernel's where it is one slot's, whole tiles of
    tokens, and a ring holds it beside the window (the kernel attends once
    the block is in place); speculation's verify, a few tokens at every
    slot, stays XLA's."""
    kernel = length % TILE == 0 and (T == 1 or (
        B == 1 and T % TOKENS == 0 and T <= length
        and (window is None or length >= window + T)))
    return _decode_impl() if kernel else "xla"


def attend(cache: Dict[str, jax.Array], layer: jax.Array, q: jax.Array,
           k_new: jax.Array, v_new: jax.Array, at: Step,
           windowed: bool = False):
    """Full layer ``layer`` of ``cache`` (``windowed``: window layer
    ``layer``, in the ring) with ``k_new`` / ``v_new`` [B, T, KV, D]
    in place, and q attended over it -> (cache, what q's shape is): q is
    [B, T, KV, D] or, G query heads sharing a kv head, [B, T, KV, G, D]. The
    cache is a scan's carry and, donated, one buffer from the program's
    argument to its result on every path."""
    B, T, KV = q.shape[:3]
    names = WINDOW if windowed else FULL
    window = at.window if windowed else None
    impl = _impl(B, T, cache[names[0]].shape[-1], window)
    if impl == "xla":
        return _attend_xla(cache, names, layer, q, k_new, v_new, *(
            (at.ring_mask, at.ring_hit) if windowed else (at.mask, at.hit)))
    interpret = impl == "pallas_interpret"
    if T > 1:
        k, v = (_place(cache[name], layer, new, at.start[0], windowed)
                for name, new in zip(names, (k_new, v_new)))
        with jax.named_scope("attn.block"):
            out = block_attention(q, k, v, layer, at.start, window=window,
                                  interpret=interpret)
        return {**cache, names[0]: k, names[1]: v}, out
    out, k, v = decode_attention(
        q.reshape(B, KV, -1, q.shape[-1]), k_new[:, 0], v_new[:, 0],
        cache[names[0]], cache[names[1]], layer, at.start, live=at.live,
        window=window, interpret=interpret)
    return {**cache, names[0]: k, names[1]: v}, out.reshape(q.shape)


def _place(leaf: jax.Array, layer, new: jax.Array, start, ring: bool):
    """``leaf`` [L, 1, KV, D, S] with ``new`` [1, T, KV, D] on positions
    ``start .. start + T - 1`` of layer ``layer``, in place and nothing else
    touched: one update of T columns where the block lies inside the cache.
    The update never moves back over valid columns: a block that reaches
    past the end is written over the last T positions with what was there
    kept before it, its tokens past the end dropped, or, in a ring, written
    to the ring's first positions by a second update of T columns."""
    S, T = leaf.shape[-1], new.shape[1]
    cols = new[0].transpose(1, 2, 0).astype(leaf.dtype)[None, None]
    lane = jnp.arange(T)
    place = start % S if ring else start
    at = jnp.clip(place, 0, S - T)
    over = place - at                # columns that do not fit before the end
    cols = jnp.roll(cols, over, axis=-1)

    def update(leaf, at, mine):
        where = (layer, 0, 0, 0, at)
        old = jax.lax.dynamic_slice(leaf, where, (1, 1, *leaf.shape[2:4], T))
        return jax.lax.dynamic_update_slice(
            leaf, jnp.where(mine, cols, old), where)

    leaf = update(leaf, at, lane >= over)
    return update(leaf, 0, lane < over) if ring else leaf


# float32 scores of one product, in elements (512 MiB): a block of tokens
# whose scores against the whole cache would be more attends in blocks of
# queries, one after another
SCORES_AT_ONCE = 1 << 27


def _attention(q, ck, cv, mask):
    """q [B, T, KV, (G,) D] over ck / cv [B, KV, D, S] where ``mask``
    [B, T, S] allows."""
    g = "g" if q.ndim == 5 else ""    # each family the products it had
    scores = jnp.einsum(f"btk{g}d,bkds->bk{g}ts", q, ck).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    mask = jnp.expand_dims(mask, tuple(range(1, q.ndim - 2)))
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum(f"bk{g}ts,bkds->btk{g}d", probs, cv)


def _attend_xla(cache, names, layer, q, k_new, v_new, mask, hit):
    ck, cv = (
        _write(jax.lax.dynamic_index_in_dim(cache[name], layer, 0, False),
               new, hit)
        for name, new in zip(names, (k_new, v_new))
    )
    B, T = q.shape[:2]
    blocks = 1
    while (q.size // q.shape[-1] // blocks * ck.shape[-1] > SCORES_AT_ONCE
           and T % (2 * blocks) == 0):
        blocks *= 2
    if blocks == 1:
        attn = _attention(q, ck, cv, mask)
    else:
        def cut(a):       # [B, T, ..] -> [blocks, B, T / blocks, ..]
            return jnp.moveaxis(
                a.reshape(B, blocks, T // blocks, *a.shape[2:]), 1, 0)

        attn = jax.lax.map(
            lambda qm: _attention(qm[0], ck, cv, qm[1]), (cut(q), cut(mask)))
        attn = jnp.moveaxis(attn, 0, 1).reshape(q.shape)
    # The rows go back into the whole cache once attention has read them:
    # the in-place carry holds only while nothing reads the old rows once
    # the new ones are in. The barrier says so: left to itself the compiler
    # re-reads the old rows inside a later product, and copies the whole
    # cache every layer to keep them (prefill at B = 1).
    attn, ck, cv, cache = jax.lax.optimization_barrier((attn, ck, cv, cache))
    cache = {**cache, **{
        name: jax.lax.dynamic_update_index_in_dim(cache[name], rows, layer, 0)
        for name, rows in zip(names, (ck, cv))
    }}
    return cache, attn


def _write(rows: jax.Array, new: jax.Array, hit: jax.Array) -> jax.Array:
    """rows [B, KV, D, S] with new [B, T, KV, D] at the positions that
    ``hit`` [B, T, S] marks (at most one t a position), the rest as it was.
    The product moves each new column to its position: 1 x value + 0s is the
    value itself, so the cache is written exactly (``HIGHEST`` keeps that
    true of a float32 cache on the TPU; it costs a bfloat16 one nothing)."""
    placed = jnp.einsum(
        "btkd,bts->bkds", new.astype(rows.dtype), hit.astype(rows.dtype),
        precision=jax.lax.Precision.HIGHEST,
    )
    return jnp.where(hit.any(1)[:, None, None, :], placed, rows)


# positions of a latent cache up-projected and scored at once by a block of
# tokens (``_latent_blocks``): the scores are [H, T, this] float32
LATENT_POSITIONS = 1024


def latent_kv(rows, up, q_width: int):
    """The full forward's keys and values of a latent layer, a head its
    own: ``rows`` [B, T, R + Dr] (the normed latent and the rotated shared
    key), ``up`` [R, H, Dn + Dv], queries ``q_width`` = Dn + Dr wide -> (the
    keys' up-projected channels [B, H, T, Dn], the values [B, H, T, Dv], the
    shared key [B, T, Dr] as the rows hold it). What the attention
    dispatcher takes in place of [T, T] scores in XLA: the flash kernels on
    the chip, which read the shared key where it lies, one block a batch
    row for every head of it, so it is repeated into no head. Two products
    over the two halves of ``up``, heads-major each: the kernels fold them
    by a reshape, and a slice of one product's result would be a copy."""
    R = up.shape[0]
    Dn = q_width - (rows.shape[-1] - R)
    with jax.named_scope("mla.up"):
        up = up.astype(rows.dtype)
        k, v = (heads_in(rows[..., :R], w, True)
                for w in (up[..., :Dn], up[..., Dn:]))
    return k, v, rows[..., R:]


def selected_attention(q, k, v, shared, index: Indexed,
                       scale: Optional[float] = None):
    """The full forward's attention of a latent layer with an indexer, over
    ``latent_kv``'s pieces: q [B, H, T, Dn + Dr], k [B, H, T, Dn], v
    [B, H, T, Dv], ``shared`` [B, T, Dr] -> [B, H, T, Dv]. Each query scores
    every position up to its own, keeps the ``index.kept`` largest and
    attends those alone: whole [T, T] arrays in XLA, what a test or a short
    batch can hold (the served path is ``attend_latent``)."""
    T, Dn = q.shape[2], k.shape[-1]
    seen = jnp.tril(jnp.ones((T, T), bool))[None]
    picked = index_select.chosen(
        index_select.scores(index.q, index.weights, index.key), seen,
        index.kept)
    with jax.named_scope("mla.sparse"):
        sc = (jnp.einsum("bhtd,bhsd->bhts", q[..., :Dn], k)
              + jnp.einsum("bhtd,bsd->bhts", q[..., Dn:], shared)
              ).astype(jnp.float32) * (scale or q.shape[-1] ** -0.5)
        probs = jax.nn.softmax(
            jnp.where(picked[:, None], sc, -1e30), axis=-1)
        return jnp.einsum("bhts,bhsd->bhtd", probs.astype(q.dtype), v)


def attend_latent(cache: Dict[str, jax.Array], layer: jax.Array,
                  q: jax.Array, rows_new: jax.Array, up: jax.Array,
                  at: Step, scale: float,
                  index: Optional[Indexed] = None):
    """Latent layer ``layer`` of ``cache`` with ``rows_new`` [B, T, R + Dr]
    in place, and q [B, T, H, Dn + Dr] attended over it through ``up``
    [R, H, Dn + Dv] (a head's ``[Wuk | Wuv]``) -> (cache, [B, T, H, Dv]).
    A decode step absorbs ``up`` into q and into the result and never
    up-projects a row; a block of one slot's tokens (where the decode
    kernel's platform is) up-projects the filled blocks of positions, one
    after another; anything else (the tests' oracle) absorbs over the whole
    cache in XLA. ``index``: the layer's indexer at these tokens. Its keys
    go into the ``"index"`` leaf as the rows go into theirs, and each query
    attends the ``index.kept`` positions it scores highest and no other, by
    whichever of the three routes."""
    B, T, H, Dq = q.shape
    R = up.shape[0]
    Dn = Dq - (rows_new.shape[-1] - R)
    leaf = cache[LATENT]
    impl = _impl(B, T, leaf.shape[-1])
    up = up.astype(q.dtype)
    if impl != "xla" and T > 1:
        start = at.start[0]
        leaf = _place(leaf, layer, rows_new[:, :, None, :], start, False)
        if index is None:
            out = _latent_blocks(leaf, layer, q[0], up, start, Dn, scale)
            return {**cache, LATENT: leaf}, out[None]
        keys = _place_rows(cache[INDEX], layer, index.key[0], start)
        out = _attend_chosen(leaf, keys, layer, q[0], up, index, start,
                             scale, impl == "pallas_interpret")
        return {**cache, LATENT: leaf, INDEX: keys}, out[None]
    with jax.named_scope("mla.up"):
        # q_nope Wuk^T beside the rotated part: what a row is scored against
        qa = jnp.concatenate([
            jnp.einsum("bthd,rhd->bthr", q[..., :Dn], up[..., :Dn]),
            q[..., Dn:]], axis=-1)
    if impl != "xla":
        picked = None
        if index is not None:
            cache, picked = _chosen_of_step(cache, layer, index, at)
        with jax.named_scope("mla.attend" if index is None else "mla.sparse"):
            summed, leaf = latent_decode_attention(
                qa[:, 0], rows_new[:, 0], leaf, layer, at.start, values=R,
                scale=scale, live=at.live, chosen=picked,
                interpret=impl == "pallas_interpret")
            summed = summed[:, None]
    else:
        mask = at.mask
        if index is not None:
            keys = jax.lax.dynamic_index_in_dim(
                cache[INDEX], layer, 0, False)                  # [B, S, Di]
            keys = jnp.where(
                at.hit.any(1)[:, :, None], jnp.einsum(
                    "btd,bts->bsd", index.key.astype(keys.dtype),
                    at.hit.astype(keys.dtype),
                    precision=jax.lax.Precision.HIGHEST), keys)
            mask = index_select.chosen(
                index_select.scores(index.q, index.weights, keys),
                mask, index.kept)
        with jax.named_scope("mla.attend" if index is None else "mla.sparse"):
            held = _write(
                jax.lax.dynamic_index_in_dim(leaf, layer, 0, False),
                rows_new[:, :, None, :], at.hit)[:, 0]         # [B, D, S]
            scores = jnp.einsum("bthd,bds->bhts", qa, held).astype(
                jnp.float32) * scale
            probs = jax.nn.softmax(
                jnp.where(mask[:, None], scores, -1e30), axis=-1)
            summed = jnp.einsum("bhts,brs->bthr", probs.astype(q.dtype),
                                held[:, :R])
            # as ``_attend_xla``: the rows go back once attention has read
            summed, held, leaf = jax.lax.optimization_barrier(
                (summed, held, leaf))
            leaf = jax.lax.dynamic_update_index_in_dim(
                leaf, held[:, None], layer, 0)
        if index is not None:
            summed, keys, cache = jax.lax.optimization_barrier(
                (summed, keys, cache))
            cache = {**cache, INDEX: jax.lax.dynamic_update_index_in_dim(
                cache[INDEX], keys, layer, 0)}
    with jax.named_scope("mla.up"):
        out = jnp.einsum("bthr,rhd->bthd", summed.astype(q.dtype),
                         up[..., Dn:])
    return {**cache, LATENT: leaf}, out


# widths of the cache a chunk chooses and attends over: the narrowest that
# holds the chunk's last position (the choice is passes over [T, width]
# arrays and the kernel's grid steps over the width's blocks, and a 12k
# prompt's chunks fill a third of a 33k cache)
CHOICE_WIDTHS = (4096, 8192, 16384)


def _attend_chosen(leaf, keys, layer, q, up, index: Indexed, start,
                   scale: float, interpret: bool):
    """One slot's block of T tokens at ``start ..``, both their rows in
    place already (``leaf`` [L, 1, 1, R + Dr, S], ``keys`` [L, 1, S, Di]):
    every token's index scores against the keys its tile of queries sees
    (``index_select.scores_of_block``), its choice (``chosen_up_to``), and
    its attention over the chosen rows
    (``ops/block_attention.py:selected_block_attention``) -> [T, H, Dv], all
    three over the narrowest of ``CHOICE_WIDTHS`` that holds the chunk."""
    T, S = q.shape[0], leaf.shape[-1]

    def over(width):
        def attended(_):
            found = index_select.scores_of_block(
                index.q[0], index.weights[0], keys, layer, start,
                width=width, interpret=interpret)
            picked = index_select.chosen_up_to(
                found, start + jnp.arange(T), index.kept, interpret=interpret)
            with jax.named_scope("mla.sparse"):
                return selected_block_attention(
                    q, up, leaf, picked, layer, start, scale=scale,
                    interpret=interpret)
        return attended

    widths = [w for w in CHOICE_WIDTHS if w < S] + [S]
    return jax.lax.switch(
        sum((start + T > w).astype(jnp.int32) for w in widths[:-1]),
        [over(w) for w in widths], None)


def _place_rows(leaf: jax.Array, layer, new: jax.Array, start):
    """``_place`` for a position-major leaf [L, 1, S, D]: ``new`` [T, D] on
    positions ``start .. start + T - 1`` of layer ``layer``; a block that
    reaches past the end is written over the last T positions with what
    was there kept before it, its tokens past the end dropped."""
    S, T = leaf.shape[-2], new.shape[0]
    at = jnp.clip(start, 0, S - T)
    over = start - at                 # rows that do not fit before the end
    where = (layer, 0, at, 0)
    old = jax.lax.dynamic_slice(leaf, where, (1, 1, T, new.shape[1]))
    rows = jnp.roll(new.astype(leaf.dtype), over, axis=0)[None, None]
    return jax.lax.dynamic_update_slice(leaf, jnp.where(
        (jnp.arange(T) >= over)[None, None, :, None], rows, old), where)


def _chosen_of_step(cache, layer, index: Indexed, at: Step):
    """A decode step's choice: each live slot's one query scored against
    the keys it has filled (``index_select.scores_of_step``: an idle slot
    and a block past a slot's length are not read, and their scores hold
    nothing) and its own new one, and the new key of every slot that decodes
    put into its place of the ``"index"`` leaf (any other slot keeps its
    own, as under the decode kernel) -> (cache, [B, S] bool: the positions
    it reads, its own new one among them or not; an idle slot's row means
    nothing). The keys are scored as they were and the new one beside them,
    and a slot's row is one update in place: a scatter is laid out anew
    around the whole leaf, twice a layer."""
    keys = cache[INDEX]
    B, S, Di = keys.shape[1:]
    lens = at.start
    new = index.key[:, 0].astype(keys.dtype)                     # [B, Di]
    live = jnp.arange(B + 1, dtype=jnp.int32) if at.live is None else at.live
    slots, count = live[:B], live[B]    # ``live_slots``: the first name them
    interpret = _decode_impl() == "pallas_interpret"
    found = index_select.scores_of_step(
        index.q[:, 0], index.weights[:, 0], keys, layer, lens, live,
        interpret=interpret)
    own = index_select.scores(index.q, index.weights, new[:, None])[:, 0]
    found = jnp.where(jnp.arange(S)[None, :] == lens[:, None], own, found)
    picked = index_select.chosen_up_to(
        found, lens, index.kept, interpret=interpret)
    with jax.named_scope("mla.index"):
        for v in range(B):
            b = slots[v]
            where = (layer, b, jnp.minimum(lens[b], S - 1), 0)
            old = jax.lax.dynamic_slice(keys, where, (1, 1, 1, Di))
            # a position past the end lands nowhere
            mine = (v < count) & (lens[b] < S)
            keys = jax.lax.dynamic_update_slice(keys, jnp.where(
                mine, new[b].reshape(old.shape), old), where)
    return {**cache, INDEX: keys}, picked


def _latent_blocks(leaf, layer, q, up, start, Dn: int, scale: float):
    """q [T, H, Dn + Dr] at positions ``start ..`` over layer ``layer`` of
    ``leaf`` [L, 1, 1, R + Dr, S], the block's own rows in place already ->
    [T, H, Dv]. The positions come ``LATENT_POSITIONS`` at a time, up to
    the block that holds the last token's and no further: each block's rows
    are up-projected to H heads' keys and values, scored under the causal
    mask and folded into a running softmax (float32). No [T, S] array."""
    T, H, _ = q.shape
    R, S = up.shape[0], leaf.shape[-1]
    D = leaf.shape[-2]
    n = next(n for n in (LATENT_POSITIONS, 512, 256, TILE, S) if S % n == 0)
    f32 = jnp.float32
    pos = start + jnp.arange(T)
    q_nope, q_rope = q[..., :Dn], q[..., Dn:]

    def block(i, carry):
        m, l, acc = carry
        rows = jax.lax.dynamic_slice(
            leaf, (layer, 0, 0, 0, i * n), (1, 1, 1, D, n))[0, 0, 0]
        with jax.named_scope("mla.up"):
            kv = jnp.einsum("rs,rhd->hds", rows[:R], up)      # [H, .., n]
        sc = (jnp.einsum("thd,hds->hts", q_nope, kv[:, :Dn])
              + jnp.einsum("thd,ds->hts", q_rope, rows[R:])
              ).astype(f32) * scale
        seen = (i * n + jnp.arange(n))[None, :] <= pos[:, None]
        sc = jnp.where(seen, sc, -1e30)
        m_next = jnp.maximum(m, sc.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_next)
        p = jnp.where(seen, jnp.exp(sc - m_next), 0.0)
        acc = alpha * acc + jnp.einsum(
            "hts,hds->htd", p.astype(q.dtype), kv[:, Dn:],
            preferred_element_type=f32)
        return m_next, alpha * l + p.sum(-1, keepdims=True), acc

    with jax.named_scope("mla.attend"):
        blocks = jnp.minimum((start + T - 1) // n + 1, S // n)
        _, l, acc = jax.lax.fori_loop(0, blocks, block, (
            jnp.full((H, T, 1), -1e30, f32), jnp.zeros((H, T, 1), f32),
            jnp.zeros((H, T, up.shape[-1] - Dn), f32)))
    return jnp.swapaxes(acc / l, 0, 1).astype(q.dtype)


def recur(carried, entering, gates, layer, recurrence, shape, chunk: int):
    """A state layer's mixer between its two projections, whatever its
    recurrence: ``entering`` [B, T, C] (what enters the convolution) through
    the layer's ``conv_w`` [C, K] (and ``conv_b`` [C], where it has one),
    then ``recurrence`` (an ``ops/ssm.py:Recurrence``: Mamba-2's, the gated
    delta rule's) over what left it and ``gates``, the family's per-step
    numbers (a pytree of [B, T, H] or [B, T, H, ..] float32: a decay a head
    or a channel) -> (cache, y [B, T, H, P]
    float32). ``shape`` is a slot's state (H, ..), ``chunk`` the scan's.
    Shared, and so written here once: the convolution's tail, the steps
    that are no token (their gates are zeroed, which every recurrence takes
    as "leave the state as it is"), the cache's read and write, and which of
    the scan, the one-token step and its kernel runs.

    ``carried`` is (cache, the layer's index among the state layers, the
    ``Step``): the recurrence starts from the cache's state and tail and
    leaves there what they are after the last real token. None: from zeros
    (the full forward), and the cache returned is None."""
    B, T, C = entering.shape
    f32 = jnp.float32
    cache, index, at = carried or (None, None, None)
    real = None if at is None else at.real
    taps = layer["conv_w"].shape[-1]
    scope = recurrence.scope
    if cache is None:
        tail = jnp.zeros((B, taps - 1, C), entering.dtype)
        state = jnp.zeros((B, *shape), f32)
    else:
        tail = jax.lax.dynamic_index_in_dim(
            cache[STATE[1]], index, 0, False).reshape(B, taps - 1, C)
    with jax.named_scope(f"{scope}.conv"):
        mixed, tail = ssm.conv(entering, tail, layer["conv_w"],
                               layer.get("conv_b"), real)
    if real is not None:    # a step that is no token leaves the state
        token = jnp.arange(T)[None, :] < real[:, None]
        gates = jax.tree.map(lambda g: jnp.where(
            token.reshape(B, T, *(1,) * (g.ndim - 2)), g, 0.0), gates)
    kernel = cache is not None and T == 1 and _decode_impl() != "xla"
    if T == 1:
        mixed, gates = mixed[:, 0], jax.tree.map(lambda g: g[:, 0], gates)
    if kernel:
        with jax.named_scope(f"{scope}.update"):
            y, states = recurrence.kernel(
                layer, cache[STATE[0]], index, mixed, gates, at.live,
                _decode_impl() == "pallas_interpret")
    else:
        if cache is not None:
            state = jax.lax.dynamic_index_in_dim(
                cache[STATE[0]], index, 0, False)
        if T == 1:
            with jax.named_scope(f"{scope}.update"):
                y, state = recurrence.step(
                    layer, state, mixed, gates,
                    None if real is None else real > 0)
        else:
            with jax.named_scope(f"{scope}.scan"):
                y, state = recurrence.scan(layer, mixed, gates, state, chunk)
    if T == 1:
        y = y[:, None]
    if cache is None:
        return None, y
    if not kernel:
        states = jax.lax.dynamic_update_index_in_dim(
            cache[STATE[0]], state, index, 0)
    return {**cache, STATE[0]: states,
            STATE[1]: jax.lax.dynamic_update_index_in_dim(
                cache[STATE[1]], tail.reshape(B, -1), index, 0)}, y
