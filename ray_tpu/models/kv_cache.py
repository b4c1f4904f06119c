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
over what is filled. Two paths, parted by static shapes alone. A decode step
(T == 1; S a multiple of the chip's 128 lanes) on a TPU is one kernel (``ops/decode_attention.py``) over the whole
cache that reads the filled positions and writes one tile a slot, of the
slots it is told decode (``Step.live``) and of no other: a slot that does
not decode keeps its cache as it is and gets zeros for its row. A block
of tokens (prefill at B = 1, speculation's verify) takes the layer's slice
out, writes it whole and puts it back: the right cost where a block of
columns lands in a one-slot cache and the query block feeds the MXU.

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
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssm
from ray_tpu.ops.decode_attention import TILE, decode_attention, live_slots


FULL, WINDOW = ("k", "v"), ("k_window", "v_window")
STATE = ("ssm", "conv")


def ring_length(window: int, block: int, max_len: int) -> int:
    """Positions a window layer's ring holds where one call writes at most
    ``block`` tokens: whole lane tiles where it has one (the decode kernel
    moves tiles), and never more than a full layer would hold."""
    ring = window + block
    if ring >= TILE:
        ring = -(-ring // TILE) * TILE
    return min(ring, max_len)


def init_kv_cache(num_layers: int, batch: int, kv_heads: int, head_dim: int,
                  max_len: int, dtype, window_layers: int = 0,
                  ring: int = 0, state_layers: int = 0,
                  state=None) -> Dict[str, jax.Array]:
    """``num_layers`` full layers of ``max_len`` positions,
    ``window_layers`` rings of ``ring`` and ``state_layers`` states:
    ``state`` names each of their leaves' (shape a slot, dtype)."""
    cache = {name: jnp.zeros((state_layers, batch, *shape), kind)
             for name, (shape, kind) in (state or {}).items()
             if state_layers}
    for names, layers, length in ((FULL, num_layers, max_len),
                                  (WINDOW, window_layers, ring)):
        if layers or (names is FULL and not window_layers):
            shape = (layers, batch, kv_heads, head_dim, length)
            cache.update({n: jnp.zeros(shape, dtype) for n in names})
    return cache


class Step(NamedTuple):
    """What every layer of one ``forward_cached`` shares: where each slot's
    tokens start, which positions each token sees and which it lands on,
    in the full layers' cache and in the window layers' ring (None where
    the model has no such layer). ``live``: the slots whose tokens are
    tokens, as the decode kernel takes them (``live_slots``; None: every
    slot). What the caller says of a slot, never read off its length. The
    kernel leaves any other slot alone; the XLA path computes every slot and
    the caller drops the rows it did not ask for. ``real``: how many of a
    slot's T tokens are tokens (None: all), for a state layer, which must
    not step on the rest."""
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
    pos = (start[:, None] + jnp.arange(T)[None, :])[:, :, None]
    mask = hit = ring_mask = ring_hit = None
    if FULL[0] in cache:
        key_pos = jnp.arange(cache[FULL[0]].shape[-1])[None, None, :]
        mask, hit = key_pos <= pos, pos == key_pos
    if WINDOW[0] in cache:
        R = cache[WINDOW[0]].shape[-1]
        slot = jnp.arange(R)[None, None, :]
        last = pos[:, -1:, :]
        held = last - (last - slot) % R
        ring_mask = (held >= 0) & (held <= pos) & (held > pos - window)
        ring_hit = pos % R == slot
    return Step(start, mask, hit, window, ring_mask, ring_hit,
                None if live is None else live_slots(live), real)


def _decode_impl() -> str:
    """How a decode step runs, by the platform alone: the kernel on a TPU
    (one that fails to lower there raises, it never gives way), XLA
    elsewhere. ``pallas_interpret`` is the tests'."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def attend(cache: Dict[str, jax.Array], layer: jax.Array, q: jax.Array,
           k_new: jax.Array, v_new: jax.Array, at: Step,
           windowed: bool = False):
    """Full layer ``layer`` of ``cache`` (``windowed``: window layer
    ``layer``, in the ring) with ``k_new`` / ``v_new`` [B, T, KV, D]
    in place, and q attended over it -> (cache, what q's shape is): q is
    [B, T, KV, D] or, G query heads sharing a kv head, [B, T, KV, G, D]. The
    cache is a scan's carry and, donated, one buffer from the program's
    argument to its result on either path."""
    B, T, KV = q.shape[:3]
    names = WINDOW if windowed else FULL
    # the kernel moves whole lane tiles of positions: a cache whose length
    # they do not divide (the chip's compiler refuses a slice of it) keeps
    # the XLA path, as a block of tokens does
    kernel = T == 1 and cache[names[0]].shape[-1] % TILE == 0
    impl = _decode_impl() if kernel else "xla"
    if impl == "xla":
        return _attend_xla(cache, names, layer, q, k_new, v_new, *(
            (at.ring_mask, at.ring_hit) if windowed else (at.mask, at.hit)))
    out, k, v = decode_attention(
        q.reshape(B, KV, -1, q.shape[-1]), k_new[:, 0], v_new[:, 0],
        cache[names[0]], cache[names[1]], layer, at.start, live=at.live,
        window=at.window if windowed else None,
        interpret=impl == "pallas_interpret")
    return {**cache, names[0]: k, names[1]: v}, out.reshape(q.shape)


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


def recur(carried, entering, gates, layer, recurrence, shape, chunk: int):
    """A state layer's mixer between its two projections, whatever its
    recurrence: ``entering`` [B, T, C] (what enters the convolution) through
    the layer's ``conv_w`` [C, K] (and ``conv_b`` [C], where it has one),
    then ``recurrence`` (an ``ops/ssm.py:Recurrence``: Mamba-2's, the gated
    delta rule's) over what left it and ``gates``, the family's per-step
    numbers (a pytree of [B, T, H] float32) -> (cache, y [B, T, H, P]
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
        token = jnp.arange(T)[None, :, None] < real[:, None, None]
        gates = jax.tree.map(lambda g: jnp.where(token, g, 0.0), gates)
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
