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
cache that reads the filled positions and writes one tile a slot. A block
of tokens (prefill at B = 1, speculation's verify) takes the layer's slice
out, writes it whole and puts it back: the right cost where a block of
columns lands in a one-slot cache and the query block feeds the MXU.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.decode_attention import TILE, decode_attention


def init_kv_cache(num_layers: int, batch: int, kv_heads: int, head_dim: int,
                  max_len: int, dtype) -> Dict[str, jax.Array]:
    shape = (num_layers, batch, kv_heads, head_dim, max_len)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


class Step(NamedTuple):
    """What every layer of one ``forward_cached`` shares: where each slot's
    tokens start, which positions each token sees and which it lands on."""
    start: jax.Array   # [B] int32
    mask: jax.Array    # [B, T, S] bool
    hit: jax.Array     # [B, T, S] bool


def step(start: jax.Array, T: int, S: int) -> Step:
    """Token t of slot b sits at position ``start[b] + t``, sees the keys up
    to itself and lands on its own position. A position past the end marks
    nothing, so such a token is dropped (a ``dynamic_update_slice`` would
    move the whole write back over valid rows)."""
    pos = (start[:, None] + jnp.arange(T)[None, :])[:, :, None]
    key_pos = jnp.arange(S)[None, None, :]
    return Step(start, key_pos <= pos, pos == key_pos)


def _decode_impl() -> str:
    """How a decode step runs, by the platform alone: the kernel on a TPU
    (one that fails to lower there raises, it never gives way), XLA
    elsewhere. ``pallas_interpret`` is the tests'."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def attend(cache: Dict[str, jax.Array], layer: jax.Array, q: jax.Array,
           k_new: jax.Array, v_new: jax.Array, at: Step):
    """Layer ``layer`` of ``cache`` with ``k_new`` / ``v_new`` [B, T, KV, D]
    in place, and q attended over it -> (cache, what q's shape is): q is
    [B, T, KV, D] or, G query heads sharing a kv head, [B, T, KV, G, D]. The
    cache is a scan's carry and, donated, one buffer from the program's
    argument to its result on either path."""
    B, T, KV = q.shape[:3]
    # the kernel moves whole lane tiles of positions: a cache whose length
    # they do not divide (the chip's compiler refuses a slice of it) keeps
    # the XLA path, as a block of tokens does
    kernel = T == 1 and cache["k"].shape[-1] % TILE == 0
    impl = _decode_impl() if kernel else "xla"
    if impl == "xla":
        return _attend_xla(cache, layer, q, k_new, v_new, at)
    out, k, v = decode_attention(
        q.reshape(B, KV, -1, q.shape[-1]), k_new[:, 0], v_new[:, 0],
        cache["k"], cache["v"], layer, at.start,
        interpret=impl == "pallas_interpret")
    return {"k": k, "v": v}, out.reshape(q.shape)


def _attend_xla(cache, layer, q, k_new, v_new, at: Step):
    ck, cv = (
        _write(jax.lax.dynamic_index_in_dim(cache[name], layer, 0, False),
               new, at.hit)
        for name, new in (("k", k_new), ("v", v_new))
    )
    g = "g" if q.ndim == 5 else ""    # each family the products it had
    scores = jnp.einsum(f"btk{g}d,bkds->bk{g}ts", q, ck).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    mask = jnp.expand_dims(at.mask, tuple(range(1, q.ndim - 2)))
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    attn = jnp.einsum(f"bk{g}ts,bkds->btk{g}d", probs, cv)
    # The rows go back into the whole cache once attention has read them:
    # the in-place carry holds only while nothing reads the old rows once
    # the new ones are in. The barrier says so: left to itself the compiler
    # re-reads the old rows inside a later product, and copies the whole
    # cache every layer to keep them (prefill at B = 1).
    attn, ck, cv, cache = jax.lax.optimization_barrier((attn, ck, cv, cache))
    cache = {
        name: jax.lax.dynamic_update_index_in_dim(cache[name], rows, layer, 0)
        for name, rows in (("k", ck), ("v", cv))
    }
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
