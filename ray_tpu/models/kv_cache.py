"""The KV cache of incremental decoding: one contract for every family.

``{"k", "v"}``, each ``[L, B, KV, D, S]``: layer, slot, kv head, head
size, position. The engine sees an opaque pytree with the slot on axis 1;
only ``forward_cached`` of a model module reads the other axes.

Position is the minor axis because of how the TPU stores an array: it tiles
the two minor dimensions (8, 128), so ``[.., S, KV, D]`` with (25, 64) minor
would pad 2.4x, the compiler stores it position-minor instead and transposes
every layer's slice on its way to the attention products and back. Held
position-minor to begin with, the slice feeds both products as it lies.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def init_kv_cache(num_layers: int, batch: int, kv_heads: int, head_dim: int,
                  max_len: int, dtype) -> Dict[str, jax.Array]:
    shape = (num_layers, batch, kv_heads, head_dim, max_len)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


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


def write_positions(start: jax.Array, T: int, S: int) -> jax.Array:
    """[B, T, S] bool: token t of slot b lands on position ``start[b] + t``.
    A position past the end marks nothing, so such a token is dropped (a
    ``dynamic_update_slice`` would move the whole write back over valid
    rows)."""
    pos = start[:, None] + jnp.arange(T)[None, :]
    return pos[:, :, None] == jnp.arange(S)[None, None, :]


def read_layer(cache: Dict[str, jax.Array], layer: jax.Array,
               k_new: jax.Array, v_new: jax.Array, hit: jax.Array,
               ) -> Tuple[jax.Array, jax.Array]:
    """Layer ``layer``'s K and V rows [B, KV, D, S] with ``k_new``/``v_new``
    [B, T, KV, D] in place, for the attention products to read."""
    return tuple(
        _write(jax.lax.dynamic_index_in_dim(cache[name], layer, 0, False),
               new, hit)
        for name, new in (("k", k_new), ("v", v_new))
    )


def write_layer(cache: Dict[str, jax.Array], layer: jax.Array,
                k_rows: jax.Array, v_rows: jax.Array, after: jax.Array,
                ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Put the rows ``read_layer`` gave back into the whole cache, once
    ``after`` (what attention made of them) is computed; returns (cache,
    after). The cache is a scan's carry and, donated, one buffer from the
    program's argument to its result: that holds only while nothing reads
    the old rows once the new ones are in. The barrier says so: left to
    itself the compiler re-reads the old rows inside a later product, and
    copies the whole cache every layer to keep them (prefill at B = 1)."""
    after, k_rows, v_rows, cache = jax.lax.optimization_barrier(
        (after, k_rows, v_rows, cache)
    )
    cache = {
        name: jax.lax.dynamic_update_index_in_dim(cache[name], rows, layer, 0)
        for name, rows in (("k", k_rows), ("v", v_rows))
    }
    return cache, after
