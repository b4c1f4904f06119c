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
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# bits of the key decided a pass of ``chosen``: 32 / BITS passes, each
# 2^BITS - 1 compares an element
BITS = 4
# positions scored at once by a block of tokens (``scores_of_block``): the
# products before the sum over heads are [T, Hi, this] float32
POSITIONS = 512


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


def chosen(scores, visible, kept: int):
    """``scores`` [.., S] float32, ``visible`` [.., S] bool (the positions a
    row's query may see: at least one) -> [.., S] bool, True at the
    ``min(kept, visible positions)`` largest visible scores of each row, a
    tie to the lower position. Exact: the count of True in a row is that
    minimum, no more and no fewer."""
    with jax.named_scope("mla.select"):
        u = jnp.where(visible, _ordered(scores), jnp.uint32(0))
        k = jnp.minimum(visible.sum(-1, dtype=jnp.int32), kept)[..., None]
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
    [B, T, S] float32. A decode step's, and the oracle's."""
    with jax.named_scope("mla.index"):
        s = jnp.einsum("bthd,bsd->bths", q, keys.astype(q.dtype),
                       preferred_element_type=jnp.float32)
        return _weighted(weights, s)


def scores_of_block(q, weights, leaf, layer, filled):
    """The index scores of one slot's block of tokens against the first
    ``filled`` positions of layer ``layer`` of ``leaf`` [L, 1, S, Di]: q
    [T, Hi, Di], ``weights`` [T, Hi] float32 -> [T, S] float32. The
    positions come ``POSITIONS`` at a time (or as many as divide S), up to
    the block that holds the last filled one, so that the [T, Hi, n]
    products before the sum over heads are the only array a head wide; the
    positions of no block read -inf."""
    T = q.shape[0]
    S, Di = leaf.shape[-2:]
    n = next(n for n in (POSITIONS, 256, 128, S) if S % n == 0)

    def block(i, out):
        keys = jax.lax.dynamic_slice(
            leaf, (layer, 0, i * n, 0), (1, 1, n, Di))[0, 0]
        s = jnp.einsum("thd,sd->ths", q, keys.astype(q.dtype),
                       preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice(
            out, _weighted(weights, s), (0, i * n))

    with jax.named_scope("mla.index"):
        return jax.lax.fori_loop(
            0, jnp.minimum((filled - 1) // n + 1, S // n), block,
            jnp.full((T, S), -jnp.inf, jnp.float32))
