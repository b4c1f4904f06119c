"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference implements NO sequence parallelism anywhere (grep-verified in
SURVEY.md §5 — "absent in the reference"); this module is the TPU-native
answer the survey prescribes: the mesh's "seq" axis holds sequence chunks,
and attention runs as a ring over ICI neighbors (``ppermute`` is literally a
neighbor hop on the TPU torus), overlapping K/V transfer with blockwise
compute. Ulysses (head-sharded all-to-all) is the low-latency alternative
when heads ≥ ring size.

Both are shard_map programs over one mesh axis and differentiable end-to-end
(scan-based accumulation; online softmax in f32).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ray_tpu.ops.attention import NEG_INF


def _whole_sequence(q, k, v, causal: bool, impl: str = "xla"):
    """Attention over a sequence that is whole on this device, [B, T, H, D]
    as this module keeps its arrays (its shards are of the sequence):
    through ``ops.attention``, which is heads-major."""
    from ray_tpu.ops.attention import attention_xla, flash_attention

    q, k, v = (a.swapaxes(1, 2) for a in (q, k, v))
    out = (flash_attention(q, k, v, causal) if impl == "flash"
           else attention_xla(q, k, v, causal=causal))
    return out.swapaxes(1, 2)


def _blockwise_piece(q, k, v, scale, q_chunk, kv_chunk, t_local, causal):
    """Attention logits piece between the local Q chunk and one K/V chunk,
    returning (unnormalized o, running max m, running denom l) inputs for
    online-softmax merging. Shapes: q [B,T,H,D], k/v [B,T,H,D]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        # chunk-level: kv_chunk > q_chunk → fully masked;
        # kv_chunk == q_chunk → intra-chunk causal; else unmasked.
        q_pos = q_chunk * t_local + jax.lax.broadcasted_iota(
            jnp.int32, (t_local, t_local), 0
        )
        k_pos = kv_chunk * t_local + jax.lax.broadcasted_iota(
            jnp.int32, (t_local, t_local), 1
        )
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [B,H,T,1]
    # Guard fully-masked rows (exp(NEG_INF - NEG_INF) = 1 would poison l).
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    p = jnp.where(m <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, m_safe.transpose(0, 2, 1, 3), l.transpose(0, 2, 1, 3)  # m,l → [B,T,H,1]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = True,
    qkv_spec: Optional[P] = None,
) -> jax.Array:
    """Ring attention over a sharded sequence axis.

    q/k/v: [batch, seq, heads, head_dim] with seq sharded over ``axis``.
    Each step computes blockwise attention against the resident K/V chunk and
    rotates K/V one ICI hop (ppermute), accumulating with online softmax.
    """
    if qkv_spec is None:
        qkv_spec = P(("data", "fsdp"), axis, "tensor", None)
    n = mesh.shape[axis]
    if n == 1:
        return _whole_sequence(q, k, v, causal)

    scale = q.shape[-1] ** -0.5

    def local_fn(q, k, v):
        # q,k,v local chunks: [B, T/n, H, D]
        my = jax.lax.axis_index(axis)
        t_local = q.shape[1]
        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, _):
            o_acc, m_acc, l_acc, k_cur, v_cur, src = carry
            o, m, l = _blockwise_piece(
                q, k_cur, v_cur, scale, my, src, t_local, causal
            )
            # online-softmax merge of (o_acc, m_acc, l_acc) with (o, m, l)
            m_new = jnp.maximum(m_acc, m)
            a1 = jnp.exp(m_acc - m_new)
            a2 = jnp.exp(m - m_new)
            o_new = o_acc * a1 + o * a2
            l_new = l_acc * a1 + l * a2
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            src_nxt = jax.lax.rem(src - 1 + n, n)
            return (o_new, m_new, l_new, k_nxt, v_nxt, src_nxt), None

        B, T, H, D = q.shape
        o0 = jnp.zeros((B, T, H, D), jnp.float32)
        m0 = jnp.full((B, T, H, 1), NEG_INF / 2, jnp.float32)
        l0 = jnp.zeros((B, T, H, 1), jnp.float32)
        (o, m, l, _, _, _), _ = jax.lax.scan(
            step, (o0, m0, l0, k, v, my), None, length=n
        )
        return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = True,
    qkv_spec: Optional[P] = None,
    impl: str = "xla",
) -> jax.Array:
    """Ulysses-style sequence parallelism: all_to_all swaps the sharded axis
    from sequence to heads, runs full-sequence attention on 1/n of the heads,
    and swaps back. One all_to_all each way (lower latency than a ring when
    heads % n == 0 and the full sequence fits)."""
    if qkv_spec is None:
        qkv_spec = P(("data", "fsdp"), axis, "tensor", None)
    n = mesh.shape[axis]
    if n == 1:
        return _whole_sequence(q, k, v, causal)
    if q.shape[2] % n != 0:
        raise ValueError(f"heads {q.shape[2]} not divisible by {axis}={n}")

    def local_fn(q, k, v):
        # local: [B, T/n, H, D] → all_to_all → [B, T, H/n, D]
        def swap_in(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def swap_out(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        return swap_out(_whole_sequence(
            swap_in(q), swap_in(k), swap_in(v), causal, impl))

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v)
