"""Attention ops: XLA reference impl + pallas TPU flash-attention kernel.

The reference framework has no attention code of its own (it delegates to
vLLM/torch — SURVEY.md §2.3/§5); in a TPU-native stack the kernel layer is
ours. Design:

- ``attention_xla``: einsum softmax attention. XLA fuses this well on TPU and
  it is the autodiff path.
- ``flash_attention``: blockwise online-softmax pallas kernel (VMEM-resident
  q/k/v blocks, f32 accumulators, causal short-circuit per block row).
  Forward AND backward are pallas (FlashAttention-2-style tiling): the
  forward saves per-row logsumexp; the backward streams K/V (dq) and Q/dO
  (dk/dv) blocks and never materializes the [Tq, Tk] score matrix.
- ``attention``: dispatcher — pallas on TPU, XLA elsewhere; tests run the
  same kernel code on the CPU mesh through ``impl="flash_interpret"``.

Shapes follow [batch, seq, heads, head_dim] throughout.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bias: Optional[jax.Array] = None,
    q_offset: int | jax.Array = 0,
    kv_offset: int | jax.Array = 0,
) -> jax.Array:
    """Dense attention. q: [B, Tq, H, D]; k/v: [B, Tk, Hkv, D].

    Supports grouped-query attention (H a multiple of Hkv) and absolute
    position offsets so callers holding only a chunk of the sequence (ring /
    blockwise) mask correctly.
    """
    B, Tq, H, D = q.shape
    _, Tk, Hkv, _ = k.shape
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = D ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0) + q_offset
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1) + kv_offset
        mask = q_pos >= k_pos
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# --------------------------------------------------------------------- pallas

DEFAULT_BLOCK_Q = 512  # swept on v5e (B=32, T=1024, D=64): 512/512 runs the
DEFAULT_BLOCK_K = 512  # fwd 23% and fwd+bwd 23% faster than 256/256


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, block_k: int,
                  causal: bool, scale: float, seq_k: int):
    """One (batch*head, q_block) program: stream K/V blocks with online
    softmax. Block shapes: q/o [1, Bq, D], k/v [1, Tk, D], lse [1, 8, Bq].
    The logsumexp row statistics (written only when the training path asks
    for them) feed the pallas backward."""
    q_idx = pl.program_id(1)
    block_q = q_ref.shape[1]
    d = q_ref.shape[2]
    # Operands stay in the input dtype (bf16): the MXU runs low-precision
    # multiplies with f32 accumulation (preferred_element_type) at ~2x the
    # f32xf32 rate — casting up front would halve kernel throughput. The
    # scale is applied to the f32 scores, not the bf16 q (no rounding).
    q = q_ref[0]  # [Bq, D]

    num_k_blocks = pl.cdiv(seq_k, block_k)
    if causal:
        # Highest K block this Q block row can see (short-circuits the rest).
        last_block = ((q_idx + 1) * block_q - 1) // block_k + 1
        num_iter = jnp.minimum(num_k_blocks, last_block)
    else:
        num_iter = num_k_blocks

    def body(i, carry):
        o_acc, m, l = carry
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jnp.dot(q, k_blk.T,
                    preferred_element_type=jnp.float32) * scale  # [Bq, Bk]
        k_pos = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        # Inputs are padded to block multiples; mask keys past the true
        # sequence end so the pad rows never contribute.
        mask = k_pos < seq_k
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # p in [0, 1]: bf16 rounding is harmless and keeps PV on the fast
        # MXU path (f32 accumulator preserves the sum's precision).
        o_new = o_acc * alpha + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    o_acc, m, l = jax.lax.fori_loop(0, num_iter, body, (o0, m0, l0))
    o_ref[0] = (o_acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_ref is not None:
        # lse = m + log(l). Stored 8x-replicated on the sublane dim: mosaic
        # requires block shapes (8, 128)-divisible, so a [Bq]-vector per
        # program rides as an [8, Bq] tile (negligible bytes, legal layout).
        lse = jnp.maximum(m, NEG_INF) + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[0] = jnp.broadcast_to(lse[:, 0][None, :], (8, block_q))


def _flash_fwd_impl(q, k, v, *, causal: bool, block_q: int, block_k: int,
                    interpret: bool, with_lse: bool = False):
    B, Tq, H, D = q.shape
    _, Tk, Hkv, _ = k.shape
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    scale = D ** -0.5
    # Pad sequences to block multiples: in-kernel dynamic slices on a
    # non-multiple tail would clamp and silently re-read earlier rows.
    # Pad keys are masked in-kernel via seq_k; pad q rows are sliced off.
    Tq_p = block_q * ((Tq + block_q - 1) // block_q)
    Tk_p = block_k * ((Tk + block_k - 1) // block_k)
    if Tq_p != Tq:
        q = jnp.pad(q, ((0, 0), (0, Tq_p - Tq), (0, 0), (0, 0)))
    if Tk_p != Tk:
        k = jnp.pad(k, ((0, 0), (0, Tk_p - Tk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tk_p - Tk), (0, 0), (0, 0)))
    # Fold batch and heads into the grid's leading dim.
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq_p, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Tk_p, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Tk_p, D)
    grid = (B * H, Tq_p // block_q)
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, scale=scale, seq_k=Tk
    )
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, Tk_p, D), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, Tk_p, D), lambda b, i: (b, 0, 0)),
    ]
    o_shape = jax.ShapeDtypeStruct((B * H, Tq_p, D), q.dtype)
    o_spec = pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0))
    if with_lse:
        out, lse = pl.pallas_call(
            kernel,
            out_shape=(
                o_shape,
                jax.ShapeDtypeStruct((B * H, 8, Tq_p), jnp.float32),
            ),
            grid=grid,
            in_specs=in_specs,
            out_specs=(
                o_spec,
                pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
            ),
            interpret=interpret,
        )(qf, kf, vf)
    else:
        # Inference/no-grad path: skip the LSE output entirely (it would be
        # pure wasted write bandwidth on every serving forward).
        out = pl.pallas_call(
            kernel,
            out_shape=o_shape,
            grid=grid,
            in_specs=in_specs,
            out_specs=o_spec,
            interpret=interpret,
        )(qf, kf, vf)
        lse = None
    out = out.reshape(B, H, Tq_p, D).transpose(0, 2, 1, 3)
    if Tq_p != Tq:
        out = out[:, :Tq]
    if with_lse:
        return out, lse  # lse stays in [B*H, Tq_p] layout for the backward
    return out


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          scale: float, seq_q: int, seq_k: int):
    """One (batch*head, k_block) program: accumulate dK/dV for this key
    block by streaming Q/dO blocks. Shapes: k/v/dk/dv [1, Bk, D];
    q/do [1, Tq, D]; lse/delta [1, 8, Tq] (row 0 is the data; the 8 rows
    are sublane replication for mosaic's block-shape rules)."""
    k_idx = pl.program_id(1)
    block_k = k_ref.shape[1]
    d = k_ref.shape[2]
    # bf16 operands + f32 accumulation on every dot (see _flash_kernel).
    k = k_ref[0]  # [Bk, D]
    v = v_ref[0]

    num_q_blocks = pl.cdiv(seq_q, block_q)
    if causal:
        # Lowest Q block that can see this K block (earlier ones are fully
        # masked): first q with q_pos >= k_idx*block_k.
        start = (k_idx * block_k) // block_q
    else:
        start = 0

    def body(i, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        s = scale * jnp.dot(q_blk, k.T, preferred_element_type=jnp.float32)
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = (q_pos < seq_q) & (k_pos < seq_k)
        if causal:
            mask = mask & (q_pos >= k_pos)
        # exp(NEG_INF - lse) underflows to 0 for masked/pad rows; force it
        # for bit-exact zeros.
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)  # [Bq, Bk]
        pcast = p.astype(do_blk.dtype)
        dv_new = dv_acc + jnp.dot(pcast.T, do_blk,
                                  preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_new = dk_acc + jnp.dot(ds.astype(q_blk.dtype).T, q_blk,
                                  preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, num_q_blocks, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, causal: bool, scale: float,
                         seq_k: int):
    """One (batch*head, q_block) program: accumulate dQ for this query block
    by streaming K/V blocks. Shapes: q/do/dq [1, Bq, D]; k/v [1, Tk, D];
    lse/delta [1, 8, Bq] (row 0 is the data)."""
    q_idx = pl.program_id(1)
    block_q = q_ref.shape[1]
    d = q_ref.shape[2]
    # bf16 operands + f32 accumulation on every dot (see _flash_kernel).
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    num_k_blocks = pl.cdiv(seq_k, block_k)
    if causal:
        last_block = ((q_idx + 1) * block_q - 1) // block_k + 1
        num_iter = jnp.minimum(num_k_blocks, last_block)
    else:
        num_iter = num_k_blocks

    def body(i, dq_acc):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = scale * jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < seq_k
        if causal:
            mask = mask & (q_pos >= k_pos)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq_acc + jnp.dot(ds.astype(k_blk.dtype), k_blk,
                                preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        0, num_iter, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_impl(q, k, v, out, lse, g, *, causal: bool, block_q: int,
                    block_k: int, interpret: bool):
    """Pallas flash backward: no [Tq, Tk] materialization (reference-free
    design; same tiling as FlashAttention-2). Returns (dq, dk, dv) with
    GQA head-group reduction applied."""
    B, Tq, H, D = q.shape
    _, Tk, Hkv, _ = k.shape
    rep = H // Hkv
    if rep != 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    scale = D ** -0.5
    Tq_p = block_q * ((Tq + block_q - 1) // block_q)
    Tk_p = block_k * ((Tk + block_k - 1) // block_k)
    if Tq_p != Tq:
        q = jnp.pad(q, ((0, 0), (0, Tq_p - Tq), (0, 0), (0, 0)))
        g = jnp.pad(g, ((0, 0), (0, Tq_p - Tq), (0, 0), (0, 0)))
        out = jnp.pad(out, ((0, 0), (0, Tq_p - Tq), (0, 0), (0, 0)))
    if Tk_p != Tk:
        k = jnp.pad(k, ((0, 0), (0, Tk_p - Tk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tk_p - Tk), (0, 0), (0, 0)))
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq_p, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Tk_p, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Tk_p, D)
    dof = g.transpose(0, 2, 1, 3).reshape(B * H, Tq_p, D)
    of = out.transpose(0, 2, 1, 3).reshape(B * H, Tq_p, D)
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise reduce in XLA,
    # replicated to the same [B*H, 8, Tq] sublane layout as lse.
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (B * H, 8, Tq_p))

    dkv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, causal=causal,
            scale=scale, seq_q=Tq, seq_k=Tk,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B * H, Tk_p, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tk_p, D), q.dtype),
        ),
        grid=(B * H, Tk_p // block_k),
        in_specs=[
            pl.BlockSpec((1, Tq_p, D), lambda b, j: (b, 0, 0)),   # q
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),  # k
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),  # v
            pl.BlockSpec((1, Tq_p, D), lambda b, j: (b, 0, 0)),   # do
            pl.BlockSpec((1, 8, Tq_p), lambda b, j: (b, 0, 0)),   # lse
            pl.BlockSpec((1, 8, Tq_p), lambda b, j: (b, 0, 0)),   # delta
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
        ),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)
    dk, dv = dkv

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_k=block_k, causal=causal,
            scale=scale, seq_k=Tk,
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq_p, D), q.dtype),
        grid=(B * H, Tq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),  # q
            pl.BlockSpec((1, Tk_p, D), lambda b, i: (b, 0, 0)),   # k
            pl.BlockSpec((1, Tk_p, D), lambda b, i: (b, 0, 0)),   # v
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),  # do
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),  # lse
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),  # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    dq = dq.reshape(B, H, Tq_p, D).transpose(0, 2, 1, 3)[:, :Tq]
    dk = dk.reshape(B, H, Tk_p, D).transpose(0, 2, 1, 3)[:, :Tk]
    dv = dv.reshape(B, H, Tk_p, D).transpose(0, 2, 1, 3)[:, :Tk]
    if rep != 1:
        dk = dk.reshape(B, Tk, Hkv, rep, D).sum(axis=3)
        dv = dv.reshape(B, Tk, Hkv, rep, D).sum(axis=3)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """Flash attention: pallas forward AND pallas backward (LSE saved by
    the forward; backward never materializes the [Tq, Tk] score matrix —
    round 2 recomputed attention in XLA for grads, which put three dense
    [B, H, Tq, Tk] tensors back into every train step)."""
    return _flash_fwd_impl(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, with_lse=True,
    )
    # Named so remat policies can keep them: without this, a jax.checkpoint
    # around the transformer block re-runs the flash forward a second time
    # in the backward pass just to rebuild these residuals.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_impl(
        q, k, v, out, lse, g, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def attention(
    q, k, v, *, causal: bool = True, impl: str = "auto",
    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
    mesh=None,
):
    """Dispatcher. impl: auto | xla | flash | flash_interpret. ``auto`` is
    decided by platform alone: the pallas kernel on TPU (a kernel that fails
    to lower or compile there raises — it never gives way to XLA), XLA
    attention elsewhere.

    ``mesh``: the mesh the surrounding jit is sharded over. GSPMD cannot
    partition a Mosaic custom call, so on a mesh of several devices the
    kernel runs under ``shard_map``, each device on its shard of batch
    (``data``/``fsdp``) and heads (``tensor``), the full sequence local."""
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return attention_xla(q, k, v, causal=causal)
    if impl not in ("flash", "flash_interpret"):
        raise ValueError(f"unknown attention impl {impl}")

    def kernel(q, k, v):
        return flash_attention(
            q, k, v, causal, block_q, block_k, impl == "flash_interpret"
        )

    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        # Inside someone else's shard_map (pipeline_apply is manual over
        # ``stage``) the mesh comes from the context and only the axes that
        # are still automatic can be taken; taking all of them leaves no axis
        # for GSPMD to partition the call over.
        ctx = jax.sharding.get_abstract_mesh()
        held = set(ctx.manual_axes)
        free = set(mesh.axis_names) - held
        batch = tuple(a for a in ("data", "fsdp") if a in free)
        heads = "tensor" if "tensor" in free else None
        spec = P(batch or None, None, heads, None)
        if free:
            kernel = jax.shard_map(
                kernel, mesh=None if held else mesh, axis_names=free,
                in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
            )
    return kernel(q, k, v)
