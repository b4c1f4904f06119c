"""Sequence-chunked softmax cross-entropy whose forward makes its gradients.

The naive loss materializes float32 logits of shape [B, T, V] — for
GPT-2-small at B=16, T=1024 that is a 3.3 GB tensor written and re-read
several times by softmax and its backward, all pure HBM traffic on the
step's critical path. Here the head projection + logsumexp + gold-logit
gather run per sequence chunk inside a scan: peak residency is one
[B, c, V] chunk.

The loss is a scalar, so its cotangent is one too, and
``d loss / d logits = (softmax - onehot) * mask / count`` is known the
moment a chunk's logits exist. Under differentiation (a ``jax.custom_vjp``)
the forward therefore forms, while a chunk's logits are still there, the
chunk's ``dx`` and its share of ``dW`` for a unit cotangent: three
[tokens, V] products a chunk (logits, dx, dW) where a remat'd body needs
four (the logits again in the backward). What the forward returns to the
backward is ``(dx, dW)``; the backward is two scalings by the cotangent,
which the compiler folds when it is the constant 1 of ``value_and_grad``.
A call nobody differentiates runs the scan with the loss alone.

First derivatives in reverse mode only: ``jax.jvp`` and second derivatives
through the loss are not supported (``custom_vjp`` refuses forward mode; a
second reverse pass would differentiate the forward's own gradient
arithmetic, rounded to the activation dtype, which nothing holds to
anything).

Reference context: the reference ships no model/loss code (SURVEY §5 —
models are user code / delegated to vLLM); this is part of our TPU-native
training stack, same role as the fused linear cross entropy in public LLM
trainers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _chunk_nll(xcb, head, tcb, mcb):
    """One chunk: its logits [B, c, V], their logsumexp [B, c], and the
    masked sum of its tokens' negative log likelihoods."""
    # Keep the [B, c, V] tensor in the activation dtype: a float32 copy
    # here doubles the chunk's HBM traffic AND gets materialized (it
    # would have two consumers). The reductions below cast f32 inside
    # their fusions instead.
    logits = jnp.einsum("bce,ve->bcv", xcb, head)
    m = jnp.max(logits, axis=-1).astype(jnp.float32)          # [B, c]
    expsum = jnp.sum(
        jnp.exp((logits.astype(jnp.float32) - m[..., None])), axis=-1
    )
    lse = m + jnp.log(expsum)
    # Gold logit gathered from the SAME tensor the logsumexp reduced:
    # numerator and denominator share one precision, so lse >= gold
    # always and per-token NLL cannot go negative. (An f32 recompute of
    # the gold row dot is more precise in isolation but inconsistent
    # with the bf16 lse — and costs a [B, c, E] f32 gather + einsum.)
    gold = jnp.take_along_axis(
        logits, tcb[..., None], axis=-1
    )[..., 0].astype(jnp.float32)
    return logits, lse, ((lse - gold) * mcb).sum()


def _chunks(x, head_w, targets, mask, chunk):
    """What a scan over the sequence's chunks takes: (x [n, B, c, E],
    targets [n, B, c], mask [n, B, c] float32), the head in the activation
    dtype, and the count the loss is a mean over."""
    B, T, E = x.shape
    c = min(chunk, T)
    pad = (-T) % c  # pad the tail chunk instead of shrinking the chunk
    # (a divisor search would degenerate to c=1 for prime T — a T-step
    # sequential scan of tiny matmuls)
    mask = mask.astype(jnp.float32)
    count = jnp.maximum(mask.sum(), 1.0)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))  # pad rows weigh zero
    n = (T + pad) // c
    xc = x.reshape(B, n, c, E).transpose(1, 0, 2, 3)   # [n, B, c, E]
    tc = targets.reshape(B, n, c).transpose(1, 0, 2)   # [n, B, c]
    mc = mask.reshape(B, n, c).transpose(1, 0, 2)
    return (xc, tc, mc), head_w.astype(x.dtype), count  # cast once


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _xent(x, head_w, targets, mask, chunk):
    """The call nobody differentiates: the loss alone."""
    chunks, head, count = _chunks(x, head_w, targets, mask, chunk)

    def body(s, xs):
        xcb, tcb, mcb = xs
        return s + _chunk_nll(xcb, head, tcb, mcb)[2], None

    s, _ = jax.lax.scan(body, jnp.float32(0.0), chunks)
    return s / count


def _xent_fwd(x, head_w, targets, mask, chunk):
    """The loss and, as residuals, (dx, dW) for a unit cotangent, from one
    scan over the same chunks."""
    chunks, head, count = _chunks(x, head_w, targets, mask, chunk)

    def body(carry, xs):
        s, dw = carry
        xcb, tcb, mcb = xs
        logits, lse, nll = _chunk_nll(xcb, head, tcb, mcb)
        # (softmax - onehot) * mask / count: float32 inside the fusion,
        # held in the activation dtype like the logits it is made from
        onehot = tcb[..., None] == jnp.arange(logits.shape[-1])
        dlogits = (
            (jnp.exp(logits.astype(jnp.float32) - lse[..., None]) - onehot)
            * (mcb / count)[..., None]
        ).astype(logits.dtype)
        dxb = jnp.einsum("bcv,ve->bce", dlogits, head)
        # The chunk's product leaves the MXU in the activation dtype, as
        # autodiff of the projection gave it, and joins a float32 sum. On
        # one chip the rounding, the cast and the add fuse into the product.
        # Under fsdp the partitioner reduces THIS across chips: a bf16
        # result goes round inside a windowed einsum, beside the product; a
        # float32 one is reduce-scattered whole, twice the bytes, after it.
        dw = dw + jnp.einsum("bcv,bce->ve", dlogits, xcb).astype(jnp.float32)
        return (s + nll, dw), dxb

    (s, dw), dxc = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros(head_w.shape, jnp.float32)),
        chunks)
    B, T, E = x.shape
    dx = dxc.transpose(1, 0, 2, 3).reshape(B, -1, E)[:, :T]
    return s / count, (dx, dw.astype(head_w.dtype))


def _xent_bwd(chunk, res, g):
    dx, dw = res
    return g.astype(dx.dtype) * dx, g.astype(dw.dtype) * dw, None, None


_xent.defvjp(_xent_fwd, _xent_bwd)


def chunked_softmax_xent(
    x: jax.Array,
    head_w: jax.Array,
    targets: jax.Array,
    mask: Optional[jax.Array] = None,
    chunk: int = 512,
) -> jax.Array:
    """Mean next-token NLL without a [B, T, V] intermediate.

    x: [B, T, E] final-trunk features (pre-head). head_w: [V, E] (the tied
    embedding or LM head). targets: [B, T] int ids. mask: optional [B, T]
    weights (0 drops a position). Differentiable once, in reverse mode,
    with respect to ``x`` and ``head_w``.
    """
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    return _xent(x, head_w, targets, mask, chunk)
