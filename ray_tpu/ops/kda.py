"""Kimi Delta Attention's recurrence (KDA; "Kimi Linear", arXiv:2510.26692):
the gated delta rule (``ops/delta_rule.py``) with a decay a key CHANNEL.

A head keeps ``S [Dk, Dv]``. With a log decay ``g_t [Dk] <= 0`` (a vector,
where Gated DeltaNet has one scalar a head), a write strength ``beta_t`` in
[0, 1], a key and a query of unit length (the query further times
``Dk ** -0.5``) and a value ``v_t [Dv]``:

    S' = Diag(exp g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t

A step with ``g = 0`` and ``beta = 0`` leaves the state as it is, bit for bit
(``1 x S + k 0^T``): padding and a slot that does not decode, as the other
recurrences are told.

The pieces, in the manner of ``ops/delta_rule.py``:

- ``recurrence``: the definition, one token after another: the oracle.
- ``kda_scan`` (a block of tokens: prefill): chunked, as ``delta_scan``: a
  chunk's corrections are one unit-triangular system ``(I + A) U = beta V -
  (beta Gamma K) S_0``, ``W = (I + A)^-1 [beta V, beta Gamma K]`` made for
  every chunk at once, a short scan over the chunks. What the vector decay
  changes is ``A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` (``G`` the
  log decay summed from the chunk's start): the ratio is inside the sum over
  channels and no longer a scalar beside ``k_i . k_j``. It is factored
  through a REFERENCE ROW ``r``: ``(k_i exp(G_i - G_r)) . (k_j exp(G_r -
  G_j))``, r the first row of the sub-chunk of ``SUB`` tokens that holds i.
  Then ``G_i - G_r <= 0`` (no factor of i's grows), and of j's: a j before r
  has ``G_r - G_j <= 0``; a j in i's own sub-chunk has at most ``SUB - 1``
  steps between r and itself, so with ``g >= lower`` its exponent is at most
  ``(SUB - 1) x -lower``: 75 at the published lower bound of -5 and SUB =
  16, and float32 holds ``exp(88)``: that is what the lower bound is for.
  (A whole chunk of 64 as one reference would need ``exp(315)``.) The
  product of the two factors is the true ratio, at most 1, whatever each is.
- ``kda_update`` (one token a slot: decode): the kernel of
  ``ssm.visit_live`` (the live slots' states through VMEM in PIECES of a few
  whole heads, a ring of ``ssm.DEPTH`` pieces each way) with the delta
  rule's own step (``delta_rule._step`` reads a head's decay down the
  sublanes of its tile: there the same number ``Dk`` times, here a
  channel's own); ``kda_update_xla`` the same step over one layer's slice.

``KDA`` is the three as ``models/kv_cache.py:recur`` takes a kind of state
layer's recurrence: from what left the convolution (q, k and v side by side)
and the gates ``(g [.., H, Dk], beta [.., H])``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import delta_rule, ssm

SUB = 16    # tokens that share a reference row of the in-chunk decay


def recurrence(q, k, v, g, beta, state):
    """The recurrence as it is defined, one token after another. q and k
    [B, T, H, Dk] (normed already), v [B, T, H, Dv], g [B, T, H, Dk], beta
    [B, T, H], state [B, H, Dk, Dv]; float32 -> (o [B, T, H, Dv], state)."""
    f32 = jnp.float32

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        held = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + k_t[..., :, None] * (b_t[..., None] * (v_t - held))[
            ..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    state, o = jax.lax.scan(step, state.astype(f32), tuple(
        jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda_scan(q, k, v, g, beta, state, chunk: int):
    """q and k [B, T, H, Dk] (normed), v [B, T, H, Dv], g [B, T, H, Dk] and
    beta [B, T, H] float32 (both 0 at a step that is no token), state
    [B, H, Dk, Dv] float32 -> (o [B, T, H, Dv] float32, the state after the
    block). ``g`` no lower than ``-88 / (SUB - 1)`` a step (the module's
    text). The products between a chunk's tokens run in q's dtype and sum in
    float32; the triangular system, the decays, the state and every product
    with it are float32."""
    B, T, H, Dk = q.shape
    f32, cdt = jnp.float32, q.dtype
    hi = jax.lax.Precision.HIGHEST
    Q = min(chunk, T)
    pad = -T % Q
    if pad:     # steps that are no token: g 0 and beta 0 leave the state
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    nc = (T + pad) // Q
    sub = SUB if Q % SUB == 0 else Q
    with jax.named_scope("kda_scan"):
        # [B, nc, H, Q, ..]: a head's chunk is a matrix of Q rows
        q, k, v, g = (jnp.moveaxis(a.reshape(B, nc, Q, H, -1), 3, 2)
                      for a in (q, k, v, g))
        beta = jnp.moveaxis(beta.astype(f32).reshape(B, nc, Q, H), 3, 2)
        G = jnp.cumsum(g.astype(f32), axis=-2)  # log decay from the start
        kf, vf, qf = k.astype(f32), v.astype(f32), q.astype(f32)
        i = jnp.arange(Q)
        # a row's side of a ratio: from its sub-chunk's first row to itself
        first = G.reshape(B, nc, H, Q // sub, sub, Dk)[..., :1, :]
        own = jnp.exp(G.reshape(first.shape[:4] + (sub, Dk)) - first)
        # a column's side, one a sub-chunk of rows: from the column to that
        # sub-chunk's first row; a column behind the sub-chunk's last row is
        # heard by none of its rows
        ahead = i[None, :] < (jnp.arange(Q // sub)[:, None] + 1) * sub
        other = jnp.exp(jnp.where(
            ahead[..., None], first - G[..., None, :, :], -jnp.inf))
        k_other = (kf[..., None, :, :] * other).astype(cdt)

        def ratios(rows):
            """sum_c rows_ic k_jc exp(G_ic - G_jc), [.., Q, Q], float32."""
            mine = (rows.reshape(own.shape) * own).astype(cdt)
            return jnp.einsum("bchsik,bchsjk->bchsij", mine, k_other,
                              preferred_element_type=f32).reshape(
                                  B, nc, H, Q, Q)

        A = jnp.where(i[:, None] > i[None, :],
                      beta[..., :, None] * ratios(kf), 0.0)
        W = jnp.einsum(
            "bchij,bchjw->bchiw", delta_rule._unit_lower_inverse(A),
            jnp.concatenate(
                [beta[..., None] * vf,
                 beta[..., None] * jnp.exp(G) * kf], axis=-1),
            precision=hi)
        Wv, Wk = W[..., :v.shape[-1]], W[..., v.shape[-1]:]
        # step i hears step j <= i through q_i . k_j under their decays
        heard = jnp.where(i[:, None] >= i[None, :], ratios(qf), 0.0).astype(
            cdt)
        # a chunk's keys under the decay from their step to its end
        k_end = (kf * jnp.exp(G[..., -1:, :] - G)).astype(cdt)
        q_in = qf * jnp.exp(G)

        def carry(S, xs):
            Wv, Wk, heard, k_end, q_in, whole = xs
            U = Wv - jnp.einsum("bhik,bhkv->bhiv", Wk, S, precision=hi)
            o = (jnp.einsum("bhik,bhkv->bhiv", q_in, S, precision=hi)
                 + jnp.einsum("bhij,bhjv->bhiv", heard, U.astype(cdt),
                              preferred_element_type=f32))
            S = whole[..., None] * S + jnp.einsum(
                "bhjk,bhjv->bhkv", k_end, U.astype(cdt),
                preferred_element_type=f32)
            return S, o

        state, o = jax.lax.scan(
            carry, state.astype(f32), tuple(
                jnp.moveaxis(a, 1, 0) for a in (
                    Wv, Wk, heard, k_end, q_in, jnp.exp(G[..., -1, :]))))
    # [nc, B, H, Q, Dv] -> [B, T, H, Dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, nc * Q, H, -1)
    return o[:, :T], state


def kda_update_xla(state, q, k, v, g, beta, live=None):
    """One step of every slot: state [B, H, Dk, Dv] float32, q and k
    [B, H, Dk] (normed), v [B, H, Dv], g [B, H, Dk], beta [B, H] -> (o
    [B, H, Dv] float32, state). ``live`` [B] bool: the slots that decode
    (None: every slot); any other keeps its state and gets zeros."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    new = jnp.exp(g)[..., None] * state
    held = jnp.einsum("bhkv,bhk->bhv", new, k, precision=hi)
    new = new + k[..., :, None] * (beta[..., None] * (v - held))[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", new, q, precision=hi)
    if live is None:
        return o, new
    keep = live[:, None, None]
    return jnp.where(keep, o, 0.0), jnp.where(keep[..., None], new, state)


def kda_update(states, layer, q, k, v, g, beta, *, live=None,
               interpret: bool = False):
    """One step of layer ``layer`` of ``states`` [L, B, H, Dk, Dv] float32,
    in place (Dv whole lane tiles): q and k [B, H, Dk] (normed), v
    [B, H, Dv], g [B, H, Dk], beta [B, H] -> (o [B, H, Dv] float32, states:
    the operand's own buffer). ``live`` (``decode_attention.live_slots``'
    [B + 1]; None: every slot) names the slots this holds for: any other
    slot's state is left as it is and its row of ``o`` is zeros."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    alpha = jnp.exp(g)

    def down(a):    # [B, H, Dk] -> a head's column down the sublanes
        return jnp.swapaxes(a, 1, 2)

    # u = beta v - (alpha beta k) . S, new = alpha S + k u^T: the step the
    # delta rule's kernel runs, its decay a channel's own
    return ssm.visit_live(
        delta_rule._step, "kda_update", states, layer, live,
        (down(alpha), down(alpha * beta[..., None] * k), down(k), down(q),
         beta[..., None] * v),
        v.shape[1:], interpret)


def _kda_scan(layer, mixed, gates, state, chunk):
    q, k, v = delta_rule._operands(mixed, state.shape[1:])
    return kda_scan(q, k, v, *gates, state, chunk)


def _kda_step(layer, state, mixed, gates, live):
    q, k, v = delta_rule._operands(mixed, state.shape[1:])
    return kda_update_xla(state, q, k, v, *gates, live)


def _kda_kernel(layer, states, index, mixed, gates, live, interpret):
    return kda_update(states, index,
                      *delta_rule._operands(mixed, states.shape[2:]),
                      *gates, live=live, interpret=interpret)


KDA = ssm.Recurrence("kda", _kda_scan, _kda_step, _kda_kernel)
