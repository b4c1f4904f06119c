"""A gated delta-rule layer's recurrence (Gated DeltaNet, arXiv:2412.06464):
what a sequence carries from token to token is a matrix a head, and a token
CORRECTS what the matrix already holds for its key before it writes.

A head keeps ``S [Dk, Dv]``. With a log decay ``g_t <= 0`` (``alpha_t =
exp(g_t)``), a write strength ``beta_t`` in [0, 2] (past 1 the matrix's
eigenvalues may turn negative), a key and a query ``k_t``, ``q_t [Dk]`` of
unit length (the query further times ``Dk ** -0.5``) and a value ``v_t
[Dv]``:

    S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

Mamba-2 (``ops/ssm.py``) decays and adds; this reads ``S^T k`` back first,
so a step depends on the state through a product and not through a scalar
alone. A step with ``g = 0`` and ``beta = 0`` leaves the state as it is, bit
for bit (``1 x S + k 0^T``): that is how padding and a slot that does not
decode are told, as ``dt = 0`` tells Mamba-2.

Three pieces, each with the plain XLA path that is the CPU's and the tests'
oracle, in the manner of ``ops/ssm.py`` (whose ``conv`` comes before either
recurrence):

- ``recurrence``: the definition, one token after another.
- ``delta_scan`` (a block of tokens: prefill): chunked. Inside a chunk of
  ``chunk`` tokens the corrections are one unit-triangular system
  ``(I + A) U = beta V - (beta gamma K) S_0`` with ``A_ij = beta_i
  (gamma_i / gamma_j) (k_i . k_j)`` below the diagonal and ``gamma_i`` the
  decay from the chunk's start: ``W = (I + A)^-1 [beta V, beta gamma K]``
  is made for every chunk at once (the inverse by halves, from products
  alone: ``_unit_lower_inverse``), and a short scan over the chunks takes
  ``U = W_v - W_k S_0``, the chunk's outputs and the next ``S_0`` from
  products alone. Every ratio of decays is the exponential of a difference
  that is <= 0, so a decay near 0 divides nothing. XLA on every platform.
- ``delta_update`` (one token a slot: decode): the Pallas TPU kernel of
  ``ssm.visit_live`` (the whole state ``[L, B, H, Dk, Dv]`` in HBM, aliased
  to its result, the live slots' states through VMEM in PIECES of a few
  whole heads, a ring of ``ssm.DEPTH`` pieces each way, and no other
  slot's) with this recurrence's step; ``delta_update_xla`` is the same
  step over one layer's slice.

``GATED_DELTA`` is the three as ``models/kv_cache.py:recur`` takes a kind
of state layer's recurrence: from what left the convolution (q, k and v
side by side, a head after another in each) and the gates ``(g, beta)``.
The cache holds a head's matrix with its value columns padded to whole lane
tiles (``held_shape``: 192 -> 256): the chip stores the minor dimension of
an array in tiles of 128 whatever its length, and the kernel's DMA moves
whole tiles. The columns past ``Dv`` are zeros and stay zeros (no value is
written there, so nothing is read back), and the XLA paths leave them out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssm
from ray_tpu.ops.decode_attention import TILE

NORM_EPS = 1e-6     # under the root of a key's or a query's L2 norm


def recurrence(q, k, v, g, beta, state):
    """The recurrence as it is defined, one token after another: what the
    chunked scan is held to (``tests/test_delta_rule.py``). q and k
    [B, T, H, Dk] (normed already), v [B, T, H, Dv], g and beta [B, T, H],
    state [B, H, Dk, Dv]; float32 -> (o [B, T, H, Dv], state)."""
    f32 = jnp.float32

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None, None] * S
        held = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + k_t[..., :, None] * (b_t[..., None] * (v_t - held))[
            ..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    state, o = jax.lax.scan(step, state.astype(f32), tuple(
        jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of ``A`` [.., n, n] strictly lower triangular, float32,
    from products alone. A block of 8 rows or fewer is nilpotent of index 8
    at most, so its Neumann series ends: ``(I - A)(I + A^2)(I + A^4)``,
    exactly (at that size the powers stay small whatever the entries: with
    every entry at its bound of 2 the largest of ``A^4`` is 560). Larger
    ones by halves: ``[[T11, 0], [-T22 A21 T11, T22]]``. (The chip's
    triangular solve walks a chunk's 64 rows one after another: 2.3 ms a
    layer and chunk of 1,024 tokens at the published size, a quarter of a
    prefill; PERF.md, PR 47.)"""
    n = A.shape[-1]
    hi = jax.lax.Precision.HIGHEST

    def mm(x, y):
        return jnp.matmul(x, y, precision=hi)

    if n <= 8:
        eye = jnp.eye(n, dtype=A.dtype)
        A2 = mm(A, A)
        return mm(mm(eye - A, eye + A2), eye + mm(A2, A2))
    h = n // 2
    T11 = _unit_lower_inverse(A[..., :h, :h])
    T22 = _unit_lower_inverse(A[..., h:, h:])
    T21 = -mm(mm(T22, A[..., h:, :h]), T11)
    return jnp.concatenate([
        jnp.concatenate([T11, jnp.zeros_like(A[..., :h, h:])], axis=-1),
        jnp.concatenate([T21, T22], axis=-1)], axis=-2)


def delta_scan(q, k, v, g, beta, state, chunk: int):
    """q and k [B, T, H, Dk] (normed), v [B, T, H, Dv], g and beta
    [B, T, H] float32 (both 0 at a step that is no token), state
    [B, H, Dk, Dv] float32 -> (o [B, T, H, Dv] float32, the state after the
    block). The products between a chunk's tokens run in q's dtype and sum
    in float32; the triangular system, the decays, the state and every
    product with it are float32."""
    B, T, H, Dk = q.shape
    f32, cdt = jnp.float32, q.dtype
    hi = jax.lax.Precision.HIGHEST
    Q = min(chunk, T)
    pad = -T % Q
    if pad:     # steps that are no token: g 0 and beta 0 leave the state
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    nc = (T + pad) // Q
    with jax.named_scope("delta_scan"):
        # [B, nc, H, Q, ..]: a head's chunk is a matrix of Q rows
        q, k, v = (jnp.moveaxis(a.reshape(B, nc, Q, H, -1), 3, 2)
                   for a in (q, k, v))
        g, beta = (jnp.moveaxis(a.astype(f32).reshape(B, nc, Q, H), 3, 2)
                   for a in (g, beta))
        cs = jnp.cumsum(g, axis=-1)     # log decay from the chunk's start
        i = jnp.arange(Q)
        # decay from step j to step i >= j; 0 above the diagonal
        seg = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                                cs[..., :, None] - cs[..., None, :],
                                -jnp.inf))
        kk = jnp.einsum("bchik,bchjk->bchij", k, k,
                        preferred_element_type=f32)
        A = jnp.where(i[:, None] > i[None, :],
                      beta[..., :, None] * seg * kk, 0.0)
        kf, vf = k.astype(f32), v.astype(f32)
        W = jnp.einsum(
            "bchij,bchjw->bchiw", _unit_lower_inverse(A),
            jnp.concatenate(
                [beta[..., None] * vf,
                 (beta * jnp.exp(cs))[..., None] * kf], axis=-1),
            precision=hi)
        Wv, Wk = W[..., :v.shape[-1]], W[..., v.shape[-1]:]
        # step i hears step j <= i through q_i . k_j under their decay
        heard = (jnp.einsum("bchik,bchjk->bchij", q, k,
                            preferred_element_type=f32) * seg).astype(cdt)
        # a chunk's keys under the decay from their step to its end
        k_end = (kf * jnp.exp(cs[..., -1:] - cs)[..., None]).astype(cdt)
        q_in = q.astype(f32) * jnp.exp(cs)[..., None]

        def carry(S, xs):
            Wv, Wk, heard, k_end, q_in, whole = xs
            # what the chunk's tokens write, once the state they correct is
            # known; the state is the whole history: float32 products
            U = Wv - jnp.einsum("bhik,bhkv->bhiv", Wk, S, precision=hi)
            o = (jnp.einsum("bhik,bhkv->bhiv", q_in, S, precision=hi)
                 + jnp.einsum("bhij,bhjv->bhiv", heard, U.astype(cdt),
                              preferred_element_type=f32))
            S = whole[..., None, None] * S + jnp.einsum(
                "bhjk,bhjv->bhkv", k_end, U.astype(cdt),
                preferred_element_type=f32)
            return S, o

        state, o = jax.lax.scan(
            carry, state.astype(f32), tuple(
                jnp.moveaxis(a, 1, 0) for a in (
                    Wv, Wk, heard, k_end, q_in, jnp.exp(cs[..., -1]))))
    # [nc, B, H, Q, Dv] -> [B, T, H, Dv]
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, nc * Q, H, -1)
    return o[:, :T], state


def delta_update_xla(state, q, k, v, g, beta, live=None):
    """One step of every slot: state [B, H, Dk, Dv] float32, q and k
    [B, H, Dk] (normed), v [B, H, Dv], g and beta [B, H] -> (o [B, H, Dv]
    float32, state). ``live`` [B] bool: the slots that decode (None: every
    slot); any other keeps its state and gets zeros."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    new = jnp.exp(g)[..., None, None] * state
    held = jnp.einsum("bhkv,bhk->bhv", new, k, precision=hi)
    new = new + k[..., :, None] * (beta[..., None] * (v - held))[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", new, q, precision=hi)
    if live is None:
        return o, new
    keep = live[:, None, None]
    return jnp.where(keep, o, 0.0), jnp.where(keep[..., None], new, state)


def held_shape(heads: int, Dk: int, Dv: int) -> tuple:
    """A slot's state as the cache holds it: ``Dv`` up to whole lane
    tiles."""
    return heads, Dk, -(-Dv // TILE) * TILE


def _step(b, h0, sbuf, obuf, entry, decay_ref, kab_ref, k_ref, q_ref, bv_ref,
          y_ref):
    for i in range(sbuf.shape[1]):
        # a head's decay, key and query lie down the sublanes of its
        # [Dk, Dv] tile, [Dk, 1]; its value along the lanes, [1, Dv]
        head = slice(h0 + i, h0 + i + 1)
        S = sbuf[entry, i]
        held = jnp.sum(kab_ref[b, :, head] * S, axis=0, keepdims=True)
        new = (decay_ref[b, :, head] * S
               + k_ref[b, :, head] * (bv_ref[b, head, :] - held))
        obuf[entry, i] = new
        # the row is Dv wide: what the cache pads a head's values with
        # stays out of it
        y_ref[b, head, :] = jnp.sum(
            q_ref[b, :, head] * new, axis=0, keepdims=True)[
                :, :y_ref.shape[-1]]


def delta_update(states, layer, q, k, v, g, beta, *, live=None,
                 interpret: bool = False):
    """One step of layer ``layer`` of ``states`` [L, B, H, Dk, Dv or wider
    (``held_shape``)] float32, in place: q and k [B, H, Dk] (normed), v
    [B, H, Dv], g and beta [B, H] -> (o [B, H, Dv] float32, states: the
    operand's own buffer). ``live`` (``decode_attention.live_slots``'
    [B + 1]; None: every slot) names the slots this holds for: any other
    slot's state is left as it is and its row of ``o`` is zeros."""
    L, B, H, Dk, held = states.shape
    Dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    alpha = jnp.exp(g)

    def down(a):    # [B, H, Dk] -> a head's column down the sublanes
        return jnp.swapaxes(a, 1, 2)

    # u = beta v - (alpha beta k) . S, new = alpha S + k u^T: each factor a
    # head has once is folded into its key or its value here
    return ssm.visit_live(
        _step, "delta_update", states, layer, live,
        (jnp.broadcast_to(alpha[:, None, :], (B, Dk, H)),
         down((alpha * beta)[..., None] * k), down(k), down(q),
         jnp.pad(beta[..., None] * v, ((0, 0), (0, 0), (0, held - Dv)))),
        (H, Dv), interpret)


def _operands(mixed, state_shape):
    """q and k [.., H, Dk], unit length each (q further times Dk ** -0.5),
    and v [.., H, Dv] of what left the convolution: q, k and v side by
    side, a head after another in each. The norms sum in float32; the
    results keep ``mixed``'s dtype. ``state_shape`` is the cache's
    (``held_shape``): Dv is what the channels leave."""
    H, Dk, _ = state_shape
    q, k, v = jnp.split(mixed, [H * Dk, 2 * H * Dk], axis=-1)

    def unit(a, scale=1.0):
        a = a.reshape(*a.shape[:-1], H, Dk).astype(jnp.float32)
        a = a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + NORM_EPS)
        return (a * scale).astype(mixed.dtype)

    return unit(q, Dk ** -0.5), unit(k), v.reshape(*v.shape[:-1], H, -1)


def _as_held(state, was):
    """``state`` [.., Dv] with the columns the cache holds after it, as
    they came in ``was`` (zeros)."""
    return jnp.concatenate([state, was[..., state.shape[-1]:]], axis=-1)


def _gated_delta_scan(layer, mixed, gates, state, chunk):
    q, k, v = _operands(mixed, state.shape[1:])
    o, new = delta_scan(q, k, v, *gates, state[..., :v.shape[-1]], chunk)
    return o, _as_held(new, state)


def _gated_delta_step(layer, state, mixed, gates, live):
    q, k, v = _operands(mixed, state.shape[1:])
    o, new = delta_update_xla(state[..., :v.shape[-1]], q, k, v, *gates, live)
    return o, _as_held(new, state)


def _gated_delta_kernel(layer, states, index, mixed, gates, live, interpret):
    return delta_update(states, index, *_operands(mixed, states.shape[2:]),
                        *gates, live=live, interpret=interpret)


GATED_DELTA = ssm.Recurrence(
    "delta", _gated_delta_scan, _gated_delta_step, _gated_delta_kernel)
