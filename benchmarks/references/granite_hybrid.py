"""Plain float32 reference of IBM's ``granitemoehybrid`` architecture
(Granite 4.0-H Micro, https://huggingface.co/ibm-granite/granite-4.0-h-micro:
``config.json``, the layer equations of the ``transformers`` modeling code of
``granitemoehybrid`` / ``bamba``, and the Mamba-2 recurrence as ``mamba_ssm``
defines it): token embedding times ``EMBEDDING_MULTIPLIER``; decoder layers
``h += RESIDUAL_MULTIPLIER * mix(input_norm(h))``,
``h += RESIDUAL_MULTIPLIER * mlp(post_norm(h))``; a final RMSNorm and the
embedding table again as the head (tied), its logits over ``LOGITS_SCALING``.
``mlp(x) = (silu(g) * u) Wo`` with ``[g, u] = x Wi`` split in halves. No
bias but the convolution's.

``mix`` of an attention layer: 32 query heads over 8 key/value heads of 64,
no norm of q or k and no position signal of any kind; causal softmax over
``q . k * ATTENTION_MULTIPLIER`` (a stated number, NOT 64 ** -0.5); the
output projection.

``mix`` of a state layer (Mamba-2, one group): ``[z, xBC, dt] = x Win``
split in that order; ``xBC'_t = silu(b + sum_j w[:, j] xBC_{t-K+1+j})``, a
depthwise causal convolution of K taps, zeros before the first token;
``[x, B, C] = xBC'`` with ``x`` as [heads, head size], B and C [D_STATE]
shared by every head; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
ONE TOKEN AFTER ANOTHER, a head:

    S_t = exp(dt_t A) S_{t-1} + dt_t (x_t outer B_t),  y_t = S_t C_t + D x_t

from ``S = 0``; ``y = RMSNorm(y * silu(z))`` over all heads' channels
together, with a gain (the gate BEFORE the norm); the output projection.

Straightforward ``jax.numpy``: the recurrence is a ``lax.scan`` over time as
it is defined, with no chunks, no cache, no carried state between calls, no
kernel, no mixed precision. Every matrix product runs in float32 at
``jax.default_matmul_precision("highest")``, which the caller sets
(``lib/reference.py:in_blocks``). What the weights do not carry is stated
here: ``RMS_EPS``, the four factors, ``D_STATE``; which layers keep a state
follows from the weights' own layout (a state layer has ``ssm_in``), and the
sizes from the weights' shapes.

The weights are DATA: the program's own parameter pytree
(``ray_tpu.models.granite_hybrid.init_params`` under the same key), bf16 as
the model is published: ``wte`` [V, E], ``norm_f`` [E],
``blocks.segments[s][j]`` the j-th layer of segment s's period, leaves
[repeats, ...]: ``input_norm`` / ``post_norm`` [E], ``w_in`` [E, 2 M],
``w_out`` [M, E]; an attention layer's ``wq`` [E, H, D], ``wk`` / ``wv``
[E, KV, D], ``wo`` [H, D, E]; a state layer's ``ssm_in`` [E, inner + C +
heads], ``conv_w`` [C, K], ``conv_b`` [C], ``dt_bias`` / ``A_log`` / ``D``
[heads], ``gate_norm`` [inner], ``ssm_out`` [inner, E]. The layer ORDER is
the segments', a period repeated: that much of the layout is read here. A
layer's weights become float32 as the layer is reached. The arithmetic below
shares nothing with the program.

Departures from the modeling code, each marked where it happens: (1) a
projection is held as ``[in, heads, head size]``, not as a ``Linear``'s
``[out, in]``; (2) queries are attended ``Q_BLOCK`` at a time: the same sums,
a block of rows at a time; (3) the modeling code's fast path computes the
recurrence in chunks (``mamba_chunk_size``): this is the definition those
chunks compute.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5                    # rms_norm_eps
EMBEDDING_MULTIPLIER = 12.0       # embedding_multiplier
ATTENTION_MULTIPLIER = 0.015625   # attention_multiplier
RESIDUAL_MULTIPLIER = 0.22        # residual_multiplier
LOGITS_SCALING = 8.0              # logits_scaling
D_STATE = 128                     # mamba_d_state
Q_BLOCK = 512                     # queries attended at once (2)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, weight):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) * weight


def _attention(q, k, v):
    """q [B, T, H, D], k / v [B, T, KV, D] -> [B, T, H, D]: causal; H / KV
    query heads share a kv head, in head order. (2)"""
    B, T, H, D = q.shape
    G = H // k.shape[2]
    k, v = (jnp.repeat(a, G, axis=2) for a in (k, v))
    keys = jnp.arange(T)[None, :]

    def block(q, at):       # q [B, Q, H, D], at [Q] their positions
        seen = keys <= at[:, None]
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * ATTENTION_MULTIPLIER
        att = jax.nn.softmax(jnp.where(seen[None, None], att, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    if T <= Q_BLOCK:
        return block(q, jnp.arange(T))
    n = -(-T // Q_BLOCK)    # the last block's queries past T are nothing
    q = jnp.pad(q, ((0, 0), (0, n * Q_BLOCK - T), (0, 0), (0, 0)))
    out = jax.lax.map(
        lambda a: block(*a),
        (jnp.moveaxis(q.reshape(B, n, Q_BLOCK, H, D), 1, 0),
         jnp.arange(n * Q_BLOCK).reshape(n, Q_BLOCK)))
    return jnp.moveaxis(out, 0, 1).reshape(B, n * Q_BLOCK, H, D)[:, :T]


def _attend(h, p):
    B, T, E = h.shape
    H, D = p["wq"].shape[1:]
    # (1): [in, heads, head size] flattened is the Linear's transpose
    q = (h @ p["wq"].reshape(E, -1)).reshape(B, T, H, D)
    k = (h @ p["wk"].reshape(E, -1)).reshape(B, T, -1, D)
    v = (h @ p["wv"].reshape(E, -1)).reshape(B, T, -1, D)
    return _attention(q, k, v).reshape(B, T, H * D) @ p["wo"].reshape(H * D, E)


def _state_layer(h, p):
    """Mamba-2 over h [B, T, E], from a zero state, a token at a time. (3)"""
    B, T, _ = h.shape
    channels, taps = p["conv_w"].shape
    heads = p["dt_bias"].shape[0]
    inner = channels - 2 * D_STATE
    z, xbc, dt = jnp.split(h @ p["ssm_in"], [inner, inner + channels], -1)
    # the convolution: token t hears tokens t - taps + 1 .. t, zeros before
    rows = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][:, j] * rows[:, j:j + T] for j in range(taps)))
    x, b, c = jnp.split(xbc, [inner, inner + D_STATE], -1)
    x = x.reshape(B, T, heads, inner // heads)
    dt = jax.nn.softplus(dt + p["dt_bias"])              # [B, T, heads]
    a = -jnp.exp(p["A_log"])

    def token(state, now):
        x_t, dt_t, b_t, c_t = now    # [B, heads, P], [B, heads], [B, N] x 2
        state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                 + dt_t[:, :, None, None] * x_t[:, :, :, None]
                 * b_t[:, None, None, :])
        y_t = (state * c_t[:, None, None, :]).sum(-1)
        return state, y_t + p["D"][None, :, None] * x_t

    _, y = jax.lax.scan(
        token, jnp.zeros((B, heads, inner // heads, D_STATE), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, T, inner)
    y = _rms_norm(y * jax.nn.silu(z), p["gate_norm"])
    return y @ p["ssm_out"]


def _layer(x, p):
    p = _f32(p)
    h = _rms_norm(x, p["input_norm"])
    mix = _state_layer(h, p) if "ssm_in" in p else _attend(h, p)
    x = x + RESIDUAL_MULTIPLIER * mix
    h = _rms_norm(x, p["post_norm"])
    gate, up = jnp.split(h @ p["w_in"], 2, axis=-1)
    return x + RESIDUAL_MULTIPLIER * ((jax.nn.silu(gate) * up) @ p["w_out"])


def layer_order(blocks: Dict):
    """Every layer's own weights, first layer to last, from the segments'
    layout: each segment's period ``repeats`` times over."""
    out = []
    for segment in blocks["segments"]:
        repeats = jax.tree.leaves(segment[0])[0].shape[0]
        for r in range(repeats):
            out += [jax.tree.map(lambda a: a[r], p) for p in segment]
    return out


def logits(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, T] -> logits [B, T, V], float32."""
    table = jnp.asarray(params["wte"], jnp.float32)
    x = table[tokens] * EMBEDDING_MULTIPLIER
    for p in layer_order(params["blocks"]):
        x = _layer(x, p)
    x = _rms_norm(x, jnp.asarray(params["norm_f"], jnp.float32))
    return (x @ table.T) / LOGITS_SCALING
