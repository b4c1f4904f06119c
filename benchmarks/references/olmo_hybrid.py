"""Plain float32 reference of Ai2's ``olmo_hybrid`` architecture
(Olmo-Hybrid-7B, https://huggingface.co/allenai/Olmo-Hybrid-7B:
``config.json``; the layer order of the OLMo 2 / OLMo 3 family; the gated
delta rule of Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464, as the flash-linear-attention layer whose key names the
config follows has it): token embedding; decoder layers ``h += norm(mix(h))``,
``h += norm(mlp(h))`` (each branch's OUTPUT is normed, its input is not); a
final RMSNorm and a head of its own (untied). ``mlp(x) = (silu(x Wg) * x Wu)
Wd``. No bias anywhere.

``mix`` of a ``full_attention`` layer: as many key/value heads as query
heads; q and k each through an RMSNorm over ALL their channels, then split
into heads; nothing is rotated (``rope_theta: null``); causal softmax over
``q . k / sqrt(head size)``; the output projection.

``mix`` of a ``linear_attention`` layer: ``[q, k, v, gate] = x Win`` and
``[b, a] = x Wgates``, each split in that order; ``[q, k, v]'_t = silu(sum_j w[:, j] [q, k, v]_{t-K+1+j})``,
a depthwise causal convolution of K taps without bias, zeros before the
first token; a head's q and k each divided by ``sqrt(sum of squares +
L2_EPS)``, q further by ``sqrt(Dk)``; ``beta = BETA_SCALE sigmoid(b)``;
``alpha = exp(-exp(A_log) softplus(a + dt_bias))``; ONE TOKEN AFTER ANOTHER,
a head, from ``S = 0`` ``[Dk, Dv]``:

    S_t = alpha_t S_{t-1} + k_t (beta_t (v_t - alpha_t S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

each head's ``o`` through an RMSNorm over its own ``Dv`` channels (one gain,
shared by the heads), THEN times ``silu(gate)``; the output projection.

Straightforward ``jax.numpy``: the recurrence is a ``lax.scan`` over time as
it is defined, with no chunks, no cache, no carried state between calls, no
kernel, no mixed precision. Every matrix product runs in float32 at
``jax.default_matmul_precision("highest")``, which the caller sets
(``lib/reference.py:in_blocks``). What the weights do not carry is stated
here: ``RMS_EPS``, ``L2_EPS``, ``BETA_SCALE``; which layers keep a state
follows from the weights' own layout (a state layer has ``delta_in``), and
the sizes from the weights' shapes (heads from ``dt_bias``, ``Dv`` from
``gate_norm``, ``Dk`` from the convolution's channels less the values').

The weights are DATA: the program's own parameter pytree
(``ray_tpu.models.olmo_hybrid.init_params`` under the same key), bf16 as the
model is published: ``wte`` and ``lm_head`` [V, E], ``norm_f`` [E],
``blocks.segments[s][j]`` the j-th layer of segment s's period, leaves
[repeats, ...]: ``mix_norm`` / ``mlp_norm`` [E], ``w_gate`` / ``w_up``
[E, M], ``w_down`` [M, E]; an attention layer's ``wq`` / ``wk`` / ``wv``
[E, H, D], ``wo`` [H, D, E], ``q_norm`` / ``k_norm`` [H D]; a state layer's
``delta_in`` [E, 2 H Dk + 2 H Dv], ``delta_gates`` [E, 2 H], ``conv_w``
[2 H Dk + H Dv, K],
``dt_bias`` / ``A_log`` [H], ``gate_norm`` [Dv], ``delta_out`` [H Dv, E].
The layer ORDER is the segments', a period repeated: that much of the layout
is read here. A layer's weights become float32 as the layer is reached. The
arithmetic below shares nothing with the program.

Departures from the published description, each marked where it happens:
(1) a projection is held as ``[in, heads, head size]`` or ``[in, out]``, not
as a ``Linear``'s ``[out, in]``, and a state layer's six input projections
as two matrices, q, k, v and the gate side by side in one, b and a in the
other; (2) queries are attended ``Q_BLOCK`` at a time:
the same sums, a block of rows at a time; (3) the published fast path
computes the recurrence in chunks: this is the definition those chunks
compute.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6        # rms_norm_eps
L2_EPS = 1e-6         # under the root of a key's or a query's L2 norm
BETA_SCALE = 2.0      # linear_allow_neg_eigval: beta in (0, 2)
Q_BLOCK = 512         # queries attended at once (2)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, weight):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) * weight


def _attention(q, k, v):
    """q, k and v [B, T, H, D] -> [B, T, H, D]: causal. (2)"""
    B, T, H, D = q.shape
    keys = jnp.arange(T)[None, :]

    def block(q, at):       # q [B, Q, H, D], at [Q] their positions
        seen = keys <= at[:, None]
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
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
    # (1): [in, heads, head size] flattened is the Linear's transpose; the
    # norm is over every head's channels together
    q = _rms_norm(h @ p["wq"].reshape(E, -1), p["q_norm"])
    k = _rms_norm(h @ p["wk"].reshape(E, -1), p["k_norm"])
    v = h @ p["wv"].reshape(E, -1)
    q, k, v = (a.reshape(B, T, H, D) for a in (q, k, v))
    return _attention(q, k, v).reshape(B, T, H * D) @ p["wo"].reshape(H * D, E)


def _unit(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _state_layer(h, p):
    """The gated delta rule over h [B, T, E], from a zero state, a token at
    a time. (3)"""
    B, T, _ = h.shape
    channels, taps = p["conv_w"].shape
    heads, dv = p["dt_bias"].shape[0], p["gate_norm"].shape[0]
    dk = (channels - heads * dv) // (2 * heads)
    # (1): q, k, v and the gate side by side; b and a side by side
    qkv, gate = jnp.split(h @ p["delta_in"], [channels], -1)
    b, a = jnp.split(h @ p["delta_gates"], 2, -1)
    # the convolution: token t hears tokens t - taps + 1 .. t, zeros before
    rows = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(
        p["conv_w"][:, j] * rows[:, j:j + T] for j in range(taps)))
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], -1)
    q = _unit(q.reshape(B, T, heads, dk)) / jnp.sqrt(jnp.float32(dk))
    k = _unit(k.reshape(B, T, heads, dk))
    v = v.reshape(B, T, heads, dv)
    beta = BETA_SCALE * jax.nn.sigmoid(b)                     # [B, T, heads]
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"]))

    def token(state, now):
        q_t, k_t, v_t, alpha_t, beta_t = now  # [B, heads, dk | dv], [B, heads]
        state = alpha_t[:, :, None, None] * state
        held = (state * k_t[:, :, :, None]).sum(2)           # S^T k: [.., dv]
        write = beta_t[:, :, None] * (v_t - held)
        state = state + k_t[:, :, :, None] * write[:, :, None, :]
        return state, (state * q_t[:, :, :, None]).sum(2)

    _, o = jax.lax.scan(
        token, jnp.zeros((B, heads, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)))
    o = _rms_norm(jnp.moveaxis(o, 0, 1), p["gate_norm"])      # a head's own
    o = o.reshape(B, T, heads * dv) * jax.nn.silu(gate)       # THEN the gate
    return o @ p["delta_out"]


def _layer(x, p):
    p = _f32(p)
    mix = _state_layer(x, p) if "delta_in" in p else _attend(x, p)
    x = x + _rms_norm(mix, p["mix_norm"])
    mlp = (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return x + _rms_norm(mlp, p["mlp_norm"])


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
    x = jnp.asarray(params["wte"][tokens], jnp.float32)
    for p in layer_order(params["blocks"]):
        x = _layer(x, p)
    x = _rms_norm(x, jnp.asarray(params["norm_f"], jnp.float32))
    return x @ jnp.asarray(params["lm_head"], jnp.float32).T
