"""Plain float32 reference of inclusionAI's ``bailing_hybrid`` architecture
(Ling-3.0-flash, https://huggingface.co/inclusionAI/Ling-3.0-flash:
``config.json``; the state layers are Kimi Delta Attention, "Kimi Linear",
arXiv:2510.26692, as the flash-linear-attention layer whose key names the
config follows has it; the attention layers DeepSeek-V2's multi-head latent
attention without a query rank; the router DeepSeek-V3's ``noaux_tc``):
token embedding; decoder layers ``h += mix(RMSNorm(h))``, ``h +=
ffn(RMSNorm(h))``; a final RMSNorm and a head of its own (untied). No bias
anywhere.

``mix`` of a KDA layer (every layer whose number + 1 is no multiple of the
period): ``[q, k, v] = x Wqkv``; ``[q, k, v]'_t = silu(sum_j w[:, j]
[q, k, v]_{t-K+1+j})``, a depthwise causal convolution of K taps, zeros
before the first token; a head's q and k each divided by ``sqrt(sum of
squares + L2_EPS)``, q further by ``sqrt(Dk)``; ``g = LOWER_BOUND x
sigmoid(exp(A_log_h) x (x Wf + dt_bias))`` a head and KEY CHANNEL; ``beta =
sigmoid(x Wb)`` a head; ONE TOKEN AFTER ANOTHER, a head, from ``S = 0``
``[Dk, Dv]``:

    S' = Diag(exp g_t) S_{t-1}
    S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
    o_t = S_t^T q_t

each head's ``o`` through an RMSNorm over its own ``Dv`` channels (one gain,
shared by the heads) times ``sigmoid(x Wz)``, ONE gate a head; the output
projection. Nothing is rotated: position is in the decay.

``mix`` of a latent layer (every ``PERIOD``-th): ``q = x Wq`` a head ``[Dn |
Dr]``; ``[c | kr] = x Wdkv``, ``c = RMSNorm(c)``; ``[k_nope | v] = c Wukv`` a
head; interleaved RoPE (pairs (0, 1), (2, 3), ..) at ``ROPE_THETA`` on q's
last ``Dr`` and on ``kr``, which every head shares; causal softmax of
``(q_nope . k_nope + q_rope . kr) / sqrt(Dn + Dr)``; each head's ``o``
times ``sigmoid(x Wz)``; the output projection.

``ffn``: ``(silu(x Wg) * x Wu) Wd`` in a dense layer. In a routed one: ``s
= sigmoid(x Wr)`` over ALL experts; the choice is by ``s + bias``: the
experts lie in ``N_GROUP`` groups side by side, a group's score is the sum
of its two largest, the ``TOPK_GROUP`` best groups stay, and the ``TOP_K``
largest of what stays are chosen; their gates are ``s`` WITHOUT the bias
over their sum times ``ROUTE_SCALE``; the held experts' (``FIRST_HELD`` on,
as many as the weights hold) gated SwiGLUs are summed BY A LOOP over them,
plus the shared expert. What the experts held elsewhere would add is left
out: the weights are one chip's share, and so is the result.

Straightforward ``jax.numpy``: no chunk, no cache, no absorbed product, no
kernel, no mixed precision. Every matrix product runs in float32 at
``jax.default_matmul_precision("highest")``, which the caller sets. What the
weights do not carry is stated here as constants, which a test at another
size patches; every size comes from the weights' shapes.

The weights are DATA: the program's own parameter pytree
(``ray_tpu.models.bailing_hybrid.init_params`` under the same key):
``wte`` / ``lm_head`` [V, E], ``norm_f`` [E], ``blocks.segments[s][j]`` the
j-th layer of segment s's period, leaves [repeats, ...]: ``mix_norm`` /
``mlp_norm`` [E]; a KDA layer's ``kda_in`` [E, 3 H Dk], ``conv_w`` [3 H Dk,
K], ``kda_f`` [E, H Dk], ``kda_gates`` [E, 2 H] (beta | the head gate),
``A_log`` [H], ``dt_bias`` [H Dk], ``gate_norm`` [Dv], ``kda_out`` [H Dv,
E]; a latent layer's ``wq`` [E, H, Dn + Dr], ``w_dkv`` [E, R + Dr],
``kv_norm`` [R], ``w_ukv`` [R, H, Dn + Dv], ``wz`` [E, H], ``wo`` [H, Dv,
E]; a dense layer's ``w_gate`` / ``w_up`` / ``w_down``, a routed layer's
``shared_*``; ``blocks.experts`` every routed layer's ``router_w`` [E,
experts], ``expert_bias`` [experts], ``expert_fc`` / ``expert_gate`` [held,
E, M], ``expert_out`` [held, M, E]. The arithmetic below shares nothing with
the program.

Departures from the published description, each marked where it happens:
(1) a projection is held as ``[in, out]`` or ``[in, heads, head size]``, not
a ``Linear``'s ``[out, in]``, and beta's and the head gate's side by side;
(2) queries are attended ``Q_BLOCK`` at a time: the same sums; (3) the
published fast path computes the recurrence in chunks and the latent
attention with the up-projection absorbed: these are the definitions both
compute; (4) group-limited choice masks the losing groups with -inf, where
DeepSeek-V3's code fills 0: the same choice wherever ``s + bias > 0``, which
a sigmoid beside a small bias gives.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6          # rms_norm_eps
L2_EPS = 1e-6           # under the root of a key's or a query's L2 norm
LOWER_BOUND = -5.0      # kda_lower_bound
ROPE_THETA = 6e6        # rope_theta
TOP_K = 8               # num_experts_per_tok
N_GROUP = 8             # n_group
TOPK_GROUP = 4          # topk_group
ROUTE_SCALE = 2.5       # routed_scaling_factor; norm_topk_prob is true
FIRST_HELD = 0          # the first expert this chip holds
Q_BLOCK = 512           # queries attended at once (2)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, weight):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) * weight


def _unit(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _rope(x):
    """x [B, T, .., D]: position t rotated by t x inv_freq, pair (2i, 2i+1)
    by frequency i (``rope_interleave``)."""
    T, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / ROPE_THETA ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = angles.reshape((1, T) + (1,) * (x.ndim - 3) + (D // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _kda(h, p):
    """Kimi Delta Attention over h [B, T, E] (normed), from a zero state, a
    token at a time. (3)"""
    B, T, _ = h.shape
    channels, taps = p["conv_w"].shape
    heads, dv = p["A_log"].shape[0], p["gate_norm"].shape[0]
    dk = (channels - heads * dv) // (2 * heads)
    qkv = h @ p["kda_in"]
    rows = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(
        p["conv_w"][:, j] * rows[:, j:j + T] for j in range(taps)))
    q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], -1)
    q = _unit(q.reshape(B, T, heads, dk)) / jnp.sqrt(jnp.float32(dk))
    k = _unit(k.reshape(B, T, heads, dk))
    v = v.reshape(B, T, heads, dv)
    f = (h @ p["kda_f"] + p["dt_bias"]).reshape(B, T, heads, dk)
    g = LOWER_BOUND * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * f)
    b, z = jnp.split(h @ p["kda_gates"], 2, -1)               # (1)
    beta = jax.nn.sigmoid(b)                                  # [B, T, heads]

    def token(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        state = jnp.exp(g_t)[..., None] * state               # a channel's
        held = (state * k_t[:, :, :, None]).sum(2)            # S^T k
        write = beta_t[:, :, None] * (v_t - held)
        state = state + k_t[:, :, :, None] * write[:, :, None, :]
        return state, (state * q_t[:, :, :, None]).sum(2)

    _, o = jax.lax.scan(
        token, jnp.zeros((B, heads, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    o = _rms_norm(jnp.moveaxis(o, 0, 1), p["gate_norm"])      # a head's own
    o = o * jax.nn.sigmoid(z)[..., None]                      # a gate a head
    return o.reshape(B, T, heads * dv) @ p["kda_out"]


def _attention(q, k, v):
    """q and k [B, T, H, Dq], v [B, T, H, Dv] -> [B, T, H, Dv]: causal. (2)"""
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
    return jnp.moveaxis(out, 0, 1).reshape(
        B, n * Q_BLOCK, H, v.shape[-1])[:, :T]


def _latent(h, p):
    """Multi-head latent attention over h [B, T, E] (normed), from the
    up-projected keys and values. (3)"""
    B, T, E = h.shape
    H, dq = p["wq"].shape[1:]
    rank = p["kv_norm"].shape[0]
    dr = p["w_dkv"].shape[1] - rank
    dn = dq - dr
    q = (h @ p["wq"].reshape(E, -1)).reshape(B, T, H, dq)     # (1)
    c, kr = jnp.split(h @ p["w_dkv"], [rank], -1)
    kv = (_rms_norm(c, p["kv_norm"]) @ p["w_ukv"].reshape(rank, -1)).reshape(
        B, T, H, -1)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:])], -1)
    kr = jnp.broadcast_to(_rope(kr)[:, :, None, :], (B, T, H, dr))
    o = _attention(q, jnp.concatenate([k_nope, kr], -1), v)
    o = o * jax.nn.sigmoid(h @ p["wz"])[..., None]            # a gate a head
    return o.reshape(B, T, -1) @ p["wo"].reshape(-1, E)


def route(x, router_w, expert_bias):
    """x [N, E] -> gates [N, experts] float32, 0 for an expert not chosen."""
    scores = jax.nn.sigmoid(x @ router_w)
    choice = scores + expert_bias
    N, X = choice.shape
    grouped = choice.reshape(N, N_GROUP, X // N_GROUP)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)        # [N, groups]
    _, best = jax.lax.top_k(group_score, TOPK_GROUP)
    stays = jnp.zeros((N, N_GROUP), bool).at[
        jnp.arange(N)[:, None], best].set(True)
    choice = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(N, X)  # (4)
    _, chosen = jax.lax.top_k(choice, TOP_K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = ROUTE_SCALE * picked / picked.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(N)[:, None], chosen].set(picked)


def _experts(x, gates, moe, layer=None):
    """sum over the HELD experts of gate x their SwiGLU: a loop. An expert's
    matrices are read where they lie, ``moe``'s leaves [held, ..] or, with
    ``layer``, every routed layer's [layers, held, ..]: a layer's slice of
    the stacked weights would be a copy of all its experts, and six of those
    beside a long sequence's activations do not fit the chip."""
    names = ("expert_fc", "expert_gate", "expert_out")
    at = () if layer is None else (layer,)
    held = moe["expert_fc"].shape[len(at)]
    gates = gates[:, FIRST_HELD:FIRST_HELD + held]

    def one(acc, expert):
        e, g = expert
        w_up, w_gate, w_down = _f32(tuple(moe[n][at + (e,)] for n in names))
        return acc + g[:, None] * _swiglu(x, w_gate, w_up, w_down), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(held), gates.T))
    return acc


def _layer(x, p, moe, routed):
    """One layer; ``routed`` is its index among the routed layers of
    ``moe`` (every routed layer's router and experts, stacked), None for a
    dense one."""
    B, T, E = x.shape
    p = _f32(p)
    h = _rms_norm(x, p["mix_norm"])
    x = x + (_kda(h, p) if "kda_in" in p else _latent(h, p))
    h = _rms_norm(x, p["mlp_norm"]).reshape(B * T, E)
    if routed is None:
        y = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    else:
        gates = route(h, *_f32((moe["router_w"][routed],
                                moe["expert_bias"][routed])))
        y = _experts(h, gates, moe, routed) + _swiglu(
            h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return x + y.reshape(B, T, E)


def layer_order(blocks: Dict):
    """[(a layer's own weights, its index among the routed layers or None)]
    first layer to last, from the segments' layout: each segment's period
    ``repeats`` times over."""
    out, routed = [], 0
    for segment in blocks["segments"]:
        repeats = jax.tree.leaves(segment[0])[0].shape[0]
        for r in range(repeats):
            for p in segment:
                dense = "w_gate" in p
                out.append((jax.tree.map(lambda a: a[r], p),
                            None if dense else routed))
                routed += not dense
    return out


def logits(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, T] -> logits [B, T, V], float32."""
    x = jnp.asarray(params["wte"][tokens], jnp.float32)
    for p, routed in layer_order(params["blocks"]):
        x = _layer(x, p, params["blocks"].get("experts"), routed)
    x = _rms_norm(x, jnp.asarray(params["norm_f"], jnp.float32))
    return x @ jnp.asarray(params["lm_head"], jnp.float32).T
