"""Plain float32 reference of Arcee's ``afmoe`` architecture (Trinity-Mini,
https://huggingface.co/arcee-ai/Trinity-Mini: ``config.json`` and the layer
equations of the repository's ``modeling_afmoe.py``): token embedding times
``sqrt(hidden_size)`` (``mup_enabled``); decoder layers
``h += post_attn_norm(Attn(input_norm(h)))``,
``h += post_mlp_norm(FFN(pre_mlp_norm(h)))``; a final RMSNorm and an untied
LM head. No biases anywhere.

Attention: 32 query heads over 4 key/value heads of 128; an RMSNorm over each
head's 128 values of q and of k (one gain of 128); rotary positions on q and
k in a ``sliding_attention`` layer only, none at all in a ``full_attention``
layer; causal softmax attention, in a sliding layer over the token and the
``SLIDING_WINDOW - 1`` before it (a band mask); the result times
``sigmoid(x Wg)`` elementwise; the output projection. FFN: a SwiGLU MLP in a
dense layer; in the others ``shared(x) + sum_i gate_i * expert_i(x)`` with
``s = sigmoid(x Wr)``, the ``TOP_K`` experts of ``s + expert_bias``, gates
``s`` at the chosen (without the bias) over their sum, times ``ROUTE_SCALE``.

Straightforward ``jax.numpy``: no kernels, no cache, no ring, no sorting, no
grouped products, no mixed precision. Every matrix product runs in float32
at ``jax.default_matmul_precision("highest")``, which the caller sets
(``lib/reference.py:in_blocks``). What the weights do not carry is stated
here: ``RMS_EPS``, ``ROPE_BASE``, ``TOP_K``, ``ROUTE_SCALE``,
``SLIDING_WINDOW``, ``MUP``, ``LAYER_TYPES`` (which layers slide); which are
dense follows from the weights' own layout (below).

The weights are DATA: the program's own parameter pytree
(``ray_tpu.models.afmoe.init_params`` under the same key), bf16 as the model
is published: ``wte`` / ``lm_head`` [V, E], ``norm_f`` [E],
``blocks.segments[s][j]`` the j-th layer of segment s's period, leaves
[repeats, ...] (``wq`` / ``wg`` [E, H, D], ``wk`` / ``wv`` [E, KV, D], ``wo``
[H, D, E], ``q_norm`` / ``k_norm`` [D], four norms [E]; ``w_gate`` / ``w_up``
/ ``w_down`` in a dense layer, ``shared_*`` in a routed one), and
``blocks.experts`` every routed layer's ``router_w`` [Lr, E, X],
``expert_bias`` [Lr, X], ``expert_fc`` (up) / ``expert_gate`` [Lr, X, E, M],
``expert_out`` (down) [Lr, X, M, E], in layer order. The layer ORDER is the
segments', a period repeated: that much of the layout is read here. A layer's
weights become float32 as the layer is reached, an expert's as the expert is
reached. The arithmetic below shares nothing with the program.

Departures from ``modeling_afmoe.py``, each marked where it happens: (1) the
experts are a ``lax.scan`` so that they compile once, and every expert is
computed for every token and weighted by its gate, 0 for one not chosen (the
model loops over experts and computes each for its own tokens: the same
sum); (2) a projection is held as ``[in, heads, head size]``, not as a
``Linear``'s ``[out, in]``; (3) queries are attended ``Q_BLOCK`` at a time, so that 32 heads of T x T
scores fit beside the weights at the check's length: the same sums, a block
of rows at a time; (4) no attention mask beyond the causal
band, no dropout.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5           # rms_norm_eps
ROPE_BASE = 10000.0      # rope_theta
TOP_K = 8                # num_experts_per_tok
ROUTE_SCALE = 2.826      # route_scale; route_norm is true
SLIDING_WINDOW = 2048    # sliding_window
MUP = True               # mup_enabled
# layer_types of the cut the benchmark runs: a leading dense layer, then one
# whole period of three sliding layers and a full one
# (global_attn_every_n_layers 4)
LAYER_TYPES = ("sliding_attention",) * 4 + ("full_attention",)
Q_BLOCK = 512            # queries attended at once (3)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, weight):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) * weight


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x):
    """x [B, T, H, D]: position t of every head rotated by t x inv_freq,
    the frequencies laid out twice over the head (rotate-half)."""
    T, D = x.shape[1], x.shape[3]
    inv_freq = 1.0 / ROPE_BASE ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def route(x, router_w, expert_bias, top_k: int, scale: float):
    """x [N, E] -> gates [N, experts] float32: sigmoid scores; the ``top_k``
    of score + bias are chosen; their gates are the scores WITHOUT the bias,
    over their sum, times ``scale``; 0 for the others."""
    scores = jax.nn.sigmoid(x @ router_w)
    _, chosen = jax.lax.top_k(scores + expert_bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = scale * picked / picked.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(picked)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _experts(x, gates, moe):
    """sum over experts of gate x down(silu(gate_proj(x)) * up(x)). (1)"""

    def one(acc, expert):
        w_up, w_gate, w_down, g = expert
        y = _swiglu(x, *_f32((w_gate, w_up, w_down)))
        return acc + g[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        moe["expert_fc"], moe["expert_gate"], moe["expert_out"], gates.T))
    return acc


def _attention(q, k, v, window):
    """q [B, T, H, D], k / v [B, T, KV, D] -> [B, T, H, D]: causal, within
    ``window`` where there is one; H / KV query heads share a kv head, in
    head order. (3): ``Q_BLOCK`` queries at a time."""
    B, T, H, D = q.shape
    G = H // k.shape[2]
    k, v = (jnp.repeat(a, G, axis=2) for a in (k, v))
    keys = jnp.arange(T)[None, :]

    def block(q, at):       # q [B, Q, H, D], at [Q] their positions
        seen = keys <= at[:, None]
        if window is not None:
            seen &= keys > at[:, None] - window
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
        att = jax.nn.softmax(jnp.where(seen[None, None], att, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    if T <= Q_BLOCK:
        return block(q, jnp.arange(T))
    n = -(-T // Q_BLOCK)    # the last block's queries past T see and are nothing
    q = jnp.pad(q, ((0, 0), (0, n * Q_BLOCK - T), (0, 0), (0, 0)))
    out = jax.lax.map(
        lambda a: block(*a),
        (jnp.moveaxis(q.reshape(B, n, Q_BLOCK, H, D), 1, 0),
         jnp.arange(n * Q_BLOCK).reshape(n, Q_BLOCK)))
    return jnp.moveaxis(out, 0, 1).reshape(B, n * Q_BLOCK, H, D)[:, :T]


def _layer(x, p, moe, rope: bool, window):
    """One decoder layer: ``p`` its own weights, ``moe`` the router and the
    experts of a routed layer (None: a dense one); a sliding layer rotates
    q and k (``rope``) and attends within ``window``, a full layer neither."""
    B, T, E = x.shape
    p = _f32(p)
    H, D = p["wq"].shape[1:]
    h = _rms_norm(x, p["attn_norm"])
    # (2): [in, heads, head size] flattened is the Linear's transpose
    q = (h @ p["wq"].reshape(E, -1)).reshape(B, T, H, D)
    k = (h @ p["wk"].reshape(E, -1)).reshape(B, T, -1, D)
    v = (h @ p["wv"].reshape(E, -1)).reshape(B, T, -1, D)
    q, k = _rms_norm(q, p["q_norm"]), _rms_norm(k, p["k_norm"])
    if rope:
        q, k = _rope(q), _rope(k)
    a = _attention(q, k, v, window)
    a = a.reshape(B, T, H * D) * jax.nn.sigmoid(h @ p["wg"].reshape(E, -1))
    x = x + _rms_norm(a @ p["wo"].reshape(H * D, E), p["post_attn_norm"])
    h = _rms_norm(x, p["pre_mlp_norm"]).reshape(B * T, E)
    if moe is None:
        y = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    else:
        gates = route(h, jnp.asarray(moe["router_w"], jnp.float32),
                      jnp.asarray(moe["expert_bias"], jnp.float32),
                      TOP_K, ROUTE_SCALE)
        y = _experts(h, gates, moe) + _swiglu(
            h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return x + _rms_norm(y, p["post_mlp_norm"]).reshape(B, T, E)


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
    B, T = tokens.shape
    x = jnp.asarray(params["wte"], jnp.float32)[tokens]
    if MUP:
        x = x * jnp.sqrt(jnp.float32(x.shape[-1]))
    order = layer_order(params["blocks"])
    # a cut of fewer layers runs the first of them
    for (p, i), kind in zip(order, LAYER_TYPES, strict=len(order) > 5):
        moe = None if i is None else jax.tree.map(
            lambda a: a[i], params["blocks"]["experts"])
        sliding = kind == "sliding_attention"
        x = _layer(x, p, moe, rope=sliding,
                   window=SLIDING_WINDOW if sliding else None)
    x = _rms_norm(x, jnp.asarray(params["norm_f"], jnp.float32))
    return x @ jnp.asarray(params["lm_head"], jnp.float32).T
