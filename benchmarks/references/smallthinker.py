"""Plain float32 reference of PowerInfer's SmallThinker architecture
(SmallThinker-21BA3B-Instruct, https://huggingface.co/PowerInfer/
SmallThinker-21BA3B-Instruct: ``config.json``, and the layer equations of
the repository's modeling code and of llama.cpp's graph for it as ISSUE 40's
author knows them), on ONE chip's share of a layer's experts and of the
vocabulary: token embedding (no multiplier); decoder layers

    r   = h Wr                                   (the router reads the
                                                  layer's INPUT, un-normed,
                                                  before attention)
    h'  = h + Attn(input_norm(h)) Wo
    h'' = h' + sum_e g_e expert_e(post_attn_norm(h'))

a final RMSNorm and an untied LM head. No biases anywhere.

Attention: 28 query heads over 4 key/value heads of 128 (28 x 128 = 3584 is
not the model's 2560); rotary positions on q and k in a WINDOW layer only
(``LAYOUT`` 1), where a token sees itself and the ``SLIDING_WINDOW - 1``
before it (a band mask); none at all in a GLOBAL layer (``LAYOUT`` 0), which
is causal over everything. Experts: ``p = softmax(r)`` over ALL the experts
the router scores, the ``TOP_K`` largest chosen, gates ``p / sum of the
chosen``; ``expert(y) = (relu(y Wg) * (y Wu)) Wd``, gated by ReLU.

THE SHARE. The weights hold the experts ``FIRST_HELD : FIRST_HELD + n`` of
each layer (n from their own shape) and rows ``0 : V`` of the embedding and
the head; the router's matrix is whole. The layer's result is the HELD
experts' part of the sum: the gate of an expert held elsewhere multiplies
nothing here, and nothing stands in for the chips that hold it. The
program computes the same part (``parallel/moe.py``).

Straightforward ``jax.numpy``: no kernels, no sorting, no grouped products,
no mixed precision. Every matrix product runs in float32 at
``jax.default_matmul_precision("highest")``, which the caller sets
(``lib/reference.py:in_blocks``). What the weights do not carry is stated
here: ``RMS_EPS``, ``ROPE_BASE``, ``TOP_K``, ``SLIDING_WINDOW``, ``LAYOUT``,
``FIRST_HELD``, ``AUX_WEIGHT``.

The weights are DATA: the program's own parameter pytree
(``ray_tpu.models.smallthinker.init_params`` under the same key): ``wte`` /
``lm_head`` [V, E], ``norm_f`` [E], ``blocks.segments[s][j]`` the j-th layer
of segment s's period, leaves [repeats, ...] (``wq`` [E, H, D], ``wk`` /
``wv`` [E, KV, D], ``wo`` [H, D, E], ``attn_norm`` / ``mlp_norm`` [E]), and
``blocks.experts`` every layer's ``router_w`` [L, E, X], ``expert_fc`` (up) /
``expert_gate`` [L, n, E, M], ``expert_out`` (down) [L, n, M, E], in layer
order. The layer ORDER is the segments', a period repeated: that much of
the layout is read here. The arithmetic below shares nothing with the
program.

Departures from the modeling code, each marked where it happens: (1) the
held experts are a ``lax.scan`` so that they compile once, and every held
expert is computed for every token and weighted by its gate, 0 for one not
chosen (the model computes each for its own tokens: the same sum); (2) a
projection is held as ``[in, heads, head size]``, not as a ``Linear``'s
``[out, in]``; (3) queries are attended ``Q_BLOCK`` at a time, so that 28
heads of T x T scores fit beside the weights at the check's length: the
same sums, a block of rows at a time, and a block is computed again in a
backward pass rather than kept (the gradient comparison on the chip, PERF.md
section 6: 28 heads of 8192 x 8192 probabilities are 7.5 GB a layer); (4) no
attention mask beyond the causal band, no dropout.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6           # rms_norm_eps
ROPE_BASE = 1.5e6        # rope_theta
TOP_K = 6                # moe_num_active_primary_experts
SLIDING_WINDOW = 4096    # sliding_window_size
# sliding_window_layout (= rope_layout) of the cut the benchmark runs: one
# whole period, a global layer and three window layers
LAYOUT = (0, 1, 1, 1)
FIRST_HELD = 0           # the first expert of this chip's share
AUX_WEIGHT = 0.01        # the program's default; config.json gives none
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


def route(logits, top_k: int):
    """The router's logits [N, X] -> (gates [N, X] float32: the softmax over
    ALL X, the ``top_k`` largest kept and divided by their sum, 0 for the
    others; the softmax itself)."""
    probs = jax.nn.softmax(logits, axis=-1)
    picked, chosen = jax.lax.top_k(probs, top_k)
    picked = picked / picked.sum(-1, keepdims=True)
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, chosen].set(picked), probs


def aux_loss(gates, probs):
    """Load-balancing loss over ALL X experts (Switch): X x sum over experts
    of (mean probability) x (share of the chosen pairs), times
    ``AUX_WEIGHT``."""
    X = probs.shape[-1]
    chosen = (gates > 0).sum(axis=0)
    return AUX_WEIGHT * X * jnp.sum(
        probs.mean(axis=0) * chosen / jnp.maximum(chosen.sum(), 1))


def experts_part(y, gates, moe):
    """sum over the HELD experts of gate x down(relu(gate_proj(y)) * up(y)):
    y [N, E], ``gates`` [N, held] the held experts' columns. (1)"""

    def one(acc, expert):
        w_up, w_gate, w_down, g = expert
        w_up, w_gate, w_down = _f32((w_up, w_gate, w_down))
        out = (jax.nn.relu(y @ w_gate) * (y @ w_up)) @ w_down
        return acc + g[:, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(y), (
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
    block = jax.checkpoint(block)
    n = -(-T // Q_BLOCK)    # the last block's queries past T see and are nothing
    q = jnp.pad(q, ((0, 0), (0, n * Q_BLOCK - T), (0, 0), (0, 0)))
    out = jax.lax.map(
        lambda a: block(*a),
        (jnp.moveaxis(q.reshape(B, n, Q_BLOCK, H, D), 1, 0),
         jnp.arange(n * Q_BLOCK).reshape(n, Q_BLOCK)))
    return jnp.moveaxis(out, 0, 1).reshape(B, n * Q_BLOCK, H, D)[:, :T]


def _layer(x, p, moe, window_layer: bool):
    """One decoder layer -> (the stream, its auxiliary loss): ``p`` its own
    weights, ``moe`` its router and the experts held; a window layer rotates
    q and k and attends within ``SLIDING_WINDOW``, a global layer neither."""
    B, T, E = x.shape
    p = _f32(p)
    H, D = p["wq"].shape[1:]
    # the router reads the layer's input as it is
    gates, probs = route(
        x.reshape(B * T, E) @ jnp.asarray(moe["router_w"], jnp.float32), TOP_K)
    h = _rms_norm(x, p["attn_norm"])
    # (2): [in, heads, head size] flattened is the Linear's transpose
    q = (h @ p["wq"].reshape(E, -1)).reshape(B, T, H, D)
    k = (h @ p["wk"].reshape(E, -1)).reshape(B, T, -1, D)
    v = (h @ p["wv"].reshape(E, -1)).reshape(B, T, -1, D)
    if window_layer:
        q, k = _rope(q), _rope(k)
    a = _attention(q, k, v, SLIDING_WINDOW if window_layer else None)
    x = x + a.reshape(B, T, H * D) @ p["wo"].reshape(H * D, E)
    y = _rms_norm(x, p["mlp_norm"]).reshape(B * T, E)
    held = moe["expert_fc"].shape[0]
    part = experts_part(y, gates[:, FIRST_HELD:FIRST_HELD + held], moe)
    return x + part.reshape(B, T, E), aux_loss(gates, probs)


def layer_order(blocks: Dict):
    """A layer's own weights, first layer to last, from the segments'
    layout: each segment's period ``repeats`` times over."""
    out = []
    for segment in blocks["segments"]:
        repeats = jax.tree.leaves(segment[0])[0].shape[0]
        for r in range(repeats):
            out.extend(jax.tree.map(lambda a: a[r], p) for p in segment)
    return out


def logits_and_aux(params: Dict, tokens: jax.Array):
    """tokens [B, T] -> (logits [B, T, V] float32, the layers' summed
    auxiliary loss)."""
    x = jnp.asarray(params["wte"], jnp.float32)[tokens]
    aux = jnp.float32(0.0)
    order = layer_order(params["blocks"])
    # a cut of fewer layers runs the first of them
    for i, (p, mark) in enumerate(
            zip(order, LAYOUT, strict=len(order) > len(LAYOUT))):
        moe = jax.tree.map(lambda a: a[i], params["blocks"]["experts"])
        x, layer_aux = _layer(x, p, moe, bool(mark))
        aux = aux + layer_aux
    x = _rms_norm(x, jnp.asarray(params["norm_f"], jnp.float32))
    return x @ jnp.asarray(params["lm_head"], jnp.float32).T, aux


def logits(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, T] -> logits [B, T, V], float32."""
    return logits_and_aux(params, tokens)[0]
