"""Plain float32 reference of the OLMoE architecture (Muennighoff et al.
2024, "OLMoE: Open Mixture-of-Experts Language Models"; layer equations of
``transformers``' ``modeling_olmoe.py``): token embedding; pre-norm decoder
layers (RMSNorm -> causal multi-head attention with an RMSNorm over the whole
projected q and over the whole projected k, then rotary positions -> residual;
RMSNorm -> 64 routed SwiGLU experts, 8 a token -> residual); a final RMSNorm
and an untied LM head. No biases anywhere.

Straightforward ``jax.numpy``: no kernels, no cache, no sorting, no grouped
products, no mixed precision. Every matrix product runs in float32 at
``jax.default_matmul_precision("highest")``, which the caller sets
(``lib/reference.py:in_blocks``; on a TPU a float32 product is otherwise
computed in bf16 passes). What the weights do not carry is stated here:
``RMS_EPS`` (``rms_norm_eps`` 1e-5), ``ROPE_BASE`` (``rope_theta`` 10000),
``TOP_K`` (``num_experts_per_tok`` 8) and ``norm_topk_prob: false``.

The weights are DATA: the program's own parameter pytree
(``ray_tpu.models.llama.init_params`` under the same key), bf16 as the model
is published. A layer's weights become float32 as the layer is reached, an
expert's as the expert is reached, never the whole tree at once: 7.1 GB of
bf16 weights and one layer in float32 fit a chip, 14.3 GB do not. The
arithmetic below shares nothing with the program.

Departures from ``modeling_olmoe.py``, each marked where it happens:
(1) the layers are a ``lax.scan`` over the stacked weights, and the experts a
``lax.scan`` inside it, only so that they compile once; (2) every expert is
computed for every token and weighted by its gate, 0 for an expert the token
did not choose (``OlmoeSparseMoeBlock`` loops over the experts and computes
each for its own tokens: the same sum); (3) a projection is held as
``[in, heads, head size]``, not as a ``Linear``'s ``[out, in]``; (4) the
router's softmax runs in float32 like everything else here (the model runs it
in float32 inside a bf16 forward); (5) ``clip_qkv`` is null in the published
configuration and is not there; (6) no attention mask beyond the causal one,
no dropout.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5      # rms_norm_eps
ROPE_BASE = 10000.0  # rope_theta
TOP_K = 8           # num_experts_per_tok; norm_topk_prob is false


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


def route(x, router_w, top_k: int = TOP_K):
    """x [N, E] -> gates [N, experts] float32: the softmax over ALL experts,
    kept for a token's ``top_k`` largest as it is (not renormalised: they do
    not sum to 1), 0 for the others."""
    probs = jax.nn.softmax(x @ router_w, axis=-1)
    weights, chosen = jax.lax.top_k(probs, top_k)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, chosen].set(weights)


def _experts(x, gates, moe):
    """sum over experts of gate x down(silu(gate_proj(x)) * up(x)). (1), (2):
    a scan over all experts, each weighted by its gate."""

    def one(acc, expert):
        w_up, w_gate, w_down, g = expert
        w_up, w_gate, w_down = _f32((w_up, w_gate, w_down))
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return acc + g[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        moe["expert_fc"], moe["expert_gate"], moe["expert_out"], gates.T))
    return acc


def logits(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, T] -> logits [B, T, V], float32. ``params`` is the
    program's pytree: wte [V, E], lm_head [V, E], norm_f [E], blocks.*
    stacked over layers (wq [L, E, H, D], wk / wv [L, E, KV, D], wo
    [L, H, D, E], q_norm [L, H*D], k_norm [L, KV*D], attn_norm / mlp_norm
    [L, E], moe.router_w [L, E, X], moe.expert_fc (up) / expert_gate
    [L, X, E, M], moe.expert_out (down) [L, X, M, E])."""
    B, T = tokens.shape
    x = jnp.asarray(params["wte"], jnp.float32)[tokens]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, p):
        moe = p.pop("moe")
        p = _f32(p)  # this layer's attention weights and norms
        E, H, D = p["wq"].shape
        h = _rms_norm(x, p["attn_norm"])
        # (3): [in, heads, head size] flattened is the Linear's transpose
        q = _rms_norm(h @ p["wq"].reshape(E, -1), p["q_norm"])
        k = _rms_norm(h @ p["wk"].reshape(E, -1), p["k_norm"])
        v = h @ p["wv"].reshape(E, -1)
        q, k = _rope(q.reshape(B, T, H, D)), _rope(k.reshape(B, T, -1, D))
        v = v.reshape(B, T, -1, D)  # 16 key/value heads for 16 query heads
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
        att = jax.nn.softmax(jnp.where(causal[None, None], att, -jnp.inf), -1)
        a = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(B, T, H * D)
        x = x + a @ p["wo"].reshape(H * D, E)
        h = _rms_norm(x, p["mlp_norm"]).reshape(B * T, E)
        gates = route(h, jnp.asarray(moe["router_w"], jnp.float32))  # (4)
        return x + _experts(h, gates, moe).reshape(B, T, E), None

    x, _ = jax.lax.scan(lambda x, p: layer(x, dict(p)), x, params["blocks"])
    x = _rms_norm(x, jnp.asarray(params["norm_f"], jnp.float32))
    return x @ jnp.asarray(params["lm_head"], jnp.float32).T
