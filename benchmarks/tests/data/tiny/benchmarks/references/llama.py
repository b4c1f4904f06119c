"""Plain float32 reference of the Llama architecture (Touvron et al. 2023,
"LLaMA: Open and Efficient Foundation Language Models"; grouped-query
attention as in Llama 2): token embeddings and no learned positions, pre-norm
blocks (RMSNorm -> causal attention with rotary positions on queries and keys,
several query heads reading one key/value head -> residual, RMSNorm -> gated
SiLU MLP -> residual), a final RMSNorm and an LM head of its own (untied).

Straightforward ``jax.numpy``, no kernels, no cache, no remat, no mixed
precision; the layers are a ``lax.scan`` over the stacked block weights only
so that many of them compile as one. What the weights do not carry is stated
here and is the published model's: rotary base 10000, pairs (i, i + D/2)
turned together (the "rotate half" layout of the released checkpoints), norm
epsilon 1e-5.

The weights are DATA: callers pass the program's parameter pytree (wte [V,E],
blocks.* stacked over layers: attn_norm, mlp_norm [L,E], wq [L,E,H,D], wk, wv
[L,E,KV,D], wo [L,H,D,E], w_gate, w_up [L,E,M], w_down [L,M,E]; norm_f [E],
lm_head [V,E]). The arithmetic below shares nothing with the program.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

ROPE_BASE = 10000.0
NORM_EPS = 1e-5


def _rms_norm(x, g):
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + NORM_EPS) * g


def _rotary(x):
    """x [B, T, heads, D]: the pair (x[i], x[i + D/2]) at position t turned by
    the angle t * ROPE_BASE ** (-2i / D)."""
    T, half = x.shape[1], x.shape[-1] // 2
    angle = (jnp.arange(T, dtype=jnp.float32)[:, None]
             * ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def logits(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, T] -> logits [B, T, V], float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    B, T = tokens.shape
    blocks = params["blocks"]
    (H, D), KV = blocks["wq"].shape[2:], blocks["wk"].shape[2]
    G = H // KV  # query heads to a key/value head: head h reads group h // G
    x = f32(params["wte"])[tokens]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def block(x, p):
        h = _rms_norm(x, p["attn_norm"])
        q = _rotary((h @ p["wq"].reshape(-1, H * D)).reshape(B, T, H, D))
        k = _rotary((h @ p["wk"].reshape(-1, KV * D)).reshape(B, T, KV, D))
        v = (h @ p["wv"].reshape(-1, KV * D)).reshape(B, T, KV, D)
        q = q.reshape(B, T, KV, G, D)
        att = jnp.einsum("bqngd,bknd->bngqk", q, k) / jnp.sqrt(jnp.float32(D))
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        a = jnp.einsum("bngqk,bknd->bqngd", att, v).reshape(B, T, H * D)
        x = x + a @ p["wo"].reshape(H * D, -1)
        h = _rms_norm(x, p["mlp_norm"])
        gate = h @ p["w_gate"]
        h = gate / (1.0 + jnp.exp(-gate)) * (h @ p["w_up"])  # SiLU gate
        return x + h @ p["w_down"], None

    x, _ = jax.lax.scan(block, x, jax.tree.map(f32, blocks))
    return _rms_norm(x, f32(params["norm_f"])) @ f32(params["lm_head"]).T
