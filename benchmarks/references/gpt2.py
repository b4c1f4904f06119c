"""Plain float32 reference of the GPT-2 architecture (Radford et al. 2019,
"Language Models are Unsupervised Multitask Learners"; layout of OpenAI's
``gpt-2/src/model.py``): learned token and position embeddings, pre-LN
blocks (LN -> causal multi-head attention -> residual, LN -> 4x GELU MLP ->
residual), a final LN and the LM head tied to the token embedding.

Straightforward ``jax.numpy``, no kernels, no cache, no remat, no mixed
precision (the layers are a ``lax.scan`` over the stacked block weights
only so that 48 of them compile as one): every matrix product runs in float32 at
``jax.default_matmul_precision("highest")`` (on a TPU a float32 product is
otherwise computed in bf16 passes). Departures from the published model,
shared with the program under test: the vocabulary is padded from 50257 to
50304 rows, and GELU is the tanh approximation OpenAI's code uses (so does
``jax.nn.gelu`` by default).

The weights are DATA here: the comparison needs the very weights the
program initialised, so callers pass the program's parameter pytree
(``ray_tpu.models.gpt2.init_params`` under the same key). The arithmetic
below shares nothing with the program.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def _layer_norm(x, g, b, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def logits(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, T] -> logits [B, T, V], float32. ``params`` is the
    program's pytree: wte [V,E], wpe [S,E], blocks.* stacked over layers
    (qkv_w [L,E,3,H,D], proj_w [L,H,D,E], fc_w [L,E,M], out_w [L,M,E])."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    B, T = tokens.shape
    wte, blocks = f32(params["wte"]), params["blocks"]
    x = wte[tokens] + f32(params["wpe"])[:T][None]
    H, D = blocks["qkv_w"].shape[3], blocks["qkv_w"].shape[4]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def block(x, p):
        h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
        qkv = h @ p["qkv_w"].reshape(-1, 3 * H * D) + p["qkv_b"].reshape(-1)
        q, k, v = (
            qkv[..., i * H * D:(i + 1) * H * D].reshape(B, T, H, D)
            for i in range(3)
        )
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
        att = jnp.where(causal[None, None], att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(B, T, H * D)
        x = x + a @ p["proj_w"].reshape(H * D, -1) + p["proj_b"]
        h = _layer_norm(x, p["ln2_g"], p["ln2_b"])
        h = _gelu(h @ p["fc_w"] + p["fc_b"])
        return x + h @ p["out_w"] + p["out_b"], None

    x, _ = jax.lax.scan(block, x, jax.tree.map(f32, blocks))
    x = _layer_norm(x, f32(params["ln_f_g"]), f32(params["ln_f_b"]))
    return x @ wte.T
