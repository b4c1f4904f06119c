"""GPT-2 in pure functional JAX: the flagship train/serve model family.

Matches the architecture the reference benchmarks with torch ("Ray Train
GPT-2 tokens/sec/chip", BASELINE.md north star): learned positional
embeddings, pre-LN transformer blocks, GELU MLP, weight-tied LM head. This
module is the family's pieces; the layer stack, the cached forward, the
pipeline and the loss are :mod:`ray_tpu.models.decoder`'s, which says what
each piece is given and returns.

- Params are a plain pytree with a parallel *logical axis* tree
  (``param_axes``) consumed by ``ray_tpu.parallel.sharding`` — pjit shards
  params (fsdp/tensor), XLA inserts the collectives. Block params carry a
  leading [num_layers] dim: the decoder's stacked super-layer, and the dim
  the "stage" mesh axis splits.
- bfloat16 activations, f32 params + optimizer (standard mixed precision):
  every product multiplies by its weight rounded to ``dtype``. Training
  rounds the float32 parameters it updates on use; a server holds them
  rounded once (``serving_params``), the norms' gains and biases float32 as
  ``_layer_norm`` reads them. The cached forward's residual stream is
  ``dtype`` too, and the head rounds its logits to ``dtype`` before float32
  (llama's does neither: ROADMAP Queue 3, item 3).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import narrowed
from ray_tpu.models.decoder import *  # noqa: F401,F403 — what families share
from ray_tpu.parallel.moe import (
    MoEConfig,
    init_moe_params,
    moe_layer_counted,
    moe_param_axes,
)


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304          # padded to a multiple of 128 for the MXU
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16        # activation dtype
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"     # auto | xla | flash | flash_interpret | ring | ulysses
    remat: bool = True
    # "dots": save matmul outputs, recompute elementwise (cheap recompute,
    # moderate memory — the right default below memory pressure). "full":
    # save only block boundaries (max memory savings, ~1 extra forward).
    remat_policy: str = "dots"
    seq_axis: str = "seq"            # mesh axis for ring/ulysses
    moe: Optional[MoEConfig] = None  # replace MLPs with MoE when set (EP)

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def mlp_dim(self) -> int:
        return self.embed_dim * self.mlp_ratio

    @property
    def num_kv_heads(self) -> int:
        return self.num_heads            # every query head has its own k, v


Config = GPT2Config
EXPERT_ACTIVATION = "gelu"   # of experts a configuration states no other for

# Model zoo sizes (OpenAI GPT-2 family).
GPT2_SMALL = GPT2Config(num_layers=12, num_heads=12, embed_dim=768)
GPT2_MEDIUM = GPT2Config(num_layers=24, num_heads=16, embed_dim=1024)
GPT2_LARGE = GPT2Config(num_layers=36, num_heads=20, embed_dim=1280)
GPT2_XL = GPT2Config(num_layers=48, num_heads=25, embed_dim=1600)
GPT2_TINY = GPT2Config(  # test size
    vocab_size=512, max_seq_len=128, num_layers=2, num_heads=2, embed_dim=64
)

PRESETS = {
    "gpt2-tiny": GPT2_TINY,
    "gpt2-small": GPT2_SMALL,
    "gpt2-medium": GPT2_MEDIUM,
    "gpt2-large": GPT2_LARGE,
    "gpt2-xl": GPT2_XL,
}


def init_params(config: GPT2Config, key: jax.Array) -> Dict[str, Any]:
    """Initialize parameters. Block params carry a leading [num_layers] dim
    (scanned / stage-shardable)."""
    k = jax.random.split(key, 10)
    E, H, M, V, L = (
        config.embed_dim,
        config.num_heads,
        config.mlp_dim,
        config.vocab_size,
        config.num_layers,
    )
    D = config.head_dim
    pd = config.param_dtype
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(pd)

    # residual-scaled init for output projections (GPT-2 paper)
    res_std = std / (2 * L) ** 0.5
    params = {
        "wte": normal(k[0], (V, E)),
        "wpe": normal(k[1], (config.max_seq_len, E), 0.01),
        "blocks": {
            "ln1_g": jnp.ones((L, E), pd),
            "ln1_b": jnp.zeros((L, E), pd),
            "qkv_w": normal(k[2], (L, E, 3, H, D)),
            "qkv_b": jnp.zeros((L, 3, H, D), pd),
            "proj_w": normal(k[3], (L, H, D, E), res_std),
            "proj_b": jnp.zeros((L, E), pd),
            "ln2_g": jnp.ones((L, E), pd),
            "ln2_b": jnp.zeros((L, E), pd),
            "fc_w": normal(k[4], (L, E, M)),
            "fc_b": jnp.zeros((L, M), pd),
            "out_w": normal(k[5], (L, M, E), res_std),
            "out_b": jnp.zeros((L, E), pd),
        },
        "ln_f_g": jnp.ones((E,), pd),
        "ln_f_b": jnp.zeros((E,), pd),
    }
    if config.moe is not None:
        params["blocks"]["moe"] = init_moe_params(
            k[6], E, M, config.moe, pd, num_layers=L, out_std=res_std
        )
    return params


def param_axes(config: GPT2Config) -> Dict[str, Any]:
    """Logical axis names per parameter (see sharding.DEFAULT_RULES)."""
    axes = {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": {
            "ln1_g": ("stage", "norm"),
            "ln1_b": ("stage", "norm"),
            "qkv_w": ("stage", "embed", None, "heads", "head_dim"),
            "qkv_b": ("stage", None, "heads", "head_dim"),
            "proj_w": ("stage", "heads", "head_dim", "embed"),
            "proj_b": ("stage", "norm"),
            "ln2_g": ("stage", "norm"),
            "ln2_b": ("stage", "norm"),
            "fc_w": ("stage", "embed", "mlp"),
            "fc_b": ("stage", "mlp"),
            "out_w": ("stage", "mlp", "embed"),
            "out_b": ("stage", "norm"),
        },
        "ln_f_g": ("norm",),
        "ln_f_b": ("norm",),
    }
    if config.moe is not None:
        axes["blocks"]["moe"] = moe_param_axes(
            num_layers=config.num_layers, config=config.moe
        )
    return axes


def _layer_norm(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * g + b).astype(x.dtype)


def serving_params(config: GPT2Config, params):
    """Every matrix, every bias, ``wte`` (one copy serves ``embed`` and the
    tied ``head``: both cast it) and ``wpe`` are read through
    ``.astype(config.dtype)`` alone; ``_layer_norm`` multiplies by its gains
    and biases in float32, and the router runs in float32
    (``moe.stacked_for`` leaves it alone too)."""
    return narrowed(params, config.dtype, as_given=(
        "ln1_g", "ln1_b", "ln2_g", "ln2_b", "ln_f_g", "ln_f_b", "router_w"))


def layers(config: GPT2Config, blocks, cached: bool):
    return single_kind(config, blocks, cached)   # every layer alike


def embed(config: GPT2Config, params, tokens, pos, cached: bool):
    """Token plus learned position embeddings, in ``config.dtype``. The full
    forward's positions are 0..T-1, a slice of the table."""
    x = params["wte"][tokens].astype(config.dtype)
    wpe = params["wpe"][pos] if cached else params["wpe"][: tokens.shape[1]][None]
    return x + wpe.astype(config.dtype)


def qkv(config: GPT2Config, kind, layer, x, pos, heads_major: bool = False):
    """ln1 + the fused projection: [B, T, E] → (q, k, v) each [B, T, H, D],
    or [B, H, T, D] where ``heads_major``: transposed behind the one
    product, as ``attn_out`` transposes back before its own. At 64 channels
    a head ``ops.attention.flash_attention`` undoes both (its docstring says
    why), the compiler drops each pair, and the products are the ones the
    cached forward has."""
    h = _layer_norm(x, layer["ln1_g"], layer["ln1_b"])
    qkv = jnp.einsum("bte,eshd->btshd", h, layer["qkv_w"].astype(h.dtype))
    qkv = qkv + layer["qkv_b"].astype(h.dtype)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    return swapped(q, k, v) if heads_major else (q, k, v)


def attn_out(config: GPT2Config, layer, x, attn, heads_major: bool = False):
    """Output projection + residual add; ``attn`` [B, H, T, D] where
    ``heads_major``, transposed before the product (``qkv``)."""
    if heads_major:
        (attn,) = swapped(attn)
    attn = jnp.einsum("bthd,hde->bte", attn, layer["proj_w"].astype(x.dtype))
    return x + attn + layer["proj_b"].astype(x.dtype)


def ffn(config: GPT2Config, kind, layer, x, rng, row_mask, stacked,
        from_input=None):
    """ln2 + MLP (or MoE) + residual. Returns (x, aux_loss, experts that
    received a row: 0 for the dense MLP)."""
    h = _layer_norm(x, layer["ln2_g"], layer["ln2_b"])
    if config.moe is not None:
        moe, index = (layer["moe"], None) if stacked is None else stacked
        h, aux, touched = moe_layer_counted(
            moe, h, config.moe, rng=rng, row_mask=row_mask, layer=index)
        return x + h, aux, touched
    h = jnp.einsum("bte,em->btm", h, layer["fc_w"].astype(h.dtype))
    h = jax.nn.gelu(h + layer["fc_b"].astype(h.dtype))
    h = jnp.einsum("btm,me->bte", h, layer["out_w"].astype(h.dtype))
    return (x + h + layer["out_b"].astype(h.dtype), jnp.float32(0.0),
            jnp.int32(0))


def final_norm(config: GPT2Config, params, x):
    return _layer_norm(x, params["ln_f_g"], params["ln_f_b"])


def head_weight(params):
    """The tied head: the token embedding."""
    return params["wte"]


def head(config: GPT2Config, params, x):
    """Final features [B, T, E] → logits [B, T, V] float32, rounded to
    ``x.dtype`` on the way (what cells 1-3 compile; ``llama.head`` says what
    that costs)."""
    logits = jnp.einsum("bte,ve->btv", x, head_weight(params).astype(x.dtype))
    return logits.astype(jnp.float32)
