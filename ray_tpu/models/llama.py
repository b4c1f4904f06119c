"""Llama-family decoder in pure functional JAX: second flagship model family.

Covers the architecture family the reference serves through its LLM layer
(vLLM engine passthrough, ``python/ray/llm/_internal/serve/engines/vllm/``;
the reference ships no model code of its own): RMSNorm, rotary position
embeddings (RoPE), SwiGLU MLP, grouped-query attention (GQA), untied LM
head. Its flags cover the published shapes built from that block: routed
SwiGLU experts in place of the MLP (Mixtral: 8 experts, 2 a token, gates
renormalised; OLMoE: 64 experts, 8 a token, gates as the softmax gives them),
an RMSNorm over the whole projected q and k (OLMoE's ``qk_norm="full"``),
weights held in bfloat16 (``param_dtype``). This module is the family's
pieces; the layer stack, the cached forward, the pipeline and the loss are
:mod:`ray_tpu.models.decoder`'s, which says what each piece is given and
returns.

- plain-pytree params with a parallel logical-axis tree for pjit sharding;
  block params carry a leading [num_layers] dim
- bfloat16 activations over f32 params (or bf16 params as they are: a cast
  to the dtype an array already has is no operation; a server holds what
  the cached forward rounds on use rounded once, ``serving_params``); logits
  are the head's float32 sums, and the cached forward (serving) sums its
  residual stream in float32 too
- k and v leave the preamble with the kv heads the model has (GQA-sized:
  that is what the KV cache holds)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import narrowed
from ray_tpu.models.decoder import *  # noqa: F401,F403 — what families share
from ray_tpu.parallel.moe import (
    MoEConfig,
    init_moe_params,
    moe_layer_counted,
    moe_param_axes,
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    num_layers: int = 16
    num_heads: int = 16
    # GQA: kv heads < query heads; None = as many as ``num_heads`` (MHA)
    num_kv_heads: Optional[int] = None
    embed_dim: int = 1024
    mlp_dim: Optional[int] = None    # default: 8/3 * E rounded to 128
    rope_theta: float = 10000.0      # 500000.0 for llama-3-style long context
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"     # auto | xla | flash | ring | ulysses
    remat: bool = True
    # "dots": save matmul outputs, recompute elementwise; "full": save only
    # block boundaries (max memory savings, ~1 extra forward of FLOPs).
    remat_policy: str = "dots"
    seq_axis: str = "seq"
    # Routed experts in place of the SwiGLU MLP, each ``mlp_dim`` wide (use
    # MoEConfig(activation="swiglu"); Mixtral: 8 experts, top_k 2; OLMoE: 64,
    # top_k 8, norm_topk_prob False).
    moe: Optional[MoEConfig] = None
    # "none" | "full": RMSNorm over the WHOLE projected q and k (all heads
    # together), before the split into heads and before RoPE (OLMoE).
    qk_norm: str = "none"

    def __post_init__(self):
        if self.num_kv_heads is None:
            object.__setattr__(self, "num_kv_heads", self.num_heads)
        if self.qk_norm not in ("none", "full"):
            raise ValueError(
                f"LlamaConfig.qk_norm must be 'none' or 'full', got "
                f"{self.qk_norm!r}")

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def hidden_dim(self) -> int:
        if self.mlp_dim is not None:
            return self.mlp_dim
        h = int(self.embed_dim * 8 / 3)
        return (h + 127) // 128 * 128

    # the router's numbers under the flat names a configuration file gives
    # them (``models.config_for``): ``benchmarks/`` reads a file's keys back
    @property
    def moe_num_experts(self) -> int:
        return self.moe.num_experts if self.moe is not None else 0

    @property
    def moe_top_k(self) -> Optional[int]:
        return self.moe.top_k if self.moe is not None else None

    @property
    def moe_norm_topk_prob(self) -> Optional[bool]:
        return self.moe.norm_topk_prob if self.moe is not None else None


Config = LlamaConfig
EXPERT_ACTIVATION = "swiglu"   # of experts a configuration states no other for

LLAMA_TINY = LlamaConfig(  # test size
    vocab_size=512, max_seq_len=128, num_layers=2, num_heads=4,
    num_kv_heads=2, embed_dim=64,
)
LLAMA_160M = LlamaConfig(
    num_layers=12, num_heads=12, num_kv_heads=4, embed_dim=768,
    vocab_size=32000,
)
LLAMA_1B = LlamaConfig(
    num_layers=16, num_heads=32, num_kv_heads=8, embed_dim=2048,
    max_seq_len=4096, rope_theta=500000.0,
)
LLAMA_8B = LlamaConfig(
    num_layers=32, num_heads=32, num_kv_heads=8, embed_dim=4096,
    mlp_dim=14336, max_seq_len=8192, vocab_size=128256, rope_theta=500000.0,
)

PRESETS = {
    "llama-tiny": LLAMA_TINY,
    "llama-160m": LLAMA_160M,
    "llama-1b": LLAMA_1B,
    "llama-8b": LLAMA_8B,
}


def init_params(config: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Block params carry a leading [num_layers] dim (scanned)."""
    k = jax.random.split(key, 9)
    E, H, KV, M, V, L, D = (
        config.embed_dim, config.num_heads, config.num_kv_heads,
        config.hidden_dim, config.vocab_size, config.num_layers,
        config.head_dim,
    )
    pd = config.param_dtype
    std = 0.02
    res_std = std / (2 * L) ** 0.5

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(pd)

    blocks = {
        "attn_norm": jnp.ones((L, E), pd),
        "wq": normal(k[1], (L, E, H, D)),
        "wk": normal(k[2], (L, E, KV, D)),
        "wv": normal(k[3], (L, E, KV, D)),
        "wo": normal(k[4], (L, H, D, E), res_std),
        "mlp_norm": jnp.ones((L, E), pd),
    }
    if config.qk_norm == "full":
        blocks["q_norm"] = jnp.ones((L, H * D), pd)
        blocks["k_norm"] = jnp.ones((L, KV * D), pd)
    if config.moe is not None:
        # routed experts replace the dense FFN (never materialize both)
        blocks["moe"] = init_moe_params(
            k[5], E, M, config.moe, pd, num_layers=L, out_std=res_std
        )
    else:
        blocks["w_gate"] = normal(k[5], (L, E, M))
        blocks["w_up"] = normal(k[6], (L, E, M))
        blocks["w_down"] = normal(k[7], (L, M, E), res_std)
    return {
        "wte": normal(k[0], (V, E)),
        "blocks": blocks,
        "norm_f": jnp.ones((E,), pd),
        "lm_head": normal(k[8], (V, E)),
    }


def param_axes(config: LlamaConfig) -> Dict[str, Any]:
    """Logical axis names per parameter (see sharding.DEFAULT_RULES).
    kv-head dims use the "kv" axis (replicated by default — GQA kv heads
    often don't divide the tensor axis; override rules to shard them)."""
    axes = {
        "wte": ("vocab", "embed"),
        "blocks": {
            "attn_norm": ("stage", "norm"),
            "wq": ("stage", "embed", "heads", "head_dim"),
            "wk": ("stage", "embed", "kv", "head_dim"),
            "wv": ("stage", "embed", "kv", "head_dim"),
            "wo": ("stage", "heads", "head_dim", "embed"),
            "mlp_norm": ("stage", "norm"),
            "w_gate": ("stage", "embed", "mlp"),
            "w_up": ("stage", "embed", "mlp"),
            "w_down": ("stage", "mlp", "embed"),
        },
        "norm_f": ("norm",),
        "lm_head": ("vocab", "embed"),
    }
    if config.qk_norm == "full":
        axes["blocks"]["q_norm"] = ("stage", "norm")
        axes["blocks"]["k_norm"] = ("stage", "norm")
    if config.moe is not None:
        for name in ("w_gate", "w_up", "w_down"):
            del axes["blocks"][name]
        axes["blocks"]["moe"] = moe_param_axes(
            num_layers=config.num_layers, config=config.moe
        )
    return axes


def _rms_norm(x, g, eps, dtype=None):
    """``dtype``: what the result is held in (default: as ``x`` is)."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * scale * g).astype(dtype or x.dtype)


def _turned_on_lanes(x, angles, partner, sign):
    """A rotary embedding of the full forward, on x as it lies: x [B, T, D]
    or, heads-major, [B, H, T, D]; ``angles`` [B, T, D] float32, a channel's
    own; channel j meets ``sign[j] * x[partner[j]]`` (numpy [D] each) -> x
    cos + that sin, in float32, as x's dtype. The partner comes through a
    product with a constant [D, D] matrix of 0 and +-1, which is exact, and
    the whole is one pass over whole rows: slices of the channels and their
    concatenation (half a row each, or a [.., D / 2, 2] view, or a roll)
    make the compiler lay arrays of half a row's lanes out positions-minor
    or keep them as arrays of their own, padded to whole lane tiles, and
    copy the result into the kernels' order (sandbox compiles and the
    traced steps, PR 56)."""
    D = x.shape[-1]
    swap = np.zeros((D, D), np.float32)
    swap[partner, np.arange(D)] = sign
    if x.ndim == 4:
        angles = angles[:, None]                             # [B, 1, T, D]
    # the batch rows as the product's batch dimension: a remat policy that
    # keeps the products without one (``dots``) then keeps not this one,
    # which is as cheap to make again as to read
    other = jnp.einsum("b...d,bde->b...e", x, jnp.broadcast_to(
        jnp.asarray(swap, x.dtype), (x.shape[0], D, D)),
        precision=jax.lax.Precision.HIGHEST)
    return (x.astype(jnp.float32) * jnp.cos(angles)
            + other.astype(jnp.float32) * jnp.sin(angles)).astype(x.dtype)


def _rope(x: jax.Array, pos: jax.Array, theta: float,
          heads_major: bool = False) -> jax.Array:
    """Rotary embedding, channel i with channel i + D / 2. x: [B, T, H, D],
    or [B, H, T, D] where ``heads_major`` (the full forward's:
    ``_turned_on_lanes``); pos: [B, T] absolute positions."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    angles = pos[..., None].astype(jnp.float32) * freqs      # [B, T, D/2]
    if heads_major:
        j = np.arange(D)
        return _turned_on_lanes(
            x, jnp.tile(angles, 2), (j + D // 2) % D,
            np.where(j < D // 2, -1.0, 1.0))
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)     # [B, T, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def serving_params(config: LlamaConfig, params):
    """The projections, the MLP or the experts and ``lm_head`` are read
    through ``.astype(config.dtype)`` alone. Read as they are: ``wte`` (the
    cached forward's residual stream is float32), every RMSNorm gain
    (``_rms_norm`` multiplies in float32) and the router (float32;
    ``moe.stacked_for`` leaves it alone too)."""
    return narrowed(params, config.dtype, as_given=(
        "wte", "attn_norm", "mlp_norm", "q_norm", "k_norm", "norm_f",
        "router_w"))


def layers(config: LlamaConfig, blocks, cached: bool):
    return single_kind(config, blocks, cached)   # every layer alike


def embed(config: LlamaConfig, params, tokens, pos, cached: bool):
    """Token embeddings (positions enter in ``qkv``). The cached forward
    sums its residual stream in float32 (the sublayers compute in
    ``config.dtype``): two roundings a layer of the whole stream were the
    larger part of a served token's distance from a float32 forward."""
    return params["wte"][tokens].astype(
        jnp.float32 if cached else config.dtype)


def qkv(config: LlamaConfig, kind, layer, x, pos, heads_major: bool = False):
    """The attention preamble: x [B, T, E] normed, pos [B, T] absolute -> q
    [B, T, KV, G, D] (the G query heads of a kv head together, at G = 1
    too) and k [B, T, KV, D], both rotated, and v [B, T, KV, D]; where
    ``heads_major``, q [B, H, T, D], k and v [B, KV, T, D]. A norm over all
    of a position's channels together is written positions-major only: its
    q and k are transposed behind it."""
    B, T = x.shape[:2]
    full_norm = config.qk_norm == "full"
    h = _rms_norm(x, layer["attn_norm"], config.rms_eps, config.dtype)
    q, k = (heads_in(h, layer[w].astype(h.dtype),
                     heads_major and not full_norm) for w in ("wq", "wk"))
    v = heads_in(h, layer["wv"].astype(h.dtype), heads_major)
    if full_norm:
        q = _rms_norm(q.reshape(B, T, -1), layer["q_norm"],
                      config.rms_eps).reshape(q.shape)
        k = _rms_norm(k.reshape(B, T, -1), layer["k_norm"],
                      config.rms_eps).reshape(k.shape)
        if heads_major:
            q, k = swapped(q, k)
    q = _rope(q, pos, config.rope_theta, heads_major)
    k = _rope(k, pos, config.rope_theta, heads_major)
    if heads_major:
        return q, k, v
    return q.reshape(B, T, config.num_kv_heads, -1, config.head_dim), k, v


def attn_out(config: LlamaConfig, layer, x, attn, heads_major: bool = False):
    """Output projection + residual add."""
    return x + heads_out(attn, layer["wo"].astype(attn.dtype), heads_major)


def ffn(config: LlamaConfig, kind, layer, x, rng, row_mask, stacked,
        from_input=None):
    """mlp_norm + SwiGLU MLP (or routed experts) + residual -> (x, aux_loss,
    experts that received a row: 0 for the dense MLP)."""
    h = _rms_norm(x, layer["mlp_norm"], config.rms_eps, config.dtype)
    if config.moe is not None:
        moe, index = (layer["moe"], None) if stacked is None else stacked
        h, aux, touched = moe_layer_counted(
            moe, h, config.moe, rng=rng, row_mask=row_mask, layer=index)
        return x + h, aux, touched
    gate = jnp.einsum("bte,em->btm", h, layer["w_gate"].astype(h.dtype))
    up = jnp.einsum("bte,em->btm", h, layer["w_up"].astype(h.dtype))
    h = jax.nn.silu(gate) * up
    h = jnp.einsum("btm,me->bte", h, layer["w_down"].astype(h.dtype))
    return x + h, jnp.float32(0.0), jnp.int32(0)


def final_norm(config: LlamaConfig, params, x):
    return _rms_norm(x, params["norm_f"], config.rms_eps, config.dtype)


def head_weight(params):
    return params["lm_head"]


def head(config: LlamaConfig, params, x):
    """Final features [B, T, E] -> logits [B, T, V] float32, straight from
    the product's float32 sums: a bf16 result made float32 afterwards had
    lost 0.016 of a logit of 4 (a chosen token's log-probability was off by
    that much before any layer's error), for nothing: the bytes written
    are the same."""
    return jnp.einsum("bte,ve->btv", x, head_weight(params).astype(x.dtype),
                      preferred_element_type=jnp.float32)
