"""Llama-family decoder in pure functional JAX: second flagship model.

Covers the architecture family the reference serves through its LLM layer
(vLLM engine passthrough, ``python/ray/llm/_internal/serve/engines/vllm/``;
the reference ships no model code of its own): RMSNorm, rotary position
embeddings (RoPE), SwiGLU MLP, grouped-query attention (GQA), untied LM
head. Its flags cover the published shapes built from that block: routed
SwiGLU experts in place of the MLP (Mixtral: 8 experts, 2 a token, gates
renormalised; OLMoE: 64 experts, 8 a token, gates as the softmax gives them),
an RMSNorm over the whole projected q and k (OLMoE's ``qk_norm="full"``),
weights held in bfloat16 (``param_dtype``). Same TPU-first skeleton as
:mod:`ray_tpu.models.gpt2`:

- plain-pytree params with a parallel logical-axis tree for pjit sharding
- one scanned super-layer (``lax.scan`` over depth), remat on the body
- pluggable attention (xla | flash pallas | ring | ulysses)
- bfloat16 activations over f32 params (or bf16 params as they are: a cast
  to the dtype an array already has is no operation); logits are the head's
  float32 sums, and the cached forward (serving) sums its residual stream in
  float32 too
- static-shape KV cache (GQA-sized: kv heads, not query heads) for the
  slot-based continuous-batching decode engine
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models import kv_cache
from ray_tpu.ops.attention import attention
from ray_tpu.parallel.moe import (
    MoEConfig,
    init_moe_params,
    moe_layer_counted,
    moe_param_axes,
    stacked_for,
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 4            # GQA: kv heads < query heads
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

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    # the router's numbers under the flat names ``LLMConfig`` and a
    # configuration file give them
    @property
    def moe_num_experts(self) -> int:
        return self.moe.num_experts if self.moe is not None else 0

    @property
    def moe_top_k(self) -> Optional[int]:
        return self.moe.top_k if self.moe is not None else None

    @property
    def moe_norm_topk_prob(self) -> Optional[bool]:
        return self.moe.norm_topk_prob if self.moe is not None else None


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


def _remat_policy(config):
    """See gpt2._remat_policy: "dots" saves matmul outputs, "full" saves
    only block boundaries."""
    if getattr(config, "remat_policy", "dots") == "full":
        return None
    return jax.checkpoint_policies.dots_with_no_batch_dims_saveable


def _rms_norm(x, g, eps, dtype=None):
    """``dtype``: what the result is held in (default: as ``x`` is)."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * scale * g).astype(dtype or x.dtype)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [B, T, H, D], pos: [B, T] absolute positions."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    angles = pos[..., None].astype(jnp.float32) * freqs      # [B, T, D/2]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)     # [B, T, 1, D/2]
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _repeat_kv(x: jax.Array, n: int) -> jax.Array:
    """[B, T, KV, D] -> [B, T, KV*n, D] (GQA head expansion)."""
    if n == 1:
        return x
    B, T, KV, D = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (B, T, KV, n, D)
    ).reshape(B, T, KV * n, D)


def _attention_dispatch(config: LlamaConfig, q, k, v, mesh: Optional[Mesh]):
    impl = config.attention_impl
    if impl == "ring":
        from ray_tpu.parallel.ring_attention import ring_attention

        return ring_attention(q, k, v, mesh=mesh, axis=config.seq_axis,
                              causal=True)
    if impl == "ulysses":
        from ray_tpu.parallel.ring_attention import ulysses_attention

        return ulysses_attention(q, k, v, mesh=mesh, axis=config.seq_axis,
                                 causal=True)
    return attention(q, k, v, causal=True, impl=impl, mesh=mesh)


def _head(params, x):
    """Final features [B, T, E] -> logits [B, T, V] float32, straight from
    the product's float32 sums: a bf16 result made float32 afterwards had
    lost 0.016 of a logit of 4 (a chosen token's log-probability was off by
    that much before any layer's error), for nothing: the bytes written
    are the same."""
    return jnp.einsum("bte,ve->btv", x, params["lm_head"].astype(x.dtype),
                      preferred_element_type=jnp.float32)


def _qkv(config: LlamaConfig, layer, h, pos):
    """The attention preamble, once for the full forward and the cached one:
    h [B, T, E] normed, pos [B, T] absolute -> q [B, T, H, D] and k
    [B, T, KV, D], both rotated, and v [B, T, KV, D]."""
    q = jnp.einsum("bte,ehd->bthd", h, layer["wq"].astype(h.dtype))
    k = jnp.einsum("bte,ehd->bthd", h, layer["wk"].astype(h.dtype))
    v = jnp.einsum("bte,ehd->bthd", h, layer["wv"].astype(h.dtype))
    if config.qk_norm == "full":
        B, T = h.shape[:2]
        q = _rms_norm(q.reshape(B, T, -1), layer["q_norm"],
                      config.rms_eps).reshape(q.shape)
        k = _rms_norm(k.reshape(B, T, -1), layer["k_norm"],
                      config.rms_eps).reshape(k.shape)
    return (_rope(q, pos, config.rope_theta),
            _rope(k, pos, config.rope_theta), v)


def _ffn(config: LlamaConfig, layer, x, rng=None, row_mask=None,
         stacked=None):
    """mlp_norm + SwiGLU MLP (or routed experts) + residual -> (x, aux_loss,
    experts that received a row: 0 for the dense MLP). ``row_mask`` [B, T]
    marks the rows that carry a token; only the router asks. ``stacked`` is
    (every layer's expert weights, this layer's index) where the caller kept
    them out of its layer scan (``forward_cached``)."""
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


def _block(config: LlamaConfig, mesh: Optional[Mesh], x, layer,
           pos: jax.Array, rng=None):
    """One decoder block → (x, aux). x: [B, T, E], pos: [B, T] absolute."""
    h = _rms_norm(x, layer["attn_norm"], config.rms_eps, config.dtype)
    q, k, v = _qkv(config, layer, h, pos)
    k = _repeat_kv(k, config.q_per_kv)
    v = _repeat_kv(v, config.q_per_kv)
    attn = _attention_dispatch(config, q, k, v, mesh)
    x = x + jnp.einsum("bthd,hde->bte", attn, layer["wo"].astype(attn.dtype))
    x, aux, _ = _ffn(config, layer, x, rng=rng)
    return x, aux


def forward_features(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: LlamaConfig,
    mesh: Optional[Mesh] = None,
    rng: Optional[jax.Array] = None,  # feeds MoE router jitter
) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, T] int32 -> (final-trunk features [B, T, E], aux loss).
    The loss path consumes features directly (vocab-chunked cross entropy)
    so the [B, T, V] logits tensor never materializes."""
    B, T = tokens.shape
    x = params["wte"][tokens].astype(config.dtype)
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    body = functools.partial(_block, config, mesh)
    if config.remat:
        body = jax.checkpoint(body, policy=_remat_policy(config))

    if rng is not None:
        layer_rngs = jax.random.split(rng, config.num_layers)

        def scan_rng(carry, xs):
            layer, lrng = xs
            x, aux = carry
            x, layer_aux = body(x, layer, pos, lrng)
            return (x, aux + layer_aux), None

        (x, aux), _ = jax.lax.scan(
            scan_rng, (x, jnp.float32(0.0)), (params["blocks"], layer_rngs)
        )
    else:

        def scan_fn(carry, layer):
            x, aux = carry
            x, layer_aux = body(x, layer, pos)
            return (x, aux + layer_aux), None

        (x, aux), _ = jax.lax.scan(
            scan_fn, (x, jnp.float32(0.0)), params["blocks"]
        )
    x = _rms_norm(x, params["norm_f"], config.rms_eps)
    return x, aux


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: LlamaConfig,
    mesh: Optional[Mesh] = None,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, T] int32 -> (logits [B, T, V] f32, moe aux loss)."""
    x, aux = forward_features(params, tokens, config, mesh, rng=rng)
    return _head(params, x), aux


def init_kv_cache(config: LlamaConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jax.Array]:
    """Static-shape GQA cache, [L, B, KV, D, S] as ``models/kv_cache.py``
    has it — kv heads only, an H/KV-fold HBM saving over caching
    query-expanded heads."""
    return kv_cache.init_kv_cache(
        config.num_layers, batch, config.num_kv_heads, config.head_dim,
        max_len, dtype or config.dtype,
    )


def forward_cached(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, jax.Array],
    start: jax.Array,
    config: LlamaConfig,
    real: Optional[jax.Array] = None,
) -> tuple:
    """Incremental forward with RoPE at absolute positions; same contract as
    :func:`ray_tpu.models.gpt2.forward_cached` (static shapes, every slot at
    its own offset, the cache carried through the layer scan and written in
    place) -> (logits, cache): two results for a caller that gives no
    ``real``, three for one that does, the one place where the arity follows
    an argument (a third result, even of zeros, would change the compiled
    programs of every dense model served). With routed experts every token
    reaches its top-k experts (aux loss is a training-only concern and is
    discarded here), and ``real`` [B] says how many of a row's T tokens are
    tokens: 0 for an idle decode slot, the prompt's length in a prefill
    bucket. The rest is routed to no expert. Given ``real``, a third result
    counts the distinct experts that received a row in each layer, [L]
    int32."""
    B, T = tokens.shape
    S = cache["k"].shape[-1]
    pos = start[:, None] + jnp.arange(T)[None, :]            # [B, T]
    # The residual stream is summed in float32 here (the sublayers compute
    # in ``config.dtype``): two roundings a layer of the whole stream were
    # the larger part of a served token's distance from a float32 forward.
    x = params["wte"][tokens].astype(jnp.float32)

    at = kv_cache.step(start, T, S)
    rows = None if real is None else jnp.arange(T)[None, :] < real[:, None]
    # The experts' weights stay out of the scan: it would hand each layer
    # its slice, and a slice that feeds a kernel is a copy (``moe._experts``)
    blocks = dict(params["blocks"])
    dropless = config.moe is not None and config.moe.dropless
    moe = stacked_for(blocks.pop("moe"), config.dtype) if dropless else None

    def block(carry, layer):
        x, i, cache = carry
        h = _rms_norm(x, layer["attn_norm"], config.rms_eps, config.dtype)
        q, k_new, v_new = _qkv(config, layer, h, pos)
        # GQA attention over the cache: group query heads per kv head.
        qg = q.reshape(B, T, config.num_kv_heads, config.q_per_kv,
                       config.head_dim)
        cache, attn = kv_cache.attend(cache, i, qg, k_new, v_new, at)
        attn = attn.reshape(B, T, config.num_heads, config.head_dim)
        x = x + jnp.einsum("bthd,hde->bte", attn,
                           layer["wo"].astype(attn.dtype))
        x, _, touched = _ffn(config, layer, x, row_mask=rows,
                             stacked=(moe, i) if dropless else None)
        return (x, i + 1, cache), None if real is None else touched

    (x, _, cache), touched = jax.lax.scan(
        block, (x, jnp.int32(0), cache), blocks
    )
    x = _rms_norm(x, params["norm_f"], config.rms_eps, config.dtype)
    logits = _head(params, x)
    return (logits, cache) if real is None else (logits, cache, touched)


def loss_fn(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    config: LlamaConfig,
    mesh: Optional[Mesh] = None,
    pipeline_microbatches: Optional[int] = None,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Next-token cross entropy; same batch contract as gpt2.loss_fn.
    ``rng`` feeds MoE router jitter (unpipelined path only)."""
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    if pipeline_microbatches:
        logits, aux = forward_pipelined(
            params, inputs, config, mesh, pipeline_microbatches
        )
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        mask = batch.get("mask")
        if mask is None:
            return -ll.mean() + aux
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1) + aux
    from ray_tpu.ops.xent import chunked_softmax_xent

    x, aux = forward_features(params, inputs, config, mesh, rng=rng)
    return chunked_softmax_xent(
        x, params["lm_head"], targets, batch.get("mask")
    ) + aux


def forward_pipelined(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: LlamaConfig,
    mesh: Mesh,
    num_microbatches: int = 4,
) -> Tuple[jax.Array, jax.Array]:
    """Pipeline-parallel forward over the "stage" mesh axis (GPipe microbatch
    loop, ``parallel.pipeline.pipeline_apply``); embedding/head outside.
    MoE models accumulate the router's load-balancing aux loss across the
    microbatch loop (``pipeline_apply(collect_aux=True)``)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.pipeline import pipeline_apply

    B, T = tokens.shape
    x = params["wte"][tokens].astype(config.dtype)
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

    body = functools.partial(_block, config, mesh)
    if config.remat:
        body = jax.checkpoint(body, policy=_remat_policy(config))
    collect_aux = config.moe is not None

    def apply_stage(local_blocks, mb):
        # Microbatches split the batch dim; positions are batch-invariant.
        mb_pos = pos[: mb.shape[0]]

        def scan_fn(carry, layer):
            x, aux = carry
            y, a = body(x, layer, mb_pos)
            return (y, aux + a.astype(jnp.float32)), None

        (out, aux), _ = jax.lax.scan(
            scan_fn, (mb, jnp.float32(0.0)), local_blocks
        )
        return (out, aux) if collect_aux else out

    params_spec = jax.tree.map(lambda _: P("stage"), params["blocks"])
    res = pipeline_apply(
        params["blocks"], x, mesh=mesh, apply_stage=apply_stage,
        num_microbatches=num_microbatches, params_spec=params_spec,
        x_spec=P(), collect_aux=collect_aux,
    )
    x, aux = res if collect_aux else (res, jnp.float32(0.0))
    x = _rms_norm(x, params["norm_f"], config.rms_eps)
    return _head(params, x), aux


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def flops_per_token(config: LlamaConfig) -> float:
    """~6N FLOPs/token for training; N = ACTIVE non-embedding params
    (MoE counts only the top_k routed experts per token)."""
    E, D = config.embed_dim, config.head_dim
    attn = E * config.num_heads * D * 2 + E * config.num_kv_heads * D * 2
    if config.moe is not None:
        per_expert = (
            3 if config.moe.activation == "swiglu" else 2
        ) * E * config.hidden_dim
        mlp = config.moe.top_k * per_expert + E * config.moe.num_experts
    else:
        mlp = 3 * E * config.hidden_dim
    n = config.num_layers * (attn + mlp) + config.vocab_size * E
    return 6.0 * n
