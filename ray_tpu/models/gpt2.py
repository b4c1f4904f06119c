"""GPT-2 in pure functional JAX: the flagship train/serve model.

Matches the architecture the reference benchmarks with torch ("Ray Train
GPT-2 tokens/sec/chip", BASELINE.md north star): learned positional
embeddings, pre-LN transformer blocks, GELU MLP, weight-tied LM head.
TPU-first choices:

- Params are a plain pytree with a parallel *logical axis* tree
  (``param_axes``) consumed by ``ray_tpu.parallel.sharding`` — pjit shards
  params (fsdp/tensor), XLA inserts the collectives.
- Layers are stacked into one scanned super-layer (``lax.scan`` over the
  depth dimension): O(1) compile time in depth and the natural layout for
  pipeline parallelism (the "stage" mesh axis splits the stacked dim).
- ``jax.checkpoint`` on the block body: remat trades FLOPs for HBM.
- Attention pluggable: xla | flash (pallas) | ring (seq-parallel) | ulysses.
- bfloat16 activations, f32 params + optimizer (standard mixed precision).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

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


def _remat_policy(config):
    """Checkpoint policy for the block body. "full" recomputes everything;
    "dots" (default) keeps matmul outputs + the flash-attention forward's
    named residuals (out + logsumexp, so the backward never re-runs the
    attention kernel) and recomputes elementwise ops; "dots_all"
    additionally keeps batched dots — least recompute short of remat=False,
    for chips with HBM headroom."""
    policy = getattr(config, "remat_policy", "dots")
    if policy == "full":
        return None
    base = (
        jax.checkpoint_policies.dots_saveable
        if policy == "dots_all"
        else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    )
    return jax.checkpoint_policies.save_from_both_policies(
        base,
        jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse"
        ),
    )


def _layer_norm(x, g, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * g + b).astype(x.dtype)


def _attention_dispatch(config: GPT2Config, q, k, v, mesh: Optional[Mesh]):
    """Adds the mesh-aware ring/ulysses branches on top of the shared
    single-device dispatcher (``ops.attention.attention``)."""
    impl = config.attention_impl
    if impl == "ring":
        from ray_tpu.parallel.ring_attention import ring_attention

        return ring_attention(q, k, v, mesh=mesh, axis=config.seq_axis, causal=True)
    if impl == "ulysses":
        from ray_tpu.parallel.ring_attention import ulysses_attention

        return ulysses_attention(q, k, v, mesh=mesh, axis=config.seq_axis, causal=True)
    return attention(q, k, v, causal=True, impl=impl, mesh=mesh)


def _qkv(layer, h):
    """[B, T, E] → (q, k, v) each [B, T, H, D]."""
    qkv = jnp.einsum("bte,eshd->btshd", h, layer["qkv_w"].astype(h.dtype))
    qkv = qkv + layer["qkv_b"].astype(h.dtype)
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def _attn_residual(layer, x, attn):
    """Output projection + residual add."""
    attn = jnp.einsum("bthd,hde->bte", attn, layer["proj_w"].astype(x.dtype))
    return x + attn + layer["proj_b"].astype(x.dtype)


def _mlp_residual(config: GPT2Config, layer, x, rng=None, row_mask=None,
                  stacked=None):
    """ln2 + MLP (or MoE) + residual. Returns (x, aux_loss, experts that
    received a row: 0 for the dense MLP). ``row_mask`` [B, T] marks the rows
    that carry a token; only the router asks. ``stacked`` is (every layer's
    expert weights, this layer's index) where the caller kept them out of
    its layer scan (``forward_cached``)."""
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


def _block(config: GPT2Config, mesh: Optional[Mesh], x, layer, rng=None):
    """One transformer block. x: [B, T, E] (dtype), layer: one slice of the
    stacked block params. ``rng`` (optional) feeds MoE router jitter."""
    h = _layer_norm(x, layer["ln1_g"], layer["ln1_b"])
    q, k, v = _qkv(layer, h)
    attn = _attention_dispatch(config, q, k, v, mesh)
    x = _attn_residual(layer, x, attn)
    return _mlp_residual(config, layer, x, rng=rng)[:2]


def forward_features(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: GPT2Config,
    mesh: Optional[Mesh] = None,
    rng: Optional[jax.Array] = None,
) -> tuple:
    """tokens [B, T] int32 → (final-trunk features [B, T, E], aux loss).
    The loss path consumes features directly (vocab-chunked cross entropy,
    ``ops/xent.py``) so the [B, T, V] logits tensor never materializes.
    ``rng``: optional key enabling stochastic layers (MoE router jitter)."""
    B, T = tokens.shape
    x = params["wte"][tokens].astype(config.dtype)
    x = x + params["wpe"][:T][None].astype(config.dtype)

    body = functools.partial(_block, config, mesh)
    if config.remat:
        body = jax.checkpoint(body, policy=_remat_policy(config))

    if rng is not None:
        layer_rngs = jax.random.split(rng, config.num_layers)

        def scan_fn(carry, xs):
            layer, lrng = xs
            x, aux = carry
            x, layer_aux = body(x, layer, lrng)
            return (x, aux + layer_aux), None

        (x, aux), _ = jax.lax.scan(
            scan_fn, (x, jnp.float32(0.0)), (params["blocks"], layer_rngs)
        )
    else:

        def scan_fn(carry, layer):
            x, aux = carry
            x, layer_aux = body(x, layer)
            return (x, aux + layer_aux), None

        (x, aux), _ = jax.lax.scan(
            scan_fn, (x, jnp.float32(0.0)), params["blocks"]
        )
    x = _layer_norm(x, params["ln_f_g"], params["ln_f_b"])
    return x, aux


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: GPT2Config,
    mesh: Optional[Mesh] = None,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """tokens [B, T] int32 → (logits [B, T, V] f32, moe aux loss scalar)."""
    x, aux = forward_features(params, tokens, config, mesh, rng=rng)
    logits = jnp.einsum("bte,ve->btv", x, params["wte"].astype(x.dtype))
    return logits.astype(jnp.float32), aux


def init_kv_cache(config: GPT2Config, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jax.Array]:
    """Static-shape KV cache for incremental decoding: ``{"k", "v"}``, each
    [L, B, H, D, S], position minor (``models/kv_cache.py`` says why).
    (Reference capability analog: the vLLM engine Ray LLM delegates to —
    ``llm/_internal/serve/engines/vllm``; here the cache is a jax pytree so
    the whole decode step stays one XLA program.)"""
    return kv_cache.init_kv_cache(
        config.num_layers, batch, config.num_heads, config.head_dim,
        max_len, dtype or config.dtype,
    )


def forward_cached(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, jax.Array],
    start: jax.Array,
    config: GPT2Config,
    real: Optional[jax.Array] = None,
) -> tuple:
    """Incremental forward: attend over the KV cache, append new K/V.

    tokens [B, T] — a prompt chunk (prefill, start=0) or one decode step
    (T=1, start=seq_len). start [B] int32: absolute position of tokens[:, 0]
    per sequence. Returns (logits [B, T, V] f32, updated cache). All shapes
    static and every slot at its own offset, so slot-based continuous
    batching is one compiled program. The whole cache rides the layer scan
    as its carry and only the new tokens' columns change: a caller that
    donates the cache gets it back in the same buffer. ``real`` [B] (routed
    experts only) is how many of a row's T tokens are tokens, as
    :func:`ray_tpu.models.llama.forward_cached` has it; with it a third
    result counts the experts that received a row in each layer, [L].
    """
    B, T = tokens.shape
    S = cache["k"].shape[-1]
    pos = start[:, None] + jnp.arange(T)[None, :]          # [B, T] absolute
    x = params["wte"][tokens].astype(config.dtype)
    x = x + params["wpe"][pos].astype(config.dtype)

    at = kv_cache.step(start, T, S)
    rows = None if real is None else jnp.arange(T)[None, :] < real[:, None]
    # as ``llama.forward_cached``: dropless experts stay out of the scan
    blocks = dict(params["blocks"])
    dropless = config.moe is not None and config.moe.dropless
    moe = stacked_for(blocks.pop("moe"), config.dtype) if dropless else None

    def block(carry, layer):
        x, i, cache = carry
        h = _layer_norm(x, layer["ln1_g"], layer["ln1_b"])
        q, k_new, v_new = _qkv(layer, h)
        # attention core differs from _block: queries attend the cache
        cache, attn = kv_cache.attend(cache, i, q, k_new, v_new, at)
        x = _attn_residual(layer, x, attn)
        x, _, touched = _mlp_residual(
            config, layer, x, row_mask=rows,
            stacked=(moe, i) if dropless else None)
        return (x, i + 1, cache), None if real is None else touched

    (x, _, cache), touched = jax.lax.scan(
        block, (x, jnp.int32(0), cache), blocks
    )
    x = _layer_norm(x, params["ln_f_g"], params["ln_f_b"])
    logits = jnp.einsum("bte,ve->btv", x, params["wte"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    return (logits, cache) if real is None else (logits, cache, touched)


def loss_fn(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    config: GPT2Config,
    mesh: Optional[Mesh] = None,
    pipeline_microbatches: Optional[int] = None,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Next-token cross entropy. batch: {"tokens": [B, T+1]} or
    {"inputs": [B,T], "targets": [B,T]}. ``rng`` feeds MoE router jitter
    (unpipelined path only)."""
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
        x, params["wte"], targets, batch.get("mask")
    ) + aux


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def flops_per_token(config: GPT2Config) -> float:
    """~6N FLOPs/token for training (fwd+bwd), N = non-embedding params."""
    L, E, M = config.num_layers, config.embed_dim, config.mlp_dim
    n = L * (4 * E * E + 2 * E * M) + config.vocab_size * E
    return 6.0 * n


def forward_pipelined(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: GPT2Config,
    mesh: Mesh,
    num_microbatches: int = 4,
) -> jax.Array:
    """Pipeline-parallel forward: blocks run under the GPipe microbatch loop
    (``parallel.pipeline.pipeline_apply``) over the "stage" mesh axis;
    embedding/head run outside the pipe. MoE models accumulate the router's
    load-balancing aux loss across the microbatch loop
    (``pipeline_apply(collect_aux=True)``)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.pipeline import pipeline_apply

    B, T = tokens.shape
    x = params["wte"][tokens].astype(config.dtype)
    x = x + params["wpe"][:T][None].astype(config.dtype)

    body = functools.partial(_block, config, mesh)
    if config.remat:
        body = jax.checkpoint(body, policy=_remat_policy(config))
    collect_aux = config.moe is not None

    def apply_stage(local_blocks, mb):
        def scan_fn(carry, layer):
            x, aux = carry
            y, a = body(x, layer)
            return (y, aux + a.astype(jnp.float32)), None

        (out, aux), _ = jax.lax.scan(
            scan_fn, (mb, jnp.float32(0.0)), local_blocks
        )
        return (out, aux) if collect_aux else out

    # Manual spec covers only the stage dim; tensor/fsdp dims of the weights
    # remain auto-sharded by XLA inside the stage program.
    params_spec = jax.tree.map(lambda _: P("stage"), params["blocks"])
    res = pipeline_apply(
        params["blocks"],
        x,
        mesh=mesh,
        apply_stage=apply_stage,
        num_microbatches=num_microbatches,
        params_spec=params_spec,
        x_spec=P(),
        collect_aux=collect_aux,
    )
    x, aux = res if collect_aux else (res, jnp.float32(0.0))
    x = _layer_norm(x, params["ln_f_g"], params["ln_f_b"])
    logits = jnp.einsum("bte,ve->btv", x, params["wte"].astype(x.dtype))
    return logits.astype(jnp.float32), aux
