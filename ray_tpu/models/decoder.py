"""The decoder every model family shares: layer stack, cached forward,
pipeline and loss, each written once.

A family (``models/gpt2.py``, ``models/llama.py``) is a module of the pieces
that differ, found from the config through ``models.module_for``, each with
one signature across families:

- ``embed(config, params, tokens, pos, cached)`` -> the residual stream
  [B, T, E] (positions added where the family learns them; its dtype in the
  full and in the cached forward is the family's);
- ``qkv(config, layer, x, pos)`` -> q, and k and v [B, T, KV, D], from the
  stream (the family's norm, RoPE and q/k norm inside). q is [B, T, H, D],
  or [B, T, KV, G, D] where G query heads share a kv head;
- ``attn_out(config, layer, x, attn)`` -> the stream after the output
  projection and the residual;
- ``ffn(config, layer, x, rng, row_mask, stacked)`` -> (stream, aux loss,
  experts that received a row: 0 for a dense feed-forward). Only a router
  asks for the last three: ``rng`` its jitter, ``row_mask`` [B, T] the rows
  that carry a token, ``stacked`` (every layer's expert weights, this
  layer's index) where the caller kept them out of its layer scan;
- ``final_norm(config, params, x)``, ``head(config, params, x)`` -> float32
  logits, ``head_weight(params)`` -> the [V, E] matrix the chunked
  cross-entropy multiplies by;
- ``serving_params(config, params)`` -> the tree as ``forward_cached`` (its
  cache in ``config.dtype``) wants to be given it by a caller that calls it
  more than once: each leaf that the pieces above cast to ``config.dtype``
  before every use held in ``config.dtype``, every other leaf the array
  given (``models.narrowed``). The casts stay in the pieces, where on such
  a leaf they are nothing, so the results are the same bit for bit, and a
  compiled program no longer rounds every weight each time it runs. Nothing
  here calls it: a server does, once, as it takes its weights
  (``llm/engine.py``); training and the full forward keep the tree as it
  was initialised;
- ``config.num_kv_heads``.

What follows from shapes alone is decided here: a grouped q is flattened and
k and v repeated G times for the full forward, the cache is attended with q
as it came (``kv_cache.attend`` takes either), and the output projection
gets [B, T, H, D] from both.

Layers are stacked into one scanned super-layer (``lax.scan`` over depth:
O(1) compile time in depth, and the layout the "stage" mesh axis splits),
with ``jax.checkpoint`` on the block body (remat trades FLOPs for HBM).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models import kv_cache, module_for
from ray_tpu.ops.attention import attention
from ray_tpu.parallel.moe import stacked_for

# what a family module hands on under its own name (``gpt2.forward``,
# ``module_for(cfg).loss_fn``): each the one definition below
__all__ = ["forward_features", "forward", "init_kv_cache", "forward_cached",
           "forward_pipelined", "loss_fn", "count_params"]


def _remat_policy(config):
    """Checkpoint policy for the block body. "full" recomputes everything;
    "dots" (default) keeps matmul outputs + the flash-attention forward's
    named residuals (out + logsumexp, so the backward never re-runs the
    attention kernel) and recomputes elementwise ops; "dots_all"
    additionally keeps batched dots — least recompute short of remat=False,
    for chips with HBM headroom."""
    if config.remat_policy == "full":
        return None
    base = (
        jax.checkpoint_policies.dots_saveable
        if config.remat_policy == "dots_all"
        else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    )
    return jax.checkpoint_policies.save_from_both_policies(
        base,
        jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse"
        ),
    )


def _attention_dispatch(config, q, k, v, mesh: Optional[Mesh]):
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


def _repeat_kv(x: jax.Array, n: int) -> jax.Array:
    """[B, T, KV, D] -> [B, T, KV*n, D] (GQA head expansion)."""
    if n == 1:
        return x
    B, T, KV, D = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (B, T, KV, n, D)
    ).reshape(B, T, KV * n, D)


def _body(config, mesh: Optional[Mesh], pos):
    """One decoder block as a layer scan calls it, remat applied:
    ``(x [B, T, E], layer, rng=None) -> (x, aux)``. layer: one slice of the
    stacked block params, pos: [B, T] absolute. ``rng`` (optional) feeds MoE
    router jitter."""
    family = module_for(config)

    def block(x, layer, rng=None):
        q, k, v = family.qkv(config, layer, x, pos)
        if q.ndim == 5:  # [B, T, KV, G, D]: G query heads share a kv head
            k, v = _repeat_kv(k, q.shape[3]), _repeat_kv(v, q.shape[3])
            q = q.reshape(*q.shape[:2], -1, q.shape[-1])
        attn = _attention_dispatch(config, q, k, v, mesh)
        x = family.attn_out(config, layer, x, attn)
        x, aux, _ = family.ffn(
            config, layer, x, rng=rng, row_mask=None, stacked=None)
        return x, aux

    if config.remat:
        return jax.checkpoint(block, policy=_remat_policy(config))
    return block


def _embed(params, tokens, config):
    """The full forward's stream and its positions [B, T], 0..T-1."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    return module_for(config).embed(
        config, params, tokens, pos, cached=False), pos


def forward_features(
    params: Dict[str, Any],
    tokens: jax.Array,
    config,
    mesh: Optional[Mesh] = None,
    rng: Optional[jax.Array] = None,
) -> tuple:
    """tokens [B, T] int32 → (final-trunk features [B, T, E], aux loss).
    The loss path consumes features directly (vocab-chunked cross entropy,
    ``ops/xent.py``) so the [B, T, V] logits tensor never materializes.
    ``rng``: optional key enabling stochastic layers (MoE router jitter),
    one key a layer."""
    x, pos = _embed(params, tokens, config)
    body = _body(config, mesh, pos)
    xs = (params["blocks"],)
    if rng is not None:
        xs += (jax.random.split(rng, config.num_layers),)

    def scan_fn(carry, xs):
        x, aux = carry
        x, layer_aux = body(x, *xs)
        return (x, aux + layer_aux), None

    (x, aux), _ = jax.lax.scan(scan_fn, (x, jnp.float32(0.0)), xs)
    return module_for(config).final_norm(config, params, x), aux


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    config,
    mesh: Optional[Mesh] = None,
    rng: Optional[jax.Array] = None,
) -> tuple:
    """tokens [B, T] int32 → (logits [B, T, V] f32, moe aux loss scalar)."""
    x, aux = forward_features(params, tokens, config, mesh, rng=rng)
    return module_for(config).head(config, params, x), aux


def init_kv_cache(config, batch: int, max_len: int,
                  dtype=None) -> Dict[str, jax.Array]:
    """Static-shape KV cache for incremental decoding: ``{"k", "v"}``, each
    [L, B, KV, D, S], position minor (``models/kv_cache.py`` says why) — kv
    heads only, an H/KV-fold HBM saving over caching query-expanded heads.
    (Reference capability analog: the vLLM engine Ray LLM delegates to —
    ``llm/_internal/serve/engines/vllm``; here the cache is a jax pytree so
    the whole decode step stays one XLA program.)"""
    return kv_cache.init_kv_cache(
        config.num_layers, batch, config.num_kv_heads, config.head_dim,
        max_len, dtype or config.dtype,
    )


def forward_cached(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, jax.Array],
    start: jax.Array,
    config,
    real: Optional[jax.Array] = None,
) -> tuple:
    """Incremental forward: attend over the KV cache, append new K/V.

    tokens [B, T] — a prompt chunk (prefill, start=0) or one decode step
    (T=1, start=seq_len). start [B] int32: absolute position of tokens[:, 0]
    per sequence. Returns (logits [B, T, V] f32, updated cache). All shapes
    static and every slot at its own offset, so slot-based continuous
    batching is one compiled program. The whole cache rides the layer scan
    as its carry and only the new tokens' columns change: a caller that
    donates the cache gets it back in the same buffer.

    Two results for a caller that gives no ``real``, three for one that
    does, the one place where the arity follows an argument (a third result,
    even of zeros, would change the compiled programs of every dense model
    served). With routed experts every token reaches its top-k experts (aux
    loss is a training-only concern and is discarded here), and ``real`` [B]
    says how many of a row's T tokens are tokens: 0 for an idle decode slot,
    the prompt's length in a prefill bucket. The rest is routed to no
    expert. Given ``real``, a third result counts the distinct experts that
    received a row in each layer, [L] int32."""
    family = module_for(config)
    B, T = tokens.shape
    S = cache["k"].shape[-1]
    pos = start[:, None] + jnp.arange(T)[None, :]          # [B, T] absolute
    x = family.embed(config, params, tokens, pos, cached=True)

    at = kv_cache.step(start, T, S)
    rows = None if real is None else jnp.arange(T)[None, :] < real[:, None]
    # The experts' weights stay out of the scan: it would hand each layer
    # its slice, and a slice that feeds a kernel is a copy (``moe._experts``)
    blocks = dict(params["blocks"])
    dropless = config.moe is not None and config.moe.dropless
    moe = stacked_for(blocks.pop("moe"), config.dtype) if dropless else None

    def block(carry, layer):
        x, i, cache = carry
        q, k_new, v_new = family.qkv(config, layer, x, pos)
        # the cache is attended as the family groups its heads (GQA: the
        # query heads of a kv head together); the projection takes them flat
        cache, attn = kv_cache.attend(cache, i, q, k_new, v_new, at)
        x = family.attn_out(
            config, layer, x, attn.reshape(B, T, -1, attn.shape[-1]))
        x, _, touched = family.ffn(
            config, layer, x, rng=None, row_mask=rows,
            stacked=(moe, i) if dropless else None)
        return (x, i + 1, cache), None if real is None else touched

    (x, _, cache), touched = jax.lax.scan(
        block, (x, jnp.int32(0), cache), blocks
    )
    logits = family.head(config, params, family.final_norm(config, params, x))
    return (logits, cache) if real is None else (logits, cache, touched)


def loss_fn(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    config,
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
        x, module_for(config).head_weight(params), targets, batch.get("mask")
    ) + aux


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def forward_pipelined(
    params: Dict[str, Any],
    tokens: jax.Array,
    config,
    mesh: Mesh,
    num_microbatches: int = 4,
) -> tuple:
    """Pipeline-parallel forward: blocks run under the GPipe microbatch loop
    (``parallel.pipeline.pipeline_apply``) over the "stage" mesh axis;
    embedding/head run outside the pipe. MoE models accumulate the router's
    load-balancing aux loss across the microbatch loop
    (``pipeline_apply(collect_aux=True)``)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.pipeline import pipeline_apply

    family = module_for(config)
    x, pos = _embed(params, tokens, config)
    collect_aux = config.moe is not None

    def apply_stage(local_blocks, mb):
        # Microbatches split the batch dim; positions are batch-invariant.
        body = _body(config, mesh, pos[: mb.shape[0]])

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
    return family.head(config, params, family.final_norm(config, params, x)), aux
