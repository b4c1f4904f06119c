"""PowerInfer's SmallThinker decoder (SmallThinker-21BA3B / -4BA0.6B) as
pieces over the one decoder: every layer routed, and the router in front.

What the published ``config.json`` and the modeling code describe:

- token embedding, no multiplier; untied head; RMSNorm everywhere;
- with ``h`` a layer's input, the router's logits are ``h Wr`` in float32:
  the router reads the layer's INPUT, before the attention's norm and before
  attention (so that an inference engine can fetch the chosen experts while
  attention runs). ``at_input`` computes them, ``ffn`` gets them;
- ``h' = h + Attn(input_norm(h))``: bias-free q, k, v with G query heads a kv
  head (published: 28 over 4 of 128, G = 7, and 28 x 128 is not the model's
  width); RoPE on q and k in the layers ``rope_layout`` marks, which are the
  layers ``sliding_window_layout`` marks: a window layer sees the token and
  the ``sliding_window - 1`` before it, a global layer everything before it
  and no position signal at all;
- ``h'' = h' + sum_e g_e expert_e(post_attn_norm(h'))``: a softmax over all
  experts, the ``top_k`` largest chosen, their gates renormalised;
  ``expert(y) = (relu(y Wg) * (y Wu)) Wd``, gated by ReLU ("reglu"). No
  shared expert, no dense layer.

A chip may hold a SHARE of each layer's experts (``moe_num_held`` of
``moe_num_experts``: ``parallel/moe.py``): the router scores all of them,
the layer adds the held experts' part.

The stack (``layers``) is the layout's shortest whole period as often as it
repeats (published: 13 x [global, window, window, window]); the weights'
layout is ``afmoe``'s: ``blocks["segments"][s][j]`` the j-th layer of segment
s's period in every repeat, ``blocks["experts"]`` every layer's router and
experts, out of every scan as one operand.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import narrowed
from ray_tpu.models.decoder import *  # noqa: F401,F403 — what families share
from ray_tpu.models.decoder import Layer, Segment
from ray_tpu.models.llama import _rms_norm, _rope
from ray_tpu.parallel.moe import (
    MoEConfig,
    init_moe_params,
    moe_layer_counted,
    moe_param_axes,
    router_logits,
)


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    max_seq_len: int = 16384
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: Optional[int] = None   # None = as many as ``num_heads``
    embed_dim: int = 2560
    head_dim: Optional[int] = None       # None = embed_dim / num_heads
    moe_mlp_dim: int = 768               # one expert's width
    rope_theta: float = 1.5e6
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    remat_policy: str = "dots"
    seq_axis: str = "seq"
    moe: MoEConfig = MoEConfig(
        num_experts=64, top_k=6, activation="reglu", dropless=True)
    # 1 a layer whose attention has the window (and RoPE), 0 a global one
    # (neither), first layer to last, as ``config.json`` lists them; the
    # first ``num_layers`` count. None = every layer global. Held as given, a
    # JSON file's list too, so out of the hash
    sliding_window_layout: Optional[Sequence[int]] = field(
        default=None, hash=False)
    sliding_window: Optional[int] = None

    def __post_init__(self):
        if self.num_kv_heads is None:
            object.__setattr__(self, "num_kv_heads", self.num_heads)
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.embed_dim // self.num_heads)
        if self.moe is None or not self.moe.dropless:
            raise ValueError(
                "SmallThinkerConfig.moe: every layer is routed, dropless "
                "(the sorted dispatch: ``moe_dropless``)")
        layout = self.window_layout
        if len(layout) != self.num_layers or set(layout) - {0, 1}:
            raise ValueError(
                f"SmallThinkerConfig.sliding_window_layout must mark "
                f"{self.num_layers} layers or more 0 or 1, got "
                f"{self.sliding_window_layout!r}")
        if 1 in layout and not self.sliding_window:
            raise ValueError(
                "SmallThinkerConfig.sliding_window: the layout has window "
                "layers")

    @property
    def window_layout(self) -> Tuple[int, ...]:
        given = self.sliding_window_layout or (0,) * self.num_layers
        return tuple(given[:self.num_layers])

    # the router's numbers under the flat names a configuration file gives
    # them (``models.config_for``): ``benchmarks/`` reads a file's keys back
    @property
    def moe_num_experts(self) -> int:
        return self.moe.num_experts

    @property
    def moe_top_k(self) -> int:
        return self.moe.top_k

    @property
    def moe_norm_topk_prob(self) -> bool:
        return self.moe.norm_topk_prob

    @property
    def moe_num_held(self) -> Optional[int]:
        return self.moe.num_held

    @property
    def moe_first_held(self) -> int:
        return self.moe.first_held


Config = SmallThinkerConfig
EXPERT_ACTIVATION = "reglu"

SMALLTHINKER_TINY = SmallThinkerConfig(  # test size: one period, G = 7
    vocab_size=512, max_seq_len=128, num_layers=4, num_heads=7,
    num_kv_heads=1, embed_dim=64, head_dim=16, moe_mlp_dim=32,
    moe=MoEConfig(num_experts=8, top_k=2, activation="reglu", dropless=True),
    sliding_window=8, sliding_window_layout=(0, 1, 1, 1),
)

PRESETS = {"smallthinker-tiny": SMALLTHINKER_TINY}

WINDOW, GLOBAL = "window", "global"
# Deviation of a fresh embedding's entries. The router reads the stream as it
# is, un-normed: at the 0.02 of the other families the branches' outputs
# (RMSNorm makes their inputs unit-sized) bury the token's own signal after
# one layer, what is left is common to every token (a global layer without
# positions hands each one the mean of its prefix), and four tokens in five
# route to ONE expert in the third layer before a step is taken (2,048
# tokens at the published widths, PR 40). At 1 the token's signal leads the
# stream and a fresh router is balanced (16 experts' fullest 1.17 x their
# mean, every layer), as a trained one is.
EMBED_STD = 1.0


def _plan(config: SmallThinkerConfig):
    """[(kinds of one period, repeats)]: the layout's shortest WHOLE period
    (published: [global, window, window, window] x 13), with no lead: a cut
    of one period is then no scan at all. (``afmoe``'s plan, the fewest
    kinds to compile, would make four layers a global lead and a scan of
    three window layers.)"""
    kinds = tuple(
        Layer(WINDOW, config.sliding_window, True) if mark
        else Layer(GLOBAL, None, True) for mark in config.window_layout)
    L = len(kinds)
    period = next(p for p in range(1, L + 1)
                  if L % p == 0 and kinds == kinds[:p] * (L // p))
    return [(kinds[:period], L // period)]


def init_params(config: SmallThinkerConfig, key: jax.Array) -> Dict[str, Any]:
    E, H, KV, D, V = (config.embed_dim, config.num_heads, config.num_kv_heads,
                      config.head_dim, config.vocab_size)
    pd = config.param_dtype
    std = 0.02
    res_std = std / (2 * config.num_layers) ** 0.5
    k_wte, k_head, k_experts, k_layers = jax.random.split(key, 4)

    def layer(key, n: int):
        k = jax.random.split(key, 4)

        def normal(key, shape, s=std):
            return (jax.random.normal(key, (n,) + shape) * s).astype(pd)

        return {
            "attn_norm": jnp.ones((n, E), pd), "mlp_norm": jnp.ones((n, E), pd),
            "wq": normal(k[0], (E, H, D)), "wk": normal(k[1], (E, KV, D)),
            "wv": normal(k[2], (E, KV, D)),
            "wo": normal(k[3], (H, D, E), res_std),
        }

    segments = tuple(
        tuple(layer(jax.random.fold_in(jax.random.fold_in(k_layers, s), j),
                    repeats) for j, _ in enumerate(kinds))
        for s, (kinds, repeats) in enumerate(_plan(config)))
    return {
        "wte": (jax.random.normal(k_wte, (V, E)) * EMBED_STD).astype(pd),
        "blocks": {
            "segments": segments,
            "experts": init_moe_params(
                k_experts, E, config.moe_mlp_dim, config.moe, pd,
                num_layers=config.num_layers, out_std=res_std)},
        "norm_f": jnp.ones((E,), pd),
        "lm_head": (jax.random.normal(k_head, (V, E)) * std).astype(pd),
    }


def param_axes(config: SmallThinkerConfig) -> Dict[str, Any]:
    layer = {
        "attn_norm": ("stage", "norm"), "mlp_norm": ("stage", "norm"),
        "wq": ("stage", "embed", "heads", "head_dim"),
        "wk": ("stage", "embed", "kv", "head_dim"),
        "wv": ("stage", "embed", "kv", "head_dim"),
        "wo": ("stage", "heads", "head_dim", "embed"),
    }
    return {
        "wte": ("vocab", "embed"),
        "blocks": {
            "segments": tuple(tuple(dict(layer) for _ in kinds)
                              for kinds, _ in _plan(config)),
            "experts": moe_param_axes(
                num_layers=config.num_layers, config=config.moe)},
        "norm_f": ("norm",), "lm_head": ("vocab", "embed"),
    }


def serving_params(config: SmallThinkerConfig, params):
    """The projections, the experts and ``lm_head`` are read through
    ``.astype(config.dtype)`` alone. Read as they are: ``wte`` (the cached
    forward's stream is float32), every RMSNorm gain, the router."""
    return narrowed(params, config.dtype, as_given=(
        "wte", "attn_norm", "mlp_norm", "norm_f", "router_w"))


def layers(config: SmallThinkerConfig, blocks, cached: bool):
    """The plan's segments over ``blocks["segments"]``, and every layer's
    router and experts: out of the scan in both forwards."""
    plan = _plan(config)
    held = [(None,) * len(kinds) for kinds, _ in plan] if blocks is None \
        else blocks["segments"]
    return ([Segment(kinds, params, repeats)
             for (kinds, repeats), params in zip(plan, held)],
            None if blocks is None else blocks["experts"])


def embed(config: SmallThinkerConfig, params, tokens, pos, cached: bool):
    """Token embeddings (positions enter in ``qkv``, where they enter at
    all). The cached forward sums its stream in float32, as llama's."""
    return params["wte"][tokens].astype(
        jnp.float32 if cached else config.dtype)


def at_input(config: SmallThinkerConfig, kind, layer, x, stacked):
    """The router's logits [B * T, experts] float32, from the block's input
    as it is: un-normed, before attention."""
    moe, index = stacked
    return router_logits(moe, x, index)


def qkv(config: SmallThinkerConfig, kind, layer, x, pos, heads_major: bool = False):
    """x [B, T, E] normed -> q [B, T, KV, G, D], k and v [B, T, KV, D] (q
    [B, H, T, D], k and v [B, KV, T, D] where ``heads_major``); q and k
    rotated in a window layer only."""
    B, T = x.shape[:2]
    h = _rms_norm(x, layer["attn_norm"], config.rms_eps, config.dtype)
    q, k, v = (heads_in(h, layer[w].astype(h.dtype), heads_major)
               for w in ("wq", "wk", "wv"))
    if kind == WINDOW:
        q, k = (_rope(a, pos, config.rope_theta, heads_major)
                for a in (q, k))
    if heads_major:
        return q, k, v
    return q.reshape(B, T, config.num_kv_heads, -1, config.head_dim), k, v


def attn_out(config: SmallThinkerConfig, layer, x, attn, heads_major: bool = False):
    """Output projection + residual add."""
    return x + heads_out(attn, layer["wo"].astype(attn.dtype), heads_major)


def ffn(config: SmallThinkerConfig, kind, layer, x, rng, row_mask, stacked,
        from_input=None):
    """post-attention norm, the routed experts under the gates the router
    made of the block's input (``from_input``), residual -> (x, aux, experts
    that received a row)."""
    h = _rms_norm(x, layer["mlp_norm"], config.rms_eps, config.dtype)
    moe, index = stacked
    y, aux, touched = moe_layer_counted(
        moe, h, config.moe, rng=rng, row_mask=row_mask, layer=index,
        logits=from_input)
    return x + y, aux, touched


def final_norm(config: SmallThinkerConfig, params, x):
    return _rms_norm(x, params["norm_f"], config.rms_eps, config.dtype)


def head_weight(params):
    return params["lm_head"]


def head(config: SmallThinkerConfig, params, x):
    """Final features [B, T, E] -> logits [B, T, V] float32, straight from
    the product's float32 sums (as ``llama.head``)."""
    return jnp.einsum("bte,ve->btv", x, head_weight(params).astype(x.dtype),
                      preferred_element_type=jnp.float32)
