"""Arcee's ``afmoe`` decoder (Trinity-Mini / -Nano) as pieces over the one
decoder: the third family, and the first whose layers are not all alike.

What the published ``config.json`` and ``modeling_afmoe.py`` describe:

- the stream is ``wte[tokens] * sqrt(embed_dim)`` (``mup_enabled``); untied
  head; RMSNorm everywhere;
- a layer is ``h = h + post_attn_norm(Attn(attn_norm(h)))``, then
  ``h = h + post_mlp_norm(FFN(pre_mlp_norm(h)))``: four norms, two of them
  on a branch's OUTPUT;
- attention: bias-free q, k, v with G query heads a kv head; an RMSNorm over
  each HEAD's ``head_dim`` values of q and of k (one gain of ``head_dim``
  shared by the heads); RoPE on q and k in ``sliding_attention`` layers
  ONLY, a ``full_attention`` layer gets no position signal at all; causal
  softmax attention, in a sliding layer over the token and the
  ``sliding_window - 1`` before it; the result times ``sigmoid(x Wg)``
  elementwise (a gate a head and channel, from the same normed ``x``);
  then the output projection;
- ``layer_types`` says which layers slide; the first ``num_dense_layers``
  have a SwiGLU MLP of ``mlp_dim``, the others routed SwiGLU experts of
  ``moe_mlp_dim`` (sigmoid scores, the k chosen under ``expert_bias``, gates
  renormalised and scaled: ``parallel/moe.py``) beside ``num_shared_experts``
  shared ones that every token passes.

The stack (``layers``) is the fewest kinds of layer that cover it: a lead of
layers that fit no period, then the shortest period as often as it repeats
(published: 2 dense + 2 routed, then 7 x [sliding, sliding, sliding, full]).
``blocks["segments"][s][j]`` holds the weights of the j-th layer of segment
s's period in every repeat, leaves ``[repeats, ...]``; ``blocks["experts"]``
every routed layer's router and experts, ``[routed layers, ...]``, which stay
out of every scan as one operand (``decoder.forward_cached`` says why).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import narrowed
from ray_tpu.models.decoder import *  # noqa: F401,F403 — what families share
from ray_tpu.models.decoder import Layer, Segment, periods
from ray_tpu.models.llama import _rms_norm, _rope
from ray_tpu.parallel.moe import (
    MoEConfig,
    init_moe_params,
    moe_layer_counted,
    moe_param_axes,
    shared_expert,
)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    max_seq_len: int = 8192
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None   # None = as many as ``num_heads``
    embed_dim: int = 2048
    head_dim: Optional[int] = None       # None = embed_dim / num_heads
    mlp_dim: Optional[int] = None        # the dense layers' MLP; None = 3 E
    moe_mlp_dim: Optional[int] = None    # one expert's; None = ``mlp_dim``
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    remat_policy: str = "dots"
    seq_axis: str = "seq"
    # routed experts in every layer but the first ``num_dense_layers``
    # (None: every layer dense), each beside ``num_shared_experts`` shared
    moe: Optional[MoEConfig] = None
    num_dense_layers: int = 0
    num_shared_experts: int = 0
    # a layer's attention, first to last, as ``config.json`` names it:
    # ``sliding_attention`` (RoPE, the last ``sliding_window`` positions) |
    # ``full_attention`` (no position signal, everything before); the first
    # ``num_layers`` of them count. None = every layer full. Held as given,
    # a JSON file's list too, so out of the hash (``attention_types`` is
    # what the code reads)
    layer_types: Optional[Sequence[str]] = field(default=None, hash=False)
    sliding_window: Optional[int] = None
    mup_enabled: bool = True     # the embedding times sqrt(embed_dim)

    def __post_init__(self):
        if self.num_kv_heads is None:
            object.__setattr__(self, "num_kv_heads", self.num_heads)
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.embed_dim // self.num_heads)
        if self.mlp_dim is None:
            object.__setattr__(self, "mlp_dim", 3 * self.embed_dim)
        if self.moe_mlp_dim is None:
            object.__setattr__(self, "moe_mlp_dim", self.mlp_dim)
        types = self.attention_types
        if len(types) != self.num_layers or set(types) - {SLIDING, FULL}:
            raise ValueError(
                f"AfmoeConfig.layer_types must name {self.num_layers} layers "
                f"or more {SLIDING!r} or {FULL!r}, got {self.layer_types!r}")
        if SLIDING in types and not self.sliding_window:
            raise ValueError(
                "AfmoeConfig.sliding_window: layer_types has sliding layers")

    @property
    def attention_types(self) -> Tuple[str, ...]:
        """``layer_types`` of the ``num_layers`` layers there are, by name."""
        given = self.layer_types or (FULL,) * self.num_layers
        return tuple(given[:self.num_layers])

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

    @property
    def moe_score_func(self) -> Optional[str]:
        return self.moe.score_func if self.moe is not None else None

    @property
    def moe_route_scale(self) -> Optional[float]:
        return self.moe.route_scale if self.moe is not None else None


Config = AfmoeConfig
EXPERT_ACTIVATION = "swiglu"

AFMOE_TINY = AfmoeConfig(  # test size: a dense lead, one period of 3 + 1
    vocab_size=512, max_seq_len=128, num_layers=5, num_heads=4,
    num_kv_heads=2, embed_dim=64, head_dim=16, mlp_dim=96, moe_mlp_dim=32,
    moe=MoEConfig(num_experts=8, top_k=2, activation="swiglu",
                  score_func="sigmoid", expert_bias=True,
                  expert_bias_init_std=0.02, route_scale=2.826),
    num_dense_layers=1, num_shared_experts=1, sliding_window=8,
    layer_types=(SLIDING,) * 4 + (FULL,),
)

PRESETS = {"afmoe-tiny": AFMOE_TINY}


def _kinds(config: AfmoeConfig) -> Tuple[Layer, ...]:
    return tuple(
        Layer(("sliding" if kind == SLIDING else "full")
              + ("/routed" if routed else "/dense"),
              config.sliding_window if kind == SLIDING else None, routed)
        for i, kind in enumerate(config.attention_types)
        for routed in [config.moe is not None and i >= config.num_dense_layers]
    )


def _plan(config: AfmoeConfig):
    """[(kinds of one period, repeats)]: ``decoder.periods`` of the stack."""
    return periods(_kinds(config))


def _routed_layers(config: AfmoeConfig) -> int:
    return sum(k.routed for k in _kinds(config))


def init_params(config: AfmoeConfig, key: jax.Array) -> Dict[str, Any]:
    E, H, KV, D, V = (config.embed_dim, config.num_heads, config.num_kv_heads,
                      config.head_dim, config.vocab_size)
    pd = config.param_dtype
    std = 0.02
    res_std = std / (2 * config.num_layers) ** 0.5
    k_wte, k_head, k_experts, k_layers = jax.random.split(key, 4)

    def layer(key, kind: Layer, n: int):
        k = jax.random.split(key, 8)

        def normal(key, shape, s=std):
            return (jax.random.normal(key, (n,) + shape) * s).astype(pd)

        def ones(*shape):
            return jnp.ones((n,) + shape, pd)

        out = {
            "attn_norm": ones(E), "post_attn_norm": ones(E),
            "pre_mlp_norm": ones(E), "post_mlp_norm": ones(E),
            "q_norm": ones(D), "k_norm": ones(D),
            "wq": normal(k[0], (E, H, D)), "wk": normal(k[1], (E, KV, D)),
            "wv": normal(k[2], (E, KV, D)), "wg": normal(k[3], (E, H, D)),
            "wo": normal(k[4], (H, D, E), res_std),
        }
        pre = "shared_" if kind.routed else "w_"
        M = (config.moe_mlp_dim * config.num_shared_experts if kind.routed
             else config.mlp_dim)
        if M:
            out[pre + "gate"] = normal(k[5], (E, M))
            out[pre + "up"] = normal(k[6], (E, M))
            out[pre + "down"] = normal(k[7], (M, E), res_std)
        return out

    segments = tuple(
        tuple(layer(jax.random.fold_in(jax.random.fold_in(k_layers, s), j),
                    kind, repeats) for j, kind in enumerate(kinds))
        for s, (kinds, repeats) in enumerate(_plan(config)))
    blocks = {"segments": segments}
    if _routed_layers(config):
        blocks["experts"] = init_moe_params(
            k_experts, E, config.moe_mlp_dim, config.moe, pd,
            num_layers=_routed_layers(config), out_std=res_std)
    return {
        "wte": (jax.random.normal(k_wte, (V, E)) * std).astype(pd),
        "blocks": blocks,
        "norm_f": jnp.ones((E,), pd),
        "lm_head": (jax.random.normal(k_head, (V, E)) * std).astype(pd),
    }


def param_axes(config: AfmoeConfig) -> Dict[str, Any]:
    def layer(kind: Layer):
        axes = {
            **{name: ("stage", "norm") for name in (
                "attn_norm", "post_attn_norm", "pre_mlp_norm",
                "post_mlp_norm", "q_norm", "k_norm")},
            "wq": ("stage", "embed", "heads", "head_dim"),
            "wk": ("stage", "embed", "kv", "head_dim"),
            "wv": ("stage", "embed", "kv", "head_dim"),
            "wg": ("stage", "embed", "heads", "head_dim"),
            "wo": ("stage", "heads", "head_dim", "embed"),
        }
        pre = "shared_" if kind.routed else "w_"
        if not kind.routed or config.num_shared_experts:
            axes.update({pre + "gate": ("stage", "embed", "mlp"),
                         pre + "up": ("stage", "embed", "mlp"),
                         pre + "down": ("stage", "mlp", "embed")})
        return axes

    blocks = {"segments": tuple(
        tuple(layer(kind) for kind in kinds) for kinds, _ in _plan(config))}
    if _routed_layers(config):
        blocks["experts"] = moe_param_axes(
            num_layers=_routed_layers(config), config=config.moe)
    return {"wte": ("vocab", "embed"), "blocks": blocks,
            "norm_f": ("norm",), "lm_head": ("vocab", "embed")}


def serving_params(config: AfmoeConfig, params):
    """The projections, the MLPs, the experts and ``lm_head`` are read
    through ``.astype(config.dtype)`` alone. Read as they are: ``wte`` (the
    cached forward's stream is float32), every RMSNorm gain, the router and
    its bias (float32)."""
    return narrowed(params, config.dtype, as_given=(
        "wte", "attn_norm", "post_attn_norm", "pre_mlp_norm",
        "post_mlp_norm", "q_norm", "k_norm", "norm_f", "router_w",
        "expert_bias"))


def layers(config: AfmoeConfig, blocks, cached: bool):
    """The plan's segments over ``blocks["segments"]``, and the routed
    layers' router and experts: out of the scan in both forwards."""
    plan = _plan(config)
    held = [(None,) * len(kinds) for kinds, _ in plan] if blocks is None \
        else blocks["segments"]
    return ([Segment(kinds, params, repeats)
             for (kinds, repeats), params in zip(plan, held)],
            None if blocks is None else blocks.get("experts"))


def embed(config: AfmoeConfig, params, tokens, pos, cached: bool):
    """Token embeddings, times sqrt(embed_dim) under ``mup_enabled``
    (positions enter in ``qkv``, where they enter at all). The cached
    forward sums its stream in float32, as llama's."""
    x = params["wte"][tokens].astype(jnp.float32 if cached else config.dtype)
    return x * (config.embed_dim ** 0.5) if config.mup_enabled else x


def qkv(config: AfmoeConfig, kind, layer, x, pos, heads_major: bool = False):
    """x [B, T, E] normed -> q [B, T, KV, G, D], k and v [B, T, KV, D] (q
    [B, H, T, D], k and v [B, KV, T, D] where ``heads_major``); q and k
    normed head by head, and rotated in a sliding layer only."""
    B, T = x.shape[:2]
    h = _rms_norm(x, layer["attn_norm"], config.rms_eps, config.dtype)
    q, k, v = (heads_in(h, layer[w].astype(h.dtype), heads_major)
               for w in ("wq", "wk", "wv"))
    q = _rms_norm(q, layer["q_norm"], config.rms_eps)
    k = _rms_norm(k, layer["k_norm"], config.rms_eps)
    if kind.startswith("sliding"):
        q, k = (_rope(a, pos, config.rope_theta, heads_major)
                for a in (q, k))
    if heads_major:
        return q, k, v
    return q.reshape(B, T, config.num_kv_heads, -1, config.head_dim), k, v


def attn_out(config: AfmoeConfig, layer, x, attn, heads_major: bool = False):
    """The gate (from the normed stream ``qkv`` projected), the output
    projection, the norm of the branch's output, the residual."""
    h = _rms_norm(x, layer["attn_norm"], config.rms_eps, config.dtype)
    gate = jax.nn.sigmoid(
        heads_in(h, layer["wg"].astype(h.dtype), heads_major))
    out = heads_out(attn * gate.astype(attn.dtype),
                    layer["wo"].astype(attn.dtype), heads_major)
    return x + _rms_norm(out, layer["post_attn_norm"], config.rms_eps)


def ffn(config: AfmoeConfig, kind, layer, x, rng, row_mask, stacked,
        from_input=None):
    """pre_mlp_norm, the dense MLP or the routed experts beside the shared
    one, post_mlp_norm on the sum, residual -> (x, aux loss, experts that
    received a row)."""
    h = _rms_norm(x, layer["pre_mlp_norm"], config.rms_eps, config.dtype)
    aux, touched = jnp.float32(0.0), jnp.int32(0)
    if kind.endswith("routed"):
        moe, index = stacked
        if not config.moe.dropless and index is not None:
            # capacity queues (training) take a layer's own weights
            moe, index = jax.tree.map(lambda w: w[index], moe), None
        y, aux, touched = moe_layer_counted(
            moe, h, config.moe, rng=rng, row_mask=row_mask, layer=index)
        if config.num_shared_experts:
            y = y + shared_expert(h, layer["shared_gate"],
                                  layer["shared_up"], layer["shared_down"])
    else:
        gate = jnp.einsum("bte,em->btm", h, layer["w_gate"].astype(h.dtype))
        up = jnp.einsum("bte,em->btm", h, layer["w_up"].astype(h.dtype))
        y = jnp.einsum("btm,me->bte", jax.nn.silu(gate) * up,
                       layer["w_down"].astype(h.dtype))
    return (x + _rms_norm(y, layer["post_mlp_norm"], config.rms_eps), aux,
            touched)


def final_norm(config: AfmoeConfig, params, x):
    return _rms_norm(x, params["norm_f"], config.rms_eps, config.dtype)


def head_weight(params):
    return params["lm_head"]


def head(config: AfmoeConfig, params, x):
    """Final features [B, T, E] -> logits [B, T, V] float32, straight from
    the product's float32 sums (as ``llama.head``)."""
    return jnp.einsum("bte,ve->btv", x, head_weight(params).astype(x.dtype),
                      preferred_element_type=jnp.float32)
