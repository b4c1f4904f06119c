"""The ``deepseek_v32`` decoder (DeepSeek-V3.2-Exp; the model repository's
``inference/model.py``, whose key names its ``config.json`` follows) as
pieces over the one decoder: the ninth family. DeepSeek-V3's block as
``joyai_llm_flash`` has it (latent attention with a query rank in every
layer, a dense lead, sigmoid-routed experts under a bias beside one shared
expert, a share of them held here), SERVED, and what no other family has:

- a LIGHTNING INDEXER a layer (DeepSeek sparse attention): ``qI = cq WqI``,
  ``Hi`` heads of ``Di`` from the normed query rank; ``kI = LayerNorm(x
  WkI)`` [Di] with a gain and a bias, ONE key a position that the heads
  share; RoPE on the first ``qk_rope_head_dim`` channels of both in the
  half-split layout (pairs (i, i + Dr / 2)); ``w = (x Ww) x Hi^-0.5 x
  Di^-0.5`` in float32; ``I[t, s] = sum_h w[t, h] x ReLU(qI[t, h] .
  kI[s])``. A query attends the ``index_topk`` positions it scores highest
  among those up to its own, and no other (``ops/index_select.py``,
  ``kv_cache.attend_latent``): a layer's cache holds the rotated ``kI``
  beside the latent row (``decoder.Layer.index``). The published code
  multiplies ``qI`` and ``kI`` by one Hadamard matrix and quantises both to
  FP8: the matrix is orthogonal, so ``qI . kI`` is the same without it, and
  neither is done here (bfloat16 keys);
- YaRN (``bailing_hybrid.Yarn``): the rotations' frequencies blended toward
  a context ``rope_factor`` times ``rope_original_max_position``, on the
  latent layer's rotated channels (interleaved) and on the indexer's, and
  the softmax scale ``192^-0.5 x m^2`` (``Layer.scale``);
- group-limited routing (``moe.n_group`` / ``topk_group``:
  ``moe._in_best_groups``) in DeepSeek-V3's own block, which JoyAI leaves
  off.

No prediction layer: the published one is a training device and a serving
draft, and the cached forward would leave it out (``joyai_llm_flash``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import narrowed
from ray_tpu.models.bailing_hybrid import (
    Yarn, _inv_freq, _latent_q, _latent_rows, _of_moe,
)
from ray_tpu.models.decoder import *  # noqa: F401,F403 — what families share
from ray_tpu.models.decoder import Index, Layer, Segment
from ray_tpu.models import joyai_llm_flash as v3
from ray_tpu.models.llama import _rms_norm
from ray_tpu.ops.index_select import Indexed
from ray_tpu.parallel.moe import MoEConfig, init_moe_params, moe_param_axes


@dataclass(frozen=True)
class DeepSeekV32Config:
    vocab_size: int = 129280
    max_seq_len: int = 163840
    num_layers: int = 61
    num_heads: int = 128
    embed_dim: int = 7168
    mlp_dim: int = 18432                 # the dense layers' MLP
    moe_mlp_dim: int = 2048              # one expert's, and the shared one's
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    remat_policy: str = "dots"
    seq_axis: str = "seq"
    # routed experts in every layer but the first ``first_k_dense`` (None:
    # every layer dense), each beside ``num_shared_experts`` shared; held as
    # a share (``num_held``; not stated: all of them, which is a share too)
    moe: Optional[MoEConfig] = None
    first_k_dense: int = 3
    num_shared_experts: int = 1
    # latent attention's sizes, under their published names
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    # YaRN (``rope_scaling``): on where the model's context passes the
    # original one, whatever a request's length
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # the lightning indexer's
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6

    def __post_init__(self):
        if self.moe is not None and not self.moe.dropless:
            raise ValueError(
                "DeepSeekV32Config.moe: the routed layers hold a share of the "
                "experts, dropless (the sorted dispatch: ``moe_dropless``)")
        if self.moe is not None and self.moe.num_held is None:
            object.__setattr__(self, "moe", dataclasses.replace(
                self.moe, num_held=self.moe.num_experts, first_held=0))
        if self.first_k_dense < 0:
            raise ValueError(
                f"DeepSeekV32Config.first_k_dense {self.first_k_dense}")
        if not 0 < self.qk_rope_head_dim <= self.index_head_dim:
            raise ValueError(
                "DeepSeekV32Config: the indexer rotates its first "
                f"{self.qk_rope_head_dim} channels of {self.index_head_dim}")

    @property
    def num_kv_heads(self) -> int:
        """No layer holds keys and values a head; the decoder asks."""
        return self.num_heads

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """A position's row of a layer's cache (its indexer's key beside)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def yarn(self) -> Optional[Yarn]:
        """None where the model's context is the original one."""
        if self.max_seq_len <= self.rope_original_max_position:
            return None
        return Yarn(self.rope_factor, self.rope_original_max_position,
                    self.rope_beta_fast, self.rope_beta_slow)

    @property
    def softmax_scale(self) -> float:
        if self.yarn is None:
            return self.head_dim ** -0.5
        return self.yarn.softmax_scale(self.head_dim, self.rope_mscale_all_dim)

    # the router's numbers under the flat names a configuration file gives
    # them (``models.config_for``): ``benchmarks/`` reads a file's keys back
    moe_num_experts = _of_moe("num_experts")
    moe_top_k = _of_moe("top_k")
    moe_norm_topk_prob = _of_moe("norm_topk_prob")
    moe_score_func = _of_moe("score_func")
    moe_route_scale = _of_moe("route_scale")
    moe_n_group = _of_moe("n_group")
    moe_topk_group = _of_moe("topk_group")
    moe_num_held = _of_moe("num_held")
    moe_first_held = _of_moe("first_held")
    moe_router_init_std = _of_moe("router_init_std")
    moe_expert_bias_init_std = _of_moe("expert_bias_init_std")


Config = DeepSeekV32Config
EXPERT_ACTIVATION = "swiglu"

DEEPSEEK_V32_TINY = DeepSeekV32Config(  # test size: 16 positions kept
    vocab_size=512, max_seq_len=128, num_layers=3, num_heads=2, embed_dim=64,
    mlp_dim=160, moe_mlp_dim=24, first_k_dense=1, q_lora_rank=48,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_original_max_position=32, index_n_heads=4, index_head_dim=16,
    index_topk=16,
    moe=MoEConfig(num_experts=16, top_k=4, activation="swiglu",
                  score_func="sigmoid", expert_bias=True,
                  expert_bias_init_std=0.02, route_scale=2.5, n_group=4,
                  topk_group=2, dropless=True, num_held=4, first_held=0),
)

PRESETS = {"deepseek-v32-tiny": DEEPSEEK_V32_TINY}

DENSE, ROUTED = "dense", "routed"
# Deviations of a fresh model's matrices. The embedding's entries at 1
# (``joyai_llm_flash.EMBED_STD``). The queries' up-projection at twice the
# rest: with every matrix at 0.02 a score's deviation over positions is 1.5
# and a softmax over 2,048 chosen positions spreads over some 200 of them,
# its output their mean, 2% of the stream, and WHICH positions were chosen
# moves no logit; at 0.04 it is 3, a few positions carry a query's weight as
# in a trained model, and a wrong choice shows (the cell's faults:
# ``benchmarks/tests/faults_deepseek_v32.py``).
EMBED_STD = 1.0
QUERY_STD = 0.04


def _kind(config: Config, routed: bool) -> Layer:
    routed = routed and config.moe is not None
    return Layer(
        ROUTED if routed else DENSE, routed=routed, latent=config.latent_dim,
        index=Index(config.index_n_heads, config.index_head_dim,
                    config.index_topk),
        scale=config.softmax_scale)


def _routed_layers(config: Config) -> int:
    """Every layer behind the dense lead (a model shorter than its lead is
    all of it dense)."""
    return 0 if config.moe is None else max(
        config.num_layers - config.first_k_dense, 0)


def _plan(config: Config):
    """[(kinds of one period, repeats)]: the dense lead, then the routed
    layers, each kind one scan."""
    routed = _routed_layers(config)
    lead = config.num_layers - routed
    plan = [((_kind(config, False),), lead)] if lead else []
    return plan + ([((_kind(config, True),), routed)] if routed else [])


def init_params(config: Config, key: jax.Array) -> Dict[str, Any]:
    """The embedding at ``EMBED_STD``, the queries' up-projection at
    ``QUERY_STD``, every other matrix at 0.02 (into the residual stream at
    0.02 / sqrt(2 L)), the router at its ``router_init_std`` with a bias at
    ``expert_bias_init_std``, gains 1, the indexer's LayerNorm bias 0."""
    E, H, V = config.embed_dim, config.num_heads, config.vocab_size
    Rq, R = config.q_lora_rank, config.kv_lora_rank
    Dn, Dr, Dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    Hi, Di = config.index_n_heads, config.index_head_dim
    pd = config.param_dtype
    std = 0.02
    res_std = std / (2 * config.num_layers) ** 0.5
    k_wte, k_head, k_experts, k_layers = jax.random.split(key, 4)

    def layer(key, kind: Layer, n: int):
        k = jax.random.split(key, 11)

        def normal(key, shape, s=std):
            return (jax.random.normal(key, (n,) + shape) * s).astype(pd)

        pre = "shared_" if kind.routed else "w_"
        M = (config.moe_mlp_dim * config.num_shared_experts if kind.routed
             else config.mlp_dim)
        out = {
            "mix_norm": jnp.ones((n, E), pd), "mlp_norm": jnp.ones((n, E), pd),
            "w_dq": normal(k[0], (E, Rq)), "q_norm": jnp.ones((n, Rq), pd),
            "w_uq": normal(k[1], (Rq, H, Dn + Dr), QUERY_STD),
            "w_dkv": normal(k[2], (E, R + Dr)),
            "kv_norm": jnp.ones((n, R), pd),
            "w_ukv": normal(k[3], (R, H, Dn + Dv)),
            "wo": normal(k[4], (H, Dv, E), res_std),
            "w_iq": normal(k[5], (Rq, Hi, Di)),
            "w_ik": normal(k[6], (E, Di)),
            "ik_norm": jnp.ones((n, Di), pd), "ik_bias": jnp.zeros((n, Di), pd),
            "w_iw": normal(k[7], (E, Hi))}
        if M:
            out.update({pre + "gate": normal(k[8], (E, M)),
                        pre + "up": normal(k[9], (E, M)),
                        pre + "down": normal(k[10], (M, E), res_std)})
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
        "wte": (jax.random.normal(k_wte, (V, E)) * EMBED_STD).astype(pd),
        "blocks": blocks,
        "norm_f": jnp.ones((E,), pd),
        "lm_head": (jax.random.normal(k_head, (V, E)) * std).astype(pd),
    }


def param_axes(config: Config) -> Dict[str, Any]:
    lead = ("stage",)

    def layer(kind: Layer):
        axes = {"mix_norm": lead + ("norm",), "mlp_norm": lead + ("norm",),
                "w_dq": lead + ("embed", None), "q_norm": lead + ("norm",),
                "w_uq": lead + (None, "heads", "head_dim"),
                "w_dkv": lead + ("embed", None), "kv_norm": lead + ("norm",),
                "w_ukv": lead + (None, "heads", "head_dim"),
                "wo": lead + ("heads", "head_dim", "embed"),
                "w_iq": lead + (None, "heads", "head_dim"),
                "w_ik": lead + ("embed", None),
                "ik_norm": lead + ("norm",), "ik_bias": lead + ("norm",),
                "w_iw": lead + ("embed", "heads")}
        pre = "shared_" if kind.routed else "w_"
        if not kind.routed or config.num_shared_experts:
            axes.update({pre + "gate": lead + ("embed", "mlp"),
                         pre + "up": lead + ("embed", "mlp"),
                         pre + "down": lead + ("mlp", "embed")})
        return axes

    blocks = {"segments": tuple(
        tuple(layer(kind) for kind in kinds) for kinds, _ in _plan(config))}
    if _routed_layers(config):
        blocks["experts"] = moe_param_axes(
            num_layers=_routed_layers(config), config=config.moe)
    return {"wte": ("vocab", "embed"), "blocks": blocks,
            "norm_f": ("norm",), "lm_head": ("vocab", "embed")}


def serving_params(config: Config, params):
    """The projections, the MLPs, the experts and ``lm_head`` are read
    through ``.astype(config.dtype)`` alone. Read as they are: ``wte`` (the
    cached forward's stream is float32), every norm's gain, the indexer's
    LayerNorm bias, the router and its bias (float32)."""
    return narrowed(params, config.dtype, as_given=(
        "wte", "mix_norm", "mlp_norm", "q_norm", "kv_norm", "ik_norm",
        "ik_bias", "norm_f", "router_w", "expert_bias"))


def layers(config: Config, blocks, cached: bool):
    """The plan's segments over ``blocks["segments"]``, and the routed
    layers' router and experts: out of the layers in both forwards."""
    plan = _plan(config)
    held = [(None,) * len(kinds) for kinds, _ in plan] if blocks is None \
        else blocks["segments"]
    return ([Segment(kinds, params, repeats)
             for (kinds, repeats), params in zip(plan, held)],
            None if blocks is None else blocks.get("experts"))


def _rope_halves(x, pos, freq):
    """x [B, T, .., D] rotated by its position in the half-split layout:
    channel i with channel i + D / 2 by frequency i, in float32."""
    angles = pos.astype(jnp.float32)[..., None] * freq        # [B, T, D / 2]
    angles = angles.reshape(pos.shape + (1,) * (x.ndim - 3) + freq.shape)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def _indexed(config: Config, layer, h, cq, pos) -> Indexed:
    """A layer's indexer at the tokens of h [B, T, E] (the normed stream)
    and cq [B, T, Rq] (the normed query rank)."""
    Dr = config.qk_rope_head_dim
    freq = _inv_freq(Dr, config.rope_theta, config.yarn)

    def turned(x):      # the first Dr channels rotated, the rest as they are
        return jnp.concatenate(
            [_rope_halves(x[..., :Dr], pos, freq), x[..., Dr:]], axis=-1)

    with jax.named_scope("mla.index"):
        q = jnp.einsum("btr,rhd->bthd", cq, layer["w_iq"].astype(cq.dtype))
        k = jnp.einsum("bte,ed->btd", h, layer["w_ik"].astype(h.dtype),
                       preferred_element_type=jnp.float32)
        mean = k.mean(-1, keepdims=True)
        k = (k - mean) * jax.lax.rsqrt(
            jnp.square(k - mean).mean(-1, keepdims=True)
            + config.index_norm_eps)
        k = (k * layer["ik_norm"].astype(jnp.float32)
             + layer["ik_bias"].astype(jnp.float32)).astype(h.dtype)
        weights = jnp.einsum(
            "bte,eh->bth", h, layer["w_iw"].astype(h.dtype),
            preferred_element_type=jnp.float32) * (
                config.index_n_heads ** -0.5 * config.index_head_dim ** -0.5)
        return Indexed(turned(q), weights, turned(k),
                                config.index_topk)


def qkv(config: Config, kind, layer, x, pos, heads_major: bool = False):
    """A layer's latent pieces of x [B, T, E], as ``joyai_llm_flash.qkv``
    under YaRN's frequencies, and a fourth: the layer's indexer at these
    tokens (``Indexed``; never heads-major)."""
    h = _rms_norm(x, layer["mix_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("mla.q"):
        cq = _rms_norm(
            jnp.einsum("bte,er->btr", h, layer["w_dq"].astype(h.dtype)),
            layer["q_norm"], config.rms_eps, h.dtype)
        q = heads_in(cq, layer["w_uq"].astype(h.dtype), heads_major)
        q = _latent_q(q, pos, config.qk_nope_head_dim, config.rope_theta,
                     heads_major, config.yarn)
    rows = _latent_rows(config, layer, h, pos, heads_major, config.yarn)
    return q, rows, layer["w_ukv"], _indexed(config, layer, h, cq, pos)


# The rest of the block is DeepSeek-V3's as ``joyai_llm_flash`` has it, piece
# for piece: the float32 stream of the cached forward, the output projection
# with no gate, the dense MLP or the share's routed experts beside the shared
# one, the final norm and the untied head.


def embed(config: Config, params, tokens, pos, cached: bool):
    return v3.embed(config, params, tokens, pos, cached)


def attn_out(config: Config, layer, x, attn, heads_major: bool = False):
    return v3.attn_out(config, layer, x, attn, heads_major)


def ffn(config: Config, kind, layer, x, rng, row_mask, stacked,
        from_input=None):
    return v3.ffn(config, kind, layer, x, rng, row_mask, stacked, from_input)


def final_norm(config: Config, params, x):
    return v3.final_norm(config, params, x)


def head_weight(params):
    return v3.head_weight(params)


def head(config: Config, params, x):
    return v3.head(config, params, x)
