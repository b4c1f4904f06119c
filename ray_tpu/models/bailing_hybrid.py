"""inclusionAI's ``bailing_hybrid`` decoder (Ling-3.0-flash) as pieces over
the one decoder: the seventh family. Three things in one stack that no other
family has together, and one that none has at all:

- five of every ``layer_group_size`` layers keep a STATE and attend nothing:
  Kimi Delta Attention (``ops/kda.py``: the gated delta rule with a decay a
  key channel), between ``state_in`` and ``state_out`` through
  ``models/kv_cache.py:recur``, as ``olmo_hybrid``'s delta rule;
- the sixth attends a LATENT cache (DeepSeek-V2's multi-head latent
  attention, no query rank): a position's row is ``[RMSNorm(c) | rope(kr)]``,
  ``kv_lora_rank + qk_rope_head_dim`` values that every head shares, and K
  and V are up-projections of it (``kv_cache.attend_latent``; ``qkv`` gives
  the queries, the new rows and the up-projection);
- every layer but the first ``first_k_dense`` is routed: a sigmoid router
  over ALL ``moe.num_experts`` whose k are chosen among the best
  ``moe.topk_group`` of ``moe.n_group`` groups under a bias (DeepSeek-V3's
  ``noaux_tc``; ``parallel/moe.py``), beside one shared expert. A served
  model may hold a SHARE of the experts (``moe.num_held`` from
  ``moe.first_held``: one chip of an expert-parallel group): the layer's
  result is then the held experts' part plus the shared expert, and nothing
  stands in for the other chips.

What the published ``config.json`` gives and what it leaves to the papers it
follows ("Kimi Linear", arXiv:2510.26692, and the flash-linear-attention
layer whose key names the config uses; DeepSeek-V2 and -V3) is listed as
``assumed`` in a configuration file. A layer is ``h = h + mix(norm(h))``,
``h = h + ffn(norm(h))``: pre-norm, RMSNorm with a gain, no bias anywhere;
a last norm and an untied head.

- a KDA layer: ``[q, k, v] = silu(conv(x Wqkv))`` (``short_conv_kernel_size``
  taps), ``num_heads`` heads of ``head_dim`` each; q and k to unit length, q
  further times ``head_dim ** -0.5``; ``g = kda_lower_bound x sigmoid(
  exp(A_log_h) (x Wf + dt_bias))`` a head and key channel, float32 (the
  bound keeps the chunked scan's exponents inside float32: ``ops/kda.py``);
  ``beta = sigmoid(x Wb)`` a head; the recurrence; each head's output normed
  over its own channels and gated by ``sigmoid(x Wz)``, ONE gate a head;
  the output projection. Nothing is rotated.
- a latent layer: ``q = x Wq`` a head ``[qk_nope_head_dim |
  qk_rope_head_dim]``; ``[c | kr] = x Wdkv``, c normed; ``[k_nope | v] = c
  Wukv`` a head; interleaved RoPE at ``rope_theta`` on q's rotated part and
  on kr; causal softmax of ``(q_nope . k_nope + q_rope . kr) / sqrt(192)``;
  the head gate; the output projection.

The stack (``layers``) is the fewer kinds to compile of two plans: a lead and
the shortest period (the published 42 layers: 6 + 6 x 6), or runs of equal
layers (the benchmark's one period: a dense KDA layer, four routed ones in
one scan, the latent layer).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import narrowed
from ray_tpu.models.decoder import *  # noqa: F401,F403 — what families share
from ray_tpu.models.decoder import Layer, Segment, periods
from ray_tpu.models.llama import _rms_norm, _turned_on_lanes
from ray_tpu.ops import kda
from ray_tpu.parallel.moe import (
    MoEConfig,
    aux_zero,
    init_moe_params,
    moe_layer_counted,
    moe_param_axes,
    shared_expert,
)


def _of_moe(name: str):
    """The router's ``name`` as a property of the model's config (None: no
    layer is routed)."""
    return property(lambda self: None if self.moe is None
                    else getattr(self.moe, name))


@dataclass(frozen=True)
class BailingHybridConfig:
    vocab_size: int = 157184
    max_seq_len: int = 262144
    num_layers: int = 42
    num_heads: int = 32
    embed_dim: int = 2560
    head_dim: int = 128                  # a KDA head's keys and its values
    mlp_dim: int = 6144                  # the dense layers' MLP
    moe_mlp_dim: int = 768               # one expert's, and the shared one's
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    remat_policy: str = "dots"
    seq_axis: str = "seq"
    # routed experts in every layer but the first ``first_k_dense`` (None:
    # every layer dense), each beside ``num_shared_experts`` shared
    moe: Optional[MoEConfig] = None
    first_k_dense: int = 2
    num_shared_experts: int = 1
    # layer i attends (latent) where (i + 1) % layer_group_size == 0
    layer_group_size: int = 6
    # the latent layers' sizes, under their published names
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    # the KDA layers'
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk_size: int = 64             # tokens a chunk of the scan
    # what the cache holds a state in: float32 is the one value taken
    state_dtype: str = "float32"

    def __post_init__(self):
        if self.state_dtype != "float32":
            raise ValueError(
                "BailingHybridConfig.state_dtype: the cache holds a state in "
                "float32 and nothing narrower (a step corrects what the "
                f"state holds), got {self.state_dtype!r}")
        # what ``ops/kda.py``'s reference row can hold in float32
        if not -88.0 / (kda.SUB - 1) <= self.kda_lower_bound < 0:
            raise ValueError(
                f"BailingHybridConfig.kda_lower_bound {self.kda_lower_bound}"
                f": {kda.SUB - 1} steps of it must stay above -88")

    @property
    def num_kv_heads(self) -> int:
        """No layer holds keys and values a head; the decoder asks."""
        return self.num_heads

    @property
    def latent_dim(self) -> int:
        """A position's row of a latent layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kda_conv_dim(self) -> int:
        """Channels through the convolution: q, k and v side by side."""
        return 3 * self.num_heads * self.head_dim

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


Config = BailingHybridConfig
EXPERT_ACTIVATION = "swiglu"

BAILING_HYBRID_TINY = BailingHybridConfig(  # test size: two periods of 5 + 1
    vocab_size=512, max_seq_len=128, num_layers=12, num_heads=2, embed_dim=64,
    head_dim=16, mlp_dim=96, moe_mlp_dim=32, first_k_dense=1,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    kda_chunk_size=16,
    moe=MoEConfig(num_experts=16, top_k=4, activation="swiglu",
                  score_func="sigmoid", expert_bias=True,
                  expert_bias_init_std=0.02, route_scale=2.5, n_group=4,
                  topk_group=2, dropless=True, num_held=4, first_held=4),
)

PRESETS = {"bailing-hybrid-tiny": BAILING_HYBRID_TINY}


def _kinds(config: Config) -> Tuple[Layer, ...]:
    out = []
    for i in range(config.num_layers):
        routed = config.moe is not None and i >= config.first_k_dense
        tail = "/routed" if routed else "/dense"
        if (i + 1) % config.layer_group_size:
            out.append(Layer("kda" + tail, routed=routed,
                             state=config.kda_chunk_size, recurrence=kda.KDA))
        else:
            out.append(Layer("latent" + tail, routed=routed,
                             latent=config.latent_dim))
    return tuple(out)


def _plan(config: Config):
    """[(kinds of one period, repeats)]: ``decoder.periods`` of the stack,
    or its runs of equal layers where those are fewer kinds to compile."""
    kinds = _kinds(config)
    runs = []
    for kind in kinds:
        if runs and runs[-1][0] == (kind,):
            runs[-1] = ((kind,), runs[-1][1] + 1)
        else:
            runs.append(((kind,), 1))
    return min(periods(kinds), runs,
               key=lambda plan: sum(len(kinds) for kinds, _ in plan))


def _routed_layers(config: Config) -> int:
    return sum(k.routed for k in _kinds(config))


def state_leaves(config: Config) -> Dict[str, tuple]:
    """A slot's share of a KDA layer's cache: the matrix a head, and the
    rows the convolution still needs."""
    return {
        "ssm": ((config.num_heads, config.head_dim, config.head_dim),
                jnp.float32),
        "conv": (((config.short_conv_kernel_size - 1) * config.kda_conv_dim,),
                 config.dtype),
    }


def init_params(config: Config, key: jax.Array) -> Dict[str, Any]:
    """Matrices at 0.02 (into the residual stream at 0.02 / sqrt(2 L); a
    latent layer's queries and down-projection at 0.05), gains 1; a KDA
    layer's own: ``A_log = ln U(0, 4)``, ``dt_bias`` U(-4, 1)
    (so ``g`` runs from -5 a step to nearly 0 over a head's channels: the
    state carries short and long history at once, and a decay taken as its
    head's mean shows), the convolution U[-1/sqrt(K), 1/sqrt(K)]."""
    E, H, D, V = (config.embed_dim, config.num_heads, config.head_dim,
                  config.vocab_size)
    C, K = config.kda_conv_dim, config.short_conv_kernel_size
    R, Dn, Dr, Dv = (config.kv_lora_rank, config.qk_nope_head_dim,
                     config.qk_rope_head_dim, config.v_head_dim)
    pd = config.param_dtype
    std = 0.02
    res_std = std / (2 * config.num_layers) ** 0.5
    k_wte, k_head, k_experts, k_layers = jax.random.split(key, 4)

    def layer(key, kind: Layer, n: int):
        k = jax.random.split(key, 12)

        def normal(key, shape, s=std):
            return (jax.random.normal(key, (n,) + shape) * s).astype(pd)

        def uniform(key, shape, lo, hi):
            return jax.random.uniform(key, (n,) + shape, jnp.float32, lo, hi)

        out = {"mix_norm": jnp.ones((n, E), pd),
               "mlp_norm": jnp.ones((n, E), pd)}
        pre = "shared_" if kind.routed else "w_"
        M = (config.moe_mlp_dim * config.num_shared_experts if kind.routed
             else config.mlp_dim)
        if M:
            out[pre + "gate"] = normal(k[0], (E, M))
            out[pre + "up"] = normal(k[1], (E, M))
            out[pre + "down"] = normal(k[2], (M, E), res_std)
        if kind.latent is not None:
            out.update({
                # queries and the down-projection at 2.5 times the rest: a
                # trained attention is peaked, and at 0.02 a softmax over
                # thousands of positions is flat, its output their mean and
                # the layer nothing beside the others (a key left unrotated
                # then moves no logit)
                "wq": normal(k[3], (E, H, Dn + Dr), 2.5 * std),
                "w_dkv": normal(k[4], (E, R + Dr), 2.5 * std),
                "kv_norm": jnp.ones((n, R), pd),
                "w_ukv": normal(k[5], (R, H, Dn + Dv)),
                "wz": normal(k[6], (E, H)),
                "wo": normal(k[7], (H, Dv, E), res_std)})
            return out
        out.update({
            "kda_in": normal(k[3], (E, C)),
            "kda_f": normal(k[4], (E, H * D)),
            # beta's and the head gate's columns side by side
            "kda_gates": normal(k[5], (E, 2 * H)),
            "kda_out": normal(k[6], (H * D, E), res_std),
            "conv_w": uniform(k[7], (C, K), -K ** -0.5, K ** -0.5).astype(pd),
            "dt_bias": uniform(k[8], (H * D,), -4.0, 1.0),
            "A_log": jnp.log(uniform(
                k[9], (H,), jnp.finfo(jnp.float32).tiny, 4.0)),
            "gate_norm": jnp.ones((n, D), pd),
        })
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


def param_axes(config: Config) -> Dict[str, Any]:
    def layer(kind: Layer):
        axes = {"mix_norm": ("stage", "norm"), "mlp_norm": ("stage", "norm")}
        pre = "shared_" if kind.routed else "w_"
        if not kind.routed or config.num_shared_experts:
            axes.update({pre + "gate": ("stage", "embed", "mlp"),
                         pre + "up": ("stage", "embed", "mlp"),
                         pre + "down": ("stage", "mlp", "embed")})
        if kind.latent is not None:
            axes.update({"wq": ("stage", "embed", "heads", "head_dim"),
                         "w_dkv": ("stage", "embed", None),
                         "kv_norm": ("stage", "norm"),
                         "w_ukv": ("stage", None, "heads", "head_dim"),
                         "wz": ("stage", "embed", None),
                         "wo": ("stage", "heads", "head_dim", "embed")})
            return axes
        axes.update({"kda_in": ("stage", "embed", "mlp"),
                     "kda_f": ("stage", "embed", "mlp"),
                     "kda_gates": ("stage", "embed", None),
                     "kda_out": ("stage", "mlp", "embed"),
                     "conv_w": ("stage", "mlp", None),
                     "gate_norm": ("stage", None),
                     "dt_bias": ("stage", None), "A_log": ("stage", None)})
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
    cached forward's stream is float32), every RMSNorm gain, the router and
    its bias (float32), a KDA layer's ``dt_bias`` and ``A_log`` (float32:
    they set decays near 1) and its convolution (summed in float32)."""
    return narrowed(params, config.dtype, as_given=(
        "wte", "mix_norm", "mlp_norm", "kv_norm", "gate_norm", "norm_f",
        "router_w", "expert_bias", "dt_bias", "A_log", "conv_w"))


def layers(config: Config, blocks, cached: bool):
    """The plan's segments over ``blocks["segments"]``, and the routed
    layers' router and experts: out of the scan in both forwards."""
    plan = _plan(config)
    held = [(None,) * len(kinds) for kinds, _ in plan] if blocks is None \
        else blocks["segments"]
    return ([Segment(kinds, params, repeats)
             for (kinds, repeats), params in zip(plan, held)],
            None if blocks is None else blocks.get("experts"))


def embed(config: Config, params, tokens, pos, cached: bool):
    """Token embeddings; positions enter in the latent layers' rotation and
    the KDA layers' decays. The cached forward sums its stream in float32,
    as llama's."""
    return params["wte"][tokens].astype(
        jnp.float32 if cached else config.dtype)


class Yarn(NamedTuple):
    """YaRN's change to a rotation's frequencies (arXiv:2309.00071, as
    DeepSeek-V3's ``rope_scaling`` states it): a context ``factor`` times
    the ``original`` one; a frequency that turns more than ``beta_fast``
    times over the original context stays, one that turns fewer than
    ``beta_slow`` times is divided by the factor, a linear ramp between."""
    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0

    def softmax_scale(self, width: int, mscale_all_dim: float) -> float:
        """``width ^ -0.5 x m^2``, ``m = 0.1 x mscale_all_dim x ln factor +
        1``: what the longer context does to the attention's temperature
        (the cosines and sines are not scaled)."""
        m = 0.1 * mscale_all_dim * math.log(self.factor) + 1.0
        return width ** -0.5 * m * m


def _inv_freq(D: int, theta: float, yarn: Optional[Yarn] = None):
    """The D / 2 frequencies of a rotation over D channels at ``theta``
    [D / 2] float32; under ``yarn`` the ramp's blend of each with itself
    divided by the factor."""
    freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    if yarn is None:
        return freq

    def turns(n):   # the channel pair that turns n times in the original
        return D * math.log(yarn.original / (n * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns(yarn.beta_fast)), 0)
    high = min(math.ceil(turns(yarn.beta_slow)), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low)
                    / ((high if high != low else high + 0.001) - low), 0, 1)
    return freq / yarn.factor * ramp + freq * (1 - ramp)


def _rope_interleaved(x, pos, theta: float, yarn: Optional[Yarn] = None):
    """x [B, T, .., D] rotated by its position: pair (2i, 2i + 1) by
    frequency i (``rope_interleave``), in float32."""
    D = x.shape[-1]
    freq = _inv_freq(D, theta, yarn)
    angles = pos.astype(jnp.float32)[..., None] * freq       # [B, T, D / 2]
    angles = angles.reshape(pos.shape + (1,) * (x.ndim - 3) + (D // 2,))
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], D // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _rope_lanes(x, pos, theta: float, yarn: Optional[Yarn] = None):
    """``_rope_interleaved`` for the full forward, on x as it lies ([B, T,
    D] or, heads-major, [B, H, T, D]): channel 2i meets -x[2i + 1] and
    channel 2i + 1 meets x[2i] (``llama._turned_on_lanes``)."""
    D = x.shape[-1]
    angles = pos.astype(jnp.float32)[..., None] * jnp.repeat(
        _inv_freq(D, theta, yarn), 2)
    j = np.arange(D)
    return _turned_on_lanes(x, angles, j ^ 1, np.where(j % 2, 1.0, -1.0))


def _latent_q(q, pos, Dn: int, theta: float, heads_major: bool,
              yarn: Optional[Yarn] = None):
    """A latent layer's queries with their last channels rotated: [B, T, H,
    Dn + Dr], or [B, H, T, Dn + Dr] where ``heads_major``."""
    rope = _rope_lanes if heads_major else _rope_interleaved
    return jnp.concatenate([
        q[..., :Dn], rope(q[..., Dn:], pos, theta, yarn)], axis=-1)


def _latent_rows(config, layer, h, pos, heads_major: bool,
                 yarn: Optional[Yarn] = None):
    """A position's row of a latent layer from the normed stream h [B, T,
    E]: [RMSNorm(c) | rope(kr)], [B, T, R + Dr], as the cache holds it."""
    rope = _rope_lanes if heads_major else _rope_interleaved
    with jax.named_scope("mla.down"):
        c, kr = jnp.split(
            jnp.einsum("bte,ef->btf", h, layer["w_dkv"].astype(h.dtype)),
            [config.kv_lora_rank], axis=-1)
        return jnp.concatenate([
            _rms_norm(c, layer["kv_norm"], config.rms_eps, h.dtype),
            rope(kr, pos, config.rope_theta, yarn)], axis=-1)


def qkv(config: Config, kind, layer, x, pos, heads_major: bool = False):
    """A latent layer's pieces of x [B, T, E]: (q [B, T, H, Dn + Dr], its
    last Dr rotated, [B, H, T, Dn + Dr] where ``heads_major``; the new rows
    [B, T, R + Dr] = [RMSNorm(c) | rope(kr)] as the cache holds them; the
    up-projection [R, H, Dn + Dv])."""
    h = _rms_norm(x, layer["mix_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("mla.q"):
        q = heads_in(h, layer["wq"].astype(h.dtype), heads_major)
        q = _latent_q(q, pos, config.qk_nope_head_dim, config.rope_theta,
                     heads_major)
    return q, _latent_rows(config, layer, h, pos, heads_major), layer["w_ukv"]


def attn_out(config: Config, layer, x, attn, heads_major: bool = False):
    """A latent layer's heads [B, T, H, Dv] ([B, H, T, Dv] where
    ``heads_major``) times their gates (from the normed stream ``qkv``
    projected), the output projection, the residual."""
    h = _rms_norm(x, layer["mix_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("mla.out"):
        gate = jax.nn.sigmoid(jnp.einsum(
            "bte,eh->bth", h, layer["wz"].astype(h.dtype),
            preferred_element_type=jnp.float32))
        if heads_major:
            gate = gate.swapaxes(1, 2)      # [B, H, T]: a scalar a head
        out = heads_out((attn * gate[..., None]).astype(attn.dtype),
                        layer["wo"].astype(attn.dtype), heads_major)
    return x + out


def state_in(config: Config, kind, layer, x):
    """A KDA layer up to its recurrence: x [B, T, E] normed and projected
    -> (q, k and v side by side [B, T, C], the gates (log decay g
    [B, T, H, Dk] in [lower bound, 0), beta [B, T, H]) float32, the head
    gate [B, T, H] float32)."""
    H, D = config.num_heads, config.head_dim
    h = _rms_norm(x, layer["mix_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("kda.in_proj"):
        qkv_ = jnp.einsum("bte,ef->btf", h, layer["kda_in"].astype(h.dtype))
        f = jnp.einsum("bte,ef->btf", h, layer["kda_f"].astype(h.dtype),
                       preferred_element_type=jnp.float32)
        b, z = jnp.split(jnp.einsum(
            "bte,ef->btf", h, layer["kda_gates"].astype(h.dtype),
            preferred_element_type=jnp.float32), 2, axis=-1)
        f = (f + layer["dt_bias"].astype(jnp.float32)).reshape(
            *f.shape[:2], H, D)
        g = config.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(layer["A_log"].astype(jnp.float32))[:, None] * f)
    return qkv_, (g, jax.nn.sigmoid(b)), jax.nn.sigmoid(z)


def state_out(config: Config, layer, x, y, gate):
    """What the recurrence gave, y [B, T, H, Dv] float32: each head normed
    over its own channels and gated, one gate a head; the output
    projection; the residual."""
    with jax.named_scope("kda.gate_norm"):
        y = _rms_norm(y, layer["gate_norm"], config.rms_eps) * gate[..., None]
        y = y.reshape(*y.shape[:2], -1).astype(config.dtype)
    with jax.named_scope("kda.out_proj"):
        out = jnp.einsum("btf,fe->bte", y, layer["kda_out"].astype(y.dtype))
    return x + out


def ffn(config: Config, kind, layer, x, rng, row_mask, stacked,
        from_input=None):
    """mlp_norm, the dense MLP or the routed experts (a share's part, where
    the layer holds a share) beside the shared one, the residual -> (x, aux
    loss, experts that received a row)."""
    h = _rms_norm(x, layer["mlp_norm"], config.rms_eps, config.dtype)
    # a dense layer adds what a routed one does, in its form (a share's
    # counts beside the loss)
    aux, touched = aux_zero(config.moe), jnp.int32(0)
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
    return x + y, aux, touched


def final_norm(config: Config, params, x):
    return _rms_norm(x, params["norm_f"], config.rms_eps, config.dtype)


def head_weight(params):
    return params["lm_head"]


def head(config: Config, params, x):
    """Final features [B, T, E] -> logits [B, T, V] float32, straight from
    the product's float32 sums (as ``llama.head``)."""
    return jnp.einsum("bte,ve->btv", x, head_weight(params).astype(x.dtype),
                      preferred_element_type=jnp.float32)
