"""The ``joyai_llm_flash`` decoder (JoyAI-LLM-Flash; DeepSeek-V3's block,
arXiv:2412.19437, whose key names its ``config.json`` follows) as pieces over
the one decoder: the eighth family. What no other family has:

- latent attention in EVERY layer, with a QUERY rank: ``cq = RMSNorm(x
  Wdq)``, ``q = cq Wuq`` a head ``[qk_nope_head_dim | qk_rope_head_dim]``;
  ``[c | kr] = x Wdkv``, c normed; ``[k_nope | v] = c Wukv`` a head;
  interleaved RoPE at ``rope_theta`` on q's rotated part and on kr, which
  every head shares; causal softmax of ``(q_nope . k_nope + q_rope . kr) /
  sqrt(192)``; the output projection, no gate. ``qkv`` gives the queries, a
  position's row ``[RMSNorm(c) | rope(kr)]`` and the up-projection, as
  ``bailing_hybrid``'s latent layer does: the full forward attends the
  up-projected keys (192 wide) and values (128 wide) through the flash
  kernels, the cached one the rows (``kv_cache.attend_latent``);
- a multi-token-prediction layer behind the trunk (``second_loss``;
  DeepSeek-V3 section 2.2 at depth 1): ``h'_i = [RMSNorm_h(h_i) ;
  RMSNorm_e(Emb(t_{i+1}))] M`` with ``h_i`` the last layer's output before
  the final norm, one whole routed block of its own, its own final norm,
  the trunk's table and head, and the cross entropy of ``t_{i+2}``: a step
  minimises ``L_main + mtp_loss_weight x L_mtp``. Training only: the cached
  forward leaves the layer out;
- the router's bias moved by a RULE and not by the optimizer (``step_rule``;
  ``topk_method: noaux_tc``): after a step ``b_e += u x sign(mean(n) -
  n_e)`` a routed layer, ``n_e`` the pairs expert e was chosen for in the
  step's batch over ALL experts (``moe.bias_update_rate`` is u). The bias
  has no gradient (it moves a choice of indices), and the rule's update
  stands in the place of the optimizer's, weight decay included.

The first ``first_k_dense`` layers are dense, every other is routed:
a sigmoid router over ALL ``moe.num_experts`` (no group limit), the k chosen
under the bias, gates renormalised times ``route_scale``, beside one shared
expert. A layer holds a SHARE of the experts (``moe.num_held`` from
``moe.first_held``: one chip of an expert-parallel group; all of them is a
share too): its result is the held experts' part plus the shared expert.

The stack (``layers``) is the dense lead and then the routed layers as ONE
segment that is not scanned: each layer's place in the experts' stack is
known as the program is traced, so the block cuts its own experts out inside
its remat (``decoder._body(own=)``) and their gradient is the layer's, not a
stack-sized cotangent a repeat (ROADMAP.md Queue 2, B5c). The price is one
traced body a routed layer.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import narrowed
from ray_tpu.models.bailing_hybrid import _of_moe, _latent_q, _latent_rows
from ray_tpu.models.decoder import *  # noqa: F401,F403 — what families share
from ray_tpu.models.decoder import Layer, Segment, _body
from ray_tpu.models.llama import _rms_norm
from ray_tpu.parallel.moe import (
    MoEConfig,
    aux_zero,
    bias_rule_update,
    counts_apart,
    init_moe_params,
    moe_layer_counted,
    moe_param_axes,
    shared_expert,
)


@dataclass(frozen=True)
class JoyAIFlashConfig:
    vocab_size: int = 129280
    max_seq_len: int = 131072
    num_layers: int = 40                 # the trunk's; the MTP layer beside
    num_heads: int = 32
    embed_dim: int = 2048
    mlp_dim: int = 7168                  # the dense layers' MLP
    moe_mlp_dim: int = 768               # one expert's, and the shared one's
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    remat_policy: str = "dots"
    seq_axis: str = "seq"
    # routed experts in every layer but the first ``first_k_dense`` and in
    # the prediction layer (None: every layer dense), each beside
    # ``num_shared_experts`` shared; held as a share (``num_held``; not
    # stated: all of them, which is a share too)
    moe: Optional[MoEConfig] = None
    first_k_dense: int = 1
    num_shared_experts: int = 1
    # latent attention's sizes, under their published names
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    # multi-token prediction: layers behind the trunk (0 or 1) and the
    # weight of their loss in what a step minimises
    num_mtp_layers: int = 1
    mtp_loss_weight: float = 0.3

    def __post_init__(self):
        if self.moe is not None and not self.moe.dropless:
            raise ValueError(
                "JoyAIFlashConfig.moe: the routed layers hold a share of the "
                "experts, dropless (the sorted dispatch: ``moe_dropless``)")
        if self.moe is not None and self.moe.num_held is None:
            object.__setattr__(self, "moe", dataclasses.replace(
                self.moe, num_held=self.moe.num_experts, first_held=0))
        if self.num_mtp_layers not in (0, 1):
            raise ValueError(
                "JoyAIFlashConfig.num_mtp_layers: one prediction layer or "
                f"none, got {self.num_mtp_layers}")
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError(
                f"JoyAIFlashConfig.first_k_dense {self.first_k_dense} of "
                f"{self.num_layers} layers")

    @property
    def num_kv_heads(self) -> int:
        """No layer holds keys and values a head; the decoder asks."""
        return self.num_heads

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """A position's row of a layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    # the router's numbers under the flat names a configuration file gives
    # them (``models.config_for``): ``benchmarks/`` reads a file's keys back
    moe_num_experts = _of_moe("num_experts")
    moe_top_k = _of_moe("top_k")
    moe_norm_topk_prob = _of_moe("norm_topk_prob")
    moe_score_func = _of_moe("score_func")
    moe_route_scale = _of_moe("route_scale")
    moe_num_held = _of_moe("num_held")
    moe_first_held = _of_moe("first_held")
    moe_bias_update_rate = _of_moe("bias_update_rate")
    moe_aux_loss_weight = _of_moe("aux_loss_weight")
    moe_router_init_std = _of_moe("router_init_std")
    moe_expert_bias_init_std = _of_moe("expert_bias_init_std")


Config = JoyAIFlashConfig
EXPERT_ACTIVATION = "swiglu"

JOYAI_FLASH_TINY = JoyAIFlashConfig(  # test size: every ratio of the widths
    vocab_size=512, max_seq_len=128, num_layers=3, num_heads=2, embed_dim=64,
    mlp_dim=224, moe_mlp_dim=24, q_lora_rank=48, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    moe=MoEConfig(num_experts=16, top_k=2, activation="swiglu",
                  score_func="sigmoid", expert_bias=True,
                  expert_bias_init_std=0.02, route_scale=2.5,
                  aux_loss_weight=0.0, bias_update_rate=0.001, dropless=True,
                  num_held=4, first_held=0),
)

PRESETS = {"joyai-flash-tiny": JOYAI_FLASH_TINY}

DENSE, ROUTED = "dense", "routed"
# Deviation of a fresh embedding's entries, as ``smallthinker.EMBED_STD``: at
# 1 the token's own signal leads the stream, so that a fresh model's first
# loss is not ln V whatever its layers do.
EMBED_STD = 1.0


def _kind(config: Config, routed: bool) -> Layer:
    routed = routed and config.moe is not None
    return Layer(ROUTED if routed else DENSE, routed=routed,
                 latent=config.latent_dim)


def _plan(config: Config):
    """[(kinds of one period, repeats)]: the dense lead, then every routed
    layer in one period that comes once (no scan: the module's docstring)."""
    routed = _routed_layers(config)
    lead = config.num_layers - routed
    plan = [((_kind(config, False),) * lead, 1)] if lead else []
    return plan + ([((_kind(config, True),) * routed, 1)] if routed else [])


def _routed_layers(config: Config) -> int:
    return 0 if config.moe is None else (
        config.num_layers - config.first_k_dense)


def init_params(config: Config, key: jax.Array) -> Dict[str, Any]:
    """The embedding at ``EMBED_STD``, every other matrix at 0.02 (into the
    residual stream at 0.02 / sqrt(2 L)), the router at its
    ``router_init_std`` with a bias at ``expert_bias_init_std``, gains 1."""
    E, H, V = config.embed_dim, config.num_heads, config.vocab_size
    Rq, R = config.q_lora_rank, config.kv_lora_rank
    Dn, Dr, Dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    pd = config.param_dtype
    std = 0.02
    res_std = std / (2 * config.num_layers) ** 0.5
    k_wte, k_head, k_experts, k_layers, k_mtp = jax.random.split(key, 5)

    def layer(key, kind: Layer, lead: Tuple[int, ...]):
        k = jax.random.split(key, 8)

        def normal(key, shape, s=std):
            return (jax.random.normal(key, lead + shape) * s).astype(pd)

        def ones(n):
            return jnp.ones(lead + (n,), pd)

        pre = "shared_" if kind.routed else "w_"
        M = (config.moe_mlp_dim * config.num_shared_experts if kind.routed
             else config.mlp_dim)
        out = {
            "mix_norm": ones(E), "mlp_norm": ones(E),
            "w_dq": normal(k[0], (E, Rq)), "q_norm": ones(Rq),
            "w_uq": normal(k[1], (Rq, H, Dn + Dr)),
            "w_dkv": normal(k[2], (E, R + Dr)), "kv_norm": ones(R),
            "w_ukv": normal(k[3], (R, H, Dn + Dv)),
            "wo": normal(k[4], (H, Dv, E), res_std)}
        if M:
            out.update({pre + "gate": normal(k[5], (E, M)),
                        pre + "up": normal(k[6], (E, M)),
                        pre + "down": normal(k[7], (M, E), res_std)})
        return out

    segments = tuple(
        tuple(layer(jax.random.fold_in(jax.random.fold_in(k_layers, s), j),
                    kind, (repeats,)) for j, kind in enumerate(kinds))
        for s, (kinds, repeats) in enumerate(_plan(config)))
    blocks = {"segments": segments}
    if _routed_layers(config):
        blocks["experts"] = init_moe_params(
            k_experts, E, config.moe_mlp_dim, config.moe, pd,
            num_layers=_routed_layers(config), out_std=res_std)
    params = {
        "wte": (jax.random.normal(k_wte, (V, E)) * EMBED_STD).astype(pd),
        "blocks": blocks,
        "norm_f": jnp.ones((E,), pd),
        "lm_head": (jax.random.normal(k_head, (V, E)) * std).astype(pd),
    }
    if config.num_mtp_layers:
        k_proj, k_layer, k_moe = jax.random.split(k_mtp, 3)
        params["mtp"] = {
            "norm_h": jnp.ones((E,), pd), "norm_e": jnp.ones((E,), pd),
            "eh_proj": (jax.random.normal(k_proj, (2 * E, E)) * std
                        ).astype(pd),
            "layer": layer(k_layer, _kind(config, True), ()),
            "norm_f": jnp.ones((E,), pd)}
        if config.moe is not None:
            params["mtp"]["experts"] = init_moe_params(
                k_moe, E, config.moe_mlp_dim, config.moe, pd,
                out_std=res_std)
    return params


def param_axes(config: Config) -> Dict[str, Any]:
    def layer(kind: Layer, lead: Tuple[str, ...]):
        axes = {"mix_norm": lead + ("norm",), "mlp_norm": lead + ("norm",),
                "w_dq": lead + ("embed", None), "q_norm": lead + ("norm",),
                "w_uq": lead + (None, "heads", "head_dim"),
                "w_dkv": lead + ("embed", None), "kv_norm": lead + ("norm",),
                "w_ukv": lead + (None, "heads", "head_dim"),
                "wo": lead + ("heads", "head_dim", "embed")}
        pre = "shared_" if kind.routed else "w_"
        if not kind.routed or config.num_shared_experts:
            axes.update({pre + "gate": lead + ("embed", "mlp"),
                         pre + "up": lead + ("embed", "mlp"),
                         pre + "down": lead + ("mlp", "embed")})
        return axes

    blocks = {"segments": tuple(
        tuple(layer(kind, ("stage",)) for kind in kinds)
        for kinds, _ in _plan(config))}
    if _routed_layers(config):
        blocks["experts"] = moe_param_axes(
            num_layers=_routed_layers(config), config=config.moe)
    axes = {"wte": ("vocab", "embed"), "blocks": blocks,
            "norm_f": ("norm",), "lm_head": ("vocab", "embed")}
    if config.num_mtp_layers:
        axes["mtp"] = {
            "norm_h": ("norm",), "norm_e": ("norm",),
            "eh_proj": (None, "embed"),
            "layer": layer(_kind(config, True), ()),
            "norm_f": ("norm",)}
        if config.moe is not None:
            axes["mtp"]["experts"] = moe_param_axes(config=config.moe)
    return axes


def serving_params(config: Config, params):
    """The projections, the MLPs, the experts and ``lm_head`` are read
    through ``.astype(config.dtype)`` alone. Read as they are: ``wte`` (the
    cached forward's stream is float32), every RMSNorm gain, the router and
    its bias (float32). The prediction layer is held as the rest and not
    run."""
    return narrowed(params, config.dtype, as_given=(
        "wte", "mix_norm", "mlp_norm", "q_norm", "kv_norm", "norm_f",
        "norm_h", "norm_e", "router_w", "expert_bias"))


def layers(config: Config, blocks, cached: bool):
    """The plan's segments over ``blocks["segments"]``, and the routed
    layers' router and experts: out of the layers in both forwards."""
    plan = _plan(config)
    held = [(None,) * len(kinds) for kinds, _ in plan] if blocks is None \
        else blocks["segments"]
    return ([Segment(kinds, params, repeats)
             for (kinds, repeats), params in zip(plan, held)],
            None if blocks is None else blocks.get("experts"))


def embed(config: Config, params, tokens, pos, cached: bool):
    """Token embeddings; positions enter in the rotation. The cached forward
    sums its stream in float32, as llama's."""
    return params["wte"][tokens].astype(
        jnp.float32 if cached else config.dtype)


def qkv(config: Config, kind, layer, x, pos, heads_major: bool = False):
    """A layer's latent pieces of x [B, T, E]: (q [B, T, H, Dn + Dr] from
    the normed query rank, its last Dr rotated, [B, H, T, Dn + Dr] where
    ``heads_major``; the new rows [B, T, R + Dr] = [RMSNorm(c) | rope(kr)]
    as the cache holds them; the up-projection [R, H, Dn + Dv])."""
    h = _rms_norm(x, layer["mix_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("mla.q"):
        cq = _rms_norm(
            jnp.einsum("bte,er->btr", h, layer["w_dq"].astype(h.dtype)),
            layer["q_norm"], config.rms_eps, h.dtype)
        q = heads_in(cq, layer["w_uq"].astype(h.dtype), heads_major)
        q = _latent_q(q, pos, config.qk_nope_head_dim, config.rope_theta,
                     heads_major)
    return q, _latent_rows(config, layer, h, pos, heads_major), layer["w_ukv"]


def attn_out(config: Config, layer, x, attn, heads_major: bool = False):
    """The heads [B, T, H, Dv] ([B, H, T, Dv] where ``heads_major``)
    through the output projection, the residual."""
    with jax.named_scope("mla.out"):
        return x + heads_out(attn, layer["wo"].astype(attn.dtype),
                             heads_major)


def ffn(config: Config, kind, layer, x, rng, row_mask, stacked,
        from_input=None):
    """mlp_norm, the dense MLP or the share's routed experts beside the
    shared one, the residual -> (x, aux, experts that received a row)."""
    h = _rms_norm(x, layer["mlp_norm"], config.rms_eps, config.dtype)
    if kind == ROUTED:
        moe, index = stacked
        y, aux, touched = moe_layer_counted(
            moe, h, config.moe, rng=rng, row_mask=row_mask, layer=index)
        if config.num_shared_experts:
            y = y + shared_expert(h, layer["shared_gate"],
                                  layer["shared_up"], layer["shared_down"])
        return x + y, aux, touched
    gate = jnp.einsum("bte,em->btm", h, layer["w_gate"].astype(h.dtype))
    up = jnp.einsum("bte,em->btm", h, layer["w_up"].astype(h.dtype))
    y = jnp.einsum("btm,me->bte", jax.nn.silu(gate) * up,
                   layer["w_down"].astype(h.dtype))
    # a dense layer adds what a routed one does, in its form
    return x + y, aux_zero(config.moe), jnp.int32(0)


def final_norm(config: Config, params, x):
    return _rms_norm(x, params["norm_f"], config.rms_eps, config.dtype)


def head_weight(params):
    return params["lm_head"]


def head(config: Config, params, x):
    """Final features [B, T, E] -> logits [B, T, V] float32, straight from
    the product's float32 sums (as ``llama.head``)."""
    return jnp.einsum("bte,ve->btv", x, head_weight(params).astype(x.dtype),
                      preferred_element_type=jnp.float32)


def _mtp_features(config: Config, params, x, following, mesh=None):
    """The prediction layer over the trunk: x [B, T, E] the last layer's
    output before the final norm, ``following`` [B, T] the token AFTER each
    position -> (the block's output [B, T, E], not yet normed; the block's
    aux as a routed layer gives it)."""
    mtp = params["mtp"]
    B, T = following.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    with jax.named_scope("mtp.in"):
        e = embed(config, params, following, pos, cached=False)
        both = jnp.concatenate([
            _rms_norm(x, mtp["norm_h"], config.rms_eps, config.dtype),
            _rms_norm(e, mtp["norm_e"], config.rms_eps, config.dtype)], -1)
        h = jnp.einsum("btf,fe->bte", both,
                       mtp["eh_proj"].astype(both.dtype))
    with jax.named_scope("mtp.block"):
        return _body(config, mesh, pos, _kind(config, True))(
            h, mtp["layer"], None, (mtp.get("experts"), None))


def second_loss(config: Config, params, x, targets, mask, aux, mesh=None):
    """``decoder.loss_fn``'s optional piece: x [B, T, E] the trunk's output
    before the final norm, ``targets`` [B, T] each position's next token,
    ``aux`` what the trunk's layers added up -> ``aux`` with the prediction
    layer's in it, its cross entropy as ``mtp_loss`` and, weighted, as
    ``second_loss`` (what ``moe.aux_loss_of`` adds). Position i is fed
    ``targets[i]`` and scores ``targets[i + 1]``; the last has none and is
    masked, so the shapes stay those of the main loss."""
    if not config.num_mtp_layers:
        return aux
    from ray_tpu.ops.xent import chunked_softmax_xent

    h, layer_aux = _mtp_features(config, params, x, targets, mesh)
    has_next = jnp.arange(targets.shape[1]) < targets.shape[1] - 1
    seen = jnp.broadcast_to(has_next[None, :], targets.shape)
    if mask is not None:  # a position counts where it and its target do
        seen = seen & (mask > 0) & (jnp.roll(mask, -1, axis=1) > 0)
    with jax.named_scope("mtp.head"):
        mtp_loss = chunked_softmax_xent(
            _rms_norm(h, params["mtp"]["norm_f"], config.rms_eps,
                      config.dtype),
            head_weight(params), jnp.roll(targets, -1, axis=1),
            seen.astype(jnp.float32))
    layer_aux, counts = counts_apart(layer_aux)
    if config.moe is None:      # a dense model's aux is its scalar 0
        aux, layer_aux = {"aux_loss": aux}, {"aux_loss": layer_aux}
    aux = {name: value + layer_aux.get(name, 0) for name, value in aux.items()}
    if counts is not None:   # behind the trunk's, where it has routed layers
        trunk = [aux["moe_counts"]] if "moe_counts" in aux else []
        aux["moe_counts"] = jnp.concatenate(trunk + [counts[None]])
    return {**aux, "mtp_loss": mtp_loss,
            "second_loss": config.mtp_loss_weight * mtp_loss}


def _biases(tree):
    """The routers' biases of a parameter-shaped tree, the trunk's [L, X]
    and then the prediction layer's [1, X]: the order of ``moe_counts``."""
    found = []
    if "experts" in tree["blocks"]:
        found.append(tree["blocks"]["experts"]["expert_bias"])
    if "mtp" in tree:
        found.append(tree["mtp"]["experts"]["expert_bias"][None])
    return jnp.concatenate(found)


def step_rule(config: Config, params, updates, counted):
    """``train/step.py``'s optional piece: the optimizer's ``updates`` with
    those of the leaves a rule moves replaced by the rule's, and what the
    step counted with the rule's own counters in place of its input.
    The routers' biases move by ``moe.bias_rule_update`` of the step's
    counts over all experts (``counted["moe_counts"]`` [routed layers, X]);
    whatever the optimizer made of their zero gradient (AdamW's decay) is
    dropped. ``moe_rows_max_all``: each routed layer's fullest expert of
    all, summed; ``moe_bias_abs_mean``: of the biases as the step leaves
    them."""
    counted = dict(counted)
    counts = counted.pop("moe_counts", None)
    if counts is None:
        return updates, counted
    moves = bias_rule_update(counts, config.moe.bias_update_rate)
    updates = jax.tree.map(lambda u: u, updates)  # a copy to write into
    L = _routed_layers(config)
    if L:
        updates["blocks"]["experts"]["expert_bias"] = moves[:L]
    if "mtp" in updates:
        updates["mtp"]["experts"]["expert_bias"] = moves[L]
    counted["moe_rows_max_all"] = counts.max(axis=1).sum()
    counted["moe_bias_abs_mean"] = jnp.abs(_biases(params) + moves).mean()
    return updates, counted
