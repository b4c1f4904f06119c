"""Mixture-of-Experts: routed expert layers, two dispatches.

The reference delegates EP entirely to vLLM (SURVEY.md §2.3); here experts are
a mesh axis. Routing is one float32 score over all experts and a top-k: a
softmax (Switch/GShard, Mixtral, OLMoE: ``norm_topk_prob`` says whether the
k gates are renormalised to sum to 1), or a sigmoid an expert, the k chosen
under a learned bias that the gates do not carry, renormalised and scaled
(``score_func``, ``expert_bias``, ``route_scale``: the DeepSeek-V3 router
that Trinity's ``afmoe`` takes). What follows is one of two dispatches:

- capacity (training default): the sharded-einsum formulation of GShard/Switch.
  Routing builds a dispatch one-hot [tokens, experts, capacity]; einsums
  against it ARE the all-to-alls once the expert dim is sharded (XLA lowers
  the dispatch/combine contractions to ``all_to_all`` over ICI when experts
  live on the "expert" axis). Bounded work an expert, tokens over capacity
  are dropped.
- dropless (inference; ``MoEConfig.dropless``): one sorted, grouped dispatch.
  The T x k (token, expert) pairs are sorted by expert, the rows gathered in
  that order, and the expert products run as grouped matrix products over
  the row groups (``jax.lax.ragged_dot``: on a TPU one Mosaic kernel a
  product, which visits only the experts that received a row). Work is
  proportional to the routed rows, the weights read are those of the experts
  touched, and no [tokens, experts, width] array exists. One function for a
  2048-token prefill and a 16-row decode tick. It takes the weights in one
  of two forms: a layer's own ``[E, ..]`` (a caller whose layer scan hands
  each layer its slice: the full forward, training), or every layer's
  ``[L, E, ..]`` with the layer's index (``layer=``; ``stacked_for``), for
  a caller that must not cut a layer out of the stack: a slice that feeds a
  kernel is a copy, which the cached forward of a served model pays every
  tick (``_experts``).

Both are differentiable; auxiliary load-balancing loss included. The device
operations carry the scopes ``moe.route``, ``moe.dispatch``, ``moe.experts``
and ``moe.combine`` (``jax.named_scope``) for a trace's reader; a shared
expert that every token passes beside the routed ones is a plain gated MLP
under ``moe.shared`` (``shared_expert``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    router_jitter: float = 0.0
    # "gelu" (Switch-style experts) | "swiglu" (Mixtral/OLMoE gated experts)
    activation: str = "gelu"
    # The k chosen gates divided by their sum (Switch, Mixtral). OLMoE states
    # false: the k softmax probabilities weight the experts as they are.
    norm_topk_prob: bool = True
    # Scale of the router's initial weights. 0.02 starts a router near
    # uniform (k gates of about 1/E each), which is where training starts.
    # Random weights that stand in for a TRAINED model's state a larger one:
    # a trained router is peaked, its gates carry the experts' share of the
    # residual stream, and the expert at the k-th place has a small gate.
    router_init_std: float = 0.02
    # What an expert's score is: "softmax" over all experts, or "sigmoid" of
    # its own logit alone.
    score_func: str = "softmax"
    # A parameter ``expert_bias`` [E] (a trained model's load balancer) is
    # added to the scores for the CHOICE of the k; the gates are the scores
    # without it. Fresh weights draw it with ``expert_bias_init_std``.
    expert_bias: bool = False
    expert_bias_init_std: float = 0.0
    # The k gates times this, after any renormalising.
    route_scale: float = 1.0
    # Dropless routing (inference): every token reaches its
    # top-k experts, no capacity queues. Required for KV-cache decode to
    # reproduce full-forward outputs — capacity drops depend on the other
    # tokens in the batch, which differ between prefill and per-step decode.
    # The decode engine flips this on; training defaults to capacity
    # (bounded per-expert work => static shapes for the all-to-alls).
    dropless: bool = False

    def __post_init__(self):
        if self.activation not in ("gelu", "swiglu"):
            raise ValueError(
                f"MoEConfig.activation must be 'gelu' or 'swiglu', got "
                f"{self.activation!r}"
            )
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"MoEConfig.score_func must be 'softmax' or 'sigmoid', got "
                f"{self.score_func!r}")


def init_moe_params(
    key: jax.Array, embed_dim: int, mlp_dim: int, config: MoEConfig,
    param_dtype=jnp.float32, num_layers: Optional[int] = None,
    out_std: float = 0.02,
) -> Dict[str, jax.Array]:
    """Per-layer expert weights; with num_layers, adds a leading stacked dim.
    ``out_std`` is the scale of the experts' output projection: a model
    passes what it gives its other projections into the residual stream."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    lead = () if num_layers is None else (num_layers,)
    E = config.num_experts

    def normal(key, shape, s=0.02):
        return (jax.random.normal(key, shape) * s).astype(param_dtype)

    params = {
        "router_w": normal(k1, lead + (embed_dim, E), config.router_init_std),
        "expert_fc": normal(k2, lead + (E, embed_dim, mlp_dim)),
        "expert_out": normal(k3, lead + (E, mlp_dim, embed_dim), out_std),
    }
    if config.activation == "swiglu":
        # Mixtral-style gated experts: fc is the "up" proj, gate multiplies
        params["expert_gate"] = normal(k4, lead + (E, embed_dim, mlp_dim))
    if config.expert_bias:
        # float32 whatever the weights': it decides a choice among scores
        params["expert_bias"] = config.expert_bias_init_std * (
            jax.random.normal(k5, lead + (E,), jnp.float32))
    return params


def moe_param_axes(num_layers: Optional[int] = None,
                   config: Optional[MoEConfig] = None) -> Dict[str, tuple]:
    lead = () if num_layers is None else ("stage",)
    axes = {
        "router_w": lead + ("embed", None),
        "expert_fc": lead + ("expert", "embed", "mlp"),
        "expert_out": lead + ("expert", "mlp", "embed"),
    }
    if config is not None and config.activation == "swiglu":
        axes["expert_gate"] = lead + ("expert", "embed", "mlp")
    if config is not None and config.expert_bias:
        axes["expert_bias"] = lead + (None,)
    return axes


def stacked_for(params: Dict[str, jax.Array], dtype) -> Dict[str, jax.Array]:
    """Every layer's MoE weights as ``moe_layer_counted(.., layer=i)`` takes
    them: the experts' held in ``dtype``, the activations' (nothing happens
    where they already are; the router's stay as they are, it runs in
    float32). A caller does this ONCE, outside its layer loop: a cast of the
    stack is a copy of every layer's experts."""
    return {name: w if name in ("router_w", "expert_bias")
            else w.astype(dtype) for name, w in params.items()}


def _route(params, tokens, config: MoEConfig, rng, layer):
    """tokens [T, D] -> (scores [T, E] float32 over ALL experts, the k chosen
    experts' gates [T, k] (their scores as they are: ``_normalised`` does
    the rest) and indices [T, k])."""
    with jax.named_scope("moe.route"):
        def own(name):
            w = params[name]
            return w if layer is None else jax.lax.dynamic_index_in_dim(
                w, layer, 0, False)

        router_w = own("router_w")
        router_logits = jnp.einsum(
            "td,de->te", tokens.astype(jnp.float32),
            router_w.astype(jnp.float32),
        )
        if config.router_jitter and rng is not None:
            router_logits += config.router_jitter * jax.random.normal(
                rng, router_logits.shape
            )
        if config.score_func == "sigmoid":
            probs = jax.nn.sigmoid(router_logits)
        else:
            probs = jax.nn.softmax(router_logits, axis=-1)
        if config.expert_bias:
            # the bias moves the choice and not the gates
            _, chosen = jax.lax.top_k(
                probs + own("expert_bias").astype(jnp.float32), config.top_k)
            gates = jnp.take_along_axis(probs, chosen, axis=-1)
        else:
            gates, chosen = jax.lax.top_k(probs, config.top_k)
    return probs, gates, chosen


def _normalised(gates: jax.Array, config: MoEConfig) -> jax.Array:
    """A token's gates [..., k or E] divided by their sum, where the
    configuration says so, times its ``route_scale``."""
    if config.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates if config.route_scale == 1.0 else gates * config.route_scale


def shared_expert(x: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """The gated MLP every token passes beside its routed experts, x
    [.., D] in its own dtype: plain products, under ``moe.shared``."""
    with jax.named_scope("moe.shared"):
        h = jax.nn.silu(x @ w_gate.astype(x.dtype)) * (
            x @ w_up.astype(x.dtype))
        return h @ w_down.astype(x.dtype)


def _aux_loss(probs, chosen_share, config: MoEConfig):
    """Load-balancing auxiliary loss (Switch §2.2): mean gate fraction x
    token fraction per expert, scaled by E."""
    return config.aux_loss_weight * config.num_experts * jnp.sum(
        probs.mean(axis=0) * chosen_share)


def _experts(params, rows, counts, config: MoEConfig, layer):
    """rows [R, D] sorted by expert, ``counts`` [E] rows an expert -> [R, D]
    float32. Rows past ``counts.sum()`` belong to no expert; what comes back
    for them is undefined.

    With ``layer`` the weights are every layer's, [L, E, ..]: the products
    then run over L x E groups of which only this layer's hold rows. A
    grouped product visits the groups that have rows, so it reads what it
    would have read of the layer's own [E, ..] slice, and nobody has to cut
    that slice out first: on the TPU the product is a kernel, a kernel's
    operand is a whole array, and a layer's experts cut out of the stack
    were a copy of all of them (6.4 GB a decode tick for OLMoE at depth 8,
    19.6 of 48 ms; my chip run, PR 27)."""
    dtype = rows.dtype

    def weights(name):
        w = params[name]
        if layer is None:
            return w.astype(dtype)
        if w.dtype != dtype:  # a cast of every layer's experts, every layer
            raise TypeError(
                f"stacked {name} is {w.dtype}, the rows {dtype}: "
                f"``stacked_for`` casts the stack once, outside the loop")
        return w.reshape((-1,) + w.shape[2:])

    if layer is not None:
        L, E = params["expert_fc"].shape[:2]
        counts = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), counts.dtype), counts, (layer * E,))
    with jax.named_scope("moe.experts"):
        h = jax.lax.ragged_dot(rows, weights("expert_fc"), counts)
        if config.activation == "swiglu":
            g = jax.lax.ragged_dot(rows, weights("expert_gate"), counts)
            h = jax.nn.silu(g) * h
        else:
            h = jax.nn.gelu(h)
        return jax.lax.ragged_dot(
            h, weights("expert_out"), counts,
            preferred_element_type=jnp.float32)


def _grouped(params, tokens, gates, chosen, row_mask, config: MoEConfig,
             layer):
    """The sorted, grouped dispatch: tokens [T, D] with their k gates and
    experts -> (out [T, D], rows an expert [E]). A token that ``row_mask``
    [T] leaves out reaches no expert: its pairs sort behind the last group
    and cost their place in the sort."""
    T, D = tokens.shape
    E, k = config.num_experts, config.top_k
    with jax.named_scope("moe.dispatch"):
        expert = chosen.reshape(T * k)
        if row_mask is not None:
            expert = jnp.where(jnp.repeat(row_mask, k), expert, E)
        order = jnp.argsort(expert, stable=True)       # pair ids by expert
        counts = jnp.zeros((E,), jnp.int32).at[expert].add(1, mode="drop")
        rows = tokens[order // k]                      # [T*k, D]
    y = _experts(params, rows, counts, config, layer)
    with jax.named_scope("moe.combine"):
        real = (jnp.arange(T * k) < counts.sum())[:, None]
        y = jnp.where(real, y, 0.0) * gates.reshape(T * k)[order][:, None]
        back = jnp.zeros((T * k,), jnp.int32).at[order].set(jnp.arange(T * k))
        out = y[back].reshape(T, k, D).sum(axis=1)
    return out.astype(tokens.dtype), counts


def moe_layer_counted(
    params: Dict[str, jax.Array],
    x: jax.Array,
    config: MoEConfig,
    *,
    rng: Optional[jax.Array] = None,
    row_mask: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, T, D] -> (out [B, T, D], aux_loss scalar, the number of
    distinct experts that received a row). ``row_mask`` [B, T] bool marks the
    rows that carry a token (padding of a prefill bucket and idle decode
    slots do not): the others get no expert and come back as zeros. With
    ``layer`` (dropless only), ``params`` are the weights of ALL layers,
    stacked, and ``layer`` the index of this one (``_experts`` says why)."""
    B, T, D = x.shape
    E, k = config.num_experts, config.top_k
    tokens = x.reshape(B * T, D)
    n_tok = B * T
    mask = None if row_mask is None else row_mask.reshape(n_tok)
    if layer is not None and not config.dropless:
        raise ValueError("stacked weights with a layer index: dropless only")
    probs, gates, chosen = _route(params, tokens, config, rng, layer)

    if config.dropless:
        out, counts = _grouped(
            params, tokens, _normalised(gates, config), chosen, mask, config,
            layer)
        aux = _aux_loss(probs, counts / jnp.maximum(counts.sum(), 1), config)
        return out.reshape(B, T, D), aux, (counts > 0).sum()

    capacity = max(int(n_tok * k * config.capacity_factor / E), k)
    topk_mask = jax.nn.one_hot(chosen, E, dtype=probs.dtype).sum(axis=1)
    if mask is not None:
        topk_mask = topk_mask * mask[:, None]
    # Position of each token within its expert's queue; drop overflow.
    pos = jnp.cumsum(topk_mask, axis=0) * topk_mask          # [T, E] 1-based
    keep = (pos > 0) & (pos <= capacity)
    pos = (pos - 1).astype(jnp.int32)

    gates = _normalised(probs * topk_mask * keep, config)   # [T, E]

    # dispatch [T, E, C]: one-hot over capacity slots
    dispatch = keep[..., None] * jax.nn.one_hot(pos, capacity, dtype=x.dtype)
    combine = gates[..., None].astype(jnp.float32) * dispatch

    # These einsums become all_to_all when "expert" is a sharded mesh axis.
    expert_in = jnp.einsum("tec,td->ecd", dispatch, tokens)  # [E, C, D]
    h = jnp.einsum("ecd,edm->ecm", expert_in,
                   params["expert_fc"].astype(x.dtype))
    if config.activation == "swiglu":
        gate = jnp.einsum("ecd,edm->ecm", expert_in,
                          params["expert_gate"].astype(x.dtype))
        h = jax.nn.silu(gate) * h
    else:
        h = jax.nn.gelu(h)
    expert_out = jnp.einsum("ecm,emd->ecd", h,
                            params["expert_out"].astype(x.dtype))
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    aux = _aux_loss(probs, topk_mask.mean(axis=0) / k, config)
    return out.reshape(B, T, D), aux, keep.any(axis=0).sum()


def moe_layer(
    params: Dict[str, jax.Array],
    x: jax.Array,
    config: MoEConfig,
    *,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """x: [B, T, D] → (out [B, T, D], aux_loss scalar)."""
    out, aux, _ = moe_layer_counted(params, x, config, rng=rng)
    return out, aux
