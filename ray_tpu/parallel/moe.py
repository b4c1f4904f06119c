"""Mixture-of-Experts: routed expert layers, two dispatches.

The reference delegates EP entirely to vLLM (SURVEY.md §2.3); here experts are
a mesh axis. Routing is one float32 score over all experts and a top-k: a
softmax (Switch/GShard, Mixtral, OLMoE: ``norm_topk_prob`` says whether the
k gates are renormalised to sum to 1), or a sigmoid an expert, the k chosen
under a learned bias that the gates do not carry, renormalised and scaled
(``score_func``, ``expert_bias``, ``route_scale``: the DeepSeek-V3 router
that Trinity's ``afmoe`` takes), the k chosen among the experts of the best
groups alone where the configuration groups them (``n_group``,
``topk_group``: ``_in_best_groups``). What follows is one of two dispatches:

- capacity (training default): the sharded-einsum formulation of GShard/Switch.
  Routing builds a dispatch one-hot [tokens, experts, capacity]; einsums
  against it ARE the all-to-alls once the expert dim is sharded (XLA lowers
  the dispatch/combine contractions to ``all_to_all`` over ICI when experts
  live on the "expert" axis). Bounded work an expert, tokens over capacity
  are dropped.
- dropless (inference; ``MoEConfig.dropless``): one sorted, grouped dispatch.
  The T x k (token, expert) pairs are sorted by expert, the rows gathered in
  that order with their gates, and the expert products run as grouped matrix
  products over the row groups (``ops/grouped_matmul.py:grouped_dot``: on a
  TPU a Pallas kernel a product, its tiles chosen from the call's shape,
  which visits only the experts that received a row;
  ``jax.lax.ragged_dot`` elsewhere, the platform's choice alone), the gate
  multiplied into the hidden row between the activation and the down
  product (``_experts``), so that what follows the products only moves
  rows. Work is
  proportional to the routed rows, the weights read are those of the experts
  touched, and no [tokens, experts, width] array exists. One function for a
  2048-token prefill and a 16-row decode tick. It takes the weights in one
  of two forms: a layer's own ``[E, ..]`` (a caller whose layer scan hands
  each layer its slice: the full forward, training), or every layer's
  ``[L, E, ..]`` with the layer's index (``layer=``; ``stacked_for``), for
  a caller that must not cut a layer out of the stack: a slice that feeds a
  kernel is a copy, which the cached forward of a served model pays every
  tick (``_experts``).

A layer may hold a SHARE of the experts (``MoEConfig.num_held`` of
``num_experts``, from ``first_held``): one chip of an expert-parallel group.
The router scores all ``num_experts``, the auxiliary loss is over all of
them, and the layer's result is the held experts' part: the pairs routed to
the others are left out before the gather, and nothing stands in for the
chips that hold them (``_grouped_share``; dropless only). The router's
logits may be computed elsewhere and handed in (``logits=``,
``router_logits``): a model whose router reads another tensor than the
experts do.

Both are differentiable; auxiliary load-balancing loss included. The device
operations carry the scopes ``moe.route``, ``moe.dispatch``, ``moe.experts``
and ``moe.combine`` (``jax.named_scope``) for a trace's reader; a shared
expert that every token passes beside the routed ones is a plain gated MLP
under ``moe.shared`` (``shared_expert``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.grouped_matmul import grouped_dot, grouped_dot_grads
from ray_tpu.ops.rows_to_tokens import rows_to_tokens


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    router_jitter: float = 0.0
    # "gelu" (Switch-style experts) | "swiglu" (Mixtral/OLMoE gated experts)
    # | "reglu" (gated by ReLU: SmallThinker's sparse experts)
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
    # This layer's share of the experts: ``num_held`` of them from
    # ``first_held`` on (None: all). The router still scores ``num_experts``.
    num_held: Optional[int] = None
    first_held: int = 0
    # Group-limited choice (DeepSeek-V3's ``noaux_tc``): the experts lie in
    # ``n_group`` groups side by side, a group's score is the sum of its two
    # largest scores (with the bias), the ``topk_group`` best groups stay and
    # the k are chosen among their experts alone. None: among all experts.
    n_group: Optional[int] = None
    topk_group: Optional[int] = None
    # The bias is moved after each step by a rule and not by the optimizer
    # (``bias_rule_update``; a trainer's business: ``train/step.py``), by
    # this much: a layer that holds a share then gives its count of pairs
    # over ALL experts beside its other counts (``moe_counts``). 0: the bias
    # is a parameter like any other.
    bias_update_rate: float = 0.0

    def __post_init__(self):
        if self.activation not in ("gelu", "swiglu", "reglu"):
            raise ValueError(
                f"MoEConfig.activation must be 'gelu', 'swiglu' or 'reglu', "
                f"got {self.activation!r}"
            )
        if self.num_held is not None:
            if not self.dropless:
                raise ValueError(
                    "MoEConfig.num_held: a share of the experts is routed "
                    "dropless (the capacity path holds every expert)")
            if not (0 <= self.first_held
                    and 0 < self.num_held
                    and self.first_held + self.num_held <= self.num_experts):
                raise ValueError(
                    f"MoEConfig: experts {self.first_held} to "
                    f"{self.first_held + self.num_held - 1} of "
                    f"{self.num_experts}")
        if self.n_group is not None and not (
                self.topk_group and 0 < self.topk_group <= self.n_group
                and self.num_experts % self.n_group == 0
                and self.num_experts // self.n_group >= 2
                and self.top_k <= self.topk_group
                * (self.num_experts // self.n_group)):
            raise ValueError(
                f"MoEConfig: {self.topk_group} of {self.n_group} groups of "
                f"{self.num_experts} experts for a choice of {self.top_k}")
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"MoEConfig.score_func must be 'softmax' or 'sigmoid', got "
                f"{self.score_func!r}")


def _gated(config: MoEConfig) -> bool:
    return config.activation in ("swiglu", "reglu")


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
    # the router scores every expert; the weights are those of the share
    X = config.num_experts
    E = X if config.num_held is None else config.num_held

    def normal(key, shape, s=0.02):
        return (jax.random.normal(key, shape) * s).astype(param_dtype)

    params = {
        "router_w": normal(k1, lead + (embed_dim, X), config.router_init_std),
        "expert_fc": normal(k2, lead + (E, embed_dim, mlp_dim)),
        "expert_out": normal(k3, lead + (E, mlp_dim, embed_dim), out_std),
    }
    if _gated(config):
        # Mixtral-style gated experts: fc is the "up" proj, gate multiplies
        params["expert_gate"] = normal(k4, lead + (E, embed_dim, mlp_dim))
    if config.expert_bias:
        # float32 whatever the weights': it decides a choice among scores
        # (over every expert the router scores, held here or not)
        params["expert_bias"] = config.expert_bias_init_std * (
            jax.random.normal(k5, lead + (X,), jnp.float32))
    return params


def moe_param_axes(num_layers: Optional[int] = None,
                   config: Optional[MoEConfig] = None) -> Dict[str, tuple]:
    lead = () if num_layers is None else ("stage",)
    axes = {
        "router_w": lead + ("embed", None),
        "expert_fc": lead + ("expert", "embed", "mlp"),
        "expert_out": lead + ("expert", "mlp", "embed"),
    }
    if config is not None and _gated(config):
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


def _own(params, name, layer):
    """A layer's ``name``: ``params``' own, or its slice of a stack."""
    w = params[name]
    return w if layer is None else jax.lax.dynamic_index_in_dim(
        w, layer, 0, False)


def router_logits(params, x, layer=None):
    """x [..., D] -> the router's logits [rows, experts], float32: for a
    caller whose router reads another tensor than its experts do, to hand
    to ``moe_layer_counted(logits=)``."""
    with jax.named_scope("moe.route"):
        return jnp.einsum(
            "td,de->te", x.reshape(-1, x.shape[-1]).astype(jnp.float32),
            _own(params, "router_w", layer).astype(jnp.float32))


def _route(params, tokens, config: MoEConfig, rng, layer, logits=None):
    """tokens [T, D] -> (scores [T, E] float32 over ALL experts, the k chosen
    experts' gates [T, k] (their scores as they are: ``_normalised`` does
    the rest) and indices [T, k]). ``logits`` [T, E]: the router's product,
    where the caller made it (``router_logits``)."""
    with jax.named_scope("moe.route"):
        router_logits = logits if logits is not None else jnp.einsum(
            "td,de->te", tokens.astype(jnp.float32),
            _own(params, "router_w", layer).astype(jnp.float32),
        )
        if config.router_jitter and rng is not None:
            router_logits += config.router_jitter * jax.random.normal(
                rng, router_logits.shape
            )
        if config.score_func == "sigmoid":
            probs = jax.nn.sigmoid(router_logits)
        else:
            probs = jax.nn.softmax(router_logits, axis=-1)
        choice = probs
        if config.expert_bias:
            # the bias moves the choice and not the gates
            choice = probs + _own(params, "expert_bias", layer).astype(
                jnp.float32)
        if config.n_group is not None:
            choice = _in_best_groups(choice, config)
        if choice is probs:     # the scores decide alone: their top-k as it was
            gates, chosen = jax.lax.top_k(probs, config.top_k)
        else:
            _, chosen = jax.lax.top_k(choice, config.top_k)
            gates = jnp.take_along_axis(probs, chosen, axis=-1)
    return probs, gates, chosen


def _in_best_groups(choice: jax.Array, config: MoEConfig) -> jax.Array:
    """``choice`` [T, E], the scores the k are chosen by, with every expert
    outside the ``topk_group`` best of the ``n_group`` groups at -inf: a
    group's score is the sum of its two largest. A high score in a losing
    group is not chosen."""
    T, E = choice.shape
    grouped = choice.reshape(T, config.n_group, E // config.n_group)
    score = jax.lax.top_k(grouped, 2)[0].sum(-1)             # [T, groups]
    _, best = jax.lax.top_k(score, config.topk_group)
    # a compare and a reduction, as ``_count``: no scatter
    stays = (best[:, :, None] == jnp.arange(config.n_group)).any(axis=1)
    return jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(T, E)


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


def _count(expert: jax.Array, n: int) -> jax.Array:
    """expert [P] int -> how many of the pairs name each of experts 0 to
    n - 1, [n] int32. An id outside that range ("no expert": a masked row, a
    pair routed to another chip's share) counts nowhere. A compare and a
    column sum: on the chip a scatter-add of ones is serial, 8.7 ns a pair
    (0.859 ms at 98,304 pairs; PR 40's trace)."""
    return jnp.sum(expert[None, :] == jnp.arange(n, dtype=expert.dtype)[
        :, None], axis=1, dtype=jnp.int32)


def _experts(params, rows, gates, counts, config: MoEConfig, layer,
             named=True, add_at=None, gate_rows=None):
    """rows [R, D] sorted by expert, their gates [R] float32 and ``counts``
    [E] rows an expert -> each row's gated result, gate x its expert's
    output: [R, D] float32, or with ``add_at`` = (token [R], T) those rows
    added up, row r into row token[r] of [T, D] float32 (``_down_add``; a
    group's rows in the order of their tokens). Rows past ``counts.sum()``
    belong to no expert; what comes back for them is undefined, and they are
    added to no token. ``named``: the two products into
    the experts and the rows' gates carry the names ``moe_fc``, ``moe_gate``
    and ``moe_row_gates``. ``gate_rows``: the same rows under another name,
    read by the gate's product, for a caller that wants the two products'
    cotangents apart (``_rows_of``); None: ``rows``.

    The gate meets the row BETWEEN the activation and the down product, the
    hidden rows [R, M]: the down projection is linear, so gate x (h W) =
    (gate x h) W, and the hidden row is a third as wide and half as deep as
    the float32 output. ``act(g) * h * gate`` is one elementwise expression,
    float32 inside and rounded to the rows' dtype once, as ``act(g) * h``
    alone was. What follows the down product then moves rows and multiplies
    nothing, and the gates' gradient is <hidden, d hidden>: nothing in the
    backward pass needs the expert's output, so no checkpoint runs the down
    product again for it (2.8 + 4.6 + 7.1 ms of a 387 ms step went for that
    in the routed training cell; PERF.md section 6, PR 49). A row of no
    expert takes the gate 0, by a select over [R]: the products leave
    anything in such a row, d hidden too, and the select's transpose keeps
    <hidden, d hidden> of it out of the gates' gradient.

    With ``layer`` the weights are every layer's, [L, E, ..]: the products
    then run over L x E groups of which only this layer's hold rows. A
    grouped product visits the groups that have rows, so it reads what it
    would have read of the layer's own [E, ..] slice, and nobody has to cut
    that slice out first: on the TPU the product is a kernel, a kernel's
    operand is a whole array, and a layer's experts cut out of the stack
    were a copy of all of them (6.4 GB a decode tick for OLMoE at depth 8,
    19.6 of 48 ms; my chip run, PR 27).

    The three products are ``grouped_dot``s: on a TPU the Pallas kernel
    ``grouped_matmul`` (in the backward pass the same kernel with the
    weights read transposed, and ``grouped_matmul_dw`` for the weights'
    gradient), ``jax.lax.ragged_dot`` on any other platform."""
    dtype = rows.dtype
    E = params["expert_fc"].shape[-3]       # the groups that can hold rows

    def weights(name):
        w = params[name]
        if layer is None:
            return w.astype(dtype)
        if w.dtype != dtype:  # a cast of every layer's experts, every layer
            raise TypeError(
                f"stacked {name} is {w.dtype}, the rows {dtype}: "
                f"``stacked_for`` casts the stack once, outside the loop")
        return w.reshape((-1,) + w.shape[2:])

    filled, own = counts.sum(), counts
    if layer is not None:
        L = params["expert_fc"].shape[0]
        counts = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), counts.dtype), counts, (layer * E,))
    with jax.named_scope("moe.experts"):
        # a checkpoint whose policy keeps the names
        # (``decoder._remat_policy``) hands the two products into the
        # experts to the backward pass, where a grouped product is no
        # ``dot_general`` and would run again, and the rows' gates, whose
        # gather is 8.5 ns a row on the chip; anywhere else a name is the
        # identity
        name = checkpoint_name if named else lambda x, _: x
        gates = name(jnp.where(jnp.arange(rows.shape[0]) < filled, gates,
                               0.0), "moe_row_gates")

        def product(x, w, out=None):
            return grouped_dot(x, weights(w), counts, out, live_groups=E,
                               scope="moe.experts")

        h = name(product(rows, "expert_fc"), "moe_fc")
        g = name(product(rows if gate_rows is None else gate_rows,
                         "expert_gate"), "moe_gate") if _gated(
            config) else None
        h = _gated_hidden(config.activation, h, g, gates)
        if add_at is None:
            return product(h, "expert_out", jnp.float32)
        w_out = weights("expert_out")
    return _down_add(h, w_out, counts, own, *add_at, E)


@functools.partial(jax.checkpoint, static_argnums=0)
def _gated_hidden(activation, h, g, gates):
    """``act(g) * h * gate`` (``gelu(h) * gate`` of experts that are not
    gated, g None): h, g [R, M] with the rows' gates [R] -> [R, M] in h's
    dtype, float32 inside and rounded once. Under a checkpoint of its own:
    what its backward pass needs beyond h, g and the gates it makes again
    inside that pass's one fusion. Without it the float32 values between
    the factors are residuals, and a checkpoint around the layer writes
    them out, three [R, M] float32 arrays a layer (3.5 ms of the routed
    training cell's step; PERF.md section 6, PR 49)."""
    f32 = jnp.float32
    if g is None:
        x = jax.nn.gelu(h.astype(f32))
    else:
        act = jax.nn.silu if activation == "swiglu" else jax.nn.relu
        x = act(g.astype(f32)) * h.astype(f32)
    return (x * gates[:, None].astype(f32)).astype(h.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _down_add(h, w, sizes, own, token, T, live_groups):
    """The down product and the combine of a share as one function: hidden
    rows h [R, M] (gated) x w [G, M, D] by ``sizes``, row r of the float32
    result added into row ``token[r]`` of [T, D] float32
    (``ops/rows_to_tokens.py``: on a TPU a kernel that walks the tokens in
    tiles; ``own`` [E] are the sizes of the groups that hold rows, ``sizes``
    without a stack's other layers; a row past the last group is never
    read). One function because of its backward pass: the cotangent [T, D]
    holds what the caller's cast to the rows' dtype made of it, its rows
    are gathered in THAT dtype and reach the two backward kernels so.
    Autodiff gives the float32 product a float32 cotangent: a [R, D] gather
    at twice the bytes and two kernels that read it to round it again (7.2
    ms of gathers where the dispatch's bf16 gathers of as many rows take
    2.3; PR 44's trace)."""
    with jax.named_scope("moe.experts"):
        y = grouped_dot(h, w, sizes, jnp.float32, live_groups=live_groups,
                        scope="moe.experts")
    with jax.named_scope("moe.combine"):
        return rows_to_tokens(y, token, own, T)


def _down_add_fwd(h, w, sizes, own, token, T, live_groups):
    return _down_add(h, w, sizes, own, token, T, live_groups), (
        h, w, sizes, token)


def _down_add_bwd(T, live_groups, kept, ct):
    h, w, sizes, token = kept
    with jax.named_scope("moe.combine"):
        # a row past the last group reads some token's: finite, and neither
        # product reads it (``grouped_dot_grads``)
        dy = ct.astype(h.dtype)[token]
    return *grouped_dot_grads(h, w, sizes, dy, live_groups=live_groups,
                              scope="moe.experts"), None, None, None


_down_add.defvjp(_down_add_fwd, _down_add_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rows_of(tokens, token, sizes, T, readers):
    """The dispatch's gather, ``tokens[token]``: tokens [T, D] -> rows [R,
    D], in groups of ``sizes`` from row 0 and inside a group in the order of
    their tokens; as a tuple that names the same rows once for each of
    their ``readers`` (the products into the experts). A function of its
    own for its backward pass: the readers' cotangents are added into the
    tokens' by ``rows_to_tokens``, float32 inside and rounded once to the
    tokens' dtype, where the gather's transpose is XLA's scatter-add
    (serial on the chip; it adds in the rows' dtype, rounding at every
    row) of the cotangents' sum (one more pass over [R, D], which the
    kernel adds in VMEM: hence a cotangent a reader). A row past the last
    group holds a real token's row, finite, which no product reads; what
    the products' backward pass leaves in such a row of a cotangent is
    never read either, so no select stands on either side."""
    return (tokens[token],) * readers


def _rows_of_fwd(tokens, token, sizes, T, readers):
    return _rows_of(tokens, token, sizes, T, readers), (token, sizes)


def _rows_of_bwd(T, readers, kept, cts):
    token, sizes = kept
    # a backward function does not inherit its call site's scope
    with jax.named_scope("moe.dispatch"):
        return rows_to_tokens(cts, token, sizes, T), None, None


_rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


def _grouped(params, tokens, gates, chosen, row_mask, config: MoEConfig,
             layer):
    """The sorted, grouped dispatch: tokens [T, D] with their k gates and
    experts -> (out [T, D], rows an expert [E]). A token that ``row_mask``
    [T] leaves out reaches no expert: its pairs sort behind the last group
    and cost their place in the sort. A pair's gate rides its row through
    ``_experts``; what comes back is put in the tokens' order and a token's
    k rows added in float32. The rows past the last group are the masked
    tokens' and come back undefined: such a token's SUM is made zeros, by
    one select over [T, D] (over the [T * k, D] rows before the gather it
    was a pass over k times as much, 0.41 ms a layer of a 2,048-token
    prefill; PERF.md section 6, PR 49). A masked token's own gradient is
    whatever its rows' was: nothing reads it."""
    T, D = tokens.shape
    E, k = config.num_experts, config.top_k
    with jax.named_scope("moe.dispatch"):
        expert = chosen.reshape(T * k)
        if row_mask is not None:
            expert = jnp.where(jnp.repeat(row_mask, k), expert, E)
        order = jnp.argsort(expert, stable=True)       # pair ids by expert
        counts = _count(expert, E)
        rows = tokens[order // k]                      # [T*k, D]
        row_gates = gates.reshape(T * k)[order]
    y = _experts(params, rows, row_gates, counts, config, layer)
    with jax.named_scope("moe.combine"):
        back = jnp.zeros((T * k,), jnp.int32).at[order].set(jnp.arange(T * k))
        out = y[back].reshape(T, k, D).sum(axis=1)
        if row_mask is not None:
            out = jnp.where(row_mask[:, None], out, 0.0)
    return out.astype(tokens.dtype), counts


# A share's row buffer, in units of the rows a uniform router sends it
# (tokens x top_k x num_held / num_experts): one pass of the grouped products
# takes that many rows, and a routing that sends the share more takes further
# passes (``_grouped_share``), so no row is ever dropped. The buffer's rows
# are gathered and added back whether or not a pair fills them.
HELD_ROWS_FACTOR = 1.5


def held_rows_bound(n_tok: int, config: MoEConfig) -> int:
    """Rows one pass of a share's grouped products takes: what a uniform
    router sends the share, times ``HELD_ROWS_FACTOR``, in whole sublanes,
    and never more than every pair there is."""
    pairs = n_tok * config.top_k
    want = pairs * config.num_held / config.num_experts
    return min(pairs, 8 * -(-int(want * HELD_ROWS_FACTOR) // 8))


def _grouped_share(params, tokens, gates, chosen, row_mask, counts,
                   config: MoEConfig, layer):
    """The sorted, grouped dispatch of a layer that holds experts
    ``first_held : first_held + num_held`` alone: tokens [T, D] with their k
    gates and experts (of all ``num_experts``) and the pairs a held expert
    got (``counts`` [num_held], masked rows left out: the caller's count over
    all experts holds them) -> the held experts' part of the result [T, D].

    The pairs are sorted by held expert, those routed elsewhere (or masked)
    behind the last group. The rows gathered, multiplied and added back are
    the first ``held_rows_bound`` of that order: a pair routed elsewhere is
    never gathered, and the grouped products stop at the last group's last
    row. Where the routing sends the share more rows than one pass holds,
    further passes take the next ``held_rows_bound`` pairs each until none
    is left (each recomputed in the backward pass, so their residuals do not
    add up): whatever the routing, every pair of a held expert is computed.

    A pair's gate is gathered beside its row and meets it inside
    ``_experts``, on the hidden row. The sort is stable, so inside a group
    the pairs, and with them the tokens, ascend: the buffer is ``num_held``
    runs each sorted by destination, which is what lets a kernel add rows
    to tokens without sorting or permuting anything
    (``ops/rows_to_tokens.py``). Both places where rows are added to tokens
    call it: the combine (``_down_add``: the experts' float32 rows into
    their tokens') and the dispatch's backward pass (``_rows_of``: the rows'
    cotangent into the tokens'). It reads no buffer row past the last pair,
    and no product does: what lies there, forward (a real token's row, then
    whatever the products leave) and backward (whatever their transposes
    leave), reaches nothing, with no select over [R, D] on the way. The one
    select left is over [R]: the gates' in ``_experts``, whose transpose
    keeps an undefined <hidden, d hidden> out of the gates' gradient."""
    T, D = tokens.shape
    k, n_held = config.top_k, config.num_held
    R = held_rows_bound(T, config)
    passes = -(-T * k // R)
    with jax.named_scope("moe.dispatch"):
        expert = chosen.reshape(T * k) - config.first_held
        here = (expert >= 0) & (expert < n_held)
        if row_mask is not None:
            here &= jnp.repeat(row_mask, k)
        expert = jnp.where(here, expert, n_held)
        order = jnp.argsort(expert, stable=True)       # pair ids by expert
        ends = jnp.cumsum(counts)
        total = ends[-1]
        # pair id T*k: no pair (its token, T, is no row of ``tokens``)
        order = jnp.pad(order, (0, passes * R - T * k), constant_values=T * k)
        flat_gates = gates.reshape(T * k)

    def one(tokens, params, start, named=False):
        """The part of the result that pairs ``start : start + R`` of the
        order make, [T, D] float32. ``named``: of ``_experts``; an outer
        checkpoint's policy reaches through the one around an overflow pass,
        whose residuals would add up pass by pass."""
        with jax.named_scope("moe.dispatch"):
            ids = jax.lax.dynamic_slice(order, (start,), (R,))
            real = start + jnp.arange(R) < total
            token = jnp.where(real, ids // k, 0)
            sizes = (jnp.clip(ends - start, 0, R)
                     - jnp.clip(ends - counts - start, 0, R))
            # a buffer row past the last pair holds token 0's row: no
            # product reads it, forward or backward
            rows = _rows_of(tokens, token, sizes, T,        # each [R, D]
                            2 if _gated(config) else 1)
            gate = flat_gates[jnp.where(real, ids, 0)]
        # the combine: a row added to its token's and nothing else; a row
        # past the last pair to none
        return _experts(params, rows[0], gate, sizes, config, layer, named,
                        add_at=(token, T), gate_rows=rows[-1])

    if passes == 1:
        out = one(tokens, params, 0, named=True)
    else:
        def overflow(tokens, params):
            # the checkpoint AROUND the cond: what a pass keeps for the
            # backward pass is then the checkpoint's own inputs, which but
            # for ``start`` are the scan's constants and are kept once; a
            # cond's residuals are values made in the loop, and the scan
            # stacked them, ``passes`` copies of the tokens and of the
            # share's three matrices (2.3 GB at 16 of 256 experts held,
            # filled with zeros every layer whichever branch ran)
            @jax.checkpoint
            def a_pass(tokens, params, start):
                return jax.lax.cond(
                    start < total, one,
                    lambda *_: jnp.zeros((T, D), jnp.float32),
                    tokens, params, start)

            def step(acc, start):
                return acc + a_pass(tokens, params, start), None

            return jax.lax.scan(step, jnp.zeros((T, D), jnp.float32),
                                jnp.arange(passes) * R)[0]

        out = jax.lax.cond(total <= R, lambda t, p: one(t, p, 0, named=True),
                           overflow, tokens, params)
    return out.astype(tokens.dtype)


def aux_zero(config: Optional[MoEConfig]):
    """What a stack of layers adds its layers' auxiliary results up from:
    the scalar loss, or for a layer that holds a share of the experts the
    loss and its two counts of rows (``moe_layer_counted``)."""
    if config is None or config.num_held is None:
        return jnp.float32(0.0)
    return {"aux_loss": jnp.float32(0.0), "moe_rows_held": jnp.int32(0),
            "moe_rows_max_expert": jnp.int32(0)}


def aux_loss_of(aux) -> jax.Array:
    """What ``aux_zero`` and the layers added up adds to the cross entropy
    a step minimises: the auxiliary loss and, where a family put one beside
    it, its weighted ``second_loss`` (``decoder.second_loss``)."""
    if not isinstance(aux, dict):
        return aux
    return aux["aux_loss"] + aux.get("second_loss", 0.0)


def counts_apart(aux):
    """A layer's aux -> (the aux a stack adds up, the layer's ``moe_counts``
    or None): the counts a rule moves the bias by are kept a layer apart."""
    if not isinstance(aux, dict) or "moe_counts" not in aux:
        return aux, None
    aux = dict(aux)
    return aux, aux.pop("moe_counts")


def bias_rule_update(counts: jax.Array, rate: float) -> jax.Array:
    """DeepSeek-V3's balancing rule (``noaux_tc``), the move of one step:
    ``counts`` [.., E] the pairs each expert was chosen for in the step's
    batch -> ``rate x sign(mean(counts) - counts)`` [.., E] float32: an
    expert chosen less than the mean is chosen more readily from the next
    step on. The caller adds it to ``expert_bias`` in the optimizer's
    place (the bias moves a choice of indices and has no gradient)."""
    counts = counts.astype(jnp.float32)
    return rate * jnp.sign(counts.mean(axis=-1, keepdims=True) - counts)


def moe_layer_counted(
    params: Dict[str, jax.Array],
    x: jax.Array,
    config: MoEConfig,
    *,
    rng: Optional[jax.Array] = None,
    row_mask: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    logits: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, T, D] -> (out [B, T, D], aux_loss scalar, the number of
    distinct experts that received a row). ``row_mask`` [B, T] bool marks the
    rows that carry a token (padding of a prefill bucket and idle decode
    slots do not): the others get no expert and come back as zeros. With
    ``layer`` (dropless only), ``params`` are the weights of ALL layers,
    stacked, and ``layer`` the index of this one (``_experts`` says why).
    ``logits`` [B * T, experts]: the router's logits where the caller
    computed them (``router_logits``). A layer that holds a share of the
    experts (``num_held``) gives, in place of the scalar loss, the loss (over
    ALL experts) beside the rows it computed and the rows of its fullest
    expert: ``{"aux_loss", "moe_rows_held", "moe_rows_max_expert"}``, and
    where a rule moves its bias (``bias_update_rate``) the pairs each of ALL
    experts was chosen for, ``moe_counts`` [E] int32, which a stack of
    layers keeps a layer apart (``decoder._trunk``)."""
    B, T, D = x.shape
    E, k = config.num_experts, config.top_k
    tokens = x.reshape(B * T, D)
    n_tok = B * T
    mask = None if row_mask is None else row_mask.reshape(n_tok)
    if layer is not None and not config.dropless:
        raise ValueError("stacked weights with a layer index: dropless only")
    probs, gates, chosen = _route(params, tokens, config, rng, layer, logits)

    if config.num_held is not None:
        with jax.named_scope("moe.route"):
            pairs = chosen.reshape(-1)
            if mask is not None:
                pairs = jnp.where(jnp.repeat(mask, k), pairs, E)
            # one count a layer: the share's is a slice of it
            every = _count(pairs, E)
            counts = every[config.first_held:
                           config.first_held + config.num_held]
            aux = _aux_loss(probs, every / jnp.maximum(every.sum(), 1), config)
        out = _grouped_share(
            params, tokens, _normalised(gates, config), chosen, mask, counts,
            config, layer)
        counted = {"aux_loss": aux, "moe_rows_held": counts.sum(),
                   "moe_rows_max_expert": counts.max()}
        if config.bias_update_rate:
            counted["moe_counts"] = every
        return out.reshape(B, T, D), counted, (counts > 0).sum()

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
