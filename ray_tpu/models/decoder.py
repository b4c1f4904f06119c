"""The decoder every model family shares: layer stack, cached forward,
pipeline and loss, each written once.

A family (``models/gpt2.py``, ``models/llama.py``) is a module of the pieces
that differ, found from the config through ``models.module_for``, each with
one signature across families:

- ``embed(config, params, tokens, pos, cached)`` -> the residual stream
  [B, T, E] (positions added where the family learns them; its dtype in the
  full and in the cached forward is the family's);
- ``layers(config, blocks, cached)`` -> (the stack as ``Segment``s, the
  experts' weights of every routed layer or None): which kinds of layer
  follow one another, and where each one's weights are in ``blocks``
  (``Layer``, ``Segment`` below). ``blocks`` None asks for the kinds alone;
- ``qkv(config, kind, layer, x, pos, heads_major=False)`` -> q, and k and v
  [B, T, KV, D], from the stream (the family's norm, RoPE and q/k norm
  inside; ``kind`` is the ``Layer.name`` the family gave this layer). q is
  [B, T, H, D], or [B, T, KV, G, D] where G query heads share a kv head.
  That is the cached forward's order (its rows go into the cache a
  position at a time, and its call is the one it always was). The full
  forward asks for q [B, H, T, D] and k and v [B, KV, T, D]
  (``heads_major=True``, a constant there): the order the attention kernels
  fold by a reshape, which the projection's product writes itself
  (``ops.attention.heads_in``; a transpose after the rotation is a copy of
  the whole array, the same order asked of the product is not);
- ``attn_out(config, layer, x, attn, heads_major=False)`` -> the stream
  after the output projection and the residual; ``attn`` [B, H, T, D] where
  ``heads_major``, else [B, T, H, D];
- ``at_input(config, kind, layer, x, stacked)`` -> whatever the family's
  ``ffn`` wants of the block's INPUT, the stream before the attention's norm
  (a router that reads it gives its logits), or None: the default below,
  the only one, which a family's ``import *`` brings and its own overrides;
- ``ffn(config, kind, layer, x, rng, row_mask, stacked, from_input)`` ->
  (stream, aux loss, experts that received a row: 0 for a dense
  feed-forward). Only a router asks for the last four: ``rng`` its jitter,
  ``row_mask`` [B, T] the rows that carry a token, ``stacked`` (every routed
  layer's expert weights, this layer's index among them) where they are kept
  out of the layer scan, ``from_input`` what ``at_input`` gave. The aux loss
  is a scalar, or for a layer that holds a share of the experts the loss
  beside its counts of rows (``moe.aux_zero``): the stack adds up either;
- for a family with state layers (``Layer.state``: a recurrence in place of
  attention, so no ``qkv`` / ``attn_out``), three pieces more:
  ``state_in(config, kind, layer, x)`` -> (what enters the layer's
  convolution [B, T, C], the recurrence's per-step gates (a pytree of
  [B, T, H] float32: Mamba-2's dt, the delta rule's log decay and beta),
  whatever ``state_out`` wants kept); between the two the skeleton runs
  ``kv_cache.recur`` (from the cache's state in the cached forward, from
  zeros in the full one) over the layer's ``conv_w`` with the kind's
  ``Layer.recurrence``; ``state_out(config, layer, x, y, kept)`` -> the stream after
  the gate, the output projection and the residual;
  ``state_leaves(config)`` names such a cache's leaves: name -> (shape a
  slot, dtype);
- ``second_loss(config, params, x, targets, mask, aux, mesh)`` and
  ``step_rule(config, params, updates, counted)``: a prediction layer's loss
  behind the trunk, and leaves that a rule moves in the optimizer's place
  (the defaults below: neither);
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

What follows from shapes alone is decided here: the cache is attended with q
as it came (``kv_cache.attend`` takes it grouped or flat) and the output
projection gets [B, T, H, D] from it; the full forward's attention takes k
and v with the kv heads they have.

Layers are stacked into scanned super-layers (``lax.scan`` over depth:
O(1) compile time in depth, and the layout the "stage" mesh axis splits),
with ``jax.checkpoint`` on the block body (remat trades FLOPs for HBM). A
stack of one kind of layer (``gpt2``, ``llama``) is one scan over the layers.
A stack of several kinds is a few ``Segment``s, each one period of kinds
scanned as many times as it repeats (a leading dense layer, then periods of
three window layers and a full one): every kind is compiled once, whatever
the depth. What the skeleton must know of a kind is in ``Layer``: whether
its attention is windowed (its cache is then a ring, ``kv_cache.py``),
whether its feed-forward reads the experts' stack, and whether its mixer is
a state and no attention at all.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_tpu.models import kv_cache, module_for
from ray_tpu.ops.attention import (
    attention, heads_in, heads_out, swapped, with_shared,
)
from ray_tpu.parallel.moe import (
    aux_loss_of,
    aux_zero,
    counts_apart,
    stacked_for,
)



class Index(NamedTuple):
    """A lightning indexer, as far as the skeleton has to know it."""
    heads: int
    dim: int
    kept: int


class Layer(NamedTuple):
    """One kind of layer, as far as the skeleton has to know it."""
    name: Optional[str] = None    # the family's word for it: ``kind`` of
    #                               its ``qkv`` and ``ffn``
    # attention over the token itself and the ``window - 1`` before it (its
    # cache a ring); None: over everything before it
    window: Optional[int] = None
    routed: bool = False          # its ``ffn`` reads the experts' stack
    # its mixer is a recurrence over a state a sequence carries (the
    # family's ``state_in`` / ``state_out`` around ``kv_cache.recur``), not
    # attention over a column a position: the chunk its scan takes a block
    # of tokens in; None: attention
    state: Optional[int] = None
    # which recurrence (an ``ops/ssm.py:Recurrence``: ``ssm.MAMBA2``,
    # ``delta_rule.GATED_DELTA``, ``kda.KDA``); None with ``state``
    recurrence: Optional[Any] = None
    # its attention is over a LATENT row a position that every head shares
    # (``kv_cache.attend_latent``): how many channels the row has (the
    # latent's rank and the shared rotated key); None: keys and values a
    # head. The family's ``qkv`` then gives (q [B, T, H, Dn + Dr], the new
    # rows [B, T, latent], the up-projection [R, H, Dn + Dv])
    latent: Optional[int] = None
    # a latent layer's lightning indexer (``kv_cache.Indexed``): its heads,
    # the channels of its one key a position (a second row of the layer's
    # cache) and how many positions a query's attention keeps; None: every
    # earlier position. The family's ``qkv`` then gives a fourth piece, the
    # ``kv_cache.Indexed`` of the call's tokens
    index: Optional[Index] = None
    # its softmax's scale where that is not the queries' width ^ -0.5
    scale: Optional[float] = None


class Segment(NamedTuple):
    """``repeats`` periods of ``kinds``: ``params[j]`` holds the weights of
    the period's j-th layer in every repeat, leaves ``[repeats, ...]`` (None
    where only the kinds were asked for)."""
    kinds: Tuple[Layer, ...]
    params: tuple
    repeats: int


def single_kind(config, blocks, cached: bool):
    """``layers`` of a family whose layers are all alike: one segment of
    ``blocks`` as they are, leaves ``[num_layers, ...]``. Routed experts
    served dropless leave the layer scan of the cached forward as one stack
    (``forward_cached`` says why); everywhere else a layer's experts are its
    slice of ``blocks["moe"]``."""
    out = cached and config.moe is not None and config.moe.dropless
    experts = None
    if out and blocks is not None:
        blocks = dict(blocks)
        experts = blocks.pop("moe")
    return ([Segment((Layer(routed=out),), (blocks,), config.num_layers)],
            experts)


def periods(kinds: Tuple[Layer, ...]):
    """[(kinds of one period, repeats)] over a stack of ``kinds``: a lead
    that fits no period (one repeat) and the shortest period of what
    follows, chosen so that the kinds to compile, lead + period, are
    fewest."""
    L = len(kinds)
    lead, period = min(
        ((lead, p) for lead in range(L) for p in range(1, L - lead + 1)
         if (L - lead) % p == 0
         and kinds[lead:] == kinds[lead:lead + p] * ((L - lead) // p)),
        key=lambda lp: (sum(lp), lp[0]))
    plan = [(kinds[:lead], 1)] if lead else []
    return plan + [(kinds[lead:lead + period], (L - lead) // period)]


def layer_kinds(config) -> Tuple[Layer, ...]:
    """Every layer's kind, first to last."""
    segments, _ = module_for(config).layers(config, None, cached=True)
    return tuple(k for s in segments for _ in range(s.repeats)
                 for k in s.kinds)


def at_input(config, kind, layer, x, stacked):
    """The piece's default: nothing of a block's input is kept for its
    feed-forward."""
    return None


def second_loss(config, params, x, targets, mask, aux, mesh):
    """The piece's default: no loss but the next token's. A family with a
    prediction layer behind the trunk gives its own: x [B, T, E] the last
    layer's output before the final norm, ``targets`` [B, T] each position's
    next token, ``mask`` the batch's or None, ``aux`` what the trunk's layers
    added up -> ``aux`` with its layers' in it and, weighted, its loss as
    ``second_loss`` (``moe.aux_loss_of`` adds it to what is minimised)."""
    return aux


def step_rule(config, params, updates, counted):
    """The piece's default: every leaf moves by the optimizer's update. A
    family whose leaves a rule moves (a router's bias under a balancing
    rule) gives its own: the optimizer's ``updates`` and what the step
    counted beside its loss -> (the updates with the rule's in their place,
    what is reported of the counts)."""
    return updates, counted


# what a family module hands on under its own name (``gpt2.forward``,
# ``module_for(cfg).loss_fn``): each the one definition here
__all__ = ["forward_features", "forward", "init_kv_cache", "forward_cached",
           "forward_pipelined", "loss_fn", "count_params", "Layer", "Segment",
           "Index",
           "single_kind", "periods", "at_input", "second_loss", "step_rule",
           "heads_in", "heads_out", "swapped"]




def _remat_policy(config):
    """Checkpoint policy for the block body. "full" recomputes everything;
    "dots" (default) keeps matmul outputs + the flash-attention forward's
    named residuals (out + logsumexp, so the backward never re-runs the
    attention kernel) and the routed experts' two up-projections (grouped
    products, which are no ``dot_general``: ``parallel/moe.py:_experts``)
    with their rows' gates [rows] (a gather of scalars)
    and recomputes elementwise ops; "dots_all"
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
            "flash_out", "flash_lse", "moe_fc", "moe_gate", "moe_row_gates"
        ),
    )


def _attention_dispatch(config, q, k, v, mesh: Optional[Mesh],
                        window: Optional[int] = None, shared=None):
    """Adds the mesh-aware ring/ulysses branches on top of the shared
    single-device dispatcher (``ops.attention.attention``). q [B, H, T, D],
    k and v [B, KV, T, D]; ``window``: of a layer whose attention has one;
    ``shared`` [B, T, Dr]: a latent layer's rotated key, which every head
    reads behind its own channels of k."""
    impl = config.attention_impl
    if impl in ("ring", "ulysses"):
        from ray_tpu.parallel import ring_attention

        if window is not None:
            raise ValueError(f"attention_impl {impl!r} has no window")
        if shared is not None:
            k = with_shared(k, shared)
        rep = q.shape[1] // k.shape[1]
        # the sequence's shards are [B, T / n, H, D] there: its own order
        (out,) = swapped({"ring": ring_attention.ring_attention,
                          "ulysses": ring_attention.ulysses_attention}[impl](
            *swapped(q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)),
            mesh=mesh, axis=config.seq_axis, causal=True))
        return out
    return attention(q, k, v, causal=True, impl=impl, mesh=mesh,
                     window=window, shared=shared)


def _window_attention(q, k, v, window: int):
    """Causal attention over the token itself and the ``window - 1`` before
    it, [B, H, T, D] each: a band mask over whole [B, H, T, T] scores in
    plain XLA. What the kernels' window is held to (``tests/``); the layer
    stack goes through ``_attention_dispatch``."""
    i = jnp.arange(q.shape[2])
    band = (i[None, :] <= i[:, None]) & (i[None, :] > i[:, None] - window)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(band, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


def _body(config, mesh: Optional[Mesh], pos, kind: Layer = Layer(),
          own: Optional[int] = None):
    """One decoder block of ``kind`` as a layer scan calls it, remat applied:
    ``(x [B, T, E], layer, rng=None, stacked=None) -> (x, aux)``. layer: one
    slice of the stacked block params, pos: [B, T] absolute. ``rng``
    (optional) feeds MoE router jitter; ``stacked`` is the experts' stack and
    this layer's index in it, where the family keeps them out of the scan.
    ``own``: that index where it is known as the program is traced (a
    segment that is not scanned). The block then cuts the layer's experts
    out of the stack itself, inside the remat, and hands the family
    ``(the layer's own weights, None)``: their gradient is the layer's, not
    a stack of zeros around it (four layers' worth of those were 2.9 GB at
    16 experts of 2560 x 768), and the cut is made again in the backward
    pass instead of being kept."""
    family = module_for(config)

    def block(x, layer, rng=None, stacked=None):
        if own is not None:
            stacked = (jax.tree.map(lambda w: w[own], stacked[0]), None)
        from_input = family.at_input(config, kind.name, layer, x, stacked)
        if kind.state is not None:
            x = _recur(config, kind, layer, x, None)[0]
        else:
            x = _mixer(config, mesh, pos, kind, layer, x)
        x, aux, _ = family.ffn(
            config, kind.name, layer, x, rng=rng, row_mask=None,
            stacked=stacked, from_input=from_input)
        return x, aux

    if config.remat:
        return jax.checkpoint(block, policy=_remat_policy(config))
    return block


def _mixer(config, mesh: Optional[Mesh], pos, kind: Layer, layer, x):
    """The full forward's attention of one layer, between the family's two
    pieces: heads-major from the projections to the kernels and back, so
    that nothing is transposed on the way."""
    family = module_for(config)
    if kind.index is not None:
        # a choice of positions a query: [T, T] scores in XLA, whatever the
        # backend (the served path is the cached forward's)
        q, rows, up, index = family.qkv(
            config, kind.name, layer, x, pos, heads_major=True)
        k, v, shared = kv_cache.latent_kv(
            rows, up.astype(q.dtype), q.shape[-1])
        attn = kv_cache.selected_attention(q, k, v, shared, index, kind.scale)
    elif kind.latent is not None:
        # the full forward attends up-projected keys and values, a head
        # its own, through the dispatcher (the flash kernels on the chip:
        # no [T, T] scores): the keys' own channels and the values a head,
        # the rotated key once for all of them
        q, rows, up = family.qkv(
            config, kind.name, layer, x, pos, heads_major=True)
        k, v, shared = kv_cache.latent_kv(
            rows, up.astype(q.dtype), q.shape[-1])
        attn = _attention_dispatch(config, q, k, v, mesh, shared=shared)
    else:
        q, k, v = family.qkv(
            config, kind.name, layer, x, pos, heads_major=True)
        attn = _attention_dispatch(config, q, k, v, mesh, kind.window)
    return family.attn_out(config, layer, x, attn, heads_major=True)


def _recur(config, kind: Layer, layer, x, carried):
    """A state layer's mixer between the family's two pieces -> (x, cache):
    ``carried`` as ``kv_cache.recur`` takes it (None: from a zero state, and
    the cache that comes back is None)."""
    family = module_for(config)
    entering, gates, kept = family.state_in(config, kind.name, layer, x)
    cache, y = kv_cache.recur(
        carried, entering, gates, layer, kind.recurrence,
        family.state_leaves(config)[kv_cache.STATE[0]][0], kind.state)
    return family.state_out(config, layer, x, y, kept), cache


def _embed(params, tokens, config):
    """The full forward's stream and its positions [B, T], 0..T-1."""
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    return module_for(config).embed(
        config, params, tokens, pos, cached=False), pos


def _nth(repeat, every: int, first: int):
    """The index, in a stack of their own, of a layer that comes ``every``
    times a period, ``first`` of them before it in the first: in period
    ``repeat`` (a scan's counter, or a plain 0)."""
    at = repeat if every == 1 else repeat * every
    return at + first if first else at


def _places(segments, stack_of):
    """Where each layer's share of a stack that only some kinds of layer
    have lies (a cache of its sort, the routed layers' experts):
    ``stack_of(kind)`` names the stack or is None. For every segment, for
    every place in its period, ``_nth``'s (every, first) or None: the layers
    of that stack in a period, and those before this one in the segments
    before and in its own period."""
    before: Dict[Any, int] = {}
    out = []
    for kinds, _, repeats in segments:
        names = [stack_of(kind) for kind in kinds]
        out.append([
            None if name is None else
            (names.count(name), before.get(name, 0) + names[:j].count(name))
            for j, name in enumerate(names)])
        for name in set(names) - {None}:
            before[name] = before.get(name, 0) + repeats * names.count(name)
    return out


def _scanned(period, carry, xs, repeats: int):
    """``period(carry, one repeat of xs) -> (carry, ys)`` over ``repeats``;
    a single one is called as it is, on the only slice there is."""
    if repeats == 1:
        carry, ys = period(carry, jax.tree.map(lambda a: a[0], xs))
        return carry, jax.tree.map(lambda y: y[None], ys)
    return jax.lax.scan(period, carry, xs)


def _trunk(params, tokens, config, mesh, rng) -> tuple:
    """tokens [B, T] int32 -> (the last layer's output [B, T, E], not yet
    normed; aux loss: the layers' summed, in the form their ``ffn`` gives
    it). Where a router keeps its counts over all experts for a rule
    (``moe_counts`` in a layer's aux: ``moe.MoEConfig.bias_update_rate``)
    they are not summed: ``aux["moe_counts"]`` is [routed layers, experts],
    in the order of the experts' stack."""
    x, pos = _embed(params, tokens, config)
    segments, experts = module_for(config).layers(
        config, params["blocks"], cached=False)
    keys = None if rng is None else jax.random.split(rng, config.num_layers)
    aux = aux_zero(config.moe)
    routed_at = _places(
        segments, lambda kind: "experts" if kind.routed and experts is not None
        else None)
    scanned = None
    if experts is not None and any(s.repeats > 1 for s in segments):
        # a scan reads the stack by a traced index: cast once, not a layer
        scanned = stacked_for(experts, config.dtype)
    layers_before = 0
    every_count = []
    for (kinds, layers, repeats), routed in zip(segments, routed_at):
        # one repeat is no scan: every layer's place in the stack is known
        bodies = [
            _body(config, mesh, pos, kind,
                  own=place[1] if repeats == 1 and place else None)
            for kind, place in zip(kinds, routed)]
        stack = experts if repeats == 1 else scanned
        xs = (layers,)
        if keys is not None:
            n = repeats * len(kinds)
            xs += (keys[layers_before:layers_before + n].reshape(
                repeats, len(kinds), *keys.shape[1:]),)
            layers_before += n

        def period(carry, xs, bodies=bodies, routed=routed, stack=stack):
            x, aux, repeat = carry
            layers, *rngs = xs
            counts = []
            for j, body in enumerate(bodies):
                stacked = routed[j] and (stack, _nth(repeat, *routed[j]))
                x, layer_aux = body(
                    x, layers[j], rngs[0][j] if rngs else None, stacked)
                layer_aux, layer_counts = counts_apart(layer_aux)
                if layer_counts is not None:
                    counts.append(layer_counts)
                aux = jax.tree.map(jnp.add, aux, layer_aux)
            return (x, aux, repeat + 1), (
                jnp.stack(counts) if counts else None)

        (x, aux, _), counts = _scanned(period, (x, aux, 0), xs, repeats)
        if counts is not None:
            every_count.append(counts.reshape(-1, counts.shape[-1]))
    if every_count:
        aux = {**aux, "moe_counts": jnp.concatenate(every_count)}
    return x, aux


def forward_features(
    params: Dict[str, Any],
    tokens: jax.Array,
    config,
    mesh: Optional[Mesh] = None,
    rng: Optional[jax.Array] = None,
) -> tuple:
    """tokens [B, T] int32 → (final-trunk features [B, T, E], aux loss: the
    layers' summed, in the form their ``ffn`` gives it).
    The loss path consumes features directly (vocab-chunked cross entropy,
    ``ops/xent.py``) so the [B, T, V] logits tensor never materializes.
    ``rng``: optional key enabling stochastic layers (MoE router jitter),
    one key a layer."""
    x, aux = _trunk(params, tokens, config, mesh, rng)
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


def init_kv_cache(config, batch: int, max_len: int, dtype=None,
                  block: int = 1) -> Dict[str, jax.Array]:
    """Static-shape KV cache for incremental decoding: ``{"k", "v"}``, each
    [L, B, KV, D, S], position minor (``models/kv_cache.py`` says why) — kv
    heads only, an H/KV-fold HBM saving over caching query-expanded heads.
    A model with window layers holds those layers' columns in rings of
    their own beside it (``{"k_window", "v_window"}``), long enough for the
    window and the longest ``block`` of tokens one call will write; one with
    state layers their states (``{"ssm", "conv"}``), whatever ``max_len``.
    (Reference capability analog: the vLLM engine Ray LLM delegates to —
    ``llm/_internal/serve/engines/vllm``; here the cache is a jax pytree so
    the whole decode step stays one XLA program.)"""
    kinds = layer_kinds(config)
    latent = [k.latent for k in kinds if k.latent is not None]
    if len(set(latent)) > 1:
        raise ValueError(f"latent layers of several widths: {set(latent)}")
    indexed = [k.index.dim for k in kinds if k.index is not None]
    if indexed and (len(set(indexed)) > 1 or len(indexed) != len(latent)):
        raise ValueError(
            "latent layers with and without an indexer, or indexers of "
            f"several widths: {indexed} of {len(latent)} layers")
    windows = [k.window for k in kinds
               if k.state is None and k.latent is None]
    lengths = {w for w in windows if w is not None}
    if len(lengths) > 1:
        raise ValueError(f"window layers of several lengths: {lengths}")
    ring = kv_cache.ring_length(lengths.pop(), block, max_len) if lengths else 0
    states = len(kinds) - len(windows) - len(latent)
    return kv_cache.init_kv_cache(
        windows.count(None), batch, config.num_kv_heads, config.head_dim,
        max_len, dtype or config.dtype, len(windows) - windows.count(None),
        ring, states,
        module_for(config).state_leaves(config) if states else None,
        len(latent), latent[0] if latent else 0,
        indexed[0] if indexed else 0,
    )


def forward_cached(
    params: Dict[str, Any],
    tokens: jax.Array,
    cache: Dict[str, jax.Array],
    start: jax.Array,
    config,
    real: Optional[jax.Array] = None,
    rows: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,
) -> tuple:
    """Incremental forward: attend over the KV cache, append new K/V.

    tokens [B, T] — a prompt chunk (prefill, start=0) or one decode step
    (T=1, start=seq_len). start [B] int32: absolute position of tokens[:, 0]
    per sequence. Returns (logits [B, T, V] f32, updated cache). All shapes
    static and every slot at its own offset, so slot-based continuous
    batching is one compiled program. The whole cache rides the layer scan
    as its carry and only the new tokens' columns change: a caller that
    donates the cache gets it back in the same buffer. ``rows`` [R] int32
    names the tokens whose logits the caller will read (a prefill uses its
    last one): the head then runs on those alone, logits [B, R, V].
    ``live`` [B] bool names the slots whose tokens are tokens (None: every
    slot): a decode step's kernel leaves the cache of any other slot as it
    is (``kv_cache.Step``), and such a slot's logits mean nothing.

    Two results for a caller that gives no ``real``, three for one that
    does, the one place where the arity follows an argument (a third result,
    even of zeros, would change the compiled programs of every dense model
    served). With routed experts every token reaches its top-k experts (aux
    loss is a training-only concern and is discarded here), and ``real`` [B]
    says how many of a row's T tokens are tokens: 0 for an idle decode slot,
    the prompt's length in a prefill bucket. The rest is routed to no
    expert. Given ``real``, a third result counts the distinct experts that
    received a row in each layer, [L] int32; of a model whose layers hold a
    SHARE of the experts (``MoEConfig.num_held``), beside it the rows the
    held experts computed: [L, 2] int32."""
    family = module_for(config)
    B, T = tokens.shape
    pos = start[:, None] + jnp.arange(T)[None, :]          # [B, T] absolute
    x = family.embed(config, params, tokens, pos, cached=True)

    segments, experts = family.layers(config, params["blocks"], cached=True)
    windows = {k.window for s in segments for k in s.kinds} - {None}
    at = kv_cache.step(start, T, cache, *windows, live=live, real=real)
    mask = None if real is None else jnp.arange(T)[None, :] < real[:, None]
    # The experts' weights stay out of the scan: it would hand each layer
    # its slice, and a slice that feeds a kernel is a copy (``moe._experts``)
    if experts is not None:
        experts = stacked_for(experts, config.dtype)

    def block(kind, x, layer, index, stacked, cache):
        from_input = family.at_input(config, kind.name, layer, x, stacked)
        if kind.state is not None:
            x, cache = _recur(config, kind, layer, x, (cache, index, at))
        elif kind.latent is not None:
            # with an indexer a fourth piece, its ``kv_cache.Indexed``
            q, rows, up, *chooser = family.qkv(
                config, kind.name, layer, x, pos)
            cache, attn = kv_cache.attend_latent(
                cache, index, q, rows, up, at,
                kind.scale or q.shape[-1] ** -0.5, *chooser)
            x = family.attn_out(config, layer, x, attn)
        else:
            q, k_new, v_new = family.qkv(config, kind.name, layer, x, pos)
            # the cache is attended as the family groups its heads (GQA:
            # the query heads of a kv head together); the projection takes
            # them flat
            cache, attn = kv_cache.attend(
                cache, index, q, k_new, v_new, at, kind.window is not None)
            x = family.attn_out(
                config, layer, x, attn.reshape(B, T, -1, attn.shape[-1]))
        x, aux, touched = family.ffn(
            config, kind.name, layer, x, rng=None, row_mask=mask,
            stacked=stacked, from_input=from_input)
        if share:   # beside it, the rows the held experts computed
            touched = jnp.stack(
                [touched, aux["moe_rows_held"]]).astype(jnp.int32)
        return x, cache, touched

    share = config.moe is not None and config.moe.num_held is not None
    cache_at = _places(
        segments, lambda kind: "state" if kind.state is not None
        else "latent" if kind.latent is not None
        else "ring" if kind.window is not None else "full")
    routed_at = _places(
        segments, lambda kind: "experts" if kind.routed and experts is not None
        else None)
    every_touched = []
    for (kinds, layers, repeats), in_cache, routed in zip(
            segments, cache_at, routed_at):

        def period(carry, layers, kinds=kinds, in_cache=in_cache,
                   routed=routed):
            x, repeat, cache = carry
            touched = []
            for j, kind in enumerate(kinds):
                x, cache, n = block(
                    kind, x, layers[j], _nth(repeat, *in_cache[j]),
                    routed[j] and (experts, _nth(repeat, *routed[j])), cache)
                touched.append(n)
            if real is None:
                return (x, repeat + 1, cache), None
            return (x, repeat + 1, cache), (
                touched[0] if len(kinds) == 1 else jnp.stack(touched))

        (x, _, cache), touched = _scanned(
            period, (x, jnp.int32(0) if repeats > 1 else 0, cache), layers,
            repeats)
        every_touched.append(touched)
    if rows is not None:
        x = x[:, rows]
    logits = family.head(config, params, family.final_norm(config, params, x))
    if real is None:
        return logits, cache
    width = (2,) if share else ()
    touched = (every_touched[0] if len(every_touched) == 1
               and every_touched[0].ndim == 1 + share else jnp.concatenate(
                   [t.reshape(-1, *width) for t in every_touched]))
    return logits, cache, touched


def loss_fn(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    config,
    mesh: Optional[Mesh] = None,
    pipeline_microbatches: Optional[int] = None,
    rng: Optional[jax.Array] = None,
    parts: bool = False,
) -> jax.Array:
    """Next-token cross entropy plus the layers' auxiliary loss. batch:
    {"tokens": [B, T+1]} or {"inputs": [B,T], "targets": [B,T]}. ``rng``
    feeds MoE router jitter (unpipelined path only). ``parts``: the cross
    entropy and what the layers added up beside it (``forward_features``),
    apart, for a caller that reports them apart (``moe.aux_loss_of`` is what
    the second adds to the first). A family's ``second_loss`` (a prediction
    layer behind the trunk) gets the trunk's output before the final norm
    and adds its own to the aux."""
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

    family = module_for(config)
    x, aux = _trunk(params, inputs, config, mesh, rng)
    xent = chunked_softmax_xent(
        family.final_norm(config, params, x), family.head_weight(params),
        targets, batch.get("mask"))
    aux = family.second_loss(
        config, params, x, targets, batch.get("mask"), aux, mesh)
    return (xent, aux) if parts else xent + aux_loss_of(aux)


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
    segments, experts = family.layers(config, params["blocks"], cached=False)
    if len(segments) > 1 or len(segments[0].kinds) > 1 or experts is not None:
        raise ValueError(
            "the pipeline splits a stack of one kind of layer into stages: "
            "one of several kinds has no pipelined forward")

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
