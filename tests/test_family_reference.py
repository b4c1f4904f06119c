"""Every family's full forward against its plain reference
(``benchmarks/references``), once over the table of ``tests/families.py``:
sound and one case a fault, bfloat16 activations near float32, the
configurations refused by name, the stack's period and the cache's leaves by
kind, and a layer's experts in four shares. A family's row says what to
compare and within what; which families a case runs on is read off their
layer kinds (``families.shared_case``).

CPU, float32, seeded weights, tiny widths: no device number.
"""
import dataclasses
import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import named
from ray_tpu import models
from ray_tpu.llm import DecodeEngine, LLMConfig
from ray_tpu.models import decoder, family_module
from tests import families
from tests.families import ROWS, TINY, shared_case


@shared_case(names="family, fault", rows=lambda family: [
    (family, fault) for fault in ("sound",) + tuple(
        f.id for f in ROWS[family].faults)])
def test_the_family_matches_the_reference_and_each_fault_does_not(
        family, fault, monkeypatch):
    """The full forward over the row's tokens against the reference in
    float32 is inside the row's limit, and with one fault planted (a config
    field, a weight leaf, a piece patched, a constant of the reference) it
    is over the fault's own, or, for a change that is the same function,
    still under it."""
    row = ROWS[family]
    if fault == "sound":
        gap, want = families.gap_from_the_reference(family)
        assert np.abs(want).max() > row.logits
        assert gap.max() < row.sound
        return
    (planted,) = [f for f in row.faults if f.id == fault]
    gap, _ = families.gap_from_the_reference(
        family, planted, monkeypatch.setattr)
    if planted.over is not None:
        assert gap.max() > planted.over
    if planted.under is not None:
        assert gap.max() < planted.under
    if planted.quiet is not None:
        assert gap[:, :planted.quiet].max() < row.sound


@shared_case()
def test_bfloat16_activations_stay_near_the_float32_reference(family):
    row = ROWS[family]
    cfg = families.model_config(family, dtype="bfloat16")
    params = families._moved(family, cfg)
    tokens = families._tokens(row.tokens, seed=1)
    want = families._reference_logits(
        families.reference(family), params, tokens)
    gap = np.abs(families.forward_logits(family, cfg, params, tokens) - want)
    assert row.bf16
    for reading, (low, high) in row.bf16.items():
        got = {"max": gap.max, "median": lambda: np.median(gap)}[reading]()
        assert (low is None or low < got) and (high is None or got < high), (
            reading, got)


@shared_case(
    names="family, bad, match",
    rows=lambda family: [(family, *bad) for bad in ROWS[family].refused],
    ids=lambda v: "-".join(map(str, v)) if isinstance(v, dict) else None)
def test_a_configuration_it_cannot_run_is_refused_by_name(family, bad, match):
    """By the trainer's way in (``models.config_for``) and, where the engine
    can state it, by the engine's, before anything is built."""
    sizes = {k: v for k, v in TINY[family].items()
             if k not in families.ENGINE_KEYS}
    routed = bool(sizes.get("moe_num_experts"))
    with pytest.raises(ValueError, match=match):
        models.config_for(family, **{
            **({"moe_dropless": True} if routed else {}), **sizes, **bad})
    if "moe_dropless" not in bad:   # the engine serves dropless, always
        with pytest.raises(ValueError, match=match):
            DecodeEngine(LLMConfig(**{**TINY[family], **bad}))


@shared_case()
def test_the_stack_is_the_period_and_the_cache_counts_by_kind(family):
    """A family's ``layers`` are a lead and whole periods of its kinds, in
    order; the cache holds keys and values a full layer, a ring a window
    layer, a matrix and the convolution's rows a state layer, one row a
    position a latent layer (and its indexer's key); and the costs' count
    is the leaves'. A stack of several kinds has no pipelined forward, and
    says so."""
    row, module = ROWS[family], family_module(family)
    cfg = families.model_config(family)
    shapes = jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0)))
    if len(set(decoder.layer_kinds(cfg))) > 1:
        with pytest.raises(ValueError, match="pipeline"):
            jax.eval_shape(lambda p: decoder.forward_pipelined(
                p, jnp.zeros((2, 8), jnp.int32), cfg, None), shapes)
    for changes, want in (({}, None),) + tuple(row.stacks):
        sized = dataclasses.replace(cfg, **changes)
        segments, _ = module.layers(sized, None, cached=True)
        unrolled = [k for s in segments for _ in range(s.repeats)
                    for k in s.kinds]
        assert unrolled == list(decoder.layer_kinds(sized))
        if want is not None:
            assert [(tuple(k.name for k in s.kinds), s.repeats)
                    for s in segments] == want
    sized = dataclasses.replace(cfg, **row.cache_at)
    cache = jax.eval_shape(
        lambda: decoder.init_kv_cache(sized, 3, 128, block=16))
    kinds = decoder.layer_kinds(sized)
    plain = [k for k in kinds if k.state is None and k.latent is None]
    counts = {
        "k": sum(k.window is None for k in plain),
        "k_window": sum(k.window is not None for k in plain),
        "ssm": sum(k.state is not None for k in kinds),
        "latent": sum(k.latent is not None for k in kinds),
        "index": sum(k.index is not None for k in kinds)}
    for name, layers in counts.items():
        assert (cache[name].shape[0] if name in cache else 0) == layers, name
    assert all(leaf.shape[1] == 3 for leaf in cache.values())
    if row.cache is not None:
        assert {k: (v.shape, v.dtype) for k, v in cache.items()} == row.cache
    # the layers there are, where fewer are asked for: the first of them
    two = dataclasses.replace(cfg, num_layers=2)
    assert list(decoder.layer_kinds(two)) == list(decoder.layer_kinds(cfg))[:2]
    if row.costs:
        costs = named.load(os.path.join(
            families.CHECKOUT, "benchmarks", "costs", f"{row.reference}.py"))
    for changes in row.costs:
        sized = dataclasses.replace(cfg, **changes)
        params = jax.eval_shape(
            lambda: module.init_params(sized, jax.random.PRNGKey(0)))
        model = {f.name: getattr(sized, f.name)
                 for f in dataclasses.fields(sized)}
        model.update(row.costs_keys)
        assert costs.param_count(model)["total"] == sum(
            p.size for p in jax.tree.leaves(params))


@shared_case("held", "shared")
def test_the_shares_and_the_shared_expert_once_are_the_whole_layer(
        family, monkeypatch):
    """The four chips' routed parts, each through the family's own ``ffn``
    with its share of the weights, plus the shared expert ONCE, add up to
    what the uncut reference gives for the layer: the router scores all 16
    experts on every chip, and a pair is computed on exactly one."""
    module, reference = family_module(family), families.load_reference(family)
    cfg = families.model_config(family)
    whole = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_held=None, first_held=0))
    params = families._moved(family, whole)   # all 16 experts' weights
    kinds = decoder.layer_kinds(cfg)
    at = next(i for i, k in enumerate(kinds) if k.routed)
    segments, _ = module.layers(cfg, params["blocks"], cached=False)
    layer = next(
        jax.tree.map(lambda a: a[0], stacked)
        for segment in segments
        for kind, stacked in zip(segment.kinds, segment.params)
        if kind.routed)
    stacked = jax.tree.map(lambda a: a[:1], params["blocks"]["experts"])
    experts = jax.tree.map(lambda a: a[0], stacked)
    width, held, top_k = cfg.embed_dim, cfg.moe.num_held, cfg.moe.top_k
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 24, width)),
                    jnp.float32)
    h = reference._rms_norm(x, layer["mlp_norm"]).reshape(-1, width)
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(reference, "FIRST_HELD", 0)
        gates = reference.route(h, experts["router_w"], experts["expert_bias"])
        shared = reference._swiglu(h, layer["shared_gate"],
                                   layer["shared_up"], layer["shared_down"])
        # a reference whose experts lie stacked by layer takes the layer
        own = (stacked, 0) if "layer" in inspect.signature(
            reference._experts).parameters else (experts,)
        uncut = reference._experts(h, gates, *own) + shared
        parts, rows = jnp.zeros_like(uncut), 0
        for first in range(0, cfg.moe.num_experts, held):
            share = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, first_held=first))
            mine = {k: w if k in ("router_w", "expert_bias")
                    else w[first:first + held] for k, w in experts.items()}
            out, aux, _ = module.ffn(
                share, kinds[at].name, layer, x, None, None, (mine, None))
            parts += (out - x).reshape(-1, width) - shared
            rows += int(aux["moe_rows_held"])
    # every (token, expert) pair on exactly one chip
    assert rows == 2 * 24 * top_k
    assert float(jnp.abs(uncut - shared).max()) > 0.05
    # float32 sums in another order: 2e-7 measured
    np.testing.assert_allclose(parts + shared, uncut, atol=2e-6)
