"""The weights a server holds and the two orders of a family's heads, over
every family of ``models.FAMILIES`` in the forms its tiny preset has
(``families.variants``: dense, routed, as the code finds them). A file of
its own beside ``test_family_cached.py``: ``--dist loadfile`` balances by
file. CPU, seeded weights, tiny widths: no device number.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import FAMILIES, decoder, family_module
from tests import families

# ------------------------------------------------- the weights a server holds
# ``serving_params``: what a family's cached forward rounds on every use,
# rounded once. The same values by the same operation, so not a bit moves.

VARIANTS = [(family, form) for family in FAMILIES
            for form, cfg in families.variants(family).items()
            if cfg is not None]


@functools.lru_cache(maxsize=None)
def _given(family, form, weights):
    """The form's initial weights, one program; ``perturbed``: every leaf of
    ones or zeros (norm gains, biases) and the router moved, on the host: at
    init ``bf16(1.0) == 1.0`` hides a gain that was rounded."""
    if weights == "init":
        module, cfg = family_module(family), families.variants(family)[form]
        return jax.jit(lambda key: module.init_params(cfg, key))(
            jax.random.PRNGKey(0))
    given, rng = _given(family, form, "init"), np.random.default_rng(1)

    def moved(path, a):
        host = np.asarray(a)
        constant = bool((host == host.reshape(-1)[0]).all())  # ones or zeros
        if not constant and path[-1].key != "router_w":
            return a
        return jnp.asarray(
            host + 0.37 * rng.normal(size=host.shape).astype(host.dtype))

    return jax.tree_util.tree_map_with_path(moved, given)


@functools.lru_cache(maxsize=None)
def _cached_step(family, form):
    """One jitted cached forward a configuration: ``init`` and ``perturbed``
    weights of one shape go through one compiled program."""
    cfg = families.variants(family)[form]
    return jax.jit(lambda p, t, c, s: decoder.forward_cached(p, t, c, s, cfg))


def _prefill_and_three_steps(family, form, params):
    """Every logit and the cache of a prefill of two prompts and three
    greedy decode steps of ``forward_cached``."""
    cfg, step = families.variants(family)[form], _cached_step(family, form)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(2, cfg.vocab_size, (2, 16)),
        jnp.int32)
    start = jnp.zeros((2,), jnp.int32)
    logits, cache = step(
        params, tokens, decoder.init_kv_cache(cfg, 2, 64, block=16), start)
    out = [logits]
    for i in range(3):
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        logits, cache = step(params, nxt, cache, start + 16 + i)
        out.append(logits)
    return [np.asarray(a) for a in out + list(cache.values())]


@pytest.mark.parametrize("weights", ["init", "perturbed"])
@pytest.mark.parametrize("family, form", VARIANTS)
def test_serving_params_move_no_bit_of_the_cached_forward(
        family, form, weights):
    module = family_module(family)
    cfg = families.variants(family)[form]
    assert cfg.param_dtype == jnp.float32 and cfg.dtype == jnp.bfloat16
    given = _given(family, form, weights)
    if weights == "perturbed":
        assert not any(((np.asarray(a) == 1) | (np.asarray(a) == 0)).any()
                       for a in jax.tree.leaves(given))
    held = module.serving_params(cfg, given)
    rounded = [h is not g for g, h in zip(
        jax.tree.leaves(given), jax.tree.leaves(held))]
    assert any(rounded) and not all(rounded)
    assert {a.dtype for a in jax.tree.leaves(held)} == {
        jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)}
    want = _prefill_and_three_steps(family, form, given)
    for a, b in zip(want, _prefill_and_three_steps(family, form, held)):
        np.testing.assert_array_equal(a, b)
    if weights == "perturbed":
        # and the comparison sees a leaf rounded that the forward reads as
        # it is (a norm's gain, llama's ``wte``, the router)
        everything = jax.tree.map(lambda a: a.astype(cfg.dtype), given)
        assert any((a != b).any() for a, b in zip(
            want, _prefill_and_three_steps(family, form, everything)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("family, form", VARIANTS)
def test_serving_params_are_the_arrays_given_where_none_is_wider(
        family, form, dtype):
    """``param_dtype == dtype``, and bf16 weights under float32
    activations: nothing to round, so no operation runs and no byte is
    copied (7.1 GB of bf16 experts stay where they lie)."""
    module = family_module(family)
    base = families.variants(family)[form]
    for cfg in (dataclasses.replace(base, dtype=dtype, param_dtype=dtype),
                dataclasses.replace(base, dtype=jnp.float32,
                                    param_dtype=jnp.bfloat16)):
        # the shapes alone: a leaf handed back is the leaf given, traced too
        given = jax.eval_shape(
            lambda: module.init_params(cfg, jax.random.PRNGKey(0)))
        held = module.serving_params(cfg, given)
        assert jax.tree.structure(held) == jax.tree.structure(given)
        for g, h in zip(jax.tree.leaves(given), jax.tree.leaves(held)):
            assert h is g


# --------------------------------------- the two orders of a family's heads
# The cached forward calls ``qkv`` / ``attn_out`` as it always did, [B, T, H,
# D]; the full forward asks for [B, H, T, D] (``heads_major=True``), which a
# family's products write themselves. One projection, two orders: the same
# numbers.


@pytest.mark.parametrize("family", list(FAMILIES))
def test_heads_major_pieces_are_the_cached_ones_transposed(family):
    module = family_module(family)
    cfg = dataclasses.replace(families.preset(family), dtype=jnp.float32)
    # one program: op by op every leaf's RNG call compiles on its own
    params = jax.jit(lambda key: module.init_params(cfg, key))(
        jax.random.PRNGKey(0))
    segments, _ = module.layers(cfg, params["blocks"], cached=False)
    B, T = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.embed_dim))
    pos = jnp.arange(T)[None] + jnp.asarray([[3], [40]])
    seen = 0
    for segment in segments:
        for kind, stacked in zip(segment.kinds, segment.params):
            if kind.state is not None:
                continue
            seen += 1
            layer = jax.tree.map(lambda a: a[0], stacked)
            got = module.qkv(cfg, kind.name, layer, x, pos, heads_major=True)
            want = list(module.qkv(cfg, kind.name, layer, x, pos))
            # grouped query heads [B, T, KV, G, D] are flat, kv-major, there
            want[0] = want[0].reshape(B, T, -1, want[0].shape[-1])
            if kind.index is not None:
                # an indexer's pieces are the same in either order
                for a, b in zip(jax.tree.leaves(got[3]),
                                jax.tree.leaves(want[3])):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               atol=2e-6, rtol=2e-6)
                got, want = got[:3], want[:3]
            if kind.latent is not None:   # the rows and the up-projection
                want[1:] = [a.swapaxes(1, 2) for a in want[1:]]    # as given
            for a, b in zip(got, want):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b.swapaxes(1, 2)),
                    atol=2e-6, rtol=2e-6)
            attn = jax.random.normal(jax.random.PRNGKey(2), want[0].shape[:3]
                                     + got[-1].shape[-1:])
            if kind.latent is not None:
                attn = attn[..., :cfg.v_head_dim]
            np.testing.assert_allclose(
                np.asarray(module.attn_out(cfg, layer, x, attn.swapaxes(1, 2),
                                           heads_major=True)),
                np.asarray(module.attn_out(cfg, layer, x, attn)),
                atol=2e-6, rtol=2e-6)
    assert seen
