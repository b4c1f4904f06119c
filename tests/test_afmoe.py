"""Arcee's ``afmoe`` (Trinity-Mini): what is peculiar to it. The cases every
family shares (the reference and each fault, bfloat16, the plan of a lead
and whole periods, padded chunks, idle and reused slots, two slots,
speculation verified in a ring with room for the draft) run over its row of
``tests/families.py``; here, a full layer that knows no position, the
router's sigmoid scores, bias and scale, the two kinds of KV cache through
``DecodeEngine`` across the ring's wrap, and the decode kernel with a window
at G = 8.

CPU, float32 where gates and logits are compared, seeded weights, tiny
widths; each tolerance is written where it is used. No device number.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.models import afmoe, decoder, kv_cache
from ray_tpu.ops import decode_attention as kernel
from ray_tpu.ops.block_attention import block_attention
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import MoEConfig, init_moe_params
from tests import families
from tests.families import _tokens

FAMILY = "afmoe"
S, F = afmoe.SLIDING, afmoe.FULL


def test_the_rows_router_is_dropless_sigmoid_with_a_bias_and_a_scale():
    cfg = families.model_config(FAMILY)
    assert cfg.moe.dropless and cfg.moe.score_func == "sigmoid"
    assert cfg.moe.expert_bias and cfg.moe.route_scale == 2.826


def test_the_published_stack_is_two_dense_of_32_and_the_cells_cut_four_rings():
    """At the published sizes (the segments' names: the row's ``stacks``):
    30 layers of 32 routed, three windows of 2,048 and a full layer a period;
    the cell's cut holds one full layer's 8,192 columns and four rings."""
    published = afmoe.Config(
        num_layers=32, num_dense_layers=2, sliding_window=2048,
        layer_types=(S, S, S, F) * 8, moe=MoEConfig(num_experts=128, top_k=8))
    kinds = decoder.layer_kinds(published)
    assert len(kinds) == 32 and sum(k.routed for k in kinds) == 30
    assert [k.window for k in kinds[:4]] == [2048, 2048, 2048, None]
    cut = dataclasses.replace(published, num_layers=5, num_dense_layers=1,
                              layer_types=(S, S, S, S, F))
    (kinds, _, repeats), = afmoe.layers(cut, None, cached=True)[0]
    assert len(kinds) == 5 and repeats == 1
    cache = jax.eval_shape(
        lambda: decoder.init_kv_cache(cut, 32, 8192, block=2048))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, 32, 32, 64, 8192), "v": (1, 32, 32, 64, 8192),
        "k_window": (4, 32, 32, 64, 4096), "v_window": (4, 32, 32, 64, 4096)}


@pytest.mark.parametrize("kinds, moves", [((F,), False), ((S,), True)],
                         ids=["full", "sliding"])
def test_a_full_layer_knows_no_position_and_a_sliding_one_does(kinds, moves):
    """One layer: with no position signal the last token's logits are those
    of the SET of tokens before it, whatever their order; RoPE tells the
    orders apart."""
    cfg = families.model_config(
        FAMILY, num_layers=1, num_dense_layers=1, layer_types=kinds,
        sliding_window=64)
    params = families._moved(FAMILY, cfg)
    tokens = _tokens((1, 12), seed=3)
    shuffled = np.concatenate(
        [tokens[:, :-1][:, np.random.default_rng(1).permutation(11)],
         tokens[:, -1:]], axis=1)
    a, b = (families.forward_logits(FAMILY, cfg, params, t)[0, -1]
            for t in (tokens, shuffled))
    if moves:
        assert np.abs(a - b).max() > 1e-2
    else:
        # the same sums in another order
        assert np.abs(a - b).max() < 1e-5 and np.abs(a).max() > 0.1


# ------------------------------------------------------------- the router


def _sigmoid_layer(bias_std, experts=16, top_k=4):
    cfg = MoEConfig(num_experts=experts, top_k=top_k, activation="swiglu",
                    score_func="sigmoid", expert_bias=True,
                    expert_bias_init_std=bias_std, route_scale=2.826,
                    router_init_std=0.3, dropless=True)
    params = init_moe_params(jax.random.PRNGKey(0), 16, 24, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 16))
    return cfg, params, x


def test_the_bias_moves_the_choice_and_not_the_gates():
    cfg, params, x = _sigmoid_layer(0.05)
    scores, gates, chosen = moe._route(params, x, cfg, None, None)
    want_scores = jax.nn.sigmoid(x @ params["router_w"])
    np.testing.assert_allclose(scores, want_scores, atol=1e-6)
    # chosen under the bias ...
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(jax.lax.top_k(
        want_scores + params["expert_bias"], 4)[1], -1))
    # ... which changes some tokens' choice and leaves most experts chosen
    unbiased = np.sort(jax.lax.top_k(want_scores, 4)[1], -1)
    changed = (np.sort(chosen, -1) != unbiased).any(-1)
    assert 0 < changed.sum() < len(changed)
    # the gates are the scores as they are, without it
    np.testing.assert_allclose(
        gates, jnp.take_along_axis(want_scores, chosen, -1), atol=1e-6)
    # renormalised and scaled they sum to route_scale
    np.testing.assert_allclose(
        moe._normalised(gates, cfg).sum(-1), 2.826, rtol=1e-5)
    # a bias large enough takes an expert to every token, at its own score
    params["expert_bias"] = params["expert_bias"].at[5].set(10.0)
    _, gates, chosen = moe._route(params, x, cfg, None, None)
    assert (chosen == 5).any(-1).all()
    np.testing.assert_allclose(
        gates[chosen == 5], want_scores[:, 5], atol=1e-6)


@pytest.mark.parametrize("dropless", [True, False],
                         ids=["grouped", "capacity"])
def test_sigmoid_routed_layer_matches_a_per_token_loop(dropless):
    cfg, params, x = _sigmoid_layer(0.05)
    cfg = dataclasses.replace(cfg, dropless=dropless, capacity_factor=16.0)
    params = {**jax.tree.map(lambda a: a * 20.0, params),
              "router_w": params["router_w"],
              "expert_bias": params["expert_bias"]}
    out, _, touched = moe.moe_layer_counted(params, x[None], cfg)
    scores = jax.nn.sigmoid(x @ params["router_w"])
    _, chosen = jax.lax.top_k(scores + params["expert_bias"], 4)
    want = []
    for t in range(x.shape[0]):
        g = scores[t][chosen[t]]
        g = 2.826 * g / g.sum()
        want.append(sum(
            gi * ((jax.nn.silu(x[t] @ params["expert_gate"][e])
                   * (x[t] @ params["expert_fc"][e]))
                  @ params["expert_out"][e])
            for gi, e in zip(g, np.asarray(chosen[t]))))
    # float32 both, sums in another order, outputs of 0.1 to 5
    assert float(jnp.abs(jnp.stack(want)).max()) > 0.1
    np.testing.assert_allclose(out[0], jnp.stack(want), atol=2e-5)
    assert int(touched) == len(set(np.asarray(chosen).ravel()))


# ------------------------------------- two kinds of cache, through the engine


def _full_logprobs(engine, sequence, length):
    """One program at one ``length`` (causal and dropless: the padding
    reaches nothing), where a forward a length compiled every operation."""
    padded = np.zeros((1, length), np.int32)
    padded[0, :len(sequence)] = sequence
    logits = families.forward_logits(
        FAMILY, engine.model_config, engine.params, padded)[0]
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_chunked_prefill_and_cached_decode_across_the_rings_wrap(
        impl, monkeypatch):
    """A prompt of 2.5 windows (20 tokens, window 8) in chunks of 8, 8 and 4
    into a ring of 16, then 30 decoded tokens (the ring wraps twice more),
    beside a short prompt in another slot: every chosen token's
    log-probability is the full forward's over prompt + answer. With
    ``pallas_interpret`` the engine's caches are whole tiles long (window
    128, ring 256, 384 positions; a prompt of 200 and 70 decoded tokens, past
    the ring's end) and every decode step runs the kernel, at G = 4, in both
    kinds of cache, and every prefill chunk (128, 128 and 64 tokens) attends
    through the ``block_attention`` kernel, the second one from an unaligned
    ring position on."""
    sizes = {} if impl == "xla" else dict(
        sliding_window=128, max_seq_len=384, prefill_buckets=(64, 128))
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
    monkeypatch.setattr(kernel, "BLOCK_BYTES", 2 * 16 * 128 * 4)
    blocks = []
    monkeypatch.setattr(
        kv_cache, "block_attention",
        lambda q, *a, **kw: blocks.append((q.shape[1], kw["window"]))
        or block_attention(q, *a, **kw))
    engine = families._engine(FAMILY, **sizes)
    window = engine._window
    n_long, n_new = (int(2.5 * window), 30) if impl == "xla" else (200, 70)
    assert {k: v.shape[0::4] for k, v in engine._cache.items()} == {
        "k": (1, engine.config.max_seq_len), "v": (
            1, engine.config.max_seq_len),
        "k_window": (4, 2 * window), "v_window": (4, 2 * window)}
    prompts = [[int(t) for t in _tokens((n,), seed=n)] for n in (n_long, 5)]
    params = SamplingParams(max_new_tokens=n_new, logprobs=1)
    futures = [engine.submit(p, params) for p in prompts]
    for prompt, future in zip(prompts, futures):
        out = future.result(timeout=600)
        assert len(out) == n_new
        want = _full_logprobs(engine, prompt + list(out), n_long + n_new)
        got = np.array([lp["logprob"] for lp in out.logprobs])
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + n_new)
        # float32 all through: the order of the sums (2e-6 measured)
        assert np.abs(got - want[at, list(out)]).max() < 5e-5
        assert [int(np.argmax(want[i])) for i in at] == list(out)
    engine.shutdown()
    # one trace a bucket: four window layers and a full one
    assert sorted(blocks, key=str) == ([] if impl == "xla" else sorted(
        [(T, w) for T in (64, 128) for w in (128,) * 4 + (None,)], key=str))
    admits = {a.args["prompt_tokens"]: a.args
              for a in engine._span.named("engine.admit")}
    assert admits[n_long]["chunks"] == (3 if impl == "xla" else 2)
    assert admits[5]["chunks"] == 1
    assert admits[n_long]["moe_rows"] == n_long * 4 * 4  # k x routed layers
    # what the ticks needed of each kind of cache: the spans' arguments sum
    # to the engine's counters, and the window spares the long slot's reads
    ticks, stats = engine._span.named("engine.tick"), engine.stats
    for name in ("cache_positions", "cache_positions_full",
                 "cache_positions_window"):
        assert stats[name] == sum(t.args[name] for t in ticks) > 0, name
    assert stats["cache_positions_full"] == stats["cache_positions"]
    assert stats["cache_positions_window"] < stats["cache_positions_full"]
    assert {(t.args["layers_full"], t.args["layers_window"])
            for t in ticks} == {(1, 4)}
    both = [t.args for t in ticks if t.args["active"] == 2]
    assert both and all(
        t["cache_positions_window"] <= window + 5 + n_new for t in both)
    assert stats["moe_rows"] == (n_long + 5 + stats["slot_ticks"]) * 4 * 4


def test_an_admission_counts_the_key_positions_its_chunks_see():
    """A prompt of 19 tokens is three programs: 8 tokens at 0, 8 at 8, 3
    padded to 4 at 16. In the full layer (64 positions) a chunk's tokens see
    the positions up to its last one's: 8 + 16 + 20. In each of the four
    rings (16 positions, window 8) the 7 before its first token, where there
    are that many, and its own: 8 + 15 + 11. The caches hold 64 + 4 x 16, once
    a chunk. A prompt of one chunk beside it; ``stats`` sums both."""
    engine = families._engine(FAMILY)
    for n in (19, 5):
        engine.generate([int(t) for t in _tokens((n,), seed=n)],
                        SamplingParams(max_new_tokens=2))
    engine.shutdown()
    admits = {a.args["prompt_tokens"]: a.args
              for a in engine._span.named("engine.admit")}
    assert admits[19]["chunks"] == 3
    assert admits[19]["prefill_key_positions"] == 44 + 4 * 34
    assert admits[19]["prefill_cache_positions"] == 3 * (64 + 4 * 16)
    assert admits[5]["prefill_key_positions"] == 8 + 4 * 8
    assert admits[5]["prefill_cache_positions"] == 64 + 4 * 16
    for name in ("prefill_key_positions", "prefill_cache_positions"):
        assert engine.stats[name] == admits[19][name] + admits[5][name]


# ------------------------------------------ the decode kernel with a window


@pytest.mark.parametrize("window, ring", [(128, 256), (256, 256), (100, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
def test_decode_kernel_with_a_window_equals_the_xla_path(
        monkeypatch, window, ring, dtype):
    """G = 8, slots at different lengths in one call: idle, inside the first
    window, at a tile's edge, at the ring's last place, just wrapped, and
    far beyond. The same attention within the dtype's rounding, the ring
    EQUAL bit for bit, the other layer and the full layers' cache
    untouched."""
    monkeypatch.setattr(
        kernel, "BLOCK_BYTES", 2 * 64 * 128 * jnp.dtype(dtype).itemsize)
    L, KV, G, D = 2, 2, 8, 64
    lens = np.array([0, 70, 127, 128, ring - 1, ring, ring + 3, 3 * ring + 77],
                    np.int32)
    B = len(lens)
    assert kernel._blocks(KV, D, ring, jnp.dtype(dtype).itemsize)[1] == 128
    ks = jax.random.split(jax.random.PRNGKey(window + ring), 5)
    cache = {
        "k": jnp.zeros((1, B, KV, D, 128), dtype),
        "v": jnp.zeros((1, B, KV, D, 128), dtype),
        "k_window": jax.random.normal(ks[0], (L, B, KV, D, ring), dtype),
        "v_window": jax.random.normal(ks[1], (L, B, KV, D, ring), dtype),
    }
    q = jax.random.normal(ks[2], (B, 1, KV, G, D), dtype)
    k_new = jax.random.normal(ks[3], (B, 1, KV, D), dtype)
    v_new = jax.random.normal(ks[4], (B, 1, KV, D), dtype)

    def attend(impl):
        monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
        return jax.jit(lambda cache: kv_cache.attend(
            cache, jnp.int32(1), q, k_new, v_new,
            kv_cache.step(jnp.asarray(lens), 1, cache, window),
            windowed=True))(cache)

    got_cache, got = attend("pallas_interpret")
    want_cache, want = attend("xla")
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    for name in cache:
        assert (np.asarray(got_cache[name]) == np.asarray(want_cache[name])
                ).all(), name
    for name in ("k_window", "v_window"):
        changed = (np.asarray(got_cache[name], np.float32)
                   != np.asarray(cache[name], np.float32)).any(axis=(2, 3))
        assert not changed[0].any()
        for b, n in enumerate(lens):
            assert list(np.flatnonzero(changed[1, b])) == [n % ring]
    # and the window is what was attended: a key just outside it changes
    # nothing, one just inside it does
    b = 7
    n = int(lens[b])
    for back, seen in ((window, False), (window - 1, True)):
        moved = {**cache, "k_window": cache["k_window"].at[
            1, b, :, :, (n - back) % ring].add(3.0)}
        monkeypatch.setattr(kv_cache, "_decode_impl",
                            lambda: "pallas_interpret")
        _, other = jax.jit(lambda cache: kv_cache.attend(
            cache, jnp.int32(1), q, k_new, v_new,
            kv_cache.step(jnp.asarray(lens), 1, cache, window),
            windowed=True))(moved)
        assert bool((np.asarray(other[b]) != np.asarray(got[b])).any()) == seen
