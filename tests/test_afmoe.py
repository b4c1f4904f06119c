"""Arcee's ``afmoe`` (Trinity-Mini) through the program: the family's pieces
against the benchmark's plain reference (``benchmarks/references/afmoe.py``),
the router's sigmoid scores, bias and scale, the two kinds of KV cache
through ``DecodeEngine`` across the ring's wrap, and the decode kernel with a
window at G = 8.

CPU, float32 where gates and logits are compared, seeded weights, tiny
widths; each tolerance is written where it is used, with its reason. Nothing
timed here is a device number.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams
from ray_tpu.models import afmoe, decoder, kv_cache
from ray_tpu.ops import decode_attention as kernel
from ray_tpu.ops.block_attention import block_attention
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import MoEConfig, init_moe_params

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S, F = afmoe.SLIDING, afmoe.FULL

# a dense sliding lead, then one period of three sliding layers and a full
# one, all routed: the benchmark's cut at toy widths, window 8
TINY = dict(
    model_family="afmoe", vocab_size=300, max_seq_len=64, num_layers=5,
    num_heads=8, num_kv_heads=2, embed_dim=64, head_dim=16, mlp_dim=96,
    moe_mlp_dim=32, rope_theta=10000, rms_eps=1e-5, num_dense_layers=1,
    num_shared_experts=1, sliding_window=8, layer_types=(S, S, S, S, F),
    mup_enabled=True, moe_num_experts=16, moe_top_k=4,
    moe_norm_topk_prob=True, moe_score_func="sigmoid", moe_route_scale=2.826,
    moe_router_init_std=0.3, moe_expert_bias_init_std=0.05, dtype="float32",
    max_batch_slots=3, prefill_buckets=(4, 8),
)


@pytest.fixture
def reference(monkeypatch):
    from benchmarks.lib import named

    module = named.load(os.path.join(
        CHECKOUT, "benchmarks", "references", "afmoe.py"))
    # what the weights do not carry, at the toy's values
    monkeypatch.setattr(module, "SLIDING_WINDOW", TINY["sliding_window"])
    monkeypatch.setattr(module, "TOP_K", TINY["moe_top_k"])
    return module


def _tiny_params(cfg, seed=0):
    """The family's own init with what would hide a fault moved: norm gains
    of all ones (a norm on the wrong vector), matrices of 0.02 (attention
    nearly flat, experts of 1e-4)."""
    params = afmoe.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))

    def moved(path, a):
        name = path[-1].key
        if name.endswith("norm") or name == "norm_f":
            return a * jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)
        if name in ("router_w", "expert_bias", "wte", "lm_head"):
            return a
        return a * 6.0

    return jax.tree_util.tree_map_with_path(moved, params)


def _reference_logits(reference, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits(params, jnp.asarray(tokens)))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(2, 300, shape).astype(np.int32)


# ------------------------------------------- the family against the reference


def test_the_family_matches_the_reference_and_each_fault_does_not(
        reference, monkeypatch):
    cfg = LLMConfig(**TINY).model_config()
    assert cfg.moe.dropless and cfg.moe.score_func == "sigmoid"
    assert cfg.moe.expert_bias and cfg.moe.route_scale == 2.826
    params = _tiny_params(cfg)
    tokens = _tokens((2, 24))          # three windows long
    got = np.asarray(afmoe.forward(params, jnp.asarray(tokens), cfg)[0])
    want = _reference_logits(reference, params, tokens)
    # float32 against float32: the order of the sums, 1e-6 measured on
    # logits of 0.5 to 3
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 2e-5

    def off(**changes):
        other = dataclasses.replace(cfg, **changes)
        return np.abs(np.asarray(afmoe.forward(
            params, jnp.asarray(tokens), other)[0]) - want).max()

    # the faintest faults read far above that: the window ignored, RoPE on
    # the full layer too (every layer sliding, the window too long to cut),
    # no RoPE anywhere, the embedding's multiplier, the bias in the gates'
    # place (none at all), the scale, the shared expert left out
    assert off(sliding_window=64) > 1e-2
    assert off(layer_types=(S,) * 5, sliding_window=64) > 1e-2
    assert off(layer_types=(F,) * 5) > 1e-2
    assert off(mup_enabled=False) > 1e-2
    for change in (dict(expert_bias=False), dict(route_scale=1.0),
                   dict(norm_topk_prob=False), dict(score_func="softmax")):
        assert off(moe=dataclasses.replace(cfg.moe, **change)) > 1e-3, change
    # the shared expert is counted once: the reference adds it once, and a
    # program that added it twice (its down projection doubled) is far off
    twice = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 2 if path[-1].key == "shared_down" else a, params)
    assert np.abs(np.asarray(afmoe.forward(
        twice, jnp.asarray(tokens), cfg)[0]) - want).max() > 1e-2
    # and the reference sees its own window
    monkeypatch.setattr(reference, "SLIDING_WINDOW", 64)
    assert np.abs(got - _reference_logits(reference, params, tokens)
                  ).max() > 1e-2


@pytest.mark.parametrize("kinds, moves", [((F,), False), ((S,), True)],
                         ids=["full", "sliding"])
def test_a_full_layer_knows_no_position_and_a_sliding_one_does(kinds, moves):
    """One layer: with no position signal the last token's logits are those
    of the SET of tokens before it, whatever their order; RoPE tells the
    orders apart."""
    cfg = dataclasses.replace(
        LLMConfig(**{**TINY, "num_layers": 1, "num_dense_layers": 1,
                     "layer_types": kinds, "sliding_window": 64}
                  ).model_config())
    params = _tiny_params(cfg)
    tokens = _tokens((1, 12), seed=3)
    shuffled = np.concatenate(
        [tokens[:, :-1][:, np.random.default_rng(1).permutation(11)],
         tokens[:, -1:]], axis=1)
    a, b = (np.asarray(afmoe.forward(params, jnp.asarray(t), cfg)[0])[0, -1]
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


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: every span's name and
    arguments, with no capture."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **args):
        span = _Span(name, args)
        self.seen.append(span)
        return span

    def named(self, name):
        return [s for s in self.seen if s.name == name]


class _Span:
    def __init__(self, name, args):
        self.name, self.args = name, dict(args)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        self.args.update(args)


def _engine(**changes):
    engine = DecodeEngine(LLMConfig(**{**TINY, **changes}))
    engine.params = afmoe.serving_params(
        engine.model_config, _tiny_params(engine.model_config))
    engine._span = _Spans()
    return engine


def _full_logprobs(engine, sequence):
    logits = afmoe.forward(engine.params, jnp.asarray([sequence], jnp.int32),
                           engine.model_config)[0][0]
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
    engine = _engine(**sizes)
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
        want = _full_logprobs(engine, prompt + list(out))
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
    engine = _engine()
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


def test_a_prefix_of_a_model_with_window_layers_is_a_whole_prompt():
    """A ring that went on past a bucket boundary is not that prefix's
    cache: the store keeps whole prompts only, and a continuation from one
    (its ring as the prompt left it) decodes what a fresh prefill does."""
    prompt = [int(t) for t in _tokens((19,), seed=9)]
    params = SamplingParams(max_new_tokens=6)
    fresh = _engine()
    want = [list(fresh.generate(p, params)) for p in (prompt[:12], prompt)]
    fresh.shutdown()
    engine = _engine(prefix_cache_size=4)
    got = [list(engine.generate(p, params)) for p in (prompt[:12], prompt)]
    assert got == want
    assert [len(k) for k in engine._prefix_cache] == [12, 19]
    assert engine.stats["prefix_partial_hits"] == 1
    assert list(engine.generate(prompt, params)) == want[1]
    assert engine.stats["prefix_hits"] == 1
    engine.shutdown()


def test_speculation_verifies_in_a_ring_that_has_room_for_the_draft():
    """The ring is the window and the longest block one program writes, a
    verify step's 1 + k among them: a rejected draft never lands on a
    position the token it is rolled back to still sees."""
    prompt = [7, 8, 9, 10] * 5
    params = SamplingParams(max_new_tokens=24)
    plain = _engine()
    want = list(plain.generate(prompt, params))
    plain.shutdown()
    engine = _engine(speculative_ngram_k=3, prefill_buckets=(2, 4))
    assert engine._cache["k_window"].shape[-1] == 8 + 4
    assert list(engine.generate(prompt, params)) == want
    assert engine.stats["spec_proposed"] > 0
    engine.shutdown()


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
