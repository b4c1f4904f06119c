"""IBM's ``granitemoehybrid`` (Granite 4.0-H) through the program: the
family's pieces against the benchmark's plain reference
(``benchmarks/references/granite_hybrid.py``: the recurrence one token after
another), and the state layers' cache beside the attention layers' through
``DecodeEngine``: prefill in padded chunks that hand the state on, cached
decoding, slots reused and slots idle, whole-prompt prefixes, speculation
refused.

CPU, float32 where logits are compared (bfloat16 once, against the float32
reference), seeded weights, tiny widths; each tolerance is written where it
is used. Nothing timed here is a device number.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams
from ray_tpu.llm.engine import engine_programs
from ray_tpu.models import decoder, granite_hybrid, kv_cache

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
M, A = granite_hybrid.MAMBA, granite_hybrid.ATTENTION

# one period of the published stack in small: state layers around one
# attention layer (G = 2), chunks of 8, buckets that pad
TINY = dict(
    model_family="granite_hybrid", vocab_size=300, max_seq_len=128,
    num_layers=4, num_heads=4, num_kv_heads=2, embed_dim=64, head_dim=16,
    mlp_dim=96, rms_eps=1e-5, layer_types=(M, M, A, M), mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_n_heads=8, mamba_d_head=16,
    mamba_n_groups=1, mamba_chunk_size=8, mamba_conv_bias=True,
    mamba_proj_bias=False, embedding_multiplier=12,
    attention_multiplier=0.0625, residual_multiplier=0.22, logits_scaling=8,
    ssm_state_dtype="float32", dtype="float32", max_batch_slots=3,
    prefill_buckets=(8, 16),
)


@pytest.fixture
def reference(monkeypatch):
    from benchmarks.lib import named

    module = named.load(os.path.join(
        CHECKOUT, "benchmarks", "references", "granite_hybrid.py"))
    # what the weights do not carry, at the toy's values
    monkeypatch.setattr(module, "D_STATE", TINY["mamba_d_state"])
    monkeypatch.setattr(module, "ATTENTION_MULTIPLIER",
                        TINY["attention_multiplier"])
    return module


def _tiny_params(cfg, seed=0):
    """The family's own init with what would hide a fault moved: norm gains
    of all ones (a norm on the wrong vector) and matrices of 0.02 (a mixer
    that adds a thousandth to the stream)."""
    params = granite_hybrid.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def moved(path, a):
        name = path[-1].key
        if name.endswith("norm") or name == "norm_f":
            return a * jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)
        if name in ("wte", "conv_w", "conv_b", "dt_bias", "A_log"):
            return a
        if name == "D":
            return a * jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)
        return a * 6.0

    return jax.tree_util.tree_map_with_path(moved, params)


def _reference_logits(reference, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits(params, jnp.asarray(tokens)))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(2, 300, shape).astype(np.int32)


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
    engine.params = granite_hybrid.serving_params(
        engine.model_config, _tiny_params(engine.model_config))
    engine._span = _Spans()
    return engine


# ------------------------------------------- the family against the reference


def test_the_family_matches_the_reference_and_each_fault_does_not(
        reference, monkeypatch):
    cfg = LLMConfig(**TINY).model_config()
    assert [k.state for k in decoder.layer_kinds(cfg)] == [8, 8, None, 8]
    params = _tiny_params(cfg)
    tokens = _tokens((2, 37))          # four chunks and five tokens
    got = np.asarray(granite_hybrid.forward(
        params, jnp.asarray(tokens), cfg)[0])
    want = _reference_logits(reference, params, tokens)
    # float32 against float32: the order of the sums, 2e-7 measured on
    # logits of up to 0.16
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < 2e-5

    def off(**changes):
        other = dataclasses.replace(cfg, **changes)
        return np.abs(np.asarray(granite_hybrid.forward(
            params, jnp.asarray(tokens), other)[0]) - want).max()

    # the four factors, each read far above that; the attention scaled by
    # head_dim ** -0.5 in place of the stated number; a chunk of another
    # length is the same recurrence
    assert off(embedding_multiplier=1.0) > 1e-2
    assert off(residual_multiplier=1.0) > 1e-2
    assert off(logits_scaling=1.0) > 1e-2
    assert off(attention_multiplier=16 ** -0.5) > 1e-4
    assert off(mamba_chunk_size=5) < 2e-5
    # a state layer's own: the gate after the norm, D left out, the
    # convolution's bias left out (each a weight moved, not a config)
    for name, change in (("D", lambda a: a * 0), ("conv_b", lambda a: a * 0),
                         ("A_log", lambda a: a + 1.0),
                         ("dt_bias", lambda a: a + 1.0)):
        moved = jax.tree_util.tree_map_with_path(
            lambda path, a: change(a) if path[-1].key == name else a, params)
        assert np.abs(np.asarray(granite_hybrid.forward(
            moved, jnp.asarray(tokens), cfg)[0]) - want).max() > 1e-4, name
    # and the reference sees its own factors
    monkeypatch.setattr(reference, "RESIDUAL_MULTIPLIER", 0.2)
    assert np.abs(got - _reference_logits(reference, params, tokens)
                  ).max() > 1e-3


def test_bfloat16_activations_stay_near_the_float32_reference(reference):
    cfg = LLMConfig(**{**TINY, "dtype": "bfloat16"}).model_config()
    params = _tiny_params(cfg)
    tokens = _tokens((2, 37), seed=1)
    got = np.asarray(granite_hybrid.forward(
        params, jnp.asarray(tokens), cfg)[0])
    want = _reference_logits(reference, params, tokens)
    # bf16's rounding through four layers: 4e-3 measured on logits of 0.6
    assert 1e-5 < np.abs(got - want).max() < 3e-2


def test_the_stack_is_the_shortest_period_and_the_cache_counts_by_kind():
    published = (M,) * 5 + (A,) + (M,) * 4
    cfg = LLMConfig(**{**TINY, "num_layers": 40,
                       "layer_types": published * 4}).model_config()
    segments, experts = granite_hybrid.layers(cfg, None, cached=True)
    assert experts is None and len(segments) == 1
    assert segments[0].repeats == 4
    assert [k.name for k in segments[0].kinds] == list(published)
    cache = jax.eval_shape(
        lambda: decoder.init_kv_cache(cfg, 3, 128, block=16))
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "k": ((4, 3, 2, 16, 128), jnp.float32),
        "v": ((4, 3, 2, 16, 128), jnp.float32),
        "ssm": ((36, 3, 8, 16, 16), jnp.float32),
        "conv": ((36, 3, 3 * 160), jnp.float32)}
    # the layers there are, where fewer are asked for: the first of them
    two = dataclasses.replace(cfg, num_layers=2)
    assert [k.state for k in decoder.layer_kinds(two)] == [8, 8]


@pytest.mark.parametrize("bad, match", [
    (dict(layer_types=(M, "sliding_attention", A, M)), "layer_types"),
    (dict(mamba_n_groups=2), "mamba_n_groups"),
    (dict(mamba_expand=3), "mamba_expand")])
def test_a_configuration_it_cannot_run_is_refused_by_name(bad, match):
    with pytest.raises(ValueError, match=match):
        LLMConfig(**{**TINY, **bad}).model_config()


# --------------------------------------------- the state in the engine's cache


def _prefill_then_decode(cfg, params, sequence, chunks, decode_impl="xla"):
    """The engine's own programs by hand: ``sequence``'s first tokens in
    padded ``chunks`` (real length, bucket) into a slot cache, inserted into
    slot 1 of 3, then one decode step a token: logits at every position
    from the first chunk's last on."""
    prefill, insert, decode, _ = engine_programs(cfg)
    cache1 = decoder.init_kv_cache(cfg, 1, 128, block=16)
    rows, at = [], 0
    for n, bucket in chunks:
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = sequence[at:at + n]
        logits, cache1, _ = prefill(
            params, jnp.asarray(toks), cache1, jnp.asarray([at], jnp.int32),
            jnp.asarray([n], jnp.int32), rows=jnp.asarray([n - 1]))
        rows.append(np.asarray(logits[0, 0]))
        at += n
    cache = insert(decoder.init_kv_cache(cfg, 3, 128, block=16), cache1, 1)
    ids = jnp.zeros((3,), jnp.int32)
    for t in range(at, len(sequence)):
        packed = np.zeros((3, 3), np.int32)
        packed[:, 1] = sequence[t], t, 1
        ids, logits, cache, _ = decode(params, ids, cache,
                                       jnp.asarray(packed))
        rows.append(np.asarray(logits[1]))
    return rows, cache


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_two_padded_chunks_then_sixteen_cached_steps_match_the_reference(
        reference, monkeypatch, impl):
    """A prompt of 21 tokens as a full chunk of 16 and 5 tokens padded to
    8 (the second starts from the state the first left, and its three
    padded steps must leave the state alone), then 16 decode steps, beside
    two idle slots; with ``pallas_interpret`` every decode step's state
    update is the kernel."""
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
    cfg = LLMConfig(**TINY).model_config()
    params = _tiny_params(cfg)
    sequence = _tokens((37,), seed=2)
    want = _reference_logits(reference, params, sequence[None])[0]
    rows, _ = _prefill_then_decode(cfg, params, sequence, [(16, 16), (5, 8)])
    at = [15, 20] + list(range(21, 37))
    assert len(rows) == len(at) == 18
    # float32 against float32 (1e-7 measured)
    assert np.abs(np.stack(rows) - want[at]).max() < 2e-5

    # a state dropped at the chunk boundary, or the padded steps taken as
    # tokens, read far above that
    prefill = engine_programs(cfg)[0]
    cache1 = decoder.init_kv_cache(cfg, 1, 128, block=16)
    toks = np.zeros((1, 8), np.int32)
    toks[0, :5] = sequence[16:21]
    fresh, _, _ = prefill(params, jnp.asarray(toks), cache1,
                          jnp.asarray([16], jnp.int32),
                          jnp.asarray([5], jnp.int32), rows=jnp.asarray([4]))
    assert np.abs(np.asarray(fresh[0, 0]) - want[20]).max() > 1e-3


def test_padded_steps_leave_state_and_tail_as_the_last_real_token_did():
    cfg = LLMConfig(**TINY).model_config()
    params = _tiny_params(cfg)
    prefill = engine_programs(cfg)[0]
    sequence = _tokens((16,), seed=3)
    empty = decoder.init_kv_cache(cfg, 1, 128, block=16)

    def state_after(n, bucket):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = sequence[:n]
        _, cache, _ = prefill(
            params, jnp.asarray(toks), empty, jnp.zeros((1,), jnp.int32),
            jnp.asarray([n], jnp.int32), rows=jnp.asarray([n - 1]))
        return cache

    padded, exact = state_after(5, 16), state_after(5, 8)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(padded[name], exact[name], atol=1e-6)
    assert float(jnp.abs(padded["ssm"]).max()) > 1e-3
    # the tail is the last three rows that entered: not what padding made
    longer = state_after(8, 8)
    assert float(jnp.abs(longer["conv"] - exact["conv"]).max()) > 1e-3


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_an_idle_slots_state_is_untouched_by_other_slots_ticks(
        monkeypatch, impl):
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
    cfg = LLMConfig(**TINY).model_config()
    params = _tiny_params(cfg)
    decode = engine_programs(cfg)[2]
    rng = np.random.default_rng(4)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        decoder.init_kv_cache(cfg, 3, 128, block=16))
    before = jax.tree.map(np.asarray, cache)
    packed = np.zeros((3, 3), np.int32)
    packed[:, 0] = 7, 20, 1      # slot 0 decodes at length 20
    ids = jnp.zeros((3,), jnp.int32)
    for _ in range(3):
        ids, _, cache, _ = decode(params, ids, cache, jnp.asarray(packed))
        packed[1, 0] += 1
    for name in ("ssm", "conv"):
        after = np.asarray(cache[name])
        assert (after[:, 1:] == before[name][:, 1:]).all(), name
        assert np.abs(after[:, 0] - before[name][:, 0]).max() > 1e-3, name


def test_insert_writes_a_slot_of_leaves_of_rank_three_and_five():
    cfg = LLMConfig(**TINY).model_config()
    insert = engine_programs(cfg)[1]
    rng = np.random.default_rng(5)
    batch = decoder.init_kv_cache(cfg, 3, 128, block=16)
    assert sorted(a.ndim for a in batch.values()) == [3, 5, 5, 5]
    slot = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        decoder.init_kv_cache(cfg, 1, 128, block=16))
    out = insert(batch, slot, 2)
    for name, leaf in out.items():
        assert bool((leaf[:, 2] == slot[name][:, 0]).all()), name
        assert bool((leaf[:, :2] == 0).all()), name


def test_a_reused_slot_answers_as_a_fresh_engine_does():
    """Seven requests through three slots, short and long, chunked and not:
    each answer is what an engine that has seen nothing else gives, so no
    slot starts from its last tenant's state."""
    prompts = [[int(t) for t in _tokens((n,), seed=10 + n)]
               for n in (30, 5, 21, 9, 40, 3, 17)]
    params = SamplingParams(max_new_tokens=10)
    want = []
    for prompt in prompts:
        fresh = _engine()
        want.append(list(fresh.generate(prompt, params)))
        fresh.shutdown()
    engine = _engine()
    futures = [engine.submit(p, params) for p in prompts]
    assert [list(f.result(timeout=600)) for f in futures] == want
    engine.shutdown()
    admits = engine._span.named("engine.admit")
    assert len(admits) == 7 and {a.args["slot"] for a in admits} == {0, 1, 2}
    # the counters: the spans' arguments sum to the engine's
    stats, ticks = engine.stats, engine._span.named("engine.tick")
    assert stats["ssm_prefill_tokens"] == sum(len(p) for p in prompts) == sum(
        a.args["ssm_prefill_tokens"] for a in admits)
    assert {a.args["layers_state"] for a in admits} == {3}
    assert stats["state_slot_layers"] == 3 * stats["slot_ticks"] == sum(
        t.args["state_slot_layers"] for t in ticks)
    assert {t.args["layers_full"] for t in ticks} == {1}
    assert [a.args["chunks"] for a in admits if
            a.args["prompt_tokens"] == 40] == [3]


def test_every_answer_token_is_the_full_forwards_choice(reference):
    engine = _engine()
    prompts = [[int(t) for t in _tokens((n,), seed=n)] for n in (37, 6)]
    params = SamplingParams(max_new_tokens=12, logprobs=1)
    futures = [engine.submit(p, params) for p in prompts]
    for prompt, future in zip(prompts, futures):
        out = future.result(timeout=600)
        want = jax.nn.log_softmax(_reference_logits(
            reference, engine.params, np.asarray([prompt + list(out)])
        )[0], axis=-1)
        got = np.array([lp["logprob"] for lp in out.logprobs])
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + 12)
        assert np.abs(got - np.asarray(want)[at, list(out)]).max() < 5e-5
    engine.shutdown()


def test_the_prefix_store_keeps_whole_prompts_only():
    """A state that ran past a bucket boundary is not that prefix's: the
    store keeps whole prompts, and a continuation from one (its state as the
    prompt left it) decodes what a fresh prefill does."""
    prompt = [int(t) for t in _tokens((19,), seed=9)]
    params = SamplingParams(max_new_tokens=6)
    fresh = _engine()
    want = [list(fresh.generate(p, params)) for p in (prompt[:12], prompt)]
    fresh.shutdown()
    engine = _engine(prefix_cache_size=4)
    assert engine._boundaries == ()
    got = [list(engine.generate(p, params)) for p in (prompt[:12], prompt)]
    assert got == want
    assert [len(k) for k in engine._prefix_cache] == [12, 19]
    assert engine.stats["prefix_partial_hits"] == 1
    assert list(engine.generate(prompt, params)) == want[1]
    assert engine.stats["prefix_hits"] == 1
    engine.shutdown()


def test_speculation_is_refused_with_the_reason():
    with pytest.raises(ValueError, match="cannot be rolled back"):
        DecodeEngine(LLMConfig(**{**TINY, "speculative_ngram_k": 2}))


def test_a_prefilled_state_is_handed_on_as_it_is():
    """``prefill_only`` on one engine, ``submit_prefilled`` on another: the
    pytree carries the state layers' leaves beside the keys and values."""
    prompt = [int(t) for t in _tokens((21,), seed=6)]
    params = SamplingParams(max_new_tokens=8)
    whole = _engine()
    want = list(whole.generate(prompt, params))
    handed = whole.prefill_only(prompt, params)
    whole.shutdown()
    assert sorted(handed["cache"]) == ["conv", "k", "ssm", "v"]
    other = _engine()
    assert list(other.submit_prefilled(handed, params).result(
        timeout=600)) == want
    other.shutdown()


@pytest.mark.parametrize("key, value", [
    ("ssm_state_dtype", "bfloat16"), ("ssm_state_dtype", "float16"),
    ("mamba_proj_bias", True)])
def test_a_narrower_state_or_a_projection_bias_is_refused(key, value):
    """The cache holds a state in float32 and the kernel steps nothing else;
    the projections carry no bias: a configuration that states otherwise is
    told so, by the key, before anything is built."""
    with pytest.raises(ValueError, match=key):
        dataclasses.replace(granite_hybrid.GRANITE_HYBRID_TINY,
                            **{key: value})
    with pytest.raises(ValueError, match=key):
        DecodeEngine(LLMConfig(**{**TINY, key: value}))


def test_the_cache_holds_a_state_in_float32():
    engine = _engine()
    assert engine._cache["ssm"].dtype == jnp.float32
    assert engine._cache["conv"].dtype == engine.model_config.dtype
    engine.shutdown()
