"""Every family through ``DecodeEngine``, once over the table of
``tests/families.py``: two slots of different lengths in one batch against
the plain reference's full forward, slots reused, speculation refused or
verified, a prompt admitted in chunks. Which families a case runs on is read
off their layer kinds (``families.shared_case``); an engine's second and later
twins share the first one's compiled programs (``families.one_compile``).

CPU, float32, seeded weights, tiny widths: no device number.
"""
import jax
import numpy as np
import pytest

from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams
from ray_tpu.models import decoder, kv_cache
from tests import families
from tests.families import ROWS, TINY, shared_case


pytestmark = pytest.mark.usefixtures("one_compile_a_file")


@shared_case()
def test_two_slots_of_different_lengths_answer_as_the_full_forward_does(
        family):
    """Two requests in one batch, a prompt of 37 (chunks) and one of 6:
    every answer token's log-probability is the reference's full forward
    over prompt + answer, so neither slot reads the other's cache or state
    and each is at its own position. And the counters of each kind of layer
    the family has: the spans' arguments sum to the engine's."""
    engine = families._engine(family)
    prompts = families.prompts_of(37, 6)
    params = SamplingParams(max_new_tokens=12, logprobs=1, ignore_eos=True)
    try:
        futures = [engine.submit(p, params) for p in prompts]
        for prompt, future in zip(prompts, futures):
            out = future.result(timeout=600)
            assert len(out) == 12
            # at one length for both (causal: padding reaches nothing)
            padded = np.zeros((1, 49), np.int32)
            padded[0, :len(prompt) + 12] = prompt + list(out)
            want = np.asarray(jax.nn.log_softmax(families._reference_logits(
                families.reference(family), engine.params, padded)[0],
                axis=-1))
            got = np.array([lp["logprob"] for lp in out.logprobs])
            at = np.arange(len(prompt) - 1, len(prompt) - 1 + 12)
            gap = np.abs(got - want[at, list(out)]).max()
            assert gap < ROWS[family].logprob
            assert [int(np.argmax(want[i])) for i in at] == list(out)
    finally:
        engine.shutdown()
    spans, stats = engine._span, engine.stats
    admits, ticks = spans.named("engine.admit"), spans.named("engine.tick")
    largest = max(TINY[family]["prefill_buckets"])
    assert [a.args["chunks"] for a in admits] == [-(-37 // largest), 1]
    kinds = decoder.layer_kinds(engine.model_config)
    state = sum(k.state is not None for k in kinds)
    latent = sum(k.latent is not None for k in kinds)
    window = sum(k.window is not None for k in kinds)
    assert {(t.args["layers_full"], t.args["layers_window"]) for t in ticks
            } == {(len(kinds) - state - latent - window, window)}
    if state:
        assert stats["ssm_prefill_tokens"] == 43
        families.state_counters(engine, admits, ticks, state)
    if latent:
        # a tick's latent positions are its slots' lengths and columns, a
        # layer
        assert all(t.args["latent_positions"]
                   == latent * t.args["cache_positions"] for t in ticks)
        assert stats["latent_positions"] == (
            latent * stats["cache_positions"]) == sum(
            t.args["latent_positions"] for t in ticks)
    routed = sum(k.routed for k in kinds)
    if routed:
        top_k = engine.model_config.moe.top_k
        assert sum(t.args["moe_rows"] for t in ticks) == (
            stats["slot_ticks"] * top_k * routed)
        assert stats["moe_rows"] == (43 + stats["slot_ticks"]) * top_k * routed


@shared_case()
def test_a_reused_slot_answers_as_a_fresh_engine_does(family):
    """Seven requests through three slots, short and long, chunked and not:
    each answer is what an engine that has seen nothing else gives, so no
    slot starts from its last tenant's columns, rings, rows or state."""
    prompts = families.prompts_of(30, 5, 21, 9, 40, 3, 17, seed=10)
    params = SamplingParams(max_new_tokens=10, ignore_eos=True)
    want = []
    for prompt in prompts:
        fresh = families._engine(family)
        try:
            want.append(list(fresh.generate(prompt, params)))
        finally:
            fresh.shutdown()
    engine = families._engine(family)
    try:
        futures = families.submit_together(engine, prompts, params)
        assert [list(f.result(timeout=600)) for f in futures] == want
    finally:
        engine.shutdown()
    admits = engine._span.named("engine.admit")
    assert len(admits) == 7 and {a.args["slot"] for a in admits} == {0, 1, 2}
    largest = max(TINY[family]["prefill_buckets"])
    assert [a.args["chunks"] for a in admits
            if a.args["prompt_tokens"] == 40] == [-(-40 // largest)]
    state = sum(k.state is not None
                for k in decoder.layer_kinds(engine.model_config))
    if state:
        assert engine.stats["ssm_prefill_tokens"] == sum(map(len, prompts))
        families.state_counters(
            engine, admits, engine._span.named("engine.tick"), state)


@shared_case("state|window")
def test_the_prefix_store_keeps_whole_prompts_only(family):
    """A state, or a ring, that ran past a bucket boundary is not that
    prefix's: the store keeps whole prompts only, and a continuation from
    one (its cache as the prompt left it) decodes what a fresh prefill
    does."""
    (prompt,) = families.prompts_of(19, seed=-10)
    params = SamplingParams(max_new_tokens=6, ignore_eos=True)
    fresh = families._engine(family)
    try:
        want = [list(fresh.generate(p, params)) for p in (prompt[:12], prompt)]
    finally:
        fresh.shutdown()
    engine = families._engine(family, prefix_cache_size=4)
    try:
        assert engine._boundaries == ()
        assert [list(engine.generate(p, params))
                for p in (prompt[:12], prompt)] == want
        assert [len(k) for k in engine._prefix_cache] == [12, 19]
        assert engine.stats["prefix_partial_hits"] == 1
        assert list(engine.generate(prompt, params)) == want[1]
        assert engine.stats["prefix_hits"] == 1
    finally:
        engine.shutdown()


@shared_case("state")
def test_speculation_is_refused_with_the_reason(family):
    with pytest.raises(ValueError, match="cannot be rolled back"):
        DecodeEngine(LLMConfig(**{**TINY[family], "speculative_ngram_k": 2}))


@shared_case("~state")
def test_speculation_verifies_against_the_plain_answer(family):
    """A column can be overwritten: drafts of three tokens, verified 1 + 3
    at a time (a ring has room for them beside the window), give the plain
    engine's answer, whether a draft is right (the plain answer's own next
    tokens, every other time) or wrong (each of them plus one)."""
    prompt = [7, 8, 9, 10] * 5
    params = SamplingParams(max_new_tokens=24, ignore_eos=True)
    plain = families._engine(family)
    try:
        want = list(plain.generate(prompt, params))
    finally:
        plain.shutdown()
    engine = families._engine(family, speculative_ngram_k=3,
                              prefill_buckets=(2, 4))

    def draft(slot, k):
        done = len(slot.token_ids)
        right = want[done:done + k]
        return right if done % 2 else [(t + 1) % 300 for t in right]

    engine._propose_draft = draft
    try:
        for name in kv_cache.WINDOW:
            if name in engine._cache:
                assert engine._cache[name].shape[-1] == engine._window + 4
        assert list(engine.generate(prompt, params)) == want
        stats = engine.stats
        assert 0 < stats["spec_accepted"] < stats["spec_proposed"]
    finally:
        engine.shutdown()


@shared_case()
def test_a_prompt_longer_than_the_largest_bucket_is_admitted_in_chunks(
        family):
    """17 tokens through buckets of 8 and 16 (one chunk of 16 and one of 1,
    the second continuing the first's cache) give the tokens of one
    unchunked prefill in a bucket of 32."""
    prompt = [int(t) for t in np.random.default_rng(5).integers(2, 300, 17)]
    answers = []
    for buckets in ((8, 16), (32,)):
        engine = families._engine(family, prefill_buckets=buckets)
        try:
            out = engine.generate(prompt, SamplingParams(
                max_new_tokens=6, ignore_eos=True))
        finally:
            engine.shutdown()
        answers.append(list(out))
        assert len(out) == 6
    assert answers[0] == answers[1]
    full = families._engine(family, max_seq_len=16, prefill_buckets=(8,))
    try:
        with pytest.raises(ValueError, match="no room for an answer"):
            full.generate(list(range(2, 18)))
    finally:
        full.shutdown()     # its loop would idle into every later capture
