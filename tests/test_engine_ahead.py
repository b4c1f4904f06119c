"""The engine one tick ahead of the host (``DecodeEngine._step_locked``).

A greedy row with nothing else for the host to do is a *chip row*: the
decode program takes its argmax and the next tick reads it there, so the
loop dispatches tick k+1 before it has read tick k. Any other row (sampled,
penalised, ``logprobs``, every row under speculation) is a *host row* and
holds the loop to dispatch, fetch, sample. Both must give the same tokens.

Where the order of events matters the tests drive the loop's turns by hand
(``_hand_driven``): no loop thread, one ``_step_locked`` a call. Weights
are the init's times 8: at the init's own scale the greedy answer of these
toys is one token over and over, and a stop has to be a token that comes
late. Nothing timed here is a device number.
"""
import jax
import pytest

from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams
from ray_tpu.models import module_for

CONFIGS = {
    "gpt2-tiny": dict(
        vocab_size=300, max_seq_len=64, num_layers=2, num_heads=2,
        embed_dim=32, dtype="float32", max_batch_slots=2,
        prefill_buckets=(16, 32)),
    # routed experts: the packed array's third row, and the count that
    # rides the late read
    "llama-routed": dict(
        model_family="llama", vocab_size=300, max_seq_len=64, num_layers=2,
        num_heads=4, num_kv_heads=4, embed_dim=64, mlp_dim=32,
        moe_num_experts=8, moe_top_k=2, dtype="float32", max_batch_slots=2,
        prefill_buckets=(16, 32)),
}
COUNTERS = ("ticks", "ticks_ahead", "slot_ticks", "tokens_generated",
            "overrun_rows", "requests")


def lively_params(config: LLMConfig):
    cfg = config.model_config()
    params = module_for(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return jax.tree.map(lambda a: a * 8 if a.ndim >= 2 else a, params)


pytestmark = pytest.mark.usefixtures("one_compile_a_file")


def _lively_engine(family, **more):
    config = LLMConfig(**{**CONFIGS[family], **more})
    return DecodeEngine(config, params=lively_params(config))


def _hand_driven(family, **more):
    """An engine whose loop thread never starts: ``submit`` queues, and the
    test takes the loop's turns itself."""
    engine = _lively_engine(family, **more)
    engine._ensure_loop = lambda: None
    return engine


def _turn(engine) -> bool:
    with engine._lock:
        return engine._step_locked()


def _drain(engine, limit=500):
    for _ in range(limit):
        if not _turn(engine):
            return
    raise AssertionError("the engine never came to rest")


def _prompt(i, n=4):
    return [3 + i, 9, 40 + i, 7, 11 + 2 * i, 5][:n]


def _delta(engine, before):
    return {k: engine.stats[k] - before[k] for k in COUNTERS}


@pytest.fixture(scope="module", params=list(CONFIGS))
def family(request):
    return request.param


@pytest.fixture(scope="module")
def alone(family):
    """What each test prompt answers when it is the only request, with a
    host row's loop (``logprobs=1``): today's order of events."""
    engine = _lively_engine(family)
    out = {i: list(engine.generate(
        _prompt(i), SamplingParams(max_new_tokens=14, logprobs=1)))
        for i in range(6)}
    assert engine.stats["ticks_ahead"] == 0
    engine.shutdown()
    # an answer the toy ends itself (EOS) is shorter than was asked for
    assert all(len(out[i]) >= 12 for i in (0, 1, 2))
    return out


def _asked(alone, i, n, stop=None):
    """(tokens, finish reason, tokens made) of prompt ``i`` asked for ``n``
    tokens with stop token ``stop``, from what it answers alone."""
    tokens = alone[i]
    if stop is not None:
        assert tokens.index(stop) < n
        return tokens[:tokens.index(stop)], "stop", tokens.index(stop) + 1
    if n <= len(tokens):
        return tokens[:n], "length", n
    return tokens, "eos", len(tokens) + 1


# --------------------------------------------------- (a) the same answers


@pytest.mark.parametrize("streamed", [False, True], ids=["unary", "stream"])
def test_depth_one_answers_as_depth_zero(family, alone, streamed):
    """Five greedy requests on two slots through the loop thread, one with
    a stop token that comes fourth, and their ``logprobs=1`` twins: the
    same ids and the same endings, though only the twins' ticks waited for
    the host."""
    engine = _lively_engine(family)
    stop = alone[1][3]
    assert stop not in alone[1][:3]
    asks = [(0, 9, None), (1, 12, stop), (2, 1, None), (3, 14, None),
            (4, 5, None)]

    def ask(logprobs):
        params = [SamplingParams(
            max_new_tokens=n, logprobs=logprobs,
            stop_token_ids=() if s is None else (s,)) for _, n, s in asks]
        if streamed:
            streams = [engine.submit_stream(_prompt(i), p)
                       for (i, _, _), p in zip(asks, params)]
            return [(list(s), s.finish_reason) for s in streams]
        futures = [engine.submit(_prompt(i), p)
                   for (i, _, _), p in zip(asks, params)]
        got = [f.result(120) for f in futures]
        assert all(len(g.logprobs) == len(g) * bool(logprobs) for g in got)
        return [(list(g), g.finish_reason) for g in got]

    before = dict(engine.stats)
    ahead = ask(0)
    first = _delta(engine, before)
    before = dict(engine.stats)
    twins = ask(1)
    second = _delta(engine, before)
    engine.shutdown()
    assert ahead == twins
    want = [_asked(alone, *a) for a in asks]
    assert ahead == [(tokens, reason) for tokens, reason, _ in want]
    assert first["ticks_ahead"] > first["ticks"] // 2
    assert second["ticks_ahead"] == second["overrun_rows"] == 0
    for d in (first, second):
        assert d["slot_ticks"] == d["tokens_generated"] + d["overrun_rows"]
        # the stop token is made and counted, a thrown-away row is not
        assert d["requests"] + d["tokens_generated"] == sum(
            made for _, _, made in want)


# ------------------------------------------- (b) an ending seen a tick late


def test_a_stop_seen_one_tick_late_costs_one_row(family, alone):
    """Slot 0's answer ends on its stop token while slot 1 keeps decoding:
    the tick in flight had a row for slot 0 already. The row is counted as
    an overrun and nowhere else, and the next request into slot 0 answers
    as it does alone."""
    engine = _hand_driven(family)
    stop = alone[0][4]
    assert stop not in alone[0][:4]
    stream = engine.submit_stream(_prompt(0), SamplingParams(
        max_new_tokens=14, stop_token_ids=(stop,)))
    other = engine.submit(_prompt(1), SamplingParams(max_new_tokens=12))
    _drain(engine)
    assert (list(stream), stream.finish_reason) == (alone[0][:4], "stop")
    assert list(other.result(0)) == alone[1][:12]
    assert engine.stats["overrun_rows"] == 1
    # 5 tokens of slot 0 (the stop with them), 12 of slot 1, less the two
    # first tokens, which the prefills made
    assert engine.stats["tokens_generated"] == 4 + 11
    assert engine.stats["slot_ticks"] == 4 + 11 + 1
    # both slots decode the first 5 ticks (the fifth row of slot 0 is the
    # overrun), slot 1 alone the last 6
    assert engine.stats["ticks"] == 11
    # only the first tick after the admissions had nothing to run ahead of
    assert engine.stats["ticks_ahead"] == 10
    late = engine.submit(_prompt(2), SamplingParams(max_new_tokens=8))
    _drain(engine)
    assert list(late.result(0)) == alone[2][:8]
    assert engine.stats["overrun_rows"] == 1


# ------------------------------------ (c) endings the host knows beforehand


def test_length_and_context_endings_are_known_before_the_dispatch(family):
    """``max_new_tokens`` and the end of the context: the host counts both
    itself and leaves the slot out of the next tick, so no row is thrown
    away, and the last tick's read needs no dispatch."""
    engine = _hand_driven(family)
    twin = _hand_driven(family)
    long_prompt = [2 + (7 * j) % 250 for j in range(30)]
    got = {}
    for name, e, logprobs in (("ahead", engine, 0), ("twin", twin, 1)):
        futures = [
            e.submit(_prompt(0), SamplingParams(
                max_new_tokens=6, logprobs=logprobs)),
            e.submit(long_prompt, SamplingParams(
                max_new_tokens=100, logprobs=logprobs))]
        _drain(e)
        got[name] = [(list(f.result(0)), f.result(0).finish_reason)
                     for f in futures]
    assert got["ahead"] == got["twin"]
    assert [r for _, r in got["ahead"]] == ["length", "context"]
    # 64 positions: the prompt's 30, then tokens until position 63 is
    # the last that can be written
    assert len(got["ahead"][1][0]) == 64 - 30
    for e in (engine, twin):
        assert e.stats["overrun_rows"] == 0
        assert e.stats["slot_ticks"] == e.stats["tokens_generated"] == 5 + 33
        assert e.stats["ticks"] == 33
        assert e._flying is None
    assert engine.stats["ticks_ahead"] == 32 and twin.stats[
        "ticks_ahead"] == 0
    # what the late reads brought is what the twin's own fetches did
    for counter in ("cache_positions", "moe_rows", "moe_experts_touched"):
        assert engine.stats[counter] == twin.stats[counter], counter
    assert (engine.stats["moe_experts_touched"] > 0) == (
        family == "llama-routed")
    # the last tick was read with no dispatch around it: its count (one
    # row: 2 of 8 experts in each of 2 layers) waits for the next tick span
    assert engine._touched_unspanned == (
        4 if family == "llama-routed" else 0)
    assert twin._touched_unspanned == 0


# -------------------------- (d) an admission reads the tick in flight first


def test_a_request_that_arrives_mid_flight_is_admitted_after_the_read(
        family, alone):
    engine = _hand_driven(family)
    first = engine.submit(_prompt(0), SamplingParams(max_new_tokens=10))
    assert _turn(engine) and _turn(engine)
    assert engine._flying is not None and engine._flying.number == 1
    assert engine._slots[0].produced == 2  # the prefill's and tick 0's
    seen = []
    admit = engine._admit_locked

    def watched():
        seen.append((engine._flying, engine._slots[0].produced))
        admit()

    engine._admit_locked = watched
    second = engine.submit(_prompt(1), SamplingParams(max_new_tokens=4))
    _turn(engine)
    # tick 1 was read, and its token booked, before the prefill ran
    assert seen == [(None, 3)]
    assert engine._slots[1].active
    # and the tick after the admission ran ahead of nothing
    assert engine.stats["ticks"] == 3 and engine.stats["ticks_ahead"] == 1
    _drain(engine)
    assert list(first.result(0)) == alone[0][:10]
    assert list(second.result(0)) == alone[1][:4]
    assert engine.stats["overrun_rows"] == 0


# ------------------------------------- (e) a host row holds the whole tick


@pytest.mark.parametrize("host", [
    dict(logprobs=1), dict(temperature=0.8, seed=5),
    dict(repetition_penalty=1.3), dict(presence_penalty=0.5),
    dict(frequency_penalty=0.5)], ids=lambda d: next(iter(d)))
def test_a_tick_with_a_host_row_is_not_dispatched_ahead(family, alone, host):
    """One chip row and one host row: every tick that holds the host row is
    read in its own turn (its token is the next tick's input); when the
    host row's answer has ended the chip row runs ahead again, and answers
    as it does alone all the way."""
    engine = _hand_driven(family)
    greedy = engine.submit(_prompt(0), SamplingParams(max_new_tokens=12))
    other = engine.submit(_prompt(1), SamplingParams(
        max_new_tokens=5, **host))
    while not other.done():
        assert _turn(engine)
        assert engine._flying is None
    assert engine.stats["ticks"] == 4 and engine.stats["ticks_ahead"] == 0
    _drain(engine)
    assert list(greedy.result(0)) == alone[0][:12]
    assert len(other.result(0)) == 5
    if "logprobs" in host:
        assert list(other.result(0)) == alone[1][:5]
    # 11 ticks in all; the fifth is the first without the host row, and
    # nothing was in flight before it
    assert engine.stats["ticks"] == 11 and engine.stats["ticks_ahead"] == 6
    assert engine.stats["overrun_rows"] == 0


def test_speculation_keeps_the_host_in_every_tick(family):
    """Drafts come from the host's history: with ``speculative_ngram_k``
    every row is a host row, and the answers are the plain engine's."""
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6]
    plain = _lively_engine(family)
    want = list(plain.generate(prompt, SamplingParams(max_new_tokens=10)))
    assert plain.stats["ticks_ahead"] > 0
    plain.shutdown()
    spec = _lively_engine(family, speculative_ngram_k=3)
    got = list(spec.generate(prompt, SamplingParams(max_new_tokens=10)))
    assert got == want
    assert spec.stats["ticks_ahead"] == spec.stats["overrun_rows"] == 0
    spec.shutdown()


def test_a_failed_tick_leaves_nothing_in_flight(family):
    """The loop's last resort (fail every request, clear the slots) also
    forgets the tick in flight: the next request starts from rest."""
    engine = _lively_engine(family)
    honest = engine._decode
    calls = []

    def failing(*args):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("the third tick fails")
        return honest(*args)

    engine._decode = failing
    with pytest.raises(RuntimeError, match="third tick"):
        engine.generate(_prompt(0), SamplingParams(max_new_tokens=10))
    # the failing call may have consumed the donated cache: a fresh one,
    # as a restarted replica has
    from ray_tpu.models import decoder

    engine._decode = honest
    with engine._lock:
        assert engine._flying is None
        engine._cache = decoder.init_kv_cache(
            engine.model_config, 2, engine.config.max_seq_len)
    want = _lively_engine(family)
    assert list(engine.generate(
        _prompt(1), SamplingParams(max_new_tokens=6))) == list(
        want.generate(_prompt(1), SamplingParams(max_new_tokens=6)))
    engine.shutdown(), want.shutdown()
