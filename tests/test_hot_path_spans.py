"""The engine's tick loop and the trainer's step loop in the profiler's own
trace: spans, their nesting, the request id they share, and the counters
that sum the same quantities with no capture running.

One run of a tiny engine (two slots, five requests) and of four steps of
``default_jax_train_loop`` under one CPU ``jax.profiler`` capture, Python
tracer off, read back with ``benchmarks/lib/host_spans.py``; every test
below looks at that one recording. One profiler session per process: keep
these in one file. Nothing timed here is a device number.
"""
import glob
import os
import threading
import time

import pytest

from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams

_MODEL = dict(
    vocab_size=300, max_seq_len=64, num_layers=2, num_heads=2, embed_dim=32,
    dtype="float32", max_batch_slots=2, prefill_buckets=(16, 32),
)
TICK_CHILDREN = ["engine.tick.pack", "engine.tick.dispatch",
                 "engine.tick.fetch", "engine.tick.sample"]
ADMIT_CHILDREN = ["engine.prefill.dispatch", "engine.prefill.fetch",
                  "engine.prefill.sample", "engine.insert"]
STEP_CHILDREN = ["train.next_batch", "train.dispatch", "train.loss_fetch"]


def _server():
    from ray_tpu.llm.serving import LLMServer

    srv = LLMServer.__new__(LLMServer)
    srv.config = LLMConfig(**_MODEL)
    srv.engine = DecodeEngine(srv.config, seed=0)
    return srv


def _sampled(prompt, max_tokens, seed):
    # sampled with a seed of its own: the toy model's greedy answer is one
    # token over and over, and a stop has to be a token that comes late
    return {"prompt": prompt, "max_tokens": max_tokens, "temperature": 1.0,
            "seed": seed}


def _scenario(srv, stop_token):
    """Five requests at once on two slots: three unary and one streamed
    through the serving layer, one handed to the engine directly with a
    stop token. Returns what each answered."""
    out = {}

    def unary(key, payload):
        out[key] = srv.completions(dict(payload, logprobs=1))

    def stream(key, payload):
        out[key] = list(srv.completions_stream(payload))

    def direct(key, prompt, params):
        got = srv.engine.submit(srv.engine.tokenizer.encode(prompt),
                                params).result(120)
        out[key] = (list(got), got.finish_reason)

    threads = [
        threading.Thread(target=unary, args=("a", _sampled("hi", 5, 11))),
        threading.Thread(target=unary, args=(
            "b", _sampled("hello there", 7, 12))),
        threading.Thread(target=unary, args=("eos", _sampled("abc", 6, 13))),
        threading.Thread(target=stream, args=(
            "s", _sampled("what is", 4, 14))),
        threading.Thread(target=direct, args=(
            "d", "zzzz", SamplingParams(
                max_new_tokens=6, temperature=1.0, seed=15,
                stop_token_ids=(stop_token,)))),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert sorted(out) == ["a", "b", "d", "eos", "s"], sorted(out)
    return out


def _tokens(answers):
    """What the comparison between two runs may look at: ids and times
    differ, the tokens may not."""
    return {
        "a": answers["a"]["choices"][0]["logprobs"]["tokens"],
        "b": answers["b"]["choices"][0]["logprobs"]["tokens"],
        "eos": answers["eos"]["choices"][0].get("logprobs", {}).get(
            "tokens", []),
        "d": answers["d"],
        "s": len(answers["s"]),
    }


def _train(run_dir, num_steps=4):
    from ray_tpu.train.context import TrainContext, _set_context
    from ray_tpu.train.trainer import default_jax_train_loop

    ctx = TrainContext(0, 1, 0, 1, 0, "spans", run_dir)
    result = {}

    def work():
        _set_context(ctx)
        try:
            result["out"] = default_jax_train_loop({
                "model": dict(
                    vocab_size=128, max_seq_len=16, num_layers=1,
                    num_heads=2, embed_dim=32, attention_impl="xla"),
                "mesh": {"data": -1}, "num_steps": num_steps,
                "batch_size": 8, "seq_len": 16, "checkpoint_every": 0,
            })
        finally:
            _set_context(None)

    t = threading.Thread(target=work, name="train-loop")
    t.start()
    t.join(300)
    assert result["out"] == {"final_step": num_steps}
    return [r["metrics"] for r in ctx.drain_reports()]


def _trace_files(root):
    return glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)


@pytest.fixture(scope="module")
def recording(tmp_path_factory):
    import jax

    from benchmarks.lib import host_spans

    root = str(tmp_path_factory.mktemp("spans"))
    srv = _server()
    tok = srv.engine.tokenizer
    # the tokens the two requests that are to end early would make if
    # nothing stopped them; their third becomes the EOS and the stop token.
    # Neither may turn up anywhere else, or another answer ends on it.
    probe = {}
    for key, prompt, seed in (("a", "hi", 11), ("b", "hello there", 12),
                              ("eos", "abc", 13), ("s", "what is", 14),
                              ("d", "zzzz", 15)):
        probe[key] = list(srv.engine.generate(tok.encode(prompt), SamplingParams(
            max_new_tokens=7, temperature=1.0, seed=seed)))
    eos, stop_token = probe["eos"][2], probe["d"][2]
    everything = [t for toks in probe.values() for t in toks]
    assert everything.count(eos) == 1 and everything.count(stop_token) == 1, (
        "pick other seeds: the forced EOS or stop token is made twice",
        probe)
    tok.eos_id = eos

    # once with no capture: the same run leaves no trace and the same tokens
    before = dict(srv.engine.stats)
    plain = _scenario(srv, stop_token)
    _train(os.path.join(root, "run_plain"))
    plain_stats = {k: srv.engine.stats[k] - before[k] for k in before}
    files_without_capture = _trace_files(root)

    logdir = os.path.join(root, "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    before = dict(srv.engine.stats)
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        time.sleep(0.06)  # the idle engine, inside the capture
        # a replica that takes its weights while the capture runs: float32
        # as they are made, activations in bf16
        bf16 = DecodeEngine(LLMConfig(**{**_MODEL, "dtype": "bfloat16"}))
        traced = _scenario(srv, stop_token)
        reports = _train(os.path.join(root, "run_traced"))
        time.sleep(0.06)
    finally:
        jax.profiler.stop_trace()
    stats = {k: srv.engine.stats[k] - before[k] for k in before}
    srv.engine.shutdown()
    spans = host_spans.load(logdir)
    assert spans is not None, "the capture holds no span of the program"
    engine_line = max(
        spans.lines, key=lambda l: sum(s.name == "engine.tick" for s in l))
    train_line = max(
        spans.lines, key=lambda l: sum(s.name == "train.step" for s in l))
    return {
        "spans": spans, "engine": engine_line, "train": train_line,
        "stats": stats, "plain_stats": plain_stats, "plain": plain,
        "traced": traced, "reports": reports,
        "files_without_capture": files_without_capture,
        "float32_engine": srv.engine, "bf16_engine": bf16,
    }


def test_an_engine_holds_its_weights_rounded_once(recording):
    """``engine.weights``: the weights are made in float32 and held as the
    family's cached forward reads them, the matrices in the activations'
    dtype and the norms' vectors in float32; one tree, and where nothing is
    wider than the activations, the tree as it was made."""
    import jax
    import jax.numpy as jnp

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    held = recording["bf16_engine"].params
    norms = {"ln1_g", "ln1_b", "ln2_g", "ln2_b"}
    for name, leaf in held["blocks"].items():
        assert leaf.dtype == (jnp.float32 if name in norms else jnp.bfloat16)
    assert held["wte"].dtype == held["wpe"].dtype == jnp.bfloat16
    assert held["ln_f_g"].dtype == held["ln_f_b"].dtype == jnp.float32
    (span,) = recording["spans"].named("engine.weights")
    assert span.args["leaves_rounded"] == 10  # wte, wpe, 4 matrices + biases
    assert span.args["held_bytes"] == nbytes(held)
    given = nbytes(recording["float32_engine"].params)  # the same shapes
    assert span.args["given_bytes"] == given
    assert given / 2 < span.args["held_bytes"] < given * 0.51
    # float32 activations: nothing to round
    assert {a.dtype for a in jax.tree.leaves(
        recording["float32_engine"].params)} == {jnp.dtype(jnp.float32)}


def _by_rid(spans, name):
    return {s.args["rid"]: s for s in spans.named(name)}


@pytest.mark.parametrize("name", [
    "engine.tick", *TICK_CHILDREN, "engine.admit", *ADMIT_CHILDREN,
    "engine.finish", "engine.idle", "engine.weights", "llm.request",
    "train.step", *STEP_CHILDREN, "train.report", "train.checkpoint",
])
def test_every_span_is_in_the_trace(recording, name):
    assert recording["spans"].named(name), f"no {name} span in the capture"


@pytest.mark.parametrize("parent,expected", [
    ("engine.tick", TICK_CHILDREN),
    ("engine.admit", ADMIT_CHILDREN),
])
def test_engine_children_lie_inside_their_parent_in_order(
        recording, parent, expected):
    from benchmarks.lib import host_spans

    line = recording["engine"]
    parents = [s for s in line if s.name == parent]
    assert parents
    for p in parents:
        kids = [s for s in host_spans.children(line, p)
                if s.name in expected]
        assert [s.name for s in kids] == expected, (p, kids)
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns
    # ticks are numbered by the counter and follow one another
    if parent == "engine.tick":
        numbers = [p.args["tick"] for p in parents]
        assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
        assert all(p.args["compiled"] == 0 for p in parents), (
            "a program was built inside a tick of the second, warm run")


def test_train_children_lie_inside_their_step_in_order(recording):
    from benchmarks.lib import host_spans

    line = recording["train"]
    steps = [s for s in line if s.name == "train.step"]
    assert [s.args["step_num"] for s in steps] == [0, 1, 2, 3]
    for i, step in enumerate(steps):
        kids = [s.name for s in host_spans.children(line, step)
                if s.name.startswith("train.")]
        last = "train.checkpoint" if i == 3 else "train.report"
        assert kids == STEP_CHILDREN + [last], kids
    reports = recording["reports"]
    assert [m["step"] for m in reports] == [1, 2, 3, 4]
    # the second run of the same loop builds its programs anew (a new jit
    # of a new closure), but none between two steps once it is warm
    assert reports[-1]["compiles"] == reports[1]["compiles"]


def test_one_request_one_rid(recording):
    spans = recording["spans"]
    requests = _by_rid(spans, "llm.request")
    admits, finishes = _by_rid(spans, "engine.admit"), _by_rid(
        spans, "engine.finish")
    assert len(spans.named("engine.admit")) == len(admits) == 5
    assert set(admits) == set(finishes)
    served = {recording["traced"][k]["id"] for k in ("a", "b", "eos")}
    assert served <= set(requests) and len(requests) == 4
    # the one handed to the engine directly has an engine-local number
    (direct,) = set(admits) - set(requests)
    assert direct.startswith("engine-")
    streamed = [r for r in requests.values() if r.args["stream"] == 1]
    assert len(streamed) == 1 and streamed[0].args["rid"] in admits
    for rid, admit in admits.items():
        assert admit.end_ns <= finishes[rid].end_ns
        if rid in requests:
            assert requests[rid].start_ns <= admit.start_ns
            assert requests[rid].args["prompt_tokens"] == admit.args[
                "prompt_tokens"]


def test_reasons_agree_with_how_each_request_ended(recording):
    spans, traced, stats = (recording["spans"], recording["traced"],
                            recording["stats"])
    finishes = _by_rid(spans, "engine.finish")
    reason = {k: finishes[traced[k]["id"]].args["reason"]
              for k in ("a", "b", "eos")}
    assert reason == {"a": "length", "b": "length", "eos": "eos"}
    assert [traced[k]["choices"][0]["finish_reason"]
            for k in ("a", "b", "eos")] == ["length", "length", "stop"]
    assert traced["eos"]["usage"]["completion_tokens"] == 2
    assert traced["d"][1] == "stop" and len(traced["d"][0]) == 2
    reasons = sorted(f.args["reason"] for f in finishes.values())
    assert reasons == ["eos", "length", "length", "length", "stop"]
    assert [stats["finished_" + r] for r in (
        "length", "eos", "stop", "context")] == [3, 1, 1, 0]
    # the EOS and the stop token were made and counted, then cut off
    made = {f.args["rid"]: f.args["produced"] for f in finishes.values()}
    assert made[traced["eos"]["id"]] == 3
    assert sum(made.values()) == stats["requests"] + stats[
        "tokens_generated"]


def test_counters_equal_the_sums_of_the_spans_arguments(recording):
    spans, stats = recording["spans"], recording["stats"]
    admits, ticks = spans.named("engine.admit"), spans.named("engine.tick")
    assert stats["requests"] == len(admits) == 5
    assert stats["ticks"] == len(ticks)
    assert stats["slot_ticks"] == sum(t.args["active"] for t in ticks)
    assert stats["slot_ticks"] == stats["tokens_generated"]
    # and the slots that did not decode: the visits the kernel leaves out
    assert stats["slots_skipped"] == sum(
        t.args["slots"] - t.args["active"] for t in ticks) > 0
    # what the ticks needed of the cache: every active slot's length and
    # the column it writes, so more than a position a token
    assert stats["cache_positions"] == sum(
        t.args["cache_positions"] for t in ticks)
    assert stats["cache_positions"] > stats["slot_ticks"]
    # a model of one kind of layer: all of it of the full layers' cache,
    # none of a window's; every admission one program
    for name, want in (("cache_positions_full", stats["cache_positions"]),
                       ("cache_positions_window", 0)):
        assert stats[name] == sum(t.args[name] for t in ticks) == want
    assert {(t.args["layers_full"], t.args["layers_window"])
            for t in ticks} == {(
        recording["float32_engine"].model_config.num_layers, 0)}
    assert {a.args["chunks"] for a in admits} == {1}
    assert stats["queue_wait_s"] == pytest.approx(
        sum(a.args["queued_ms"] for a in admits) / 1e3, abs=1e-5)
    # the host's clock is read just outside the span: never less, and more
    # only by what the thread waited for the interpreter in between
    in_spans = sum(a.duration_ns for a in admits) / 1e9
    assert in_spans - 1e-4 <= stats["admit_s"] <= in_spans + 0.05
    for a in admits:
        assert a.args["prefix"] == "none" and a.args["bucket"] == 16
        assert a.args["slot"] in (0, 1)
    # two slots, five requests at once: someone waited for a whole answer
    assert max(a.args["queued_ms"] for a in admits) > min(
        t.duration_ns for t in ticks) / 1e6
    for f in spans.named("engine.finish"):
        assert 0 < f.args["first_token_ms"] <= f.args["total_ms"]


def test_idle_only_while_nothing_is_active_or_pending(recording):
    spans = recording["spans"]
    idles = spans.named("engine.idle")
    finishes = _by_rid(spans, "engine.finish")
    # a slot is active from its admission to the end of its answer: the
    # loop thread itself does both, so no sleep may start in between. A
    # request is pending from its submit; the loop looks at the queue and
    # then sleeps, and a submit may fall between the two (the interpreter
    # hands over every 5 ms), so that side gets 20 ms of grace.
    busy = []
    for rid, admit in _by_rid(spans, "engine.admit").items():
        submitted = admit.start_ns - admit.args["queued_ms"] * 1e6
        busy.append((min(submitted + 20e6, admit.start_ns),
                     finishes[rid].end_ns))
    for idle in idles:
        assert idle in recording["engine"]
        assert not any(lo <= idle.start_ns < hi for lo, hi in busy), idle
    first = min(lo for lo, _ in busy)
    assert any(i.start_ns < first for i in idles), (
        "the engine idled before the first request, inside the capture")


def test_without_a_capture_no_trace_and_the_same_tokens(recording):
    assert recording["files_without_capture"] == []
    assert _tokens(recording["plain"]) == _tokens(recording["traced"])
    same = [k for k in recording["stats"]
            if k not in ("queue_wait_s", "admit_s", "compiles")]
    assert {k: recording["stats"][k] for k in same} == {
        k: recording["plain_stats"][k] for k in same}
    # no thread was started for tracing
    assert not [t.name for t in threading.enumerate()
                if "trac" in t.name.lower() or "span" in t.name.lower()]


# ------------------------------------------------ the loop one tick ahead
# A recording of its own beside the sampled one: greedy requests are chip
# rows, and the loop dispatches tick k+1 before it has read tick k.


@pytest.fixture(scope="module")
def greedy_recording(recording, tmp_path_factory):
    """One greedy request alone, then five at once on two slots (one ends
    on a stop token, one is streamed), under a capture of their own, taken
    after the sampled recording's was closed."""
    import jax

    from benchmarks.lib import host_spans
    from ray_tpu.models import module_for

    config = LLMConfig(**_MODEL)
    cfg = config.model_config()
    # the init's times 8: at the init's own scale the toy's greedy answer
    # is one token over and over, and a stop has to be a token that comes late
    engine = DecodeEngine(config, params=jax.tree.map(
        lambda a: a * 8 if a.ndim >= 2 else a,
        module_for(cfg).init_params(cfg, jax.random.PRNGKey(0))))
    prompts = [[3 + i, 9, 40 + i, 7] for i in range(5)]
    probe = list(engine.generate(prompts[1], SamplingParams(
        max_new_tokens=8)))
    stop = probe[4]
    assert stop not in probe[:4]
    logdir = str(tmp_path_factory.mktemp("spans_greedy"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    before = dict(engine.stats)
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        alone = list(engine.generate(
            [5, 6, 7, 8, 9, 10, 11], SamplingParams(max_new_tokens=9)))
        first = {k: engine.stats[k] - before[k] for k in before}
        # long: its slot is not the one that frees when the stop is seen
        streamed = engine.submit_stream(
            prompts[0], SamplingParams(max_new_tokens=14))
        futures = [engine.submit(p, SamplingParams(
            max_new_tokens=n, stop_token_ids=s))
            for p, n, s in zip(prompts[1:], (12, 10, 3, 6),
                               ((stop,), (), (), ()))]
        answers = [list(streamed)] + [list(f.result(120)) for f in futures]
    finally:
        jax.profiler.stop_trace()
    stats = {k: engine.stats[k] - before[k] for k in before}
    engine.shutdown()
    spans = host_spans.load(logdir)
    line = max(spans.lines,
               key=lambda l: sum(s.name == "engine.tick" for s in l))
    return {"spans": spans, "line": line, "stats": stats, "first": first,
            "alone": alone, "answers": answers, "probe": probe}


def test_ahead_counters_equal_the_sums_of_the_spans_arguments(
        greedy_recording):
    spans, stats = greedy_recording["spans"], greedy_recording["stats"]
    ticks = spans.named("engine.tick")
    assert stats["ticks"] == len(ticks) > 0
    assert stats["slot_ticks"] == sum(t.args["active"] for t in ticks)
    assert stats["slots_skipped"] == sum(
        t.args["slots"] - t.args["active"] for t in ticks)
    assert stats["ticks_ahead"] == sum(t.args["ahead"] for t in ticks)
    assert stats["overrun_rows"] == sum(t.args["overrun"] for t in ticks)
    assert stats["slot_ticks"] == stats["tokens_generated"] + stats[
        "overrun_rows"]
    assert stats["cache_positions"] == sum(
        t.args["cache_positions"] for t in ticks)
    # six requests' ticks, and only the first after the engine was idle or
    # had admitted did not run ahead
    assert stats["requests"] == 6
    assert stats["ticks_ahead"] >= stats["ticks"] - 6 - 1
    # the answer that ended on its stop token, seen one tick late while
    # three requests waited for a slot (so no admission read first)
    assert greedy_recording["answers"][1] == greedy_recording["probe"][:4]
    assert stats["finished_stop"] == 1 and stats["overrun_rows"] == 1
    assert all(t.args["compiled"] == 0 for t in ticks)


def test_a_tick_span_holds_its_own_dispatch_and_the_read_of_the_tick_before(
        greedy_recording):
    from benchmarks.lib import host_spans

    line = greedy_recording["line"]
    ticks = [s for s in line if s.name == "engine.tick"]
    numbers = [t.args["tick"] for t in ticks]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    reads = [s for s in line if s.name == "engine.tick.read"]
    # every tick was read once, by its number, in order
    assert [r.args["tick"] for r in reads] == numbers
    assert not [s for s in line if s.name == "engine.tick.fetch"]
    for t in ticks:
        kids = [s for s in host_spans.children(line, t)
                if s.name.startswith("engine.tick.")]
        names = [s.name for s in kids]
        assert names == ["engine.tick.pack", "engine.tick.dispatch"] + [
            "engine.tick.read", "engine.tick.sample"] * t.args["ahead"], t
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns
        if t.args["ahead"]:
            # what the span read is the program before its own
            assert kids[2].args["tick"] == t.args["tick"] - 1
        assert t.args["experts_touched"] == t.args["moe_rows"] == 0
    # a read with no dispatch around it: before an admission, or when no
    # slot is left to decode
    assert sum(t.args["ahead"] for t in ticks) < len(reads) == len(ticks)
    assert len([s for s in line if s.name == "engine.tick.sample"]) == len(
        reads)


def test_a_tick_spans_counters_are_those_of_the_program_it_dispatched(
        greedy_recording):
    """One request alone, a prompt of 7 and 9 tokens: eight ticks, one slot
    each, the j-th over 7 + j cached positions and the column it writes,
    all known at the dispatch; all but the first ran ahead."""
    first = greedy_recording["first"]
    assert len(greedy_recording["alone"]) == 9
    assert first["ticks"] == 8 and first["ticks_ahead"] == 7
    ticks = greedy_recording["spans"].named("engine.tick")[:8]
    assert [t.args["active"] for t in ticks] == [1] * 8
    assert [t.args["ahead"] for t in ticks] == [0] + [1] * 7
    assert [t.args["cache_positions"] for t in ticks] == [
        7 + j + 1 for j in range(8)]
    assert [t.args["overrun"] for t in ticks] == [0] * 8


def test_unattributed_idle_arithmetic():
    """The reduction the ``trace.idle_unattributed_share`` readers share,
    on numbers small enough to do by hand."""
    from benchmarks.lib import host_spans as H

    S = lambda name, start, dur: H.Span(name, start, dur, {})  # noqa: E731
    line = [S("engine.tick", 0, 100), S("engine.tick.pack", 0, 10),
            S("engine.tick.fetch", 20, 60), S("engine.idle", 120, 30)]
    assert [s.name for s in H.leaves(line)] == [
        "engine.tick.pack", "engine.tick.fetch", "engine.idle"]
    gaps = [(5, 25), (70, 130), (200, 210)]
    # (5,25): pack 5-10 and fetch 20-25 = 10; (70,130): fetch 70-80 and
    # idle 120-130 = 20; (200,210): nothing
    assert H.overlap_ns(gaps, H.leaves(line)) == 30
    assert [s.name for s in H.children(line, line[0])] == [
        "engine.tick.pack", "engine.tick.fetch"]
