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
ADMIT_CHILDREN = ["engine.admit.cache", "engine.prefill.dispatch",
                  "engine.prefill.fetch", "engine.prefill.sample",
                  "engine.insert"]
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


# a model with a prediction layer and a router's bias under its rule
# (``models/joyai_llm_flash.py``), as a configuration file states one
_MTP_MODEL = dict(
    family="joyai_llm_flash", vocab_size=128, max_seq_len=16, num_layers=2,
    num_heads=2, embed_dim=32, mlp_dim=64, moe_mlp_dim=16, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    attention_impl="xla", moe_num_experts=8, moe_top_k=2,
    moe_score_func="sigmoid", moe_route_scale=2.5,
    moe_expert_bias_init_std=0.02, moe_bias_update_rate=0.001,
    moe_aux_loss_weight=0.0, moe_num_held=2, moe_dropless=True)


def _train(run_dir, num_steps=4, model=None):
    from ray_tpu.train.context import TrainContext, _set_context
    from ray_tpu.train.trainer import default_jax_train_loop

    ctx = TrainContext(0, 1, 0, 1, 0, "spans", run_dir)
    result = {}

    def work():
        _set_context(ctx)
        try:
            result["out"] = default_jax_train_loop({
                "model": model or dict(
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
    # an engine that an earlier file of this worker left running idles for
    # 30 s before its loop parks, and its ``engine.idle`` spans would lie in
    # this capture on a line of their own (``test_llm.py`` leaves nineteen)
    parked = time.monotonic() + 45
    while time.monotonic() < parked and any(
            t.name == "rt-llm-engine" for t in threading.enumerate()):
        time.sleep(0.1)
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
        reports_mtp = _train(os.path.join(root, "run_mtp"), 3, _MTP_MODEL)
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
        "traced": traced, "reports": reports, "reports_mtp": reports_mtp,
        "files_without_capture": files_without_capture,
        "float32_engine": srv.engine, "bf16_engine": bf16, "logdir": logdir,
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
    "llm.done", "train.step", *STEP_CHILDREN, "train.report", "train.checkpoint",
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
    # the dense run's four steps; the run with a prediction layer that
    # follows it may come to lie on the same line (a thread's id used again)
    steps = [s for s in line if s.name == "train.step"][:4]
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


def test_a_prediction_layers_loss_and_the_rules_counters_ride_the_fetch(
        recording):
    """A model with a prediction layer behind the trunk and a router's bias
    under its rule: what its step counts beside the main loss is on each
    ``train.loss_fetch`` span and in each report, and nowhere in the dense
    model's."""
    spans = recording["spans"]
    keys = {"aux_loss", "mtp_loss", "moe_rows_held", "moe_rows_max_expert",
            "moe_rows_max_all", "moe_bias_abs_mean"}
    fetches = spans.named("train.loss_fetch")
    routed = [s for s in fetches if "mtp_loss" in s.args]
    assert len(routed) == 3 and len(fetches) == 4 + 3
    reports = recording["reports_mtp"]
    assert [m["step"] for m in reports] == [1, 2, 3]
    for span, report in zip(routed, reports):
        assert keys <= set(span.args) and keys <= set(report)
        assert float(span.args["mtp_loss"]) == pytest.approx(
            report["mtp_loss"])
        # 2 routed layers (one the prediction layer's) x 8 x 16 tokens x 2
        assert 2 * 256 / 8 <= report["moe_rows_max_all"] <= 2 * 256
        assert 0 < report["moe_bias_abs_mean"] < 0.1
        assert report["aux_loss"] == 0.0
    assert not keys & set().union(*(
        set(s.args) for s in fetches if s not in routed))
    assert not keys & set(recording["reports"][0])


def test_the_train_steps_operations_carry_the_new_scopes():
    """What a trace's reader finds latent attention's projections and the
    prediction layer by (``benchmarks/lib/train_mla.py``): every scope is on
    some operation of the compiled train step, the prediction layer's in
    the backward pass too."""
    import jax

    from ray_tpu.models import config_for
    from ray_tpu.train.step import (
        OptimizerConfig, create_train_state, make_train_step)

    model = dict(_MTP_MODEL)
    cfg = config_for(model.pop("family"), **model)
    opt = OptimizerConfig().build()
    state = jax.eval_shape(
        lambda: create_train_state(cfg, opt, jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 17), "int32")}
    text = make_train_step(cfg, opt).lower(state, batch).compile().as_text()
    for scope in ("mla.q", "mla.down", "mla.up", "mla.out", "mtp.in",
                  "mtp.block", "mtp.head", "moe.route", "moe.shared"):
        assert scope in text, scope
    assert "transpose(jvp(mtp.block))" in text


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


def test_every_requests_ledger_closes(recording):
    """``engine.finish`` says what its request cost beyond its decode ticks:
    the wait for its admission, the admission, and the admissions of others
    that held its slot; what is left of ``total_ms`` is ticks and reads."""
    spans = recording["spans"]
    admits, finishes = _by_rid(spans, "engine.admit"), _by_rid(
        spans, "engine.finish")
    assert len(finishes) == 5
    for rid, f in finishes.items():
        a, admit = f.args, admits[rid]
        # each part is rounded to the microsecond on its own
        assert a["queued_ms"] + a["admit_ms"] + a["stalled_ms"] <= a[
            "total_ms"] + 0.002, a
        assert a["first_token_ms"] == pytest.approx(
            a["queued_ms"] + a["admit_ms"], abs=1.0)
        assert a["queued_ms"] == admit.args["queued_ms"]
        assert a["prompt_tokens"] == admit.args["prompt_tokens"]
        # its own admission up to its first token: the span, whose clock
        # starts after the engine's by what the thread waited for the
        # interpreter in between
        assert 0 < a["admit_ms"] <= admit.duration_ns / 1e6 + 50
        assert a["stalled_ms"] >= 0 and a["produced"] >= 1
    # two slots: an admission holds the other slot or none
    assert {a.args["held"] for a in admits.values()} == {0, 1}


def test_stalled_seconds_equal_both_sums_of_the_spans(recording):
    """``stalled_s``: an admission's length times the slots it held, summed
    where ``admit_s`` is; the same time again as the ``stalled_ms`` of the
    requests that stood still (all five were admitted and finished inside
    the capture)."""
    spans, stats = recording["spans"], recording["stats"]
    admits = spans.named("engine.admit")
    by_admission = sum(
        a.duration_ns * a.args["held"] for a in admits) / 1e9
    held = sum(a.args["held"] for a in admits)
    assert held >= 1 and stats["stalled_s"] > 0
    # the host's clock is read just outside the span (see ``admit_s``)
    assert by_admission - 1e-4 <= stats["stalled_s"] <= (
        by_admission + 0.05 * held)
    by_request = sum(
        f.args["stalled_ms"] for f in spans.named("engine.finish")) / 1e3
    assert stats["stalled_s"] == pytest.approx(by_request, abs=1e-5)
    assert stats["stalled_s"] <= stats["admit_s"]  # one other slot at most


def test_one_request_and_one_done_a_served_request(recording):
    spans, traced = recording["spans"], recording["traced"]
    requests, dones = _by_rid(spans, "llm.request"), _by_rid(
        spans, "llm.done")
    finishes = _by_rid(spans, "engine.finish")
    assert len(spans.named("llm.request")) == len(requests) == 4
    assert len(spans.named("llm.done")) == len(dones) == 4
    assert set(requests) == set(dones) <= set(finishes)
    for rid, done in dones.items():
        request, finish = requests[rid], finishes[rid]
        assert done.args["stream"] == request.args["stream"]
        assert done.args["tokens"] <= request.args["max_tokens"]
        # ``submit`` takes the engine's lock, which the loop holds for a
        # whole turn: on a short answer ``llm.request`` may end after the
        # ``engine.finish``, never begin after it
        assert request.start_ns <= finish.start_ns <= done.end_ns
        assert 0 <= request.args["since_call_ms"] < 1000
        # the finish stamps its time just before its span opens
        assert 0 <= done.args["after_finish_ms"] <= (
            done.end_ns - finish.start_ns) / 1e6 + 50
    assert sorted(r.args["max_tokens"] for r in requests.values()) == [
        4, 5, 6, 7]  # as asked: "s", "a", "eos", "b"
    for key in ("a", "b", "eos"):
        done = dones[traced[key]["id"]].args
        assert (done["stream"], done["chunks"]) == (0, 1)
        assert done["tokens"] == traced[key]["usage"]["completion_tokens"]
    (streamed,) = [d.args for d in dones.values() if d.args["stream"]]
    # every line the stream sent, ``[DONE]`` among them
    assert streamed["chunks"] == len(traced["s"])
    assert traced["s"][-1] == "data: [DONE]\n\n"
    assert streamed["tokens"] == 4


def _mean(values):
    values = list(values)
    return sum(values) / len(values)


READERS = {
    "engine.request_ms_per_token": lambda spans: sorted(
        f.args["total_ms"] / f.args["produced"]
        for f in spans.named("engine.finish"))[2],  # the median of five
    "engine.queue_wait_ms": lambda spans: _mean(
        a.args["queued_ms"] for a in spans.named("engine.admit")),
    "engine.stalled_share": lambda spans: 100 * sum(
        f.args["stalled_ms"] for f in spans.named("engine.finish")) / sum(
        f.args["total_ms"] for f in spans.named("engine.finish")),
    # off the TPU the capture holds no program of a chip
    "engine.admit_device_ms": lambda spans: None,
    "engine.admit_cache_ms": lambda spans: _mean(
        s.duration_ns for s in spans.named("engine.admit.cache")) / 1e6,
    "serve.submit_delay_ms": lambda spans: _mean(
        r.args["since_call_ms"] for r in spans.named("llm.request")),
    "serve.deliver_ms": lambda spans: _mean(
        d.args["after_finish_ms"] for d in spans.named("llm.done")),
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_reader_returns_the_hand_sum_over_the_spans(
        recording, monkeypatch, metric):
    """The benchmark's seven readers of the request's ledger
    (``benchmarks/layer_metrics/<metric>.py``), over the CPU recording."""
    import json

    from benchmarks import run
    from benchmarks.lib import host_spans

    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert listed[metric]["moves"] == "per_token_p50_ms"
    # every serving cell, none dropped: the cells that report the metric
    # these move (the three of PR 36, PR 42's, PR 47's, PR 51's and PR 57's)
    serving = next(m["workloads"] for m in bench["end_to_end"]
                   if m["name"] == "per_token_p50_ms")
    assert len(serving) == 7
    assert listed[metric]["workloads"] == serving
    monkeypatch.setattr(host_spans, "TRACE_ROOT", recording["logdir"])
    got = run.read_layer_metric(
        metric, None, {"decode_program": "jit_decode"})
    want = READERS[metric](recording["spans"])
    assert got == (None if want is None else pytest.approx(want, rel=1e-9))
    assert want is None or want > 0 or metric == "engine.stalled_share"


def test_a_reader_finds_nothing_where_there_is_no_trace(
        monkeypatch, tmp_path):
    from benchmarks import run
    from benchmarks.lib import host_spans

    monkeypatch.setattr(host_spans, "TRACE_ROOT", str(tmp_path))
    for metric in READERS:
        assert run.read_layer_metric(
            metric, None, {"decode_program": "jit_decode"}) is None


def test_an_admissions_device_time_is_its_own_programs():
    """``request_spans.admit_device_ms`` on numbers small enough to do by
    hand: the programs whose enqueue starts inside the span, an admission
    with a program the capture does not hold left out, and a decode program
    inside an admission an error."""
    from benchmarks.lib import host_spans as H
    from benchmarks.lib import request_spans as R

    first = H.Span("engine.admit", 100, 50, {"rid": "a"})
    second = H.Span("engine.admit", 300, 40, {"rid": "b"})
    programs = [
        (90, "jit_decode", 9_000_000),        # before: the tick in flight
        (110, "jit_broadcast_in_dim", 1_000_000),
        (120, "jit_prefill", 5_000_000), (149, "jit_insert", 2_000_000),
        (150, "jit_decode", 9_000_000),       # at the span's end: outside
        (310, "jit_prefill", 3_000_000), (339, None, 0),
    ]
    assert R.admit_device_ms([first], programs, "jit_decode") == 8.0
    # the second's insert ran after the capture's end: not whole
    assert R.admit_device_ms([first, second], programs, "jit_decode") == 8.0
    assert R.admit_device_ms([second], programs, "jit_decode") is None
    assert R.admit_device_ms([first], [], "jit_decode") is None
    with pytest.raises(RuntimeError, match="reads the tick in flight"):
        R.admit_device_ms([first], programs + [(130, "jit_decode(7)", 1)],
                          "jit_decode")


def test_a_recorded_admission_pairs_with_its_programs_by_run_id():
    """On a capture taken on the chip (PR 24's recording of a toy engine):
    every admission's programs, by the runtime's ``run_id`` and no shifted
    clock, held against a walk over the trace written out here."""
    from jax.profiler import ProfileData

    from benchmarks import run
    from benchmarks.lib import host_spans as H
    from benchmarks.lib import request_spans as R

    path = os.path.join(run.BENCH_DIR, "tests", "data",
                        "v5e_1chip_spans.xplane.pb")
    admits = H.load(path).named("engine.admit")
    assert len(admits) == 4
    ran, enqueues = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name == "/device:TPU:0" and (
                        line.name == "XLA Modules"):
                    ran[dict(ev.stats)["run_id"]] = (ev.name, ev.duration_ns)
                elif ev.name == "DoEnqueueProgram":
                    enqueues.append((ev.start_ns, dict(ev.stats)["run_id"]))
    per_admit = []
    for admit in admits:
        inside = sorted((start, *ran[run_id]) for start, run_id in enqueues
                        if admit.start_ns <= start < admit.end_ns)
        names = [name.split("(")[0] for _, name, _ in inside]
        # two ``zeros``, the prefill; the insert's enqueue may fall just
        # after the span (the fourth's does)
        assert names[:7] == [
            "jit_convert_element_type", "jit_broadcast_in_dim"] * 2 + [
            "jit_convert_element_type"] * 2 + ["jit_prefill"], names
        assert names[7:] in ([], ["jit_insert"])
        per_admit.append(sum(ns for _, _, ns in inside))
    found = R.enqueued(path)
    assert [sum(a.start_ns <= start < a.end_ns for start, _, _ in found)
            for a in admits] == [8, 8, 8, 7]
    assert R.admit_device_ms(admits, found, "jit_decode") == pytest.approx(
        sum(per_admit) / 4 / 1e6, rel=1e-12)


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
    # what five threads at once cannot move: how many ticks the answers
    # took and who shared them follows how the submissions fell
    same = ["requests", "tokens_generated", "finished_length",
            "finished_eos", "finished_stop", "finished_context"]
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


# A third recording, after the two before it were closed: a model whose
# layers hold a SHARE of group-routed experts, keep states and attend a
# latent cache (``models/bailing_hybrid.py``), for the two counters only
# such a model's ``engine.tick`` carries.


def _recorded(family, logdir):
    """Three requests (37, 6 and 20 prompt tokens, 9 answer tokens each) on
    the family's row of ``tests/families.py`` under one capture."""
    import jax

    from benchmarks.lib import host_spans
    from tests.families import TINY

    engine = DecodeEngine(LLMConfig(**TINY[family]))
    prompts = [[3 + i] * n for i, n in enumerate((37, 6, 20))]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    before = dict(engine.stats)
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        futures = [engine.submit(p, SamplingParams(max_new_tokens=9))
                   for p in prompts]
        answers = [list(f.result(300)) for f in futures]
    finally:
        jax.profiler.stop_trace()
    stats = {k: engine.stats[k] - before[k] for k in before}
    engine.shutdown()
    return {"spans": host_spans.load(logdir), "stats": stats,
            "answers": answers}


@pytest.fixture(scope="module")
def share_recording(greedy_recording, tmp_path_factory):
    return _recorded("bailing_hybrid",
                     str(tmp_path_factory.mktemp("spans_share")))


def test_a_share_models_ticks_carry_held_rows_and_latent_positions(
        share_recording):
    """``moe_rows_held`` (of the programs read since the span before, as
    ``experts_touched``: the program's own count of the pairs the held
    experts computed) and ``latent_positions`` (what the tick needed of the
    latent layers' cache: known at the dispatch) are on every ``engine.tick``
    of the capture, and ``stats`` sums the same quantities."""
    spans, stats = share_recording["spans"], share_recording["stats"]
    ticks = spans.named("engine.tick")
    assert stats["ticks"] == len(ticks) > 0
    assert all(len(a) == 9 for a in share_recording["answers"])
    assert stats["latent_positions"] == sum(
        t.args["latent_positions"] for t in ticks) > 0
    # the toy's latent layers, and none that holds keys and values a head
    from tests import families

    kinds = families.kinds("bailing_hybrid")
    latent = sum(k.latent is not None for k in kinds)
    assert all(t.args["latent_positions"] == latent * t.args["cache_positions"]
               and t.args["layers_full"] == 0 for t in ticks)
    # every decode program's held rows are on some tick's span; the
    # admissions' (three prompts' chunks) are in ``stats`` beside them
    held = sum(t.args["moe_rows_held"] for t in ticks)
    assert 0 < held <= stats["moe_rows_held"]
    assert sum(t.args["moe_rows"] for t in ticks) == (
        stats["slot_ticks"] * 4 * sum(k.routed for k in kinds))
    assert held < sum(t.args["moe_rows"] for t in ticks)
    assert stats["moe_rows_held"] < stats["moe_rows"]


def test_a_model_that_holds_every_expert_carries_neither(greedy_recording):
    ticks = greedy_recording["spans"].named("engine.tick")
    assert ticks and not any(
        "moe_rows_held" in t.args or "latent_positions" in t.args
        for t in ticks)
    assert greedy_recording["stats"]["moe_rows_held"] == 0
    assert greedy_recording["stats"]["latent_positions"] == 0


# A fourth recording, after the three before it were closed: a model whose
# latent layers choose what they attend (``models/deepseek_v32.py``: 16
# positions kept), for the two counters only such a model's spans carry.


@pytest.fixture(scope="module")
def indexed_recording(share_recording, tmp_path_factory):
    from ray_tpu.ops import block_attention

    # blocks and sub-tiles of 8, so that a chunk of 16 has a diagonal (the
    # count is the host's: off the TPU no kernel attends)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(block_attention, "SELECTED_POSITIONS", 8)
        return _recorded("deepseek_v32",
                         str(tmp_path_factory.mktemp("spans_indexed")))


def test_an_indexed_models_ticks_carry_what_was_scored_and_what_was_read(
        indexed_recording):
    """``index_positions`` (every live slot's visible positions, a latent
    layer: what the tick needed of the latent cache, scored) and
    ``selected_positions`` (16 of them a slot, or all where a slot holds
    fewer) are on every ``engine.tick`` of the capture, known at the
    dispatch."""
    spans, stats = indexed_recording["spans"], indexed_recording["stats"]
    ticks = spans.named("engine.tick")
    assert stats["ticks"] == len(ticks) > 0
    assert all(len(a) == 9 for a in indexed_recording["answers"])
    # three latent layers, each with its indexer
    assert all(t.args["index_positions"] == t.args["latent_positions"]
               == 3 * t.args["cache_positions"] for t in ticks)
    assert all(3 * t.args["active"] <= t.args["selected_positions"]
               <= min(t.args["index_positions"], 3 * 16 * t.args["active"])
               for t in ticks)
    # the slot that holds 6 prompt tokens reads all it sees, the others 16
    assert any(t.args["selected_positions"] < 3 * 16 * t.args["active"]
               for t in ticks)
    assert any(t.args["selected_positions"] < t.args["index_positions"]
               for t in ticks)


def test_an_indexed_models_admissions_carry_them_and_stats_sums_both(
        indexed_recording):
    """An admission's chunks score every query's visible positions and read
    16 of them or all; ``stats`` sums the ticks' and the admissions'."""
    spans, stats = indexed_recording["spans"], indexed_recording["stats"]
    admits = spans.named("engine.admit")
    assert len(admits) == 3
    # prompts of 37, 6 and 20 tokens in chunks of 16 with their padding:
    # every padded query counted as the program computes it
    seen = {37: [(0, 16), (16, 16), (32, 8)], 6: [(0, 8)],
            20: [(0, 16), (16, 8)]}
    want = sorted(3 * sum(t + 1 for start, n in chunks
                          for t in range(start, start + n))
                  for chunks in seen.values())
    assert sorted(a.args["index_positions"] for a in admits) == want
    assert all(0 < a.args["selected_positions"] <= a.args["index_positions"]
               for a in admits)
    # the prompt of 6 in a bucket of 8: nothing is left out
    assert min(a.args["index_positions"] - a.args["selected_positions"]
               for a in admits) == 0
    both = admits + spans.named("engine.tick")
    for name in ("index_positions", "selected_positions"):
        assert stats[name] == sum(s.args[name] for s in both) > 0
    cache = spans.named("engine.admit.cache")
    assert len(cache) == 3 and {s.args["bytes"] for s in cache} == {
        3 * 128 * (40 + 16) * 4}


def test_an_indexed_models_spans_carry_the_positions_the_kernels_visit(
        indexed_recording):
    """``index_positions_read``: whole blocks of the keys of the slots that
    decode, and of what a chunk's tiles of queries see, and of nothing else.
    At 128 positions a slot one block is a slot's whole leaf: a tick reads it
    and the token's own key once a live slot and layer, whatever the three
    slots hold; a chunk reads it once a padded query. What was NEEDED
    (``index_positions``) is not what was read, and is as it was."""
    from ray_tpu.ops import index_select

    spans, stats = indexed_recording["spans"], indexed_recording["stats"]
    ticks, admits = spans.named("engine.tick"), spans.named("engine.admit")
    assert all(t.args["index_positions_read"] == 3 * (128 + 1)
               * t.args["active"] for t in ticks)
    assert all(t.args["index_positions"] == 3 * t.args["cache_positions"]
               < t.args["index_positions_read"] for t in ticks)
    padded = {37: 40, 6: 8, 20: 24}
    assert sorted(a.args["index_positions_read"] for a in admits) == sorted(
        3 * 128 * n for n in padded.values())
    assert stats["index_positions_read"] == sum(
        s.args["index_positions_read"] for s in ticks + admits)
    # whole blocks: a cache of 33,280 at the cell's sizes, on the host
    assert index_select.positions_read(12000, 1, 33280) == 2 * 6656 + 1
    assert index_select.positions_read(0, 1, 33280) == 1
    tile = index_select.QUERIES       # of queries; blocks of 512 positions
    assert index_select.positions_read(10240, 2048, 33280) == sum(
        tile * 512 * ((10240 + first + tile - 1) // 512 + 1)
        for first in range(0, 2048, tile))
    assert index_select.positions_read(32768, 2048, 33280) == 2048 * 33280


def test_an_indexed_models_admissions_carry_the_tiles_attention_computes(
        indexed_recording):
    """``sparse_tiles``: (sub-tile of 8 queries, block of 8 positions) pairs
    up to each chunk's last token's block, and ``sparse_tiles_computed``:
    those whose sub-tile's last query sees the block's first position, a
    latent layer. The prompt of 37 is three chunks, (0, 16), (16, 16) and
    (32, 8): 4 + 8 + 5 pairs, of which the second sub-tile's alone computes
    each chunk's last block where the chunk has two: 3 + 7 + 5."""
    spans, stats = indexed_recording["spans"], indexed_recording["stats"]
    admits = spans.named("engine.admit")
    assert sorted((a.args["sparse_tiles"], a.args["sparse_tiles_computed"])
                  for a in admits) == [(3 * 1, 3 * 1), (3 * 7, 3 * 6),
                                       (3 * 17, 3 * 15)]
    three_chunks = max(admits, key=lambda a: a.args["sparse_tiles"])
    assert three_chunks.args["chunks"] == 3
    assert three_chunks.args["sparse_tiles_computed"] / three_chunks.args[
        "sparse_tiles"] == pytest.approx(15 / 17)
    for name in ("sparse_tiles", "sparse_tiles_computed"):
        assert stats[name] == sum(a.args[name] for a in admits)
        assert not any(name in t.args for t in spans.named("engine.tick"))


def test_a_model_without_an_indexer_carries_neither_counter(
        greedy_recording, share_recording):
    for recording in (greedy_recording, share_recording):
        spans = recording["spans"]
        assert not any(
            "index_positions" in s.args or "selected_positions" in s.args
            or "index_positions_read" in s.args or "sparse_tiles" in s.args
            for s in spans.named("engine.tick") + spans.named("engine.admit"))
        assert recording["stats"]["index_positions"] == 0
        assert recording["stats"]["index_positions_read"] == 0
        assert recording["stats"]["sparse_tiles"] == 0
        assert recording["stats"]["selected_positions"] == 0
