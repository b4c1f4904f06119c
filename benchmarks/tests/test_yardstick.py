"""The peaks table, the FLOP and byte functions and the traffic generator,
each against a count made by hand."""
import json
import math
import os

import pytest

from benchmarks.lib import costs, named, peaks, traffic
from benchmarks.lib.loadgen import percentile

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "tiny", "benchmarks")


def _named(key, name, bench_dir=named.BENCH_DIR):
    return named.load(named.find(key, name, bench_dir, "the yardstick's test"))


MEDIUM = {"vocab_size": 50304, "max_seq_len": 1024, "num_layers": 24,
          "num_heads": 16, "embed_dim": 1024}
XL = {"vocab_size": 50304, "max_seq_len": 1024, "num_layers": 48,
      "num_heads": 25, "embed_dim": 1600}


def test_v5e_peaks_and_unknown_device_is_an_error():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9000")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_param_count_by_hand():
    # medium: a block holds 12 E^2 of matrices (4 E^2 attention, 8 E^2 MLP)
    # and 13 E of vectors (qkv 3E, proj E, fc 4E, out E, two LNs 4E)
    gpt2 = _named("costs", "gpt2")
    n = gpt2.param_count(MEDIUM)
    assert n["block_matrices"] == 24 * 12 * 1024 * 1024 == 301_989_888
    assert n["embedding"] == 50304 * 1024 == 51_511_296
    assert n["total"] == (301_989_888 + 24 * 13 * 1024 + 51_511_296
                          + 1024 * 1024 + 2 * 1024) == 354_871_296
    # xl: published as "1.5B"; with the padded vocabulary 1,557,686,400
    assert gpt2.param_count(XL)["total"] == (
        48 * (12 * 1600 * 1600 + 13 * 1600) + 50304 * 1600 + 1024 * 1600
        + 3200) == 1_557_686_400


def test_train_flops_per_token_by_hand():
    # medium, T=1024: 6 x (301,989,888 + 51,511,296) + 6 x 24 x 1024 x 1024
    want = 6 * 353_501_184 + 6 * 24 * 1024 * 1024
    assert _named("costs", "gpt2").train_flops_per_token(MEDIUM, 1024) == want
    assert want == 2_272_002_048  # 2.27 GFLOP a token
    # at 36.5k tokens/s (PR 21's bare loop) that is 42% of 197 TFLOP/s
    assert math.isclose(36_500 * want / 197e12, 0.421, abs_tol=1e-3)


def test_the_second_family_costs_by_hand():
    """The toy's ``costs/llama.py``, found under the toy's own directory:
    LLAMA_TINY is 2 layers, 64 wide, 4 query heads over 2 key/value heads of
    16, a gated MLP of 256 (8/3 x 64 = 170, rounded up to 128s), vocabulary
    512, head untied."""
    llama = _named("costs", "llama", TOY)
    tiny = {"vocab_size": 512, "max_seq_len": 128, "num_layers": 2,
            "num_heads": 4, "num_kv_heads": 2, "embed_dim": 64}
    n = llama.param_count(tiny)
    # a layer: wq, wo 64 x 64 each; wk, wv 64 x 32 each; three of 64 x 256
    assert n["block_matrices"] == 2 * (2 * 4096 + 2 * 2048 + 3 * 16384) \
        == 122_880
    assert n["embedding"] == n["head"] == 512 * 64 == 32_768
    assert n["total"] == 122_880 + 2 * 32_768 + (2 * 2 * 64 + 64) == 188_736
    assert llama.param_count({**tiny, "mlp_dim": 192})["block_matrices"] \
        == 2 * (2 * 4096 + 2 * 2048 + 3 * 64 * 192)
    # T=64: 6 x (122,880 + 32,768) + 6 x 2 x 64 x 64
    assert llama.train_flops_per_token(tiny, 64) == 933_888 + 49_152
    with pytest.raises(FileNotFoundError, match="'costs' names 'llama'"):
        _named("costs", "llama")  # the benchmark itself has no such family


def test_flash_cost_by_hand():
    # medium's step on one chip: [16, 1024, 16, 64] bf16, causal
    fwd = costs.flash_attention_cost(16, 1024, 16, 64, backward=False)
    product = 2 * 16 * 16 * 1024 * 1024 * 64 / 2  # one causal product
    assert fwd["flops"] == 2 * product == 34_359_738_368
    tensor = 16 * 1024 * 16 * 64 * 2
    assert fwd["bytes"] == 4 * tensor + 16 * 1024 * 16 * 4 == 135_266_304
    bwd = costs.flash_attention_cost(16, 1024, 16, 64, backward=True)
    assert bwd["flops"] == 5 * product
    assert bwd["bytes"] == 8 * tensor + 16 * 1024 * 16 * 4
    v5e = peaks.peaks_for("TPU v5 lite")
    roof = costs.roofline_seconds(fwd, v5e)
    # 34.4 GFLOP / 197 TFLOP/s = 174 us against 135 MB / 819 GB/s = 165 us
    assert roof["bound"] == "compute"
    assert math.isclose(roof["seconds"], 34_359_738_368 / 197e12)
    assert costs.roofline_seconds(
        {"flops": 1e9, "bytes": 1e9}, v5e)["bound"] == "memory"


MIX = {
    "rate_per_s": 6.0,
    "interarrival": {"dist": "exponential", "mean": 1.0},
    "prompt_tokens": {"dist": "lognormal", "median": 160, "sigma": 0.7,
                      "min": 32, "max": 512},
    "max_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                   "min": 16, "max": 256},
    "context_limit": 1024,
}


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.schedule(MIX, 1, 40.0)
    b = traffic.schedule(MIX, 2 ** 31 + 11, 40.0)  # beyond 32 signed bits
    assert len(a) == len(b) == 240
    sizes = lambda rs: sorted((r.prompt_tokens, r.max_tokens) for r in rs)  # noqa: E731
    assert sizes(a) == sizes(b)
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in b]
    gaps = lambda rs: sorted(round(y.due_s - x.due_s, 9)  # noqa: E731
                             for x, y in zip(rs, rs[1:]))
    assert a[0].due_s == b[0].due_s == 0.0
    assert all(0 <= r.due_s < 40.0 for r in a + b)
    assert abs(sum(gaps(a)) - sum(gaps(b))) < 40.0 / 240 * 8
    assert traffic.schedule(MIX, 1, 40.0) == a  # the seed decides all
    for r in a:
        assert 32 <= r.prompt_tokens <= 512 and 16 <= r.max_tokens <= 256
        assert len(r.prompt.encode()) == r.prompt_tokens
    prompts = sorted(r.prompt_tokens for r in a)
    assert 150 <= prompts[120] <= 170  # the median asked for


def test_quantiles_of_each_distribution():
    q = traffic.quantiles({"dist": "exponential", "mean": 2.0}, 1000)
    assert abs(q.mean() - 2.0) < 0.02
    assert list(traffic.quantiles({"dist": "fixed", "value": 7}, 3)) == [7] * 3
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "zipf"}, 3)


def test_percentile_is_nearest_rank():
    assert percentile(list(range(1, 101)), 95) == 95
    assert percentile([5.0], 95) == 5.0
    assert percentile([1, 2, 3, 4], 50) == 2
    with pytest.raises(ValueError):
        percentile([], 95)


def _counters(requests, tokens_generated):
    return {"requests": requests, "tokens_generated": tokens_generated,
            "ticks": 0}


def _answers(*lengths):
    return [None if n is None else [7] * n for n in lengths]


@pytest.mark.parametrize("name, asked, made, recount, remade, want", [
    # 3 requests asked for 4 + 6 + 8 = 18 tokens; the first of each is the
    # prefill's, so the engine counts 3 requests and 15 generated tokens
    ("every token made", [4, 6, 8], 15, None, None, (True, [4, 6, 8])),
    # one token short and nothing to explain it
    ("a token dropped", [4, 6, 8], 14, None, None, (False, [4, 6, 8])),
    # the second request made 2 tokens and EOS, which the engine counts and
    # cuts off the answer: 4 + 3 + 8 = 15 made, 12 of them generated, and
    # the recount made the same again
    ("an EOS stop", [4, 6, 8], 12, _answers(4, 2, 8), 12,
     (True, [4, 3, 8])),
    # EOS as the very first token: an answer of nothing, one token made
    ("EOS at once", [4, 6, 8], 10, _answers(4, 0, 8), 10,
     (True, [4, 1, 8])),
    # the recount finds every request whole, yet the schedule made fewer
    ("a stream cut short", [4, 6, 8], 12, _answers(4, 6, 8), 15,
     (False, [4, 6, 8])),
    # a recount the engine's counters do not bear out
    ("a recount miscounted", [4, 6, 8], 12, _answers(4, 2, 8), 15,
     (False, [4, 6, 8])),
    # one request of the recount failed
    ("a recount unanswered", [4, 6, 8], 12, _answers(4, None, 8), 12,
     (False, [4, 6, 8])),
    ("a request unanswered", [4, None, 8], 15, None, None,
     (False, [4, None, 8])),
])
def test_tokens_per_request(name, asked, made, recount, remade, want):
    from benchmarks.runners.serve_open_loop import tokens_per_request

    got = tokens_per_request(
        asked, _counters(len(asked), made), recount,
        None if remade is None else _counters(len(asked), remade))
    assert got == want, name


@pytest.mark.parametrize("name,due,want", [
    ("an arrival well inside: captured where it always was",
     [0.5, 5.0, 20.0], 3.0),
    ("arrivals only at the capture's edges: moved to the first",
     [3.2, 6.9, 20.0], 3.9),
    ("the schedule's longest gap over the old place", [1.0, 9.0], 6.0),
    ("no arrival it could hold before the window closes", [1.0, 49.5], 3.0),
])
def test_capture_start_by_hand(name, due, want):
    from benchmarks.runners.serve_open_loop import capture_start_s

    assert capture_start_s(due, 3.0, 4.0, 50.0) == pytest.approx(want), name


@pytest.mark.parametrize("seed", [
    7, 3000000019, 2147483671, 2147483683, 2147483685])
def test_capture_holds_an_arrival_in_the_serving_cell(seed):
    """The last three seeds have no arrival within 2.5-7.5 s of the
    opening, where the capture used to lie whatever the schedule: their
    traces held no ``engine.admit`` for ``engine.admit_stall_ms`` to read."""
    from benchmarks.runners.serve_open_loop import capture_start_s

    with open(os.path.join(named.BENCH_DIR, "workloads",
                           "gpt2-xl.serve-chat.json")) as f:
        mix = json.load(f)["traffic"]
    span, window = float(mix["trace_seconds"]), 50.0
    due = [r.due_s for r in traffic.schedule(mix, seed, window)]
    start = capture_start_s(
        due, float(mix["trace_after_seconds"]), span, window)
    assert start >= float(mix["trace_after_seconds"])
    assert start + span <= window
    assert any(start + span / 4 - 1e-9 <= t <= start + 3 * span / 4 + 1e-9
               for t in due)


def test_recount_reads_the_tokens_of_a_unary_answer(monkeypatch):
    """The recount sends each request again, unary with ``logprobs: 1``,
    and returns the tokens of its answer: None where it failed or holds
    more than was asked for."""
    import asyncio

    from benchmarks.lib import loadgen
    from benchmarks.runners import serve_open_loop

    answers = {"whole": [7, 7, 7, 7], "short": [7, 7], "nothing": [],
               "too many": [7] * 5, "failed": None}
    sent = []

    async def post(session, url, payload, out):
        sent.append(payload)
        tokens = answers[payload["prompt"]]
        if tokens is not None:
            out.status, out.finished = 200, True
            # the program leaves ``logprobs`` out of an answer of no token
            out.body = {"choices": [
                {"logprobs": {"tokens": tokens}} if tokens else {}]}
        return out

    monkeypatch.setattr(loadgen, "post", post)
    requests = [traffic.Request(0.0, name, 5, 4) for name in answers]
    got = asyncio.run(serve_open_loop._recount(
        None, "url", requests, {"stream": True, "temperature": 0.0}, 2))
    assert got == [[7, 7, 7, 7], [7, 7], [], None, None]
    assert all(p["stream"] is False and p["logprobs"] == 1
               and p["temperature"] == 0.0 and p["max_tokens"] == 4
               for p in sent)


def test_greedy_gaps_by_hand():
    """0 where the token is the reference's own greedy choice, else how far
    under it in log-probability; a one-layer model of 5 tokens."""
    import jax
    import numpy as np

    from benchmarks.lib import reference
    from ray_tpu.models.gpt2 import GPT2Config, init_params

    logits = _named("reference", "gpt2").logits

    cfg = GPT2Config(vocab_size=5, max_seq_len=8, num_layers=1, num_heads=1,
                     embed_dim=8)
    params = init_params(cfg, jax.random.PRNGKey(3))
    tokens = np.array([[2, 4, 1, 3, 0, 2]], np.int32)
    logp = np.asarray(jax.nn.log_softmax(
        logits(params, tokens[:, :-1]), axis=-1))[0]
    gaps = np.asarray(reference.greedy_gaps(logits, params, tokens))[0]
    for t in range(5):
        want = logp[t].max() - logp[t, tokens[0, t + 1]]
        assert gaps[t] == pytest.approx(want, abs=1e-6) and gaps[t] >= 0
    greedy = tokens.copy()
    greedy[0, 3] = logp[2].argmax()  # position 3 now holds the greedy choice
    assert np.asarray(
        reference.greedy_gaps(logits, params, greedy))[0, 2] == 0
