"""``run.py`` end to end on the CPU at ``gpt2-tiny`` and ``llama-tiny``: both
runners, traced and untraced, and the four-chip cell on four virtual devices. The no-chip
failure is lifted only here (``platform="cpu"``); ``main()`` accepts only a
TPU. Nothing timed here is a device number."""
import json
import os

import pytest

from benchmarks import run

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")
SEED = 2 ** 31 + 7  # the driver's seeds do not fit 32 signed bits


def _cell(monkeypatch, name, trace, devices=1):
    # node processes inherit the flag: as many CPU devices as the cell has
    # chips, so the device count a run reports is the one it was given
    monkeypatch.setenv(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={devices}")
    if devices > 1:
        # XLA:CPU runs a program it LOADED from the persistent cache with its
        # collectives out of step between the virtual devices: three wait in
        # the step's all-gather, the fourth in its all-reduce, and the
        # runtime ends the worker after 40 s (every run but the one that
        # compiles). A program compiled in the process does not.
        monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "false")
    result = run.run_cell(name, SEED, 2.0, trace, platform="cpu", root=TINY)
    print(json.dumps(result)[:1500])
    assert json.loads(json.dumps(result)) == result  # one JSON object
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == devices
    return result


FAMILIES = pytest.mark.parametrize("config", ["gpt2-tiny", "llama-tiny"])


@FAMILIES
def test_train_cell(monkeypatch, config):
    r = _cell(monkeypatch, config + ".train-steady", False)
    assert set(r["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert r["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0
    assert r["attempted"] >= 10  # steps measured in two seconds at toy size
    assert "breakdown" not in r


def test_train_cell_on_four_devices_traced(monkeypatch):
    r = _cell(monkeypatch, "gpt2-tiny.train-fsdp4", True, devices=4)
    # a CPU trace has no TPU plane: the readers find nothing and the
    # metrics are left out rather than invented; MFU needs a peak
    assert r["metrics"] == {}
    assert r["device"]["window_s"] == 0 and r["device"]["busy_s"] == 0
    assert r["breakdown"] == {"device_ops": [], "idle_gaps": []}


@FAMILIES
def test_serve_cell_below_the_knee(monkeypatch, config):
    r = _cell(monkeypatch, config + ".serve-chat", False)
    assert set(r["metrics"]) == {"per_token_p50_ms", "setup_s"}
    assert r["attempted"] == 20  # 10 a second for two seconds


def test_serve_cell_traced(monkeypatch):
    # a CPU trace has no TPU plane: the readers find nothing
    r = _cell(monkeypatch, "gpt2-tiny.serve-chat", True)
    assert r["metrics"] == {} and r["device"]["busy_s"] == 0


@pytest.mark.parametrize("cell", ["train-steady", "serve-chat"])
def test_the_wrong_reference_is_never_correct(monkeypatch, toy, cell):
    """The second family's weights held against the first family's block
    (``"reference": "gpt2"``, found in the benchmark's own directory): the
    run ends in an error or in ``correct: false``."""
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    try:
        r = run.run_cell("llama-tiny." + cell, SEED, 2.0, False,
                         platform="cpu", root=toy(reference="gpt2"))
    except KeyError as e:
        assert "wpe" in str(e)  # the positions the second family has none of
    else:
        assert r["correct"] is False


def test_a_request_cut_short_is_not_correct(monkeypatch):
    """The engine makes one token fewer than a request is recorded to have
    asked for, as a program would that ends streams early: every request is
    answered to its [DONE] and in better time, and ``correct`` is false."""
    from benchmarks.lib import loadgen

    honest = loadgen.payload_for

    def one_short(req, template):
        payload = honest(req, template)
        if template.get("stream"):
            payload["max_tokens"] -= 1
        return payload

    monkeypatch.setattr(loadgen, "payload_for", one_short)
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    r = run.run_cell("gpt2-tiny.serve-chat", SEED, 2.0, False, platform="cpu",
                     root=TINY)
    assert r["failed"] == 0 and r["correct"] is False


def test_without_a_chip_there_is_no_result(capsys):
    """On this machine (no TPU) the real command fails before it starts a
    cluster and prints no result line."""
    rc = run.main(["--workload", "gpt2-medium.train-steady", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == "" and "needs 1 TPU chip" in out.err


def test_the_recount_agrees_with_the_counters(monkeypatch, capfd):
    """A run whose engine seems to have made one token too few sends every
    request once more, unary: the recount finds each answer whole, its sum
    is what the engine made, and the run is correct."""
    from benchmarks.runners import serve_open_loop

    real, calls = serve_open_loop._tokens_made, []

    def one_short_at_first(counters):
        calls.append(1)  # the first call decides whether to recount
        return real(counters) - (1 if len(calls) == 1 else 0)

    monkeypatch.setattr(serve_open_loop, "_tokens_made", one_short_at_first)
    r = _cell(monkeypatch, "gpt2-tiny.serve-chat", False)
    assert r["correct"] is True
    lines = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "recount_answers_tokens_seconds" in x]
    asked, made = lines[0]["schedule_tokens_asked_made"]
    answers, tokens, _ = lines[0]["recount_answers_tokens_seconds"]
    assert answers == 25 and tokens == asked == made


def test_an_early_end_the_reference_does_not_find_is_not_correct(
        monkeypatch, capfd):
    """An answer of the recount ends one token early: the engine's counts
    can be made to agree (a short answer stands for one token more, the EOS
    the engine cuts off), but the reference does not find EOS the likeliest
    token there, and the run is not correct."""
    from benchmarks.runners import serve_open_loop

    real_made, real_recount, calls = (
        serve_open_loop._tokens_made, serve_open_loop._recount, [])

    def one_short_at_first(counters):
        calls.append(1)  # the first call decides whether to recount
        return real_made(counters) - (1 if len(calls) == 1 else 0)

    async def first_answer_ends_early(*args):
        answers = await real_recount(*args)
        return [answers[0][:-1]] + answers[1:]

    monkeypatch.setattr(serve_open_loop, "_tokens_made", one_short_at_first)
    monkeypatch.setattr(serve_open_loop, "_recount", first_answer_ends_early)
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    r = run.run_cell("gpt2-tiny.serve-chat", SEED, 2.0, False, platform="cpu",
                     root=TINY)
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "answers_ended_early" in x][0]
    assert check["token_counts_ok"] is True
    assert check["answers_ended_early"] == 1
    assert check["eos_under_the_reference_choice_by"][0] > check["tolerance"]
    assert r["failed"] == 0 and r["correct"] is False
