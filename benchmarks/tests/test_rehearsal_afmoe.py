"""``run.py`` end to end on the CPU at ``afmoe-tiny``: the serving cell of
``trinity-mini.serve-mixed`` at toy widths and a window of 16, through the
same runner (``serve_open_loop_median``), proxy, replica, engine and reference: prompts longer than the
largest bucket (admitted in chunks) and than the window (the ring wraps).
The toy's ``BENCHMARK.json`` is not edited: ``data/tiny/
afmoe-tiny.entries.json`` holds what a copy of it gains, as
``BENCHMARK.json`` gained it for the real cell. Nothing timed here is a
device number."""
import json
import os
import shutil

import pytest

from benchmarks import run

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")
SEED = 2 ** 31 + 34  # the driver's seeds do not fit 32 signed bits
CELL = "afmoe-tiny.serve-mixed"
NEW_METRICS = {"decode_attn_mixed_roofline", "attn.share_of_tick",
               "attn.window_spared_share", "moe.shared_share_of_tick"}


@pytest.fixture
def toy_with_afmoe(tmp_path):
    """A copy of the toy benchmark with the entries file merged in; called
    with keys, it sets them in the copy's ``afmoe-tiny`` configuration."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    with open(os.path.join(root, "afmoe-tiny.entries.json")) as f:
        entries = json.load(f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for kind in ("configs", "workloads", "per_layer"):
        bench[kind] += entries[kind]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in entries["append_to_workloads_of"]:
            m["workloads"].append(entries["workloads"][0]["name"])
    with open(path, "w") as f:
        json.dump(bench, f)

    def edit(**keys):
        config = os.path.join(root, "benchmarks", "configs", "afmoe-tiny.json")
        with open(config) as f:
            data = json.load(f)
        data.update(keys)
        with open(config, "w") as f:
            json.dump(data, f)
        return root

    return edit


def _run(monkeypatch, root, trace):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    return run.run_cell(CELL, SEED, 2.0, trace, platform="cpu", root=root)


def test_serve_cell_comes_out_correct(monkeypatch, toy_with_afmoe, capfd):
    r = _run(monkeypatch, toy_with_afmoe(), False)
    print(json.dumps(r)[:1500])
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 20  # 10 a second for two seconds
    assert set(r["metrics"]) == {"per_token_p50_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "max_abs_logprob_diff" in x][0]
    # bf16 weights and activations at toy widths against the float32
    # reference over the same weights, through a prompt of 70 tokens: three
    # chunks, four windows deep, once round the ring of 48
    assert check["check_sequences"] == 2 and check["token_counts_ok"]
    assert 0 < check["max_abs_logprob_diff"] < check["tolerance"]
    # the runner's other two limits: the median of all twelve answer tokens
    # and each request's own
    medians = check["request_median_abs_logprob_diff"]
    assert len(medians) == 2
    assert 0 < max(medians) < check["request_median_tolerance"]
    assert 0 < check["median_abs_logprob_diff"] < check["median_tolerance"]
    assert max(medians) <= check["max_abs_logprob_diff"]


def test_serve_cell_traced_reads_what_a_cpu_trace_holds(
        monkeypatch, toy_with_afmoe):
    """A CPU trace has no TPU plane, so the readers of the device trace find
    nothing and their metrics are left out, not invented; the engine's spans
    are on the host plane: ``moe.experts_touched`` reads between 8 and all
    16 of the toy's experts, ``attn.window_spared_share`` what a window of
    16 spares prompts of 8 to 80."""
    r = _run(monkeypatch, toy_with_afmoe(), True)
    assert r["correct"] is True and r["device"]["busy_s"] == 0
    assert set(r["metrics"]) == {"moe.experts_touched",
                                 "attn.window_spared_share"}
    assert 8 <= r["metrics"]["moe.experts_touched"]["value"] <= 16
    spared = r["metrics"]["attn.window_spared_share"]
    assert spared["unit"] == "%" and 5 < spared["value"] < 80
    _, _, _, per_layer, _ = run.load_cell(CELL, toy_with_afmoe())
    assert NEW_METRICS <= {m["name"] for m in per_layer}


def test_the_window_of_the_real_model_is_never_correct_at_the_toys(
        monkeypatch, toy_with_afmoe):
    """The real reference slides over 2048 positions: held against a program
    that slides over 16, it is ``correct: false``."""
    r = _run(monkeypatch, toy_with_afmoe(reference="afmoe"), False)
    assert r["correct"] is False


def test_an_early_end_is_held_to_the_references_greedy_choice(
        monkeypatch, toy_with_afmoe, capfd):
    """The runner's own path for an answer that ended early (as
    ``test_rehearsal.py`` drives ``serve_open_loop``'s): the recount finds
    one answer a token short, the counts can be made to agree, the
    reference does not find EOS the likeliest token there."""
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
    r = _run(monkeypatch, toy_with_afmoe(), False)
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "answers_ended_early" in x][0]
    assert check["token_counts_ok"] is True
    assert check["answers_ended_early"] == 1
    assert check["eos_under_the_reference_choice_by"][0] > check["tolerance"]
    assert check["median_abs_logprob_diff"] < check["median_tolerance"]
    assert r["failed"] == 0 and r["correct"] is False


def test_what_the_real_cell_added_keeps_the_form_of_benchmark_json():
    """The driver refuses the whole file over one line out of form (PR 34's
    first hand-in: a ``why`` of 203 characters): every line of text is 1 to
    200 printable ASCII characters, and the file ends as it did."""
    path = os.path.join(os.path.dirname(run.__file__), os.pardir,
                        "BENCHMARK.json")
    with open(path) as f:
        text = f.read()
    bench = json.loads(text)
    lines = [(e["name"], key, e[key])
             for kind in ("configs", "workloads", "per_layer")
             for e in bench[kind] for key in ("why", "source", "layer")
             if key in e and not (kind == "per_layer" and key == "source")]
    assert any(name == "trinity-mini" for name, _, _ in lines)
    out_of_form = [(name, key, len(s)) for name, key, s in lines
                   if not (1 <= len(s) <= 200 and s.isascii()
                           and s.isprintable())]
    assert out_of_form == []
    assert text.endswith("}\n") and len(text.encode()) <= 64 * 1024
