"""``run.py`` end to end on the CPU at ``olmoe-tiny``: the serving cell of
``olmoe-1b-7b.serve-assist`` at toy widths, through the same runner, proxy,
replica, engine and reference. The toy's ``BENCHMARK.json`` is not edited:
``data/tiny/olmoe-tiny.entries.json`` holds what a copy of it gains (one
configuration, one cell, four per-layer metrics, the cell's name appended to
the serving metrics' lists), as ``BENCHMARK.json`` gained them for the real
cell. Nothing timed here is a device number."""
import json
import os
import shutil

import pytest

from benchmarks import run

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")
SEED = 2 ** 31 + 11  # the driver's seeds do not fit 32 signed bits
CELL = "olmoe-tiny.serve-assist"
MOE_METRICS = {"moe.ffn_share_of_tick", "moe.dispatch_share_of_ffn",
               "moe.experts_touched", "moe_experts_roofline"}


@pytest.fixture
def toy_with_olmoe(tmp_path):
    """A copy of the toy benchmark with the entries file merged in; called
    with keys, it sets them in the copy's ``olmoe-tiny`` configuration."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    with open(os.path.join(root, "olmoe-tiny.entries.json")) as f:
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
        config = os.path.join(root, "benchmarks", "configs", "olmoe-tiny.json")
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


def test_serve_cell_comes_out_correct(monkeypatch, toy_with_olmoe, capfd):
    r = _run(monkeypatch, toy_with_olmoe(), False)
    print(json.dumps(r)[:1500])
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 20  # 10 a second for two seconds
    assert set(r["metrics"]) == {"per_token_p50_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "max_abs_logprob_diff" in x][0]
    # bf16 weights and activations at toy widths against the float32
    # reference over the same weights: 0.002-0.02 measured
    assert check["check_sequences"] == 2 and check["token_counts_ok"]
    assert 0 < check["max_abs_logprob_diff"] < check["tolerance"]


def test_serve_cell_traced_reads_what_a_cpu_trace_holds(
        monkeypatch, toy_with_olmoe):
    """A CPU trace has no TPU plane, so the three readers of the device
    trace find nothing and their metrics are left out, not invented; the
    engine's spans are on the host plane and ``moe.experts_touched`` reads
    them: between 8 (one slot decodes) and all 16 of the toy's experts."""
    r = _run(monkeypatch, toy_with_olmoe(), True)
    assert r["correct"] is True and r["device"]["busy_s"] == 0
    assert set(r["metrics"]) == {"moe.experts_touched"}
    touched = r["metrics"]["moe.experts_touched"]
    assert touched["unit"] == "experts/layer" and 8 <= touched["value"] <= 16
    _, _, _, per_layer, _ = run.load_cell(CELL, toy_with_olmoe())
    assert MOE_METRICS <= {m["name"] for m in per_layer}


def test_the_wrong_reference_is_never_correct(monkeypatch, toy_with_olmoe):
    """OLMoE's weights held against GPT-2's block: an error that names what
    that reference misses, or ``correct: false``."""
    try:
        r = _run(monkeypatch, toy_with_olmoe(reference="gpt2"), False)
    except KeyError as e:
        assert "wpe" in str(e)  # the positions this family has none of
    else:
        assert r["correct"] is False
