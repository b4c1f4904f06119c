"""``run.py`` end to end on the CPU at ``smallthinker-tiny``: the training
cell of ``smallthinker-21b-a3b.train-seq8k`` at toy widths and a window of
16, through the same runner (``train_job``), trainer and reference:
sequences four windows long, a quarter of the experts held, the router at
the layer's input. The toy's ``BENCHMARK.json`` is not edited:
``data/tiny/smallthinker-tiny.entries.json`` holds what a copy of it gains,
as ``BENCHMARK.json`` gained it for the real cell. The new readers are held
to a trace of the real cell's step recorded on a v5e chip
(``data/v5e_1chip_smallthinker.xplane.pb``). Nothing timed here is a device
number."""
import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.lib import host_spans, moe_ops, op_scopes, train_moe
from benchmarks.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny")
RECORDED = os.path.join(HERE, "data", "v5e_1chip_smallthinker.xplane.pb")
SEED = 2 ** 31 + 40  # the driver's seeds do not fit 32 signed bits
CELL = "smallthinker-tiny.train-seq8k"
REAL = "smallthinker-21b-a3b.train-seq8k"
NEW_METRICS = {
    "flash_mixed_fwd_roofline", "flash_mixed_bwd_roofline",
    "moe_train_experts_roofline", "train.moe_share_of_step",
    "train.attn_share_of_step", "moe.held_rows_share",
    "moe.expert_load_max_over_mean"}


@pytest.fixture
def toy_with_smallthinker(tmp_path):
    """A copy of the toy benchmark with the entries file merged in; called
    with keys, it sets them in the copy's ``smallthinker-tiny``
    configuration."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    with open(os.path.join(root, "smallthinker-tiny.entries.json")) as f:
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
        config = os.path.join(
            root, "benchmarks", "configs", "smallthinker-tiny.json")
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


def test_train_cell_comes_out_correct(monkeypatch, toy_with_smallthinker,
                                      capfd):
    r = _run(monkeypatch, toy_with_smallthinker(), False)
    print(json.dumps(r)[:1500])
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert r["attempted"] >= 5
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "reference_first_loss" in x][0]
    # bf16 activations against the float32 reference at initial weights: the
    # reported loss is the cross entropy ALONE (the auxiliary loss, 0.04 at
    # the toy, rides beside it), ln(512) = 6.238 and the layers move it
    # little
    assert abs(check["first_loss"] - check["reference_first_loss"]) < 0.02
    assert 6.1 < check["first_loss"] < 6.4 and check["all_losses_finite"]


def test_train_cell_traced_reads_what_a_cpu_trace_holds(
        monkeypatch, toy_with_smallthinker):
    """A CPU trace has no TPU plane: the readers of the device trace find
    nothing and their metrics are left out, not invented; the trainer's
    spans are on the host plane, and the two counters on its
    ``train.loss_fetch`` give the share of the pairs this chip's 2 of 8
    experts took (a quarter, give or take the toy's routing) and how full
    the fuller of the two was."""
    root = toy_with_smallthinker()
    r = _run(monkeypatch, root, True)
    assert r["correct"] is True and r["device"]["busy_s"] == 0
    assert {"moe.held_rows_share", "moe.expert_load_max_over_mean"} <= set(
        r["metrics"])
    assert not {m for m in r["metrics"] if "roofline" in m or "share_of" in m}
    assert 10 < r["metrics"]["moe.held_rows_share"]["value"] < 45
    assert 1 <= r["metrics"]["moe.expert_load_max_over_mean"]["value"] <= 2
    _, _, _, per_layer, _ = run.load_cell(CELL, root)
    assert NEW_METRICS <= {m["name"] for m in per_layer}


def test_the_first_loss_barely_sees_the_layers(
        monkeypatch, toy_with_smallthinker, capfd):
    """What the train runner's comparison can and cannot show (PERF.md
    section 7): at initial weights the layers move the loss so little that
    the REAL reference, which picks 6 experts where the toy's router picks
    2 and slides over 4096 positions where the toy slides over 16, still
    reads within the tolerance. The gradients tell them apart
    (``tests/test_smallthinker.py``, and on the chip PERF.md section 6)."""
    r = _run(monkeypatch, toy_with_smallthinker(reference="smallthinker"),
             False)
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "reference_first_loss" in x][0]
    assert r["correct"] is True
    assert abs(check["first_loss"] - check["reference_first_loss"]) < 0.02


# ------------------------------------------------ the recorded step's trace


def _facts():
    _, cell, config, _, _ = run.load_cell(REAL)
    return {"model": config["model"], "batch_per_chip": 2, "seq_len": 8192,
            "device_kind": "TPU v5 lite", "peak_flops_per_s": 197e12,
            "train_program": cell["job"]["train_program"], "chips": 1}


@pytest.fixture
def recorded(monkeypatch):
    if not os.path.isfile(RECORDED):
        pytest.skip("no recorded trace of the cell's step")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", RECORDED)
    return T.load(RECORDED)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_every_new_reader_reads_the_recorded_step(recorded, metric):
    value = run.read_layer_metric(metric, recorded, _facts())
    assert value is not None and value > 0
    if metric.endswith("roofline") or "share" in metric:
        assert value <= 100.0


def test_the_recorded_step_by_arithmetic_written_out(recorded):
    """Four flash forward calls a step, one full and three windowed, and as
    many backward; the scopes hold in the backward pass; the shares are
    the scopes' own nanoseconds over the steps'."""
    facts = _facts()
    dev = recorded.devices[0]
    calls = train_moe.flash_calls(dev)
    kinds = sorted(calls.values())
    assert set(kinds) == {(False, False), (False, True), (True, False),
                          (True, True)}
    ns = train_moe.step_scope_ns(facts)
    assert ns["steps"] >= 1 and all(ns[s] > 0 for s in moe_ops.SCOPES)
    ops = op_scopes.load(RECORDED)
    backward = [m for m in ops.meta.values()
                if moe_ops.scope_of(m) == "moe.experts"
                and "transpose(" in m.op_name]
    assert backward  # found as the forward ones are
    share = run.read_layer_metric("train.moe_share_of_step", recorded, facts)
    assert share == pytest.approx(
        100.0 * sum(ns[s] for s in moe_ops.SCOPES) / ns["total"])
    attn = run.read_layer_metric("train.attn_share_of_step", recorded, facts)
    assert attn == pytest.approx(100.0 * ns["flash"] / ns["total"])
    assert share + attn < 100.0


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_no_name_no_scope_no_counter_no_number(monkeypatch, metric):
    """The GPT-2 step's trace (PR 24's) has flash kernels with no name of
    their kind, no ``moe.*`` scope and no counter: every new reader gives
    None, which is what the parent gives in the new cell's traced run."""
    dense = os.path.join(HERE, "data", "v5e_1chip_spans.xplane.pb")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", dense)
    assert run.read_layer_metric(metric, T.load(dense), _facts()) is None


def test_benchmark_json_lists_the_readers_for_the_new_cell_only():
    _, _, _, per_layer, end_to_end = run.load_cell(REAL)
    names = {m["name"] for m in per_layer}
    assert NEW_METRICS <= names
    assert {"train.mfu", "train.step_device_ms", "train.host_gap_ms",
            "train.report_ms", "train.input_ms",
            "trace.idle_unattributed_share.train"} <= names
    assert not {"flash_fwd_roofline", "flash_bwd_roofline"} & names
    assert {m["name"] for m in end_to_end} == {
        "train_tokens_per_s_per_chip", "setup_s"}
    for cell in ("gpt2-medium.train-steady", "gpt2-xl.train-fsdp4"):
        _, _, _, per_layer, _ = run.load_cell(cell)
        assert not NEW_METRICS & {m["name"] for m in per_layer}
