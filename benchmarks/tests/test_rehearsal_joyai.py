"""``run.py`` end to end on the CPU at ``joyai-tiny``: the training cell of
``joyai-llm-flash.train-seq8k`` at toy widths and the published router
numbers, through the same runner (``train_job``), trainer and reference:
latent attention with a query rank in every layer, a quarter of 16 experts
held, the prediction layer and its loss, the bias moved by its rule. The
toy's ``BENCHMARK.json`` is not edited: ``data/tiny/joyai-tiny.entries.json``
holds what a copy of it gains, as ``BENCHMARK.json`` gained it for the real
cell. The new readers are held to a trace of the real cell's step recorded
on a v5e chip (``data/v5e_1chip_joyai.xplane.pb``; PR 55's first traced run,
cut to one step by ``record_smallthinker_trace.py``). Nothing timed here is
a device number."""
import json
import os
import shutil

import jax
import pytest

from benchmarks import run
from benchmarks.lib import host_spans, named, program, train_mla
from benchmarks.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny")
RECORDED = os.path.join(HERE, "data", "v5e_1chip_joyai.xplane.pb")
SEED = 2 ** 31 + 55  # the driver's seeds do not fit 32 signed bits
CELL = "joyai-tiny.train-seq8k"
REAL = "joyai-llm-flash.train-seq8k"
NEW_METRICS = {
    "flash_mla_fwd_roofline", "flash_mla_bwd_roofline",
    "train.mla_share_of_step", "train.mtp_share_of_step",
    "moe.router_load_max_over_mean"}
JOINED = {
    "train.mfu", "train.step_device_ms", "train.host_gap_ms",
    "train.report_ms", "train.input_ms",
    "trace.idle_unattributed_share.train", "moe_train_experts_roofline",
    "train.moe_share_of_step", "train.moe_dispatch_share_of_step",
    "moe.held_rows_share", "moe.expert_load_max_over_mean"}


@pytest.fixture
def toy_with_joyai(tmp_path):
    """A copy of the toy benchmark with the entries file merged in."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    with open(os.path.join(root, "joyai-tiny.entries.json")) as f:
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
    return root


def _run(monkeypatch, root, trace):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    return run.run_cell(CELL, SEED, 2.0, trace, platform="cpu", root=root)


def test_train_cell_comes_out_correct(monkeypatch, toy_with_joyai, capfd):
    r = _run(monkeypatch, toy_with_joyai, False)
    print(json.dumps(r)[:1500])
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert r["attempted"] >= 5
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "reference_first_loss" in x][0]
    # bf16 activations against the float32 reference at initial weights: the
    # reported loss is the MAIN head's cross entropy alone (the prediction
    # layer's rides beside it); the embedding at 1.0 moves it off ln(512) =
    # 6.238 whatever the layers do
    assert abs(check["first_loss"] - check["reference_first_loss"]) < 0.02
    assert 6.2 < check["first_loss"] < 6.9 and check["all_losses_finite"]


def test_train_cell_traced_reads_what_a_cpu_trace_holds(
        monkeypatch, toy_with_joyai):
    """A CPU trace has no TPU plane: the readers of the device trace find
    nothing and their metrics are left out, not invented; the trainer's
    spans are on the host plane, and the rule's counter on its
    ``train.loss_fetch`` gives how full the fullest of all 16 experts was (8
    of 16 a token: at most twice the mean)."""
    r = _run(monkeypatch, toy_with_joyai, True)
    assert r["correct"] is True and r["device"]["busy_s"] == 0
    assert "moe.router_load_max_over_mean" in r["metrics"]
    assert not {m for m in r["metrics"] if "roofline" in m or "share_of" in m}
    assert 1 <= r["metrics"]["moe.router_load_max_over_mean"]["value"] <= 2
    _, _, _, per_layer, _ = run.load_cell(CELL, toy_with_joyai)
    assert NEW_METRICS <= {m["name"] for m in per_layer}
    spans = host_spans.load()
    fetched = spans.named("train.loss_fetch")[-1].args
    assert {"mtp_loss", "moe_rows_held", "moe_rows_max_expert",
            "moe_rows_max_all", "moe_bias_abs_mean"} <= set(fetched)
    assert 6.0 < float(fetched["mtp_loss"]) < 7.0


@pytest.mark.parametrize("cell, root, total", [
    (CELL, "toy", None), (REAL, None, 680_441_088)])
def test_the_costs_count_the_programs_own_weights(toy_with_joyai, cell, root,
                                                  total):
    """``costs/joyai_llm_flash.py:param_count`` against ``count_params`` of
    the program's own ``init_params`` (shapes only), at the toy and at the
    cell's sizes: 680.4M there, the prediction layer, the routers' biases
    and every norm's gain in it."""
    from ray_tpu.models import decoder, module_for

    _, _, config, _, _ = run.load_cell(
        cell, *([toy_with_joyai] if root else []))
    cfg = program.model_config(config)
    shapes = jax.eval_shape(
        lambda: module_for(cfg).init_params(cfg, jax.random.PRNGKey(0)))
    counted = named.load(config["files"]["costs"]).param_count(
        config["model"])
    assert counted["total"] == decoder.count_params(shapes)
    if total:
        assert counted["total"] == total
        assert 47e9 < counted["published"] < 51e9      # "48B-A2.7B"


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
    """Six flash forward calls a step and six backward (five trunk mixers
    and the prediction layer's), each found by its name; the prediction
    layer's scopes hold in the backward pass, its flash call too; the shares
    are the names' and scopes' own nanoseconds over the step's."""
    facts = _facts()
    dev = recorded.devices[0]
    calls = [name for name, text in dev.op_text.items()
             if T.is_kernel(text) and train_mla.FLASH.search(name)]
    assert sum("fwd" in c for c in calls) == 6
    assert sum("bwd" in c for c in calls) == 6
    ns = train_mla.step_ns(facts)
    assert all(ns[k] > 0 for k in ("flash", "mla", "mtp", "total"))
    share = run.read_layer_metric("train.mla_share_of_step", recorded, facts)
    assert share == pytest.approx(
        100.0 * (ns["flash"] + ns["mla"]) / ns["total"])
    mtp = run.read_layer_metric("train.mtp_share_of_step", recorded, facts)
    assert mtp == pytest.approx(100.0 * ns["mtp"] / ns["total"])
    # one layer of six and one head of two: a seventh to a quarter of a step
    assert 12 < mtp < 30 and 30 < share < 70


@pytest.mark.parametrize("op_name, mla, mtp", [
    ("jit(step_fn)/jvp(mla.q)/btr,rhd->bthd/dot_general", True, False),
    ("jit(step_fn)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "mla.down/mul", True, False),
    ("jit(step_fn)/transpose(jvp(mtp.block))/jvp(mtp.block)/checkpoint/"
     "flash_mla_bwd/pallas_call", False, True),
    ("jit(step_fn)/jvp(mtp.block)/mla.up/bsr,rhd->bshd/dot_general", True,
     True),
    ("jit(step_fn)/jvp(mtp.head)/while/body/closed_call/bce,ve->bcv", False,
     True),
    ("jit(step_fn)/jvp()/cond/branch_1_fun/moe.experts/pallas_call", False,
     False),
    ("jit(step_fn)/jvp(formula.q)/mul", False, False),
])
def test_a_scope_is_found_wrapped_or_as_a_path_element(op_name, mla, mtp):
    """JAX writes a scope outside the innermost differentiated call as
    ``jvp(mla.q)`` and one inside a checkpoint as a path element: both are
    the scope, and a longer name that ends in it is not."""
    assert train_mla.scoped(train_mla.MLA, op_name) is mla
    assert train_mla.scoped(train_mla.MTP, op_name) is mtp


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_no_name_no_scope_no_counter_no_number(monkeypatch, metric):
    """SmallThinker's step (PR 40's trace) has flash kernels of other names,
    no ``mla.*`` or ``mtp.*`` scope and no ``moe_rows_max_all``: every new
    reader gives None, which is what the parent gives in a traced run."""
    other = os.path.join(HERE, "data", "v5e_1chip_smallthinker.xplane.pb")
    monkeypatch.setattr(host_spans, "TRACE_ROOT", other)
    facts = _facts()
    assert run.read_layer_metric(metric, T.load(other), facts) is None
    # and with the other cell's own facts, as the driver's parent run has them
    _, cell, config, _, _ = run.load_cell("smallthinker-21b-a3b.train-seq8k")
    facts["model"] = config["model"]
    assert run.read_layer_metric(metric, T.load(other), facts) is None


def test_benchmark_json_lists_the_readers_for_the_new_cell_only():
    _, _, _, per_layer, end_to_end = run.load_cell(REAL)
    names = {m["name"] for m in per_layer}
    assert NEW_METRICS | JOINED == names
    assert {m["name"] for m in end_to_end} == {
        "train_tokens_per_s_per_chip", "setup_s"}
    for cell in ("gpt2-medium.train-steady", "gpt2-xl.train-fsdp4",
                 "smallthinker-21b-a3b.train-seq8k"):
        _, _, _, per_layer, _ = run.load_cell(cell)
        assert not NEW_METRICS & {m["name"] for m in per_layer}
