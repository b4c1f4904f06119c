"""``run.py`` end to end on the CPU at ``granite-tiny``: the serving cell of
``granite-4.0-h-micro.serve-chat`` at toy widths, through the same runner
(``serve_open_loop_median``), proxy, replica, engine and reference: prompts
longer than the largest bucket (a second chunk starts from the state the
first left and ends in padding), slots reused. The toy's ``BENCHMARK.json``
is not edited: ``data/tiny/granite-tiny.entries.json`` holds what a copy of
it gains, as ``BENCHMARK.json`` gained it for the real cell. Then the five
new readers on a small trace of the real cell recorded on a v5e chip
(``data/v5e_1chip_granite.xplane.pb``: PR 42's call t2, a traced run of
``granite-4.0-h-micro.serve-chat`` at 2.5/s on seed 2147484243, cut by
``record_granite_trace.py`` to one admission of 546 tokens and the ticks
around it), each number a second time by arithmetic written out. Nothing timed on the CPU is a device number."""
import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.lib import host_spans, named, op_scopes, ssm_ops
from benchmarks.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny")
GRANITE = os.path.join(HERE, "data", "v5e_1chip_granite.xplane.pb")
BEFORE = [os.path.join(HERE, "data", "v5e_1chip_afmoe.xplane.pb"),
          os.path.join(HERE, "data", "v5e_1chip_spans.xplane.pb")]
SEED = 2 ** 31 + 42  # the driver's seeds do not fit 32 signed bits
CELL = "granite-tiny.serve-chat"
REAL = "granite-4.0-h-micro.serve-chat"
METRICS = ("ssm.share_of_tick", "ssm.state_share_of_tick",
           "ssm_decode_roofline", "ssm_prefill_roofline", "ssm.live_slots")
FACTS = {"decode_program": "jit_decode", "device_kind": "TPU v5 lite",
         "chips": 1}


@pytest.fixture
def toy_with_granite(tmp_path):
    """A copy of the toy benchmark with the entries file merged in; called
    with keys, it sets them in the copy's ``granite-tiny`` configuration."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    with open(os.path.join(root, "granite-tiny.entries.json")) as f:
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
            root, "benchmarks", "configs", "granite-tiny.json")
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


def test_serve_cell_comes_out_correct(monkeypatch, toy_with_granite, capfd):
    r = _run(monkeypatch, toy_with_granite(), False)
    print(json.dumps(r)[:1500])
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 20  # 10 a second for two seconds
    assert set(r["metrics"]) == {"per_token_p50_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "max_abs_logprob_diff" in x][0]
    # bf16 weights and activations at toy widths against the float32
    # reference over the same weights, through a prompt of 70 tokens: three
    # chunks, the last of them padded
    assert check["check_sequences"] == 2 and check["token_counts_ok"]
    assert 0 < check["max_abs_logprob_diff"] < check["tolerance"]
    medians = check["request_median_abs_logprob_diff"]
    assert len(medians) == 2
    assert 0 < max(medians) < check["request_median_tolerance"]
    assert 0 < check["median_abs_logprob_diff"] < check["median_tolerance"]


def test_serve_cell_traced_reads_what_a_cpu_trace_holds(
        monkeypatch, toy_with_granite):
    """A CPU trace has no TPU plane, so the readers of the device trace find
    nothing and their metrics are left out, not invented; the engine's spans
    are on the host plane: ``ssm.live_slots`` reads between 1 and the toy's
    4 slots."""
    r = _run(monkeypatch, toy_with_granite(), True)
    assert r["correct"] is True and r["device"]["busy_s"] == 0
    assert set(r["metrics"]) == {"ssm.live_slots"}
    live = r["metrics"]["ssm.live_slots"]
    assert live["unit"] == "slots" and 1 <= live["value"] <= 4
    _, _, _, per_layer, _ = run.load_cell(CELL, toy_with_granite())
    assert set(METRICS) <= {m["name"] for m in per_layer}


def test_the_state_size_of_the_real_model_does_not_fit_the_toys(
        monkeypatch, toy_with_granite):
    """The real reference keeps 128 states a channel: held against the
    toy's weights (16) it cannot even split the convolution's 160 channels
    into x, B and C, and says so."""
    with pytest.raises(ValueError, match="split"):
        _run(monkeypatch, toy_with_granite(reference="granite_hybrid"), False)


# ------------------------------------- the readers on the recorded trace


def _read(monkeypatch, metric, path):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", path)
    trace = T.load(path) if os.path.exists(path) else None
    return run.read_layer_metric(metric, trace, FACTS)


def _costs():
    return named.load(os.path.join(
        run.BENCH_DIR, "costs", "granite_hybrid.py"))


def test_the_kernel_is_found_by_name_and_sized_by_its_state():
    ops = op_scopes.load(GRANITE)
    programs = ops.program_ids("jit_decode")
    found = [m for m in ops.meta.values()
             if m.program_id in programs and ssm_ops.is_kernel(m)]
    # one instruction a state layer of the period (nine), scanned four times
    assert len(found) == 9
    assert {ssm_ops.state_shape(m.text) for m in found} == {
        (36, 48, 64, 64, 128)}
    ticks = [m for m in ops.modules if "jit_decode" in m[0]]
    runs = [mid for mid, _, _ in ops.self_ns
            if ops.meta[mid].program_id in programs
            and ssm_ops.is_kernel(ops.meta[mid])]
    # the cut keeps an event by its start: the program it opens in runs on
    assert 36 * (len(ticks) - 1) <= len(runs) <= 36 * (len(ticks) + 1)


def test_the_shares_of_a_tick_by_arithmetic_written_out(monkeypatch):
    ops = op_scopes.load(GRANITE)
    programs = ops.program_ids("jit_decode")
    total = sum(d for name, _, d in ops.modules if "jit_decode" in name)
    kernels = sum(own for mid, _, own in ops.self_ns
                  if ops.meta[mid].program_id in programs
                  and ssm_ops.is_kernel(ops.meta[mid]))
    scoped = sum(own for mid, _, own in ops.self_ns
                 if ops.meta[mid].program_id in programs
                 and not ssm_ops.is_kernel(ops.meta[mid])
                 and any(part.startswith("ssm.") for part in
                         ops.meta[mid].op_name.rstrip(":").split("/")))
    assert 0 < kernels < kernels + scoped < total
    assert _read(monkeypatch, METRICS[0], GRANITE) == pytest.approx(
        100 * (kernels + scoped) / total)
    assert _read(monkeypatch, METRICS[1], GRANITE) == pytest.approx(
        100 * kernels / total)


def test_the_decode_roofline_by_arithmetic_written_out(monkeypatch):
    share = _read(monkeypatch, METRICS[2], GRANITE)
    spans = host_spans.load(GRANITE)
    paired = host_spans.ticks_with_program(
        spans.loop_line(), T.load(GRANITE).devices[0], "jit_decode",
        spans.device_clock_offset_ns)
    assert len(paired) >= 3
    for tick, _ in paired:
        a = tick.args
        assert a["layers_state"] == 36 and a["layers_full"] == 4
        assert a["state_slot_layers"] == 36 * a["active"]
    # a slot and layer: 64 x 64 x 128 float32 read and written, and three
    # rows of 4352 bf16 beside them
    least = sum(t.args["state_slot_layers"] * 2 * (
        64 * 64 * 128 * 4 + 3 * 4352 * 2) / 819e9 for t, _ in paired)
    ops = op_scopes.load(GRANITE)
    off = spans.device_clock_offset_ns
    spent = sum(own for mid, start, own in ops.self_ns
                if ssm_ops.is_kernel(ops.meta[mid])
                and any(s - off <= start < s - off + d
                        for _, (s, d) in paired)) / 1e9
    assert share == pytest.approx(100 * least / spent)
    assert 0 < share < 100
    live = _read(monkeypatch, METRICS[4], GRANITE)
    ticks = spans.named("engine.tick")
    assert live == pytest.approx(
        sum(t.args["active"] for t in ticks) / len(ticks))


def test_the_four_attention_layers_share_of_a_tick_is_read(monkeypatch):
    """``attn.share_of_tick`` (PR 34's reader, unedited) finds the four full
    layers' ``decode_attention`` kernel in the decode program, and the
    readers of a window, a ring or a router find nothing."""
    from benchmarks.lib import decode_attn_mixed

    ops = op_scopes.load(GRANITE)
    found = decode_attn_mixed.kernels(ops, ops.program_ids("jit_decode"))
    assert set(found.values()) == {"full"}
    total = sum(d for name, _, d in ops.modules if "jit_decode" in name)
    own = sum(own for mid, _, own in ops.self_ns if mid in found)
    share = _read(monkeypatch, "attn.share_of_tick", GRANITE)
    assert share == pytest.approx(100 * own / total) and 0.5 < share < 5
    for metric in ("attn.window_spared_share", "decode_attn_mixed_roofline",
                   "moe.ffn_share_of_tick", "moe.shared_share_of_tick"):
        assert _read(monkeypatch, metric, GRANITE) is None


def test_the_prefill_roofline_counts_whole_admissions_only(monkeypatch):
    share = _read(monkeypatch, METRICS[3], GRANITE)
    assert share is not None and 0 < share < 100
    spans = host_spans.load(GRANITE)
    admits = [a for a in spans.named("engine.admit")
              if a.args.get("ssm_prefill_tokens")]
    assert admits and all(a.args["layers_state"] == 36 for a in admits)
    assert all(a.args["ssm_prefill_tokens"] == a.args["prompt_tokens"]
               for a in admits)
    cost = _costs().ssm_scan_cost(1000, {})
    # the recurrence of a token and layer: 5 x 64 x 64 x 128 operations,
    # 4352 + 4096 bf16 and 64 float32 moved: memory-bound on the v5e
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9
    assert cost["bytes"] == 1000 * ((4352 + 4096) * 2 + 64 * 4)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("path", BEFORE + [os.path.join(HERE, "data", "none")])
def test_a_model_without_state_layers_no_number(monkeypatch, metric, path):
    """A trace of a program with no state layer (Trinity-Mini's, GPT-2's: no
    ``ssm_update``, no ``ssm.*`` scope, no ``layers_state``), and no trace at
    all: None, the line leaves the metric out, nothing raises: what the
    parent commit gives under this PR's benchmark files."""
    assert _read(monkeypatch, metric, path) is None


def test_benchmark_json_lists_the_readers_for_the_one_cell():
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name and in this order, wherever later entries put them
    mine = [m for m in bench["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    for m in mine:
        assert REAL in m["workloads"]
        assert m["moves"] == "per_token_p50_ms"
        assert os.path.isfile(os.path.join(
            run.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
    cell, = [w for w in bench["workloads"] if w["name"] == REAL]
    assert cell["chips"] == 1 and cell["config"] == "granite-4.0-h-micro"
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == []
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if REAL in m.get("workloads", [])}
    assert "engine.tick_fetch_ms" not in listed
    assert {"per_token_p50_ms", "decode_attn_roofline", "attn.share_of_tick",
            "engine.decode_step_ms", "serve.deliver_ms",
            "trace.idle_unattributed_share.serve"} <= listed
    lines = [(e["name"], key, e[key])
             for kind in ("configs", "workloads", "per_layer")
             for e in bench[kind] for key in ("why", "source", "layer")
             if key in e and not (kind == "per_layer" and key == "source")]
    assert [(n, k, len(s)) for n, k, s in lines
            if not (1 <= len(s) <= 200 and s.isascii() and s.isprintable())
            ] == []


def test_the_configuration_file_states_what_the_issue_asked():
    _, cell, config, _, _ = run.load_cell(REAL)
    model = config["model"]
    assert model["num_layers"] == 40 and model["vocab_size"] == 100352
    assert model["layer_types"].count("mamba") == 36
    assert [i for i, k in enumerate(model["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert model["ssm_state_dtype"] == "float32"
    assert model["max_seq_len"] == cell["traffic"]["context_limit"] == 3072
    # the check decodes as long as the traffic's longest answer: a state
    # held narrower than float32 shows from some 256 decode steps on
    mix = cell["traffic"]
    assert mix["check_max_tokens"] == mix["max_tokens"]["max"] == 1024
    assert (max(mix["check_prompt_tokens"]) + mix["check_max_tokens"]
            <= mix["check_pad_to"] <= mix["context_limit"])
    assert config["num_hidden_layers"] == 40          # the published keys
    assert config["mamba_d_state"] == model["mamba_d_state"] == 128
    assert set(config["changed"]) == {"max_seq_len"}
    for key in ("assumed", "deployment", "weights"):
        assert config[key]
    assert _costs().param_count(model)["total"] == 3_191_396_096
