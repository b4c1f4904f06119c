"""``run.py`` end to end on the CPU at ``olmo-hybrid-tiny``: the serving cell
of ``olmo-hybrid-7b.serve-docs`` at toy widths, through the same runner
(``serve_open_loop_median``), proxy, replica, engine and reference: every
prompt longer than the largest bucket (each admission carries a matrix state
from chunk to chunk and ends in padding), slots reused. The toy's
``BENCHMARK.json`` is not edited: ``data/tiny/olmo-hybrid-tiny.entries.json``
holds what a copy of it gains, as ``BENCHMARK.json`` gained it for the real
cell (over Granite's entries, whose ``ssm.live_slots`` the new cell joins).
Then the six new readers on a small trace of the real cell recorded on a v5e
chip (``data/v5e_1chip_olmo_hybrid.xplane.pb``: PR 47's traced run of
``olmo-hybrid-7b.serve-docs``, cut by ``record_olmo_hybrid_trace.py`` to one
admission and the ticks around it), each number a second time by arithmetic
written out. Nothing timed on the CPU is a device number."""
import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.lib import delta_ops, host_spans, named, op_scopes
from benchmarks.lib import trace as T
from benchmarks.tests import faults_olmo_hybrid as faults

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny")
OLMO = os.path.join(HERE, "data", "v5e_1chip_olmo_hybrid.xplane.pb")
BEFORE = [os.path.join(HERE, "data", "v5e_1chip_granite.xplane.pb"),
          os.path.join(HERE, "data", "v5e_1chip_spans.xplane.pb")]
SEED = 2 ** 31 + 47  # the driver's seeds do not fit 32 signed bits
CELL = "olmo-hybrid-tiny.serve-docs"
REAL = "olmo-hybrid-7b.serve-docs"
METRICS = ("delta.share_of_tick", "delta.share_of_prefill",
           "delta_decode_roofline", "delta_prefill_roofline",
           "delta.prefill_share_of_busy", "delta.chunks_per_admit")
FACTS = {"decode_program": "jit_decode", "device_kind": "TPU v5 lite",
         "chips": 1}


@pytest.fixture
def toy_with_olmo(tmp_path):
    """A copy of the toy benchmark with Granite's entries file and then this
    one merged in."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for name in ("granite-tiny", "olmo-hybrid-tiny"):
        with open(os.path.join(root, name + ".entries.json")) as f:
            entries = json.load(f)
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


def test_serve_cell_comes_out_correct(monkeypatch, toy_with_olmo, capfd):
    r = _run(monkeypatch, toy_with_olmo, False)
    print(json.dumps(r)[:1500])
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 20  # 10 a second for two seconds
    assert set(r["metrics"]) == {"per_token_p50_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "max_abs_logprob_diff" in x][0]
    # bf16 weights and activations at toy widths against the float32
    # reference over the same weights, through prompts of 33 (a second
    # chunk of ONE token), 70 and 100 tokens (four chunks, the last padded).
    # The toy's limits are wide (``tests/test_olmo_hybrid.py`` says what
    # bf16 reads through branches normed at 64 channels); a state lost
    # between chunks is held to 1e-4 in float32 there
    assert check["check_sequences"] == 3 and check["token_counts_ok"]
    assert 0 < check["max_abs_logprob_diff"] < check["tolerance"]
    medians = check["request_median_abs_logprob_diff"]
    assert len(medians) == 3
    assert 0 < max(medians) < check["request_median_tolerance"]
    assert 0 < check["median_abs_logprob_diff"] < check["median_tolerance"]


def test_serve_cell_traced_reads_what_a_cpu_trace_holds(
        monkeypatch, toy_with_olmo):
    """A CPU trace has no TPU plane, so the readers of the device trace find
    nothing and their metrics are left out, not invented; the engine's spans
    are on the host plane: every captured admission ran two chunks or more,
    and ``ssm.live_slots`` (PR 42's reader, unedited) counts this family's
    state layers as it counted Granite's."""
    r = _run(monkeypatch, toy_with_olmo, True)
    assert r["correct"] is True and r["device"]["busy_s"] == 0
    assert set(r["metrics"]) == {"delta.chunks_per_admit", "ssm.live_slots"}
    chunks = r["metrics"]["delta.chunks_per_admit"]
    assert chunks["unit"] == "chunks" and 2 <= chunks["value"] <= 4
    assert 1 <= r["metrics"]["ssm.live_slots"]["value"] <= 4
    _, _, _, per_layer, _ = run.load_cell(CELL, toy_with_olmo)
    assert set(METRICS) <= {m["name"] for m in per_layer}


@pytest.mark.parametrize("variant, sound", [
    ("sound", True), ("state_zeroed", False), ("conv_dropped", False),
    ("pad_writes", False), ("beta1", False), ("gate_first", False)])
def test_a_planted_fault_reads_not_correct(
        monkeypatch, toy_with_olmo, variant, sound):
    """The controls of the real cell's comparison (``faults_olmo_hybrid.py``,
    whose chip readings the cell file's ``notes`` hold), planted at toy size
    and read through the runner's own functions under the toy cell's limits:
    the state zeroed or the convolution's rows dropped at a chunk boundary,
    a bucket's padded steps stepping the state, beta without its factor 2,
    the gate before the norm. (A state held in bfloat16 reads like a sound
    run over six answer tokens at 64 channels: the chip tells it by the
    medians of 256 tokens at the published widths.)"""
    _, cell, config, _, _ = run.load_cell(CELL, toy_with_olmo)
    _, _, reference, _, _ = run.load_cell(CELL, toy_with_olmo)
    faults.plant(variant, config, monkeypatch.setattr)
    sample, = faults.served(config, cell["traffic"], [SEED]).values()
    got = faults.read(reference, cell["traffic"], sample)
    print(variant, got)
    assert got["within"] is sound


# ------------------------------------- the readers on the recorded trace


def _read(monkeypatch, metric, path):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", path)
    trace = T.load(path) if os.path.exists(path) else None
    return run.read_layer_metric(metric, trace, FACTS)


def _costs():
    return named.load(os.path.join(run.BENCH_DIR, "costs", "olmo_hybrid.py"))


def test_the_kernel_is_found_by_name_and_sized_by_its_results():
    ops = op_scopes.load(OLMO)
    programs = ops.program_ids("jit_decode")
    found = [m for m in ops.meta.values()
             if m.program_id in programs and delta_ops.is_kernel(m)]
    # one instruction a state layer of the period (three), scanned four times
    assert len(found) == 3
    assert {tuple(delta_ops.sizes(m.text).values()) for m in found} == {
        (30, 96, 192)}
    assert all("f32[12,8,30,96,256]" in m.text for m in found)
    ticks = [m for m in ops.modules if "jit_decode" in m[0]]
    runs = [mid for mid, _, _ in ops.self_ns
            if ops.meta[mid].program_id in programs
            and delta_ops.is_kernel(ops.meta[mid])]
    # the cut keeps an event by its start: the program it opens in runs on
    assert 12 * (len(ticks) - 1) <= len(runs) <= 12 * (len(ticks) + 1)


@pytest.mark.parametrize("metric, program", [
    (METRICS[0], "jit_decode"), (METRICS[1], "jit_prefill")])
def test_the_shares_of_a_program_by_arithmetic_written_out(
        monkeypatch, metric, program):
    ops = op_scopes.load(OLMO)
    programs = ops.program_ids(program)
    total = sum(d for name, _, d in ops.modules if program in name)
    scoped = sum(own for mid, _, own in ops.self_ns
                 if ops.meta[mid].program_id in programs
                 and (delta_ops.is_kernel(ops.meta[mid])
                      or any(part.startswith("delta.") for part in
                             ops.meta[mid].op_name.rstrip(":").split("/"))))
    assert 0 < scoped < total
    assert _read(monkeypatch, metric, OLMO) == pytest.approx(
        100 * scoped / total)
    # three quarters of the layers are state layers; a tick's time goes by
    # the weights' bytes (the mixers' 2.1 of 8.2 GB, the MLPs and the head
    # are the rest), a prefill's by the scan as well
    assert 20 < 100 * scoped / total < 90


def test_the_decode_roofline_by_arithmetic_written_out(monkeypatch):
    share = _read(monkeypatch, METRICS[2], OLMO)
    spans = host_spans.load(OLMO)
    paired = host_spans.ticks_with_program(
        spans.loop_line(), T.load(OLMO).devices[0], "jit_decode",
        spans.device_clock_offset_ns)
    assert len(paired) >= 3
    for tick, _ in paired:
        a = tick.args
        assert a["layers_state"] == 12 and a["layers_full"] == 4
        assert a["state_slot_layers"] == 12 * a["active"]
    # a slot and layer: 30 x 96 x 192 float32 read and written, three rows
    # of 11520 bf16 read and written, q, k, v in and o out in bf16 (11520 +
    # 5760), two gates of 30 float32
    least = sum(t.args["state_slot_layers"] * (
        2 * (30 * 96 * 192 * 4 + 3 * 11520 * 2) + (11520 + 5760) * 2
        + 2 * 30 * 4) / 819e9 for t, _ in paired)
    ops = op_scopes.load(OLMO)
    off = spans.device_clock_offset_ns
    spent = sum(own for mid, start, own in ops.self_ns
                if delta_ops.is_kernel(ops.meta[mid])
                and any(s - off <= start < s - off + d
                        for _, (s, d) in paired)) / 1e9
    assert share == pytest.approx(100 * least / spent)
    # the cache pads a head's 192 values to 256: three quarters at most
    assert 0 < share < 75


def test_the_prefill_roofline_counts_whole_admissions_only(monkeypatch):
    share = _read(monkeypatch, METRICS[3], OLMO)
    assert share is not None and 0 < share < 100
    spans = host_spans.load(OLMO)
    admits = [a for a in spans.named("engine.admit")
              if a.args.get("ssm_prefill_tokens")]
    assert admits and all(a.args["layers_state"] == 12 for a in admits)
    assert all(a.args["ssm_prefill_tokens"] == a.args["prompt_tokens"]
               for a in admits)
    assert _read(monkeypatch, METRICS[5], OLMO) == pytest.approx(
        sum(a.args["chunks"] for a in admits) / len(admits))
    assert all(a.args["chunks"] == -(-a.args["prompt_tokens"] // 1024)
               for a in admits)
    cost = _costs().delta_scan_cost(1000, {})
    # the recurrence of a token and layer: 7 x 30 x 96 x 192 operations;
    # 11520 + 5760 bf16 and 60 float32 moved, and the state once: on the
    # v5e the bytes bound it
    assert cost["flops"] == 1000 * 7 * 30 * 96 * 192
    assert cost["bytes"] == (1000 * ((11520 + 5760) * 2 + 60 * 4)
                             + 2 * 4 * 30 * 96 * 192)
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9
    busy = _read(monkeypatch, METRICS[4], OLMO)
    assert 0 < busy < 100


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("path", BEFORE + [os.path.join(HERE, "data", "none")])
def test_a_model_without_delta_layers_no_number(monkeypatch, metric, path):
    """A trace of a program with no gated delta-rule layer (Granite's, whose
    state layers are Mamba-2's; GPT-2's), and no trace at all: None, the
    line leaves the metric out, nothing raises: what the parent commit gives
    under this PR's benchmark files. (Granite's admissions carry ``chunks``
    and ``ssm_prefill_tokens`` too: ``delta.chunks_per_admit`` is listed for
    the one cell, and reads only there.)"""
    got = _read(monkeypatch, metric, path)
    if metric == "delta.chunks_per_admit" and path == BEFORE[0]:
        assert got is not None and got >= 1
    else:
        assert got is None


def test_benchmark_json_lists_the_readers_for_the_one_cell():
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    for m in mine:
        assert m["workloads"] == [REAL]
        assert m["moves"] == "per_token_p50_ms"
        assert m["layer"] == (
            "state layers (ops/delta_rule.py, models/olmo_hybrid.py)")
        assert os.path.isfile(os.path.join(
            run.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
    cell, = [w for w in bench["workloads"] if w["name"] == REAL]
    assert cell["chips"] == 1 and cell["config"] == "olmo-hybrid-7b"
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["num_hidden_layers"]
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if REAL in m.get("workloads", [])}
    assert "engine.tick_fetch_ms" not in listed
    assert not {n for n in listed if n.startswith("ssm")} - {"ssm.live_slots"}
    assert {"per_token_p50_ms", "decode_attn_roofline", "attn.share_of_tick",
            "engine.decode_step_ms", "engine.between_ticks_ms",
            "engine.tick_sample_ms", "engine.admit_stall_ms",
            "engine.request_ms_per_token", "engine.queue_wait_ms",
            "engine.stalled_share", "engine.admit_device_ms",
            "engine.admit_cache_ms", "serve.submit_delay_ms",
            "serve.deliver_ms", "trace.idle_unattributed_share.serve",
            "ssm.live_slots"} <= listed
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
    assert model["num_layers"] == 16 and model["vocab_size"] == 100352
    kinds = model["layer_types"][:16]
    assert kinds == (["linear_attention"] * 3 + ["full_attention"]) * 4
    assert model["state_dtype"] == "float32"
    assert model["max_seq_len"] == cell["traffic"]["context_limit"] == 8704
    mix = cell["traffic"]
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 3072, "sigma": 0.5, "min": 1024,
        "max": 8192}
    assert mix["max_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.5, "min": 64,
        "max": 512}
    # open loop, exponential gaps, 0.4-0.7 x the knee of 1.3 requests/s
    assert mix["interarrival"] == {"dist": "exponential", "mean": 1.0}
    assert 0.4 * 1.3 <= mix["rate_per_s"] <= 0.7 * 1.3
    assert mix["check_prompt_tokens"][:3] == [1025, 3000, 8000]
    assert (max(mix["check_prompt_tokens"]) + mix["check_max_tokens"]
            <= mix["check_pad_to"] <= mix["context_limit"])
    assert max(config["serve"]["prefill_buckets"]) == 1024
    # the published keys, whole, and the one cut
    assert config["num_hidden_layers"] == 16
    assert config["published"] == {"num_hidden_layers": 32}
    assert len(config["layer_types"]) == 32
    for key, value in (("hidden_size", 3840), ("intermediate_size", 11008),
                       ("linear_key_head_dim", 96),
                       ("linear_value_head_dim", 192),
                       ("linear_num_key_heads", 30),
                       ("num_attention_heads", 30),
                       ("num_key_value_heads", 30), ("vocab_size", 100352)):
        assert config[key] == value, key
    assert set(config["changed"]) >= {"num_hidden_layers", "max_seq_len"}
    for key in ("assumed", "deployment", "weights"):
        assert config[key]
    assert _costs().param_count(model)["total"] == 4_100_788_944
