"""``run.py`` end to end on the CPU at ``bailing-hybrid-tiny``: the serving
cell of ``ling-3.0-flash.serve-longgen`` at toy widths, through the same
runner (``serve_open_loop_median``), proxy, replica, engine and reference:
prompts in one bucket and in chunks that hand on states and latents, a share
of group-routed experts, slots reused. The toy's ``BENCHMARK.json`` is not
edited: ``data/tiny/bailing-hybrid-tiny.entries.json`` holds what a copy of
it gains, as ``BENCHMARK.json`` gained it for the real cell (over Granite's
entries, whose ``ssm.live_slots`` the new cell joins). Then the cell's three
faults planted at toy size, the new readers where there is nothing to read,
and what ``BENCHMARK.json`` and the configuration file state. Nothing timed
on the CPU is a device number."""
import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.lib import host_spans, named
from benchmarks.lib import trace as T
from benchmarks.tests import faults_bailing_hybrid as faults
from benchmarks.tests.faults_olmo_hybrid import read, served

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny")
BEFORE = [os.path.join(HERE, "data", "v5e_1chip_olmo_hybrid.xplane.pb"),
          os.path.join(HERE, "data", "v5e_1chip_afmoe.xplane.pb"),
          os.path.join(HERE, "data", "v5e_1chip_spans.xplane.pb")]
SEED = 2 ** 31 + 51  # the driver's seeds do not fit 32 signed bits
CELL = "bailing-hybrid-tiny.serve-longgen"
REAL = "ling-3.0-flash.serve-longgen"
METRICS = ("kda.share_of_tick", "kda.share_of_prefill", "kda_decode_roofline",
           "kda_prefill_roofline", "mla.share_of_tick",
           "mla.share_of_prefill", "mla_decode_roofline",
           "moe.held_pairs_share")
FACTS = {"decode_program": "jit_decode", "device_kind": "TPU v5 lite",
         "chips": 1}


@pytest.fixture
def toy_with_bailing(tmp_path):
    """A copy of the toy benchmark with Granite's entries file and then this
    one merged in."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for name in ("granite-tiny", "bailing-hybrid-tiny"):
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


def test_serve_cell_comes_out_correct(monkeypatch, toy_with_bailing, capfd):
    r = _run(monkeypatch, toy_with_bailing, False)
    print(json.dumps(r)[:1500])
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 20  # 10 a second for two seconds
    assert set(r["metrics"]) == {"per_token_p50_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "max_abs_logprob_diff" in x][0]
    # bf16 weights and activations at toy widths against the float32
    # reference over the same weights and the same share, through prompts of
    # 12 (one bucket), 50 (two chunks) and 90 tokens (three, the last
    # padded). The toy's limits are wide; ``tests/test_bailing_hybrid.py``
    # holds the chunks and the cached steps to 5e-5 in float32
    assert check["check_sequences"] == 3 and check["token_counts_ok"]
    assert 0 < check["max_abs_logprob_diff"] < check["tolerance"]
    medians = check["request_median_abs_logprob_diff"]
    assert len(medians) == 3
    assert 0 < max(medians) < check["request_median_tolerance"]
    assert 0 < check["median_abs_logprob_diff"] < check["median_tolerance"]


def test_serve_cell_traced_reads_what_a_cpu_trace_holds(
        monkeypatch, toy_with_bailing):
    """A CPU trace has no TPU plane, so the readers of the device trace find
    nothing and their metrics are left out, not invented; the engine's spans
    are on the host plane: ``moe.held_pairs_share`` reads the ticks'
    counters (8 of 32 experts held, the first 2 of 8 groups: a quarter under
    a balanced router, and a toy's is not), and ``ssm.live_slots`` (PR 42's
    reader, unedited) counts this family's state layers."""
    r = _run(monkeypatch, toy_with_bailing, True)
    assert r["correct"] is True and r["device"]["busy_s"] == 0
    assert set(r["metrics"]) == {"moe.held_pairs_share", "ssm.live_slots"}
    held = r["metrics"]["moe.held_pairs_share"]
    assert held["unit"] == "ratio" and 0.05 < held["value"] < 0.6
    assert 1 <= r["metrics"]["ssm.live_slots"]["value"] <= 4
    _, _, _, per_layer, _ = run.load_cell(CELL, toy_with_bailing)
    assert set(METRICS) <= {m["name"] for m in per_layer}


@pytest.mark.parametrize("variant, sound", [
    ("sound", True), ("decay_head_mean", False)])
def test_a_planted_fault_reads_not_correct(
        monkeypatch, toy_with_bailing, variant, sound):
    """The controls of the real cell's comparison
    (``faults_bailing_hybrid.py``, whose chip readings the cell file's
    ``notes`` hold), planted at toy size and read through the runner's own
    functions under the toy cell's limits: the decay's channel vector as its
    head's mean. (A state held in bfloat16 and a shared key left unrotated
    read like a sound run over six answer tokens at 64 channels, where a
    softmax over a hundred positions is flat whatever the keys: the chip
    tells them at the published widths; their ``plant`` is exercised
    below.)"""
    _, cell, config, _, _ = run.load_cell(CELL, toy_with_bailing)
    _, _, reference, _, _ = run.load_cell(CELL, toy_with_bailing)
    faults.plant(variant, config, monkeypatch.setattr)
    sample, = served(config, cell["traffic"], [SEED]).values()
    got = read(reference, cell["traffic"], sample)
    print(variant, got)
    assert got["within"] is sound


def _cache_after(config, monkeypatch, variant):
    """The engine's cache after one request of 38 prompt tokens and 4
    answer tokens in slot 0, with ``variant`` planted."""
    import numpy as np

    from benchmarks.lib import program
    from ray_tpu.llm.engine import DecodeEngine, SamplingParams

    faults.plant(variant, config, monkeypatch.setattr)
    engine = DecodeEngine(program.llm_config(config))
    try:
        engine.generate(list(range(2, 40)), SamplingParams(max_new_tokens=4))
        return {k: np.asarray(v[:, 0], np.float32)
                for k, v in engine._cache.items()}
    finally:
        engine.shutdown()
        monkeypatch.undo()


def test_the_faults_the_toy_cannot_read_are_planted_where_they_say(
        monkeypatch, toy_with_bailing):
    import jax.numpy as jnp
    import numpy as np

    _, _, config, _, _ = run.load_cell(CELL, toy_with_bailing)
    sound = _cache_after(config, monkeypatch, "sound")
    narrow = _cache_after(config, monkeypatch, "state_bf16")["ssm"]
    assert np.abs(narrow).max() > 0
    assert np.array_equal(
        narrow, np.asarray(jnp.asarray(narrow).astype(jnp.bfloat16),
                           np.float32))
    assert not np.array_equal(narrow, sound["ssm"])
    # the latent rows: the normed latent as it was, the shared key another
    # from position 1 on (position 0 rotates nothing)
    rows = _cache_after(config, monkeypatch, "kr_unrotated")["latent"][0, 0]
    want = sound["latent"][0, 0]
    rank = config["model"]["kv_lora_rank"]
    assert np.array_equal(rows[:rank, :38], want[:rank, :38])
    assert np.array_equal(rows[rank:, 0], want[rank:, 0])
    assert np.abs(rows[rank:, 1:38] - want[rank:, 1:38]).max() > 0.05
    with pytest.raises(SystemExit, match="unknown variant"):
        faults.plant("no_such", config, monkeypatch.setattr)


# --------------------------------------- the readers where nothing is to read


def _read(monkeypatch, metric, path):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", path)
    trace = T.load(path) if os.path.exists(path) else None
    return run.read_layer_metric(metric, trace, FACTS)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("path", BEFORE + [os.path.join(HERE, "data", "none")])
def test_a_model_without_these_layers_no_number(monkeypatch, metric, path):
    """A trace of a program with no KDA layer, no latent layer and no share
    of its experts (Olmo-Hybrid's, whose state layers are the scalar-gated
    delta rule's; Trinity's, routed and whole; GPT-2's), and no trace at
    all: None, the line leaves the metric out, nothing raises: what the
    parent commit gives under this PR's benchmark files."""
    assert _read(monkeypatch, metric, path) is None


def _costs():
    return named.load(os.path.join(
        run.BENCH_DIR, "costs", "bailing_hybrid.py"))


def test_the_costs_by_arithmetic_written_out():
    costs, model = _costs(), run.load_cell(REAL)[2]["model"]
    # a slot and layer of the recurrence: 32 x 128 x 128 float32 read and
    # written, six rows of [32, 128] float32 (the decay, the key twice over
    # as the step folds its factors, the query, the value; the output)
    step = costs.kda_update_cost(10, model)
    assert step["bytes"] == 10 * 4 * (2 * 32 * 128 * 128 + 6 * 32 * 128)
    assert step["flops"] == 10 * 7 * 32 * 128 * 128
    assert step["flops"] / 197e12 < step["bytes"] / 819e9
    scan = costs.kda_scan_cost(1000, model)
    assert scan["flops"] == 1000 * 7 * 32 * 128 * 128
    assert scan["bytes"] == (1000 * (4 * 4096 * 2 + 4 * (4096 + 32))
                             + 2 * 4 * 32 * 128 * 128)
    # on the v5e the bytes bound it, as the scalar-gated rule's
    assert scan["flops"] / 197e12 < scan["bytes"] / 819e9
    # a latent position: 576 bf16 values read once; every head scores them
    # and sums the first 512; a visit writes a tile of 128 positions back
    latent = costs.latent_decode_cost(20000, 30, model)
    assert latent["bytes"] == 20000 * 1152 + 30 * 128 * 1152
    assert latent["flops"] == 20000 * 32 * 2 * (2 * 512 + 64)
    assert latent["flops"] / 197e12 < latent["bytes"] / 819e9
    assert costs.param_count(model)["total"] == 4_354_531_616


def test_benchmark_json_lists_the_readers_for_the_one_cell():
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    assert [m["name"] for m in bench["per_layer"][-len(METRICS):]] == list(
        METRICS)
    for m in mine:
        assert m["workloads"] == [REAL]
        assert m["moves"] == "per_token_p50_ms"
        assert os.path.isfile(os.path.join(
            run.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        assert (m["name"].endswith("_roofline")) == (
            m["unit"] == "%" and m["better"] == "higher")
    assert bench["workloads"][-1]["name"] == REAL
    cell = bench["workloads"][-1]
    assert cell["chips"] == 1 and cell["config"] == "ling-3.0-flash"
    assert bench["configs"][-1]["name"] == "ling-3.0-flash"
    assert bench["configs"][-1]["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size"]
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if REAL in m.get("workloads", [])}
    assert "engine.tick_fetch_ms" not in listed
    assert listed == set(METRICS) | {
        "per_token_p50_ms", "engine.decode_step_ms",
        "engine.between_ticks_ms", "engine.tick_sample_ms",
        "engine.admit_stall_ms", "engine.request_ms_per_token",
        "engine.queue_wait_ms", "engine.stalled_share",
        "engine.admit_device_ms", "engine.admit_cache_ms",
        "serve.submit_delay_ms", "serve.deliver_ms",
        "trace.idle_unattributed_share.serve", "moe.ffn_share_of_tick",
        "moe.dispatch_share_of_ffn", "moe.experts_touched",
        "moe_experts_roofline", "moe.shared_share_of_tick", "ssm.live_slots"}
    lines = [(e["name"], key, e[key])
             for kind in ("configs", "workloads", "per_layer")
             for e in bench[kind] for key in ("why", "source", "layer")
             if key in e and not (kind == "per_layer" and key == "source")]
    assert [(n, k, len(s)) for n, k, s in lines
            if not (1 <= len(s) <= 200 and s.isascii() and s.isprintable())
            ] == []


def test_the_configuration_file_states_what_the_issue_asked():
    _, cell, config, _, _ = run.load_cell(REAL)
    model, mix = config["model"], cell["traffic"]
    # the published widths, each under its published key
    for key, value in (
            ("hidden_size", 2560), ("num_attention_heads", 32),
            ("head_dim", 128), ("kv_lora_rank", 512),
            ("qk_rope_head_dim", 64), ("qk_nope_head_dim", 128),
            ("v_head_dim", 128), ("intermediate_size", 6144),
            ("moe_intermediate_size", 768), ("num_experts_per_tok", 8),
            ("n_group", 8), ("topk_group", 4), ("routed_scaling_factor", 2.5),
            ("layer_group_size", 6), ("kda_lower_bound", -5),
            ("short_conv_kernel_size", 4), ("rope_theta", 6000000)):
        assert config[key] == value, key
    # and the four cuts, with what was published beside them
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_experts"], config["vocab_size"]) == (
                6, 1, 128, 39296)
    assert config["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts": 512, "vocab_size": 157184}
    assert set(config["changed"]) >= set(config["published"])
    for key in ("assumed", "deployment", "weights"):
        assert config[key]
    assert "FOUR chips share each layer" in config["deployment"]
    assert (model["num_layers"], model["first_k_dense"]) == (6, 1)
    assert (model["moe_num_experts"], model["moe_num_held"],
            model["moe_first_held"], model["moe_top_k"]) == (512, 128, 0, 8)
    assert (model["moe_n_group"], model["moe_topk_group"],
            model["moe_route_scale"]) == (8, 4, 2.5)
    assert model["vocab_size"] == 39296 == 307 * 128
    assert model["state_dtype"] == "float32"
    assert model["max_seq_len"] == mix["context_limit"] == 19456
    assert config["serve"]["max_batch_slots"] == 64
    assert max(config["serve"]["prefill_buckets"]) == 2048
    assert config["serve"]["prefix_cache_size"] == 0
    # the traffic as the issue names it
    assert mix["interarrival"] == {"dist": "exponential", "mean": 1.0}
    # the issue's 16,384: its fallback of 8,192 buys no steadiness (the
    # cell's notes hold every set tried and the model's reading of both)
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 1.0, "min": 256,
        "max": 16384}
    assert 0.4 * 3.5 <= mix["rate_per_s"] <= 0.6 * 3.5
    assert mix["max_tokens"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256,
        "max": 3072}
    # every answer runs to its drawn length: a recount of 100 long answers,
    # unary, does not fit the proxy's deadline (the cell's notes)
    assert mix["request"] == {"stream": True, "temperature": 0.0,
                              "ignore_eos": True}
    assert mix["ramp_seconds"] == 8
    assert mix["rate_per_s"] * 50 >= 90   # ninety requests a window or more
    assert mix["check_prompt_tokens"] == [300, 2500, 6000]
    assert mix["check_max_tokens"] == 256
    assert (max(mix["check_prompt_tokens"]) + mix["check_max_tokens"]
            <= mix["check_pad_to"] <= mix["context_limit"])
