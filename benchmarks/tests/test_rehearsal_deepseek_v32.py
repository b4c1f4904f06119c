"""``run.py`` end to end on the CPU at ``deepseek-v32-tiny``: the serving
cell of ``deepseek-v3.2-exp.serve-longdoc`` at toy widths, through the same
runner (``serve_open_loop_median``), proxy, replica, engine and reference:
evenly paced prompts in chunks past the 16 positions the toy's indexer
keeps, two rows a position in the cache, a share of group-routed experts.
The toy's ``BENCHMARK.json`` is not edited: ``data/tiny/deepseek-v32-tiny.
entries.json`` holds what a copy of it gains, as ``BENCHMARK.json`` gained it
for the real cell. Then the cell's faults planted at toy size, the new
readers where there is nothing to read, and what ``BENCHMARK.json``, the
costs and the configuration file state. Nothing timed on the CPU is a device
number."""
import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.lib import host_spans, named
from benchmarks.lib import trace as T
from benchmarks.tests import faults_deepseek_v32 as faults
from benchmarks.tests.faults_olmo_hybrid import read, served

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny")
BEFORE = [os.path.join(HERE, "data", "v5e_1chip_olmo_hybrid.xplane.pb"),
          os.path.join(HERE, "data", "v5e_1chip_afmoe.xplane.pb"),
          os.path.join(HERE, "data", "v5e_1chip_spans.xplane.pb")]
SEED = 2 ** 31 + 57  # the driver's seeds do not fit 32 signed bits
CELL = "deepseek-v32-tiny.serve-longdoc"
REAL = "deepseek-v3.2-exp.serve-longdoc"
METRICS = ("dsa.share_of_prefill", "dsa.share_of_tick", "dsa.selected_share",
           "dsa_index_roofline", "dsa_select_roofline",
           "dsa_sparse_decode_roofline", "dsa_sparse_prefill_roofline")
FACTS = {"decode_program": "jit_decode", "device_kind": "TPU v5 lite",
         "chips": 1}


@pytest.fixture
def toy_with_deepseek(tmp_path):
    """A copy of the toy benchmark with this cell's entries merged in."""
    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    with open(os.path.join(root, "deepseek-v32-tiny.entries.json")) as f:
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


def test_serve_cell_comes_out_correct(monkeypatch, toy_with_deepseek, capfd):
    r = _run(monkeypatch, toy_with_deepseek, False)
    print(json.dumps(r)[:1500])
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 8  # 4 a second for two seconds
    assert set(r["metrics"]) == {"per_token_p50_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    check = [json.loads(x) for x in capfd.readouterr().out.splitlines()
             if x.startswith('{"cell"') and "max_abs_logprob_diff" in x][0]
    # bf16 weights and activations at toy widths against the float32
    # reference over the same weights and the same share, through prompts of
    # 12 (every position chosen), 50 (two chunks) and 90 tokens (three, the
    # last padded; 16 of up to 96 positions chosen). The toy's limits are
    # wide; ``tests/test_deepseek_v32.py`` holds the chunks and the cached
    # steps to 5e-5 in float32
    assert check["check_sequences"] == 3 and check["token_counts_ok"]
    assert 0 < check["max_abs_logprob_diff"] < check["tolerance"]
    medians = check["request_median_abs_logprob_diff"]
    assert len(medians) == 3
    assert 0 < max(medians) < check["request_median_tolerance"]
    assert 0 < check["median_abs_logprob_diff"] < check["median_tolerance"]


def test_serve_cell_traced_reads_what_a_cpu_trace_holds(
        monkeypatch, toy_with_deepseek):
    """A CPU trace has no TPU plane, so the readers of the device trace find
    nothing and their metrics are left out, not invented; the engine's spans
    are on the host plane: ``dsa.selected_share`` reads the ticks' and the
    admissions' counters (16 positions kept of 20 to 100 visible)."""
    r = _run(monkeypatch, toy_with_deepseek, True)
    assert r["correct"] is True and r["device"]["busy_s"] == 0
    assert set(r["metrics"]) == {"dsa.selected_share"}
    kept = r["metrics"]["dsa.selected_share"]
    assert kept["unit"] == "ratio" and 16 / 100 < kept["value"] < 16 / 20
    _, _, _, per_layer, _ = run.load_cell(CELL, toy_with_deepseek)
    assert set(METRICS) <= {m["name"] for m in per_layer}


def test_the_sound_program_reads_correct_through_the_faults_own_door(
        monkeypatch, toy_with_deepseek):
    """The controls of the real cell's comparison
    (``faults_deepseek_v32.py``, whose chip readings the cell file's
    ``notes`` hold) go through ``served`` and ``read``; with nothing planted
    they read the toy cell correct. (Every fault reads like a sound run over
    six answer tokens at 64 channels, where a softmax over a few dozen
    positions is flat whichever of them were chosen: the chip tells them at
    the published widths, ``tests/test_deepseek_v32.py`` with the matrices
    times 4; their ``plant`` is exercised below.)"""
    _, cell, config, _, _ = run.load_cell(CELL, toy_with_deepseek)
    _, _, reference, _, _ = run.load_cell(CELL, toy_with_deepseek)
    faults.plant("sound", config, monkeypatch.setattr)
    sample, = served(config, cell["traffic"], [SEED]).values()
    got = read(reference, cell["traffic"], sample)
    print(got)
    assert got["within"] is True


def _cache_after(config, monkeypatch, variant):
    """The engine's cache after one request of 38 prompt tokens and 4
    answer tokens in slot 0, with ``variant`` planted, and its answer's
    log-probabilities."""
    import numpy as np

    from benchmarks.lib import program
    from ray_tpu.llm.engine import DecodeEngine, SamplingParams

    config = json.loads(json.dumps(config))
    faults.plant(variant, config, monkeypatch.setattr)
    engine = DecodeEngine(program.llm_config(config))
    try:
        answer = engine.generate(
            list(range(2, 40)), SamplingParams(max_new_tokens=4, logprobs=1))
        cache = {k: np.asarray(v[:, 0], np.float32)
                 for k, v in engine._cache.items()}
        return cache, [e["logprob"] for e in answer.logprobs]
    finally:
        engine.shutdown()
        monkeypatch.undo()


def test_the_faults_the_toy_cannot_read_are_planted_where_they_say(
        monkeypatch, toy_with_deepseek):
    import numpy as np

    _, _, config, _, _ = run.load_cell(CELL, toy_with_deepseek)
    sound, logprobs = _cache_after(config, monkeypatch, "sound")
    assert set(sound) == {"latent", "index"}
    assert sound["latent"].shape[1:] == (1, 40, 128)
    assert sound["index"].shape[1:] == (128, 16)
    # the indexer's key: its first 8 channels another from position 1 on
    # (position 0 rotates nothing), the other 8 and the first layer's latent
    # rows as they were
    keys = _cache_after(config, monkeypatch, "ik_unrotated")[0]
    assert np.array_equal(keys["latent"][0], sound["latent"][0])
    assert np.array_equal(keys["index"][0, :38, 8:], sound["index"][0, :38, 8:])
    assert np.array_equal(keys["index"][0, 0, :8], sound["index"][0, 0, :8])
    assert np.abs(keys["index"][0, 1:38, :8]
                  - sound["index"][0, 1:38, :8]).max() > 0.05
    # plain theta: the rotated channels of both rows differ, from the pairs
    # the ramp reaches on
    plain = _cache_after(config, monkeypatch, "no_yarn")[0]
    rank = config["model"]["kv_lora_rank"]
    assert np.array_equal(plain["latent"][0, 0, :rank], 
                          sound["latent"][0, 0, :rank])
    assert np.abs(plain["latent"][0, 0, rank:, 1:38]
                  - sound["latent"][0, 0, rank:, 1:38]).max() > 0.05
    assert np.abs(plain["index"][0, 1:38, :8]
                  - sound["index"][0, 1:38, :8]).max() > 0.05
    # the scale, the choice and the ReLU: the cache's first layer as it
    # was (they touch no row of it), the answer another
    for variant in ("no_mscale", "recent", "no_relu"):
        cold, moved = _cache_after(config, monkeypatch, variant)
        assert np.array_equal(cold["latent"][0], sound["latent"][0]), variant
        assert np.array_equal(cold["index"][0], sound["index"][0]), variant
        assert moved != logprobs, variant
    with pytest.raises(SystemExit, match="unknown variant"):
        faults.plant("no_such", config, monkeypatch.setattr)


# --------------------------------------- the readers where nothing is to read


def _read(monkeypatch, metric, path):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", path)
    trace = T.load(path) if os.path.exists(path) else None
    return run.read_layer_metric(metric, trace, FACTS)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("path", BEFORE + [os.path.join(HERE, "data", "none")])
def test_a_model_without_an_indexer_no_number(monkeypatch, metric, path):
    """A trace of a program with no indexer (Olmo-Hybrid's, Trinity's,
    GPT-2's) and no trace at all: None, the line leaves the metric out,
    nothing raises: what the parent commit gives under this PR's benchmark
    files."""
    assert _read(monkeypatch, metric, path) is None


def _costs():
    return named.load(os.path.join(run.BENCH_DIR, "costs", "deepseek_v32.py"))


def test_the_costs_by_arithmetic_written_out():
    costs, model = _costs(), run.load_cell(REAL)[2]["model"]
    n = costs.param_count(model)
    assert n["latent_mixer"] == 187_107_328
    assert n["indexer"] == 13_959_424
    assert n["dense_mlp"] == 396_361_728
    assert n["routed_ffn"] == 750_518_528
    assert n["embedding"] + n["head"] == 231_669_760
    assert n["total"] == 4_635_518_208
    # a (query, position) pair of the indexer: 64 heads' products of 128
    # channels, a ReLU and a weight each; a position's key 128 bf16 values
    index = costs.index_scores_cost(10_000, model)
    assert index["flops"] == 10_000 * (2 * 64 * 128 + 2 * 64)
    assert index["bytes"] == 10_000 * 256
    # a decode step's one query a slot: the keys' bytes bound it; a chunk's
    # 2,048 queries share a key: the products do
    assert index["flops"] / 197e12 < index["bytes"] / 819e9
    shared = costs.index_scores_cost(10_000, model, 2048)
    assert shared["flops"] / 197e12 > shared["bytes"] / 819e9
    assert costs.selection_cost(10_000, 2_048) == {
        "flops": 10_000.0, "bytes": 4.0 * 10_000 + 4.0 * 2_048}
    # a chosen row: 576 bf16 values read once; every head scores them and
    # sums the first 512; a visit writes a tile of 128 positions back
    step = costs.selected_decode_cost(2_048, 5, model)
    assert step["bytes"] == 2_048 * 1152 + 5 * 128 * 1152
    assert step["flops"] == 2_048 * 128 * 2 * (2 * 512 + 64)
    chunk = costs.selected_prefill_cost(2_048, model, 2048)
    assert chunk["flops"] == 2_048 * 128 * 2 * (128 + 64 + 128)
    assert chunk["bytes"] == 1152
    # the costs' defaults are the published sizes
    assert costs.selected_decode_cost(2_048, 5, {}) == step


def test_benchmark_json_lists_the_readers_for_the_one_cell():
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    for m in mine:
        assert m["workloads"] == [REAL]
        assert m["moves"] == "per_token_p50_ms"
        assert os.path.isfile(os.path.join(
            run.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        assert (m["name"].endswith("_roofline")) == (
            m["unit"] == "%" and m["better"] == "higher")
    cell, = [w for w in bench["workloads"] if w["name"] == REAL]
    assert cell["chips"] == 1 and cell["config"] == "deepseek-v3.2-exp"
    mine, = [c for c in bench["configs"] if c["name"] == "deepseek-v3.2-exp"]
    assert mine["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
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
        "serve.submit_delay_ms", "serve.deliver_ms", "serve.pull_turn_ms",
        "serve.submit_lock_ms", "proxy.outside_engine_ms",
        "proxy.request_ms_per_token", "proxy.route_ms",
        "proxy.route_fetch_share", "proxy.submit_ms", "proxy.egress_ms",
        "trace.idle_unattributed_share.serve", "moe.ffn_share_of_tick",
        "moe.dispatch_share_of_ffn", "moe.experts_touched",
        "moe_experts_roofline", "moe.shared_share_of_tick",
        "moe.held_pairs_share", "mla.share_of_tick", "mla.share_of_prefill",
        "mla_decode_roofline"}
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
            ("hidden_size", 7168), ("num_attention_heads", 128),
            ("q_lora_rank", 1536), ("kv_lora_rank", 512),
            ("qk_rope_head_dim", 64), ("qk_nope_head_dim", 128),
            ("v_head_dim", 128), ("index_n_heads", 64),
            ("index_head_dim", 128), ("index_topk", 2048),
            ("intermediate_size", 18432), ("moe_intermediate_size", 2048),
            ("num_experts_per_tok", 8), ("n_group", 8), ("topk_group", 4),
            ("routed_scaling_factor", 2.5), ("rope_theta", 10000),
            ("max_position_embeddings", 163840),
            ("num_nextn_predict_layers", 1)):
        assert config[key] == value, key
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # and the four cuts, with what was published beside them
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"]) == (
                5, 1, 16, 16160)
    assert config["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280}
    assert set(config["changed"]) >= set(config["published"])
    for key in ("assumed", "deployment", "weights"):
        assert config[key]
    assert "SIXTEEN chips share each layer" in config["deployment"]
    assert (model["num_layers"], model["first_k_dense"]) == (5, 1)
    assert (model["moe_num_experts"], model["moe_num_held"],
            model["moe_first_held"], model["moe_top_k"]) == (256, 16, 0, 8)
    assert (model["moe_n_group"], model["moe_topk_group"],
            model["moe_route_scale"]) == (8, 4, 2.5)
    assert (model["index_n_heads"], model["index_head_dim"],
            model["index_topk"]) == (64, 128, 2048)
    assert (model["rope_factor"], model["rope_original_max_position"],
            model["rope_beta_fast"], model["rope_beta_slow"]) == (
                40, 4096, 32, 1)
    assert model["vocab_size"] == 16160 == 129280 // 8
    assert model["max_seq_len"] == mix["context_limit"]
    assert config["serve"]["max_batch_slots"] == 8
    assert config["serve"]["prefill_buckets"] == [256, 512, 1024, 2048]
    assert config["serve"]["prefix_cache_size"] == 0
    # the traffic as the issue names it: evenly paced, inside the band
    assert mix["interarrival"] == {"dist": "fixed", "value": 1.0}
    assert mix["request"] == {"stream": True, "temperature": 0.0,
                              "ignore_eos": True}
    assert mix["rate_per_s"] * 50 >= 8    # eight requests a window or more
    assert (max(mix["check_prompt_tokens"]) + mix["check_max_tokens"]
            <= mix["check_pad_to"] <= mix["context_limit"])
    assert mix["check_max_tokens"] == 128
    assert _costs().param_count(model)["total"] == 4_635_518_208


# ------------------------------------------------- the readers on a recording
# ``data/v5e_1chip_deepseek_v32.xplane.pb``: PR 57's traced run of the cell on
# one v5e (call 5, seed 2147483711), cut to its shortest whole admission of
# two chunks or more (5 chunks, a prompt of ~9.6k tokens) and the ticks
# behind it (``record_deepseek_v32_trace.py``).
RECORDED = os.path.join(HERE, "data", "v5e_1chip_deepseek_v32.xplane.pb")


@pytest.mark.parametrize("metric, low, high", [
    ("dsa.share_of_prefill", 50.0, 90.0), ("dsa.share_of_tick", 5.0, 40.0),
    ("dsa.selected_share", 1 / 16, 1.0), ("dsa_index_roofline", 5.0, 100.0),
    ("dsa_select_roofline", 0.1, 100.0),
    ("dsa_sparse_decode_roofline", 0.5, 100.0),
    ("dsa_sparse_prefill_roofline", 0.5, 100.0)])
def test_a_recorded_capture_reads_every_new_metric(
        monkeypatch, metric, low, high):
    """Each of the seven readers finds its scopes, its kernel and its two
    counters in a capture of the chip: a share of a roofline between 0 and
    100, the chosen share between a sixteenth and one."""
    got = _read(monkeypatch, metric, RECORDED)
    print(metric, got)
    assert got is not None and low <= got <= high


def test_the_recorded_admission_counts_what_it_scored_and_read():
    spans = host_spans.load(RECORDED)
    admit, = [a for a in spans.named("engine.admit")
              if a.args.get("index_positions")]
    assert admit.args["chunks"] == 5
    # five latent layers, five chunks of 2,048 queries from position 0
    seen = sum(t + 1 for t in range(5 * 2048)) * 5
    assert admit.args["index_positions"] == seen == 262169600
    kept = sum(min(t + 1, 2048) for t in range(5 * 2048)) * 5
    assert admit.args["selected_positions"] == kept
    ticks = [t for t in spans.named("engine.tick")
             if t.args.get("index_positions")]
    assert ticks and all(
        t.args["selected_positions"] == 5 * 2048 * t.args["active"]
        for t in ticks)
