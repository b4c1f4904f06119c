"""``lib/decode_attn_mixed.py`` and its four readers on a small trace of the
Trinity-Mini engine, recorded on a v5e chip
(``data/v5e_1chip_afmoe.xplane.pb``: PR 34's seventh chip call, a traced run
of ``trinity-mini.serve-mixed``, cut by ``record_moe_trace.py`` to its first
four decode programs): each number a second time by arithmetic written out,
and nothing where a trace has one kind of cache, no argument or no chip."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.lib import decode_attn_mixed as mixed
from benchmarks.lib import host_spans, op_scopes
from benchmarks.lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
AFMOE = os.path.join(DATA, "v5e_1chip_afmoe.xplane.pb")
BEFORE = [os.path.join(DATA, "v5e_1chip_decode_attn.xplane.pb"),  # OLMoE
          os.path.join(DATA, "v5e_1chip_spans.xplane.pb")]        # GPT-2
FACTS = {"decode_program": "jit_decode", "device_kind": "TPU v5 lite",
         "chips": 1}
METRICS = ("decode_attn_mixed_roofline", "attn.share_of_tick",
           "attn.window_spared_share", "moe.shared_share_of_tick")


def _read(monkeypatch, metric, path):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", path)
    trace = T.load(path) if os.path.exists(path) else None
    return run.read_layer_metric(metric, trace, FACTS)


def _paired():
    spans = host_spans.load(AFMOE)
    return spans, host_spans.ticks_with_program(
        spans.loop_line(), T.load(AFMOE).devices[0], "jit_decode",
        spans.device_clock_offset_ns)


def test_the_kernels_are_told_apart_by_name_and_sized_by_their_caches():
    ops = op_scopes.load(AFMOE)
    found = mixed.kernels(ops, ops.program_ids("jit_decode"))
    kinds = sorted(found.values())
    assert kinds == ["full"] + ["window"] * 4   # five layers, none scanned
    shapes = {kind: {mixed.cache_shape(ops.meta[mid].text)
                     for mid, k in found.items() if k == kind}
              for kind in ("full", "window")}
    # 32 slots, 4 kv heads of 128, bf16: one full layer of 8192 positions,
    # four window layers in rings of 4096
    assert shapes == {"full": {(1, 32, 4, 128, 8192, 2)},
                      "window": {(4, 32, 4, 128, 4096, 2)}}
    ticks = [m for m in ops.modules if "jit_decode" in m[0]]
    runs = [mid for mid, _, _ in ops.self_ns if mid in found]
    # a layer each, every tick (the cut leaves the last program short)
    assert 5 * (len(ticks) - 1) <= len(runs) <= 5 * len(ticks)


def test_the_roofline_share_by_arithmetic_written_out(monkeypatch):
    share = _read(monkeypatch, METRICS[0], AFMOE)
    spans, paired = _paired()
    ops = op_scopes.load(AFMOE)
    found = mixed.kernels(ops, ops.program_ids("jit_decode"))
    assert len(paired) >= 3
    for tick, _ in paired:
        a = tick.args
        assert a["layers_full"] == 1 and a["layers_window"] == 4
        assert a["cache_positions_full"] == a["cache_positions"]
        assert 0 < a["cache_positions_window"] <= a["cache_positions_full"]
        assert a["cache_positions_window"] <= 2048 * a["active"]
    # K and V, 4 kv heads of 128, bf16: one full layer's positions and four
    # window layers' positions, and a tile of 128 a slot and layer written
    least = sum(2 * 4 * 128 * 2 * (
        1 * (t.args["cache_positions_full"] + 128 * t.args["active"])
        + 4 * (t.args["cache_positions_window"] + 128 * t.args["active"]))
        / 819e9 for t, _ in paired)
    off = spans.device_clock_offset_ns
    spent = sum(own for mid, start, own in ops.self_ns if mid in found
                and any(s - off <= start < s - off + d
                        for _, (s, d) in paired)) / 1e9
    assert share == pytest.approx(100 * least / spent)
    assert 0 < share < 100


def test_the_shares_of_a_tick_and_what_the_window_spares(monkeypatch):
    ops = op_scopes.load(AFMOE)
    programs = ops.program_ids("jit_decode")
    found = mixed.kernels(ops, programs)
    total = sum(d for name, _, d in ops.modules if "jit_decode" in name)
    attn = sum(own for mid, _, own in ops.self_ns if mid in found)
    assert _read(monkeypatch, METRICS[1], AFMOE) == pytest.approx(
        100 * attn / total)
    shared = sum(own for mid, _, own in ops.self_ns
                 if ops.meta[mid].program_id in programs
                 and "moe.shared" in ops.meta[mid].op_name.rstrip(
                     ":").split("/"))
    assert 0 < shared < attn
    assert _read(monkeypatch, METRICS[3], AFMOE) == pytest.approx(
        100 * shared / total)
    ticks = host_spans.load(AFMOE).named("engine.tick")
    needed = sum(t.args["cache_positions_full"]
                 + 4 * t.args["cache_positions_window"] for t in ticks)
    every = sum(5 * t.args["cache_positions"] for t in ticks)
    spared = _read(monkeypatch, METRICS[2], AFMOE)
    assert spared == pytest.approx(100 * (1 - needed / every))
    # four of five layers spare what lies past 2048; 0 where no slot of the
    # recorded ticks is longer than that
    assert 0 <= spared < 80
    assert (spared == 0) == all(
        t.args["cache_positions_window"] == t.args["cache_positions_full"]
        for t in ticks)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("path", BEFORE + [os.path.join(DATA, "none")])
def test_one_kind_of_cache_no_number(monkeypatch, metric, path):
    """A trace of a program with one kind of cache (OLMoE's, GPT-2's: no
    window kernel, no ``layers_window``, no ``moe.shared``), and no trace at
    all: None, the line leaves the metric out, nothing raises. (The
    kernels' share of a tick has a reading wherever the kernel runs; the
    metric lists the one cell.)"""
    got = _read(monkeypatch, metric, path)
    if metric == "attn.share_of_tick" and path == BEFORE[0]:
        assert 0 < got < 100
    else:
        assert got is None


def test_benchmark_json_lists_the_readers_for_the_one_cell():
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(METRICS)
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == ["trinity-mini.serve-mixed"]
        assert m["moves"] == "per_token_p50_ms"
        assert os.path.isfile(os.path.join(
            run.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
