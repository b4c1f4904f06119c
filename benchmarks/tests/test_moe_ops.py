"""``lib/op_scopes.py`` and the four ``moe.*`` readers on a small trace of
the OLMoE engine recorded on a v5e chip (``data/v5e_1chip_moe.xplane.pb``,
``record_moe_trace.py``): the wire reader against ``ProfileData``, each
metric a second time by arithmetic written out, and nothing where a trace
has no scope, no argument or no chip."""
import os

import pytest

from benchmarks import run
from benchmarks.lib import host_spans, moe_ops, op_scopes
from benchmarks.lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MOE = os.path.join(DATA, "v5e_1chip_moe.xplane.pb")
DENSE = os.path.join(DATA, "v5e_1chip_spans.xplane.pb")  # GPT-2, PR 24's
FACTS = {"decode_program": "jit_decode", "device_kind": "TPU v5 lite",
         "chips": 1}
READERS = ["moe.ffn_share_of_tick", "moe.dispatch_share_of_ffn",
           "moe.experts_touched", "moe_experts_roofline"]


def _read(monkeypatch, name, path):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", path)
    return run.read_layer_metric(name, T.load(path), FACTS)


@pytest.mark.parametrize("path", [MOE, DENSE])
def test_the_wire_reader_sees_what_profile_data_sees(path):
    ops, dev = op_scopes.load(path), T.load(path).devices[0]
    assert len(ops.ops) == len(dev.ops) > 1000
    assert sorted((s, d) for _, s, d in ops.ops) == sorted(
        (s, d) for _, s, d in dev.ops)
    assert sorted(ops.modules) == sorted(dev.modules)
    # every operation has its text and its program; the texts are the names
    # ProfileData gives the events
    assert {T.instruction_name(ops.meta[m].text) for m, _, _ in ops.ops} == {
        n for n, _, _ in dev.ops}
    assert all(ops.meta[m].program_id for m, _, _ in ops.ops)
    own = ops.self_ns
    assert sum(o for _, _, o in own) == T.union_ns(
        (s, d) for _, s, d in ops.ops)


def test_operations_carry_their_scopes():
    ops = op_scopes.load(MOE)
    decode = ops.program_ids("jit_decode")
    assert len(decode) == 1 and len(ops.program_ids("jit_prefill")) == 1
    scopes = {moe_ops.scope_of(m) for m in ops.meta.values()
              if m.program_id in decode}
    assert scopes == {None, *moe_ops.SCOPES}
    kernels = [m for m in ops.meta.values() if m.program_id in decode
               and "ragged-dot-none" in m.text.split(" = ")[0]]
    # the compiler's own name, no scope: the three grouped products
    assert len(kernels) == 3
    assert {m.op_name for m in kernels} == {"ragged-dot-none:"}
    assert all(moe_ops.scope_of(m) == "moe.experts" for m in kernels)
    # every layer's experts as one operand: [8 x 64, K, N], bf16
    assert moe_ops._expert_shapes(ops, decode) == (2048, 1024, 2)
    assert all("bf16[512," in m.text for m in kernels)


def test_the_shares_by_arithmetic_written_out(monkeypatch):
    ops = op_scopes.load(MOE)
    decode = ops.program_ids("jit_decode")
    by_scope = {s: 0 for s in moe_ops.SCOPES}
    for mid, _, own in ops.self_ns:
        scope = moe_ops.scope_of(ops.meta[mid])
        if ops.meta[mid].program_id in decode and scope:
            by_scope[scope] += own
    total = sum(d for name, _, d in ops.modules if "jit_decode" in name)
    every = sum(by_scope.values())
    assert _read(monkeypatch, "moe.ffn_share_of_tick", MOE) == pytest.approx(
        100 * every / total)
    assert _read(monkeypatch, "moe.dispatch_share_of_ffn", MOE
                 ) == pytest.approx(
        100 * (every - by_scope["moe.experts"]) / every)
    # two slots decode in the recording: the cache's attention is the tick
    # and the experts 6-7% of it, the products nearly all of that
    assert 6 < 100 * every / total < 8
    assert by_scope["moe.experts"] > 10 * by_scope["moe.dispatch"] > 0


def test_touched_and_roofline_from_the_spans_arguments(monkeypatch):
    spans = host_spans.load(MOE)
    ticks = spans.named("engine.tick")
    assert len(ticks) == 5
    assert all(t.args["moe_layers"] == 8 and t.args["moe_rows"]
               == t.args["active"] * 8 * 8 for t in ticks)
    touched = [t.args["experts_touched"] / 8 for t in ticks]
    assert _read(monkeypatch, "moe.experts_touched", MOE) == pytest.approx(
        sum(touched) / 5)
    assert all(8 <= x <= 16 for x in touched)  # one or two slots decode
    share = _read(monkeypatch, "moe_experts_roofline", MOE)
    # memory-bound: the touched experts' three bf16 matrices at 819 GB/s
    # against what the three kernels and the activation took
    ops = op_scopes.load(MOE)
    paired = host_spans.ticks_with_program(
        spans.loop_line(), T.load(MOE).devices[0], "jit_decode",
        spans.device_clock_offset_ns)
    assert len(paired) == 4  # the fifth tick's program is past the cut
    least = sum((t.args["experts_touched"] * 3 * 2048 * 1024 * 2
                 + 2 * t.args["moe_rows"] * 2048 * 2) / 819e9
                for t, _ in paired)
    spent = sum(own for mid, start, own in ops.self_ns
                if moe_ops.scope_of(ops.meta[mid]) == "moe.experts"
                and any(s - spans.device_clock_offset_ns <= start
                        < s - spans.device_clock_offset_ns + d
                        for _, (s, d) in paired)) / 1e9
    assert share == pytest.approx(100 * least / spent)
    assert 50 < share < 100


@pytest.mark.parametrize("metric", READERS)
def test_no_scope_no_number(monkeypatch, metric):
    """A dense model's trace (no scope, no ``moe_*`` argument), and no
    trace at all: None, the line leaves the metric out, nothing raises."""
    assert _read(monkeypatch, metric, DENSE) is None
    monkeypatch.setattr(host_spans, "TRACE_ROOT", os.path.join(DATA, "none"))
    assert run.read_layer_metric(metric, None, FACTS) is None


def test_benchmark_json_lists_the_readers_for_the_new_cell_only():
    import json

    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(READERS[0])
    assert names[at:at + 4] == READERS
    for m in bench["per_layer"][at:at + 4]:
        assert m["workloads"] == ["olmoe-1b-7b.serve-assist"]
    # appended: what PR 24 appended stands right before them, as it was.
    # ``test_host_spans.py`` pins those seven as the LAST seven, which holds
    # for no PR that appends after it (BENCHMARK.json takes new entries at
    # the end only): that test is a ``benchmark`` PR's to edit, this is what
    # it meant.
    assert names[at - 7:at] == [
        "engine.tick_sample_ms", "engine.tick_fetch_ms",
        "engine.admit_stall_ms", "train.report_ms", "train.input_ms",
        "trace.idle_unattributed_share.train",
        "trace.idle_unattributed_share.serve"]
