"""``lib/decode_attn.py`` and its reader on a small trace of the OLMoE
engine with the decode-attention kernel, recorded on a v5e chip
(``data/v5e_1chip_decode_attn.xplane.pb``: PR 28's fifth chip call, a
traced run of ``olmoe-1b-7b.serve-assist``, cut by ``record_moe_trace.py``
to its first four decode programs, three slots decoding): the share a second time by arithmetic
written out, and nothing where a trace has no kernel, no argument or no
chip."""
import json
import os

import pytest

from benchmarks import run
from benchmarks.lib import decode_attn, host_spans, op_scopes
from benchmarks.lib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KERNEL = os.path.join(DATA, "v5e_1chip_decode_attn.xplane.pb")
BEFORE = [os.path.join(DATA, "v5e_1chip_moe.xplane.pb"),    # PR 27's OLMoE
          os.path.join(DATA, "v5e_1chip_spans.xplane.pb")]  # PR 24's GPT-2
FACTS = {"decode_program": "jit_decode", "device_kind": "TPU v5 lite",
         "chips": 1}
METRIC = "decode_attn_roofline"


def _read(monkeypatch, path):
    monkeypatch.setattr(host_spans, "TRACE_ROOT", path)
    return run.read_layer_metric(METRIC, T.load(path), FACTS)


def test_the_kernel_is_found_by_its_name_and_sized_by_its_cache():
    ops = op_scopes.load(KERNEL)
    decode = ops.program_ids("jit_decode")
    kernels = [m for m in ops.meta.values()
               if m.program_id in decode and decode_attn.is_kernel(m)]
    assert len(kernels) == 1  # one call in the layer loop
    # OLMoE at depth 8, 16 slots, 16 kv heads of 128, 4,096 positions, bf16
    assert decode_attn.cache_shape(kernels[0].text) == (8, 16, 16, 128, 4096, 2)
    runs = [own for mid, _, own in ops.self_ns
            if decode_attn.is_kernel(ops.meta[mid])]
    ticks = [m for m in ops.modules if "jit_decode" in m[0]]
    assert len(runs) == 8 * len(ticks)  # a layer each, every tick


def test_the_share_by_arithmetic_written_out(monkeypatch):
    share = _read(monkeypatch, KERNEL)
    spans, ops = host_spans.load(KERNEL), op_scopes.load(KERNEL)
    paired = host_spans.ticks_with_program(
        spans.loop_line(), T.load(KERNEL).devices[0], "jit_decode",
        spans.device_clock_offset_ns)
    assert len(paired) >= 3
    assert all(t.args["cache_positions"] > t.args["active"] > 0
               for t, _ in paired)
    # K and V, 8 layers, 16 heads of 128, bf16: the positions the decoding
    # slots hold and one tile of 128 a slot written, at 819 GB/s
    least = sum(2 * 8 * 16 * 128 * 2 * (
        t.args["cache_positions"] + 128 * t.args["active"]) / 819e9
        for t, _ in paired)
    off = spans.device_clock_offset_ns
    spent = sum(own for mid, start, own in ops.self_ns
                if decode_attn.is_kernel(ops.meta[mid])
                and any(s - off <= start < s - off + d
                        for _, (s, d) in paired)) / 1e9
    assert share == pytest.approx(100 * least / spent)
    assert 0 < share < 100


@pytest.mark.parametrize("path", BEFORE)
def test_no_kernel_no_number(monkeypatch, path):
    """A trace of a program from before the kernel (no such custom call, no
    ``cache_positions``), and no trace at all: None, the line leaves the
    metric out, nothing raises."""
    assert _read(monkeypatch, path) is None
    monkeypatch.setattr(host_spans, "TRACE_ROOT", os.path.join(DATA, "none"))
    assert run.read_layer_metric(METRIC, None, FACTS) is None


def test_benchmark_json_lists_the_reader_for_both_serving_cells():
    with open(os.path.join(run.CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    decode_step = next(m for m in bench["per_layer"]
                       if m["name"] == "engine.decode_step_ms")
    assert entry == {**decode_step, "name": METRIC, "unit": "%",
                     "better": "higher"}
    assert entry["workloads"] == ["gpt2-xl.serve-chat",
                                  "olmoe-1b-7b.serve-assist"]
