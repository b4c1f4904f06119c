"""The trace reduction against small traces recorded on the chip by
``record_small_trace.py`` (twelve runs of a jitted ``step_fn``, a 2 ms host
pause after every fourth): fixed numbers, so the yardstick cannot drift."""
import os

import pytest

from benchmarks.lib import costs, trace as T
from benchmarks.lib.kernels import flash_roofline_share

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def one_chip():
    return T.load(os.path.join(DATA, "v5e_1chip.xplane.pb"))


def test_busy_and_idle_share(one_chip):
    assert [d.ordinal for d in one_chip.devices] == [0]
    assert one_chip.window_s == pytest.approx(0.012836021, abs=1e-9)
    assert T.busy_s(one_chip) == pytest.approx(0.006726491, abs=1e-9)
    idle = 1 - T.busy_s(one_chip) / one_chip.window_s
    assert idle == pytest.approx(0.47597, abs=1e-4)


def test_per_program_time_and_gaps(one_chip):
    dev = one_chip.devices[0]
    assert len(dev.modules) == 12 and len(dev.ops) == 60
    assert T.dominant_program(dev, "step_fn") == "jit_step_fn"
    assert T.dominant_program(dev, "decode") is None
    assert T.program_median_ms(dev, "jit_step_fn") == pytest.approx(
        0.5624645, abs=1e-6)
    # eleven gaps, two of them the 2 ms host pauses (seen as ~3 ms)
    assert T.program_gap_mean_ms(dev, "jit_step_fn") == pytest.approx(
        0.5553963, abs=1e-6)
    assert T.program_median_ms(dev, "jit_other") is None


def test_top_operations_and_idle_gaps(one_chip):
    b = T.breakdown(one_chip)
    assert [name for name, _ in b["device_ops"]] == [
        "fusion bf16[2048,2048]", "fusion.1 bf16[4096,2048]",
        "convolution_tanh_fusion bf16[4096,2048]",
        "copy-done bf16[4096,2048]",
        "copy-start (bf16[4096,2048], bf16[4096,2048], u32[])"]
    assert b["device_ops"][0][1] == pytest.approx(0.002178767, abs=1e-9)
    assert len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == [
        "after jit_step_fn before jit_step_fn",
        pytest.approx(0.003167889, abs=1e-9)]
    assert b["idle_gaps"][1][1] == pytest.approx(0.002932639, abs=1e-9)


def test_no_collective_on_one_chip(one_chip):
    assert T.collective_exposed_s(one_chip.devices[0]) == 0.0


def test_collective_exposed_share_on_four_chips():
    """The same ``step_fn`` over four chips: its one all-reduce holds the op
    line while nothing else runs."""
    from benchmarks import run

    four = T.load(os.path.join(DATA, "v5e_4chip.xplane.pb"))
    assert [d.ordinal for d in four.devices] == [0, 1, 2, 3]
    assert four.window_s == pytest.approx(0.011623242, abs=1e-9)
    assert T.busy_s(four) == pytest.approx(0.00340260425, abs=1e-10)
    exposed = [T.collective_exposed_s(d) for d in four.devices]
    assert exposed == pytest.approx(
        [0.001738294, 0.001729688, 0.001730316, 0.001714983], abs=1e-9)
    share = run.read_layer_metric("train.collective_exposed_share", four, {})
    assert share == pytest.approx(50.935, abs=1e-3)
    assert T.top_ops(four, 1) == [
        ["all-reduce bf16[2048,2048]", pytest.approx(0.00172832025, abs=1e-10)]]


def test_self_time_of_nested_operations():
    # a while spans its body's ops on the same line: its own time is what
    # they leave over, and an op run twice is summed
    ops = [("while.1", 0, 100), ("fusion.2", 10, 30), ("kernel.3", 40, 50),
           ("fusion.2", 120, 30)]
    assert T.self_times(ops) == {"while.1": 20, "fusion.2": 60, "kernel.3": 50}


def test_kernels_are_told_apart_by_their_instruction():
    fwd = ('%closed_call.32 = (bf16[256,1024,64]{2,1,0}, f32[256,8,1024]{2,1,0})'
           ' custom-call(bf16[256,1024,64]{2,1,0} %a, bf16[256,1024,64]{2,1,0} %b,'
           ' bf16[256,1024,64]{2,1,0} %c), custom_call_target="tpu_custom_call"')
    other = ('%custom-call.2 = f32[50304,1024]{1,0} custom-call(), '
             'custom_call_target="AllocateBuffer"')
    assert T.is_kernel(fwd) and not T.is_kernel(other)
    assert T.operand_count(fwd) == 3 and T.operand_count(other) == 0
    assert T.op_label("closed_call.32", fwd) == (
        "kernel:closed_call.32 (bf16[256,1024,64], f32[256,8,1024])")
    dev = T.DeviceTrace(0, [("closed_call.32", 0, 5), ("closed_call.32", 9, 5),
                            ("custom-call.2", 20, 1)], [],
                        {"closed_call.32": fwd, "custom-call.2": other})
    assert T.kernel_seconds(dev, 3, 3) == (1e-8, 2, [fwd])
    assert T.kernel_seconds(dev, 4, 99) == (0.0, 0, [])
    # the reader costs [batch x heads, seq, head size] and nothing else
    facts = {"peak_flops_per_s": 197e12, "device_kind": "TPU v5 lite",
             "batch_per_chip": 16, "seq_len": 1024,
             "model": {"num_heads": 16, "embed_dim": 1024}}
    trace = T.Trace([dev], 0, 30)
    cost = costs.flash_attention_cost(16, 1024, 16, 64, backward=False)
    assert flash_roofline_share(trace, facts, backward=False) == pytest.approx(
        100.0 * (cost["flops"] / 197e12) / 5e-9)
    assert flash_roofline_share(trace, facts, backward=True) is None
    with pytest.raises(ValueError, match="not the flash kernel"):
        flash_roofline_share(trace, dict(facts, batch_per_chip=8),
                             backward=False)


def test_union_and_names():
    assert T.union_ns([(0, 10), (5, 10), (30, 5)]) == 20
    assert T.union_ns([]) == 0
    assert T.program_name("jit_step_fn(1234567)") == "jit_step_fn"
    assert T.instruction_name(
        "%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %x)") == "all-reduce.5"
    for name, exposed in [("all-reduce.5", True), ("all-gather-done.2", True),
                          ("all-gather-start.2", False), ("fusion.7", False),
                          ("reduce-scatter", True), ("all-reduce-start", False),
                          ("collective-permute-done", True)]:
        assert bool(T.COLLECTIVE.match(name)) is exposed, name
