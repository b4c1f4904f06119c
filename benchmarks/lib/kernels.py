"""Roofline share of the flash-attention kernels, from a device trace.

The program gives its Pallas kernels no name yet, so the instruction's shape
is all there is to go by (PERF.md section 7): a Pallas custom call
(``tpu_custom_call``) with three operands, q, k and v, is the forward
kernel; one with more (q, k, v, the output's gradient, the saved statistics)
belongs to the backward pass, which today is two kernels, one for dq and one
for dk and dv, whose times are summed. Every kernel so matched must give a
(first) result of the shape the cost is counted for, [batch x heads, seq,
head size]: any other Pallas kernel, or a backward pass fused otherwise,
stops the reader with an error instead of changing the share unseen.
"""
import re

from benchmarks.lib import costs, peaks, trace as T

FIRST_RESULT = re.compile(r" = \(?\w+\[([\d,]*)\]")


def flash_roofline_share(trace, facts, *, backward: bool):
    """Least time the chip could take for the calls seen on chip 0 (FLOPs and
    bytes from the call's shapes; the larger of FLOPs over peak FLOP/s and
    bytes over peak bytes/s) over their summed device time, in percent."""
    if trace is None or not trace.devices or not facts.get("peak_flops_per_s"):
        return None
    lo, hi = (4, 99) if backward else (3, 3)
    seconds, runs, texts = T.kernel_seconds(trace.devices[0], lo, hi)
    if not runs:
        return None
    model = facts["model"]
    heads, head_dim = model["num_heads"], model["embed_dim"] // model["num_heads"]
    want = f"{facts['batch_per_chip'] * heads},{facts['seq_len']},{head_dim}"
    for text in texts:
        m = FIRST_RESULT.search(text)
        if m is None or m.group(1) != want:
            raise ValueError(
                f"a Pallas kernel with {T.operand_count(text)} operands is "
                f"not the flash kernel over [{want}] this reader can cost: "
                f"{text[:200]}")
    cost = costs.flash_attention_cost(
        facts["batch_per_chip"], facts["seq_len"], heads, head_dim,
        backward=backward)
    least = costs.roofline_seconds(cost, peaks.peaks_for(facts["device_kind"]))
    # one pass is one execution of each of its kernels
    return 100.0 * least["seconds"] * (runs / len(texts)) / seconds
