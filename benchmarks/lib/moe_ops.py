"""The routed experts' share of a decode tick and of their roofline, from a
device trace and the engine's own spans.

``parallel/moe.py`` puts its device operations under four
``jax.named_scope``s: ``moe.route`` (router product, softmax, top-k),
``moe.dispatch`` (sort, counts, gather), ``moe.experts`` (the grouped
products and the activation) and ``moe.combine`` (gate weighting, un-sort,
sum). ``lib/op_scopes.py`` reads each operation's scope from the trace. One
exception is the yardstick's to know: the TPU compiler turns a
``ragged_dot`` into two custom calls of its own, ``ragged-dot-metadata`` and
``ragged-dot-none`` (the Mosaic kernel), and names them so, dropping JAX's
``op_name``; both are the grouped products and count as ``moe.experts``.

Sizes come from the operations' own shapes, as ``lib/kernels.py`` does for
flash: a grouped product's weight operand is ``[groups, K, N]``. Rows and
touched experts come from the ``engine.tick`` spans' arguments
(``moe_rows``, ``experts_touched``: the program's counters, MEASURED, never
"all experts"). A trace of a program without the scopes or the arguments
(the commits before PR 27, a dense model) gives ``None`` everywhere.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Optional

from benchmarks.lib import costs, host_spans, op_scopes, peaks

SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine")
COMPILER_NAMED = {"ragged-dot-metadata": "moe.experts",
                  "ragged-dot-none": "moe.experts"}
WEIGHTS = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def scope_of(meta: op_scopes.OpMeta) -> Optional[str]:
    return (op_scopes.scope_of(meta.op_name, SCOPES)
            or COMPILER_NAMED.get(meta.op_name.rstrip(":")))


def decode_scope_ns(facts: dict) -> Optional[Dict[str, int]]:
    """Own nanoseconds of chip 0's operations under each ``moe.*`` scope
    inside the decode program, and that program's ``total``; None where the
    trace has no such program or no such operation."""
    ops = op_scopes.load()
    if ops is None:
        return None
    programs = ops.program_ids(facts["decode_program"])
    out: Dict[str, int] = collections.Counter()
    for mid, _, own in ops.self_ns:
        meta = ops.meta[mid]
        if meta.program_id in programs:
            scope = scope_of(meta)
            if scope is not None:
                out[scope] += own
    if not out:
        return None
    out["total"] = sum(dur for name, _, dur in ops.modules
                       if facts["decode_program"] in name)
    return dict(out)


def _expert_shapes(ops: op_scopes.ScopedOps, programs) -> Optional[tuple]:
    """(embed_dim, mlp_dim, bytes a weight) from the weight operands of the
    decode program's grouped products: [groups, K, N] with (K, N) = (D, M)
    for the products into the experts and (M, D) for the one out of them."""
    pairs = collections.Counter()
    for meta in ops.meta.values():
        if (meta.program_id in programs and scope_of(meta) == "moe.experts"
                and "custom-call(" in meta.text):
            operands = meta.text.split("custom-call(", 1)[1]
            for dtype, _, k, n in WEIGHTS.findall(operands):
                pairs[(int(k), int(n), BYTES[dtype])] += 1
    if not pairs:
        return None
    # gate and up make (D, M) the commoner pair; a tie (GELU experts) goes
    # to the wider contraction, which only moves the small rows term
    (k, n, size), _ = max(pairs.items(), key=lambda kv: (kv[1], kv[0][0]))
    return k, n, size


def experts_roofline_share(trace, facts: dict, cost) -> Optional[float]:
    """Least time for the captured ticks' expert products (FLOPs of their
    rows, bytes of the experts they TOUCHED and of the rows in and out:
    ``cost(rows, experts_touched, embed_dim, mlp_dim, bytes_per_weight)``, the
    ``moe_experts_cost`` of the architecture's ``costs/<name>.py``, which the
    metric's reader hands over; against ``lib/peaks.py``) over the
    device time under ``moe.experts`` in those ticks' decode programs, in
    percent. A tick's rows and touched experts are summed over its layers
    and costed as one call: never more than the sum over layers, so the
    share reads low, not high, where a tick is near the ridge."""
    spans, ops = host_spans.load(), op_scopes.load()
    if (spans is None or ops is None or trace is None or not trace.devices
            or spans.device_clock_offset_ns is None):
        return None
    programs = ops.program_ids(facts["decode_program"])
    shapes = _expert_shapes(ops, programs)
    if shapes is None:
        return None
    embed_dim, mlp_dim, weight_bytes = shapes
    chip = peaks.peaks_for(facts["device_kind"])
    dev, offset = trace.devices[0], spans.device_clock_offset_ns
    name = next(iter(programs.values()), None)
    expert_ops = sorted(
        (start, own) for mid, start, own in ops.self_ns
        if ops.meta[mid].program_id in programs
        and scope_of(ops.meta[mid]) == "moe.experts")
    least = spent = 0.0
    i = 0
    for tick, (start, dur) in host_spans.ticks_with_program(
            spans.loop_line(), dev, name, offset):
        if "experts_touched" not in tick.args:
            return None
        lo, hi = start - offset, start - offset + dur  # the chip's clock
        while i < len(expert_ops) and expert_ops[i][0] < lo:
            i += 1
        j = i
        while j < len(expert_ops) and expert_ops[j][0] < hi:
            spent += expert_ops[j][1] / 1e9
            j += 1
        i = j
        least += costs.roofline_seconds(cost(
            tick.args["moe_rows"], tick.args["experts_touched"], embed_dim,
            mlp_dim, weight_bytes), chip)["seconds"]
    return 100.0 * least / spent if spent > 0 else None
