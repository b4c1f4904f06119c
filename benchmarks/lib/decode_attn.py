"""The decode-attention kernel's share of its roofline, from a device trace
and the engine's own spans.

``ray_tpu/ops/decode_attention.py`` is one Pallas custom call a layer, named
``decode_attention`` (the kernel's ``name=``), over the whole KV cache
``[L, B, KV, D, S]``, which is its largest operand: sizes come from that
operand's shape, as ``lib/moe_ops.py`` takes the experts' from theirs. What
a tick NEEDED of the cache comes from the ``engine.tick`` span's arguments:
``cache_positions`` (the decoding slots' lengths and the columns they write:
the program's counter, never "all positions") and ``active``. A trace of a
program without the kernel or the argument (the commits before PR 28) gives
``None``.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

from benchmarks.lib import host_spans, op_scopes, peaks
from benchmarks.lib.moe_ops import BYTES

KERNEL = re.compile(r"\s*(?:ROOT )?%?decode_attention[.\d]* = ")
CACHE = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")
TILE = 128  # positions: the finest write the chip has (its lane width)


def is_kernel(meta: op_scopes.OpMeta) -> bool:
    return KERNEL.match(meta.text) is not None and "custom-call(" in meta.text


def cache_shape(text: str) -> Optional[Tuple[int, ...]]:
    """(L, B, KV, D, S, bytes an element) of the cache operand of the
    kernel's instruction ``text``."""
    m = CACHE.search(text.split("custom-call(", 1)[1])
    if m is None:
        return None
    return (*(int(d) for d in m.groups()[1:]), BYTES[m.group(1)])


def needed_bytes(cache_positions: int, active: int, shape) -> int:
    """What one tick must move whatever the kernel: every layer's K and V
    columns at the positions the decoding slots hold, read once, and one
    tile a decoding slot, kv head and layer written."""
    L, _, KV, D, S, size = shape
    return 2 * L * KV * D * size * (cache_positions + active * min(TILE, S))


def roofline_share(trace, facts: dict) -> Optional[float]:
    """Least time for the bytes the captured ticks needed (``needed_bytes``
    at the chip's bytes/s, ``lib/peaks.py``: memory-bound, a product of one
    query row a head) over the summed device time of the kernel's
    executions in those ticks' decode programs, in percent. Ticks and
    programs are paired as ``moe_ops.experts_roofline_share`` pairs them: a
    tick cut by the capture's edge counts on neither side. The kernel moves
    whole blocks and every slot's tile, never less than was needed."""
    spans, ops = host_spans.load(), op_scopes.load()
    if (spans is None or ops is None or trace is None or not trace.devices
            or spans.device_clock_offset_ns is None):
        return None
    programs = ops.program_ids(facts["decode_program"])
    kernels = {mid: meta for mid, meta in ops.meta.items()
               if meta.program_id in programs and is_kernel(meta)}
    shape = next((cache_shape(m.text) for m in kernels.values()), None)
    if shape is None:
        return None
    bytes_per_s = peaks.peaks_for(facts["device_kind"])["hbm_bytes_per_s"]
    dev, offset = trace.devices[0], spans.device_clock_offset_ns
    name = next(iter(programs.values()), None)
    runs = sorted((start, own) for mid, start, own in ops.self_ns
                  if mid in kernels)
    least = spent = 0.0
    i = 0
    for tick, (start, dur) in host_spans.ticks_with_program(
            spans.loop_line(), dev, name, offset):
        if "cache_positions" not in tick.args:
            return None
        lo, hi = start - offset, start - offset + dur  # the chip's clock
        while i < len(runs) and runs[i][0] < lo:
            i += 1
        while i < len(runs) and runs[i][0] < hi:
            spent += runs[i][1] / 1e9
            i += 1
        least += needed_bytes(tick.args["cache_positions"],
                              tick.args["active"], shape) / bytes_per_s
    return 100.0 * least / spent if spent > 0 else None
