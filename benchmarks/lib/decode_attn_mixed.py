"""Decode attention over two kinds of KV cache, from a device trace and the
engine's own spans: a model whose full layers hold ``[Lf, B, KV, D, S]`` and
whose window layers a ring ``[Lw, B, KV, D, R]`` (``ray_tpu/models/
kv_cache.py``) runs the ``decode_attention`` kernel (``ray_tpu/ops/
decode_attention.py``) once a layer over the array of the layer's kind, under
the name ``decode_attention`` in a full layer and ``decode_attention_window``
in a window layer. Sizes come from each kernel instruction's own cache
operand. What a tick NEEDED of each kind comes from the ``engine.tick``
span's arguments: ``cache_positions_full`` (the decoding slots' lengths and
the columns they write), ``cache_positions_window`` (each slot's length or
the window, whichever is less) and ``active``: the program's counters, never
"all positions". ``lib/decode_attn.py`` reads a model of one kind of cache
and is left as it is; a trace of a program without these kernels or
arguments (the commits before PR 34, a model of one kind) gives ``None``
everywhere here.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks.lib import host_spans, op_scopes, peaks
from benchmarks.lib.decode_attn import TILE, cache_shape

KERNEL = re.compile(r"\s*(?:ROOT )?%?decode_attention(_window)?[.\d]* = ")
ARGS = {"full": "cache_positions_full", "window": "cache_positions_window"}


def kernels(ops: op_scopes.ScopedOps, programs) -> Dict[int, str]:
    """metadata id -> "full" | "window" of every decode-attention kernel
    instruction of ``programs``."""
    out = {}
    for mid, meta in ops.meta.items():
        m = KERNEL.match(meta.text)
        if (m and meta.program_id in programs
                and "custom-call(" in meta.text):
            out[mid] = "window" if m.group(1) else "full"
    return out


def _decode_total_ns(ops: op_scopes.ScopedOps, facts: dict) -> int:
    return sum(dur for name, _, dur in ops.modules
               if facts["decode_program"] in name)


def decode_kernel_ns(facts: dict) -> Optional[Dict[str, int]]:
    """Own nanoseconds of chip 0's decode-attention kernels inside the
    decode program, by kind, and that program's ``total``."""
    ops = op_scopes.load()
    if ops is None:
        return None
    found = kernels(ops, ops.program_ids(facts["decode_program"]))
    if not found:
        return None
    out = {"full": 0, "window": 0}
    for mid, _, own in ops.self_ns:
        if mid in found:
            out[found[mid]] += own
    out["total"] = _decode_total_ns(ops, facts)
    return out


def needed_bytes(args: dict, shapes: Dict[str, tuple]) -> Optional[int]:
    """What one tick must move whatever the kernel: each kind's K and V
    columns at the positions the decoding slots need of it, every layer of
    the kind, read once, and one tile a decoding slot, kv head and layer
    written."""
    total = 0
    for kind, (L, _, KV, D, S, size) in shapes.items():
        if ARGS[kind] not in args:
            return None
        total += 2 * L * KV * D * size * (
            args[ARGS[kind]] + args["active"] * min(TILE, S))
    return total


def mixed_roofline_share(trace, facts: dict) -> Optional[float]:
    """Least time for the bytes the captured ticks needed of both kinds of
    cache (``needed_bytes`` at the chip's bytes/s: memory-bound, a product
    of a few query rows a head) over the summed device time of ALL
    decode-attention kernels in those ticks' decode programs, in percent;
    None for a model of one kind of cache (``decode_attn_roofline`` reads
    that). Ticks and programs are paired as ``decode_attn.roofline_share``
    pairs them. The kernel moves whole chunks and every slot's tile, never
    less than was needed: the share cannot pass 100."""
    spans, ops = host_spans.load(), op_scopes.load()
    if (spans is None or ops is None or trace is None or not trace.devices
            or spans.device_clock_offset_ns is None):
        return None
    programs = ops.program_ids(facts["decode_program"])
    found = kernels(ops, programs)
    shapes = {}
    for mid, kind in found.items():
        shapes.setdefault(kind, cache_shape(ops.meta[mid].text))
    if set(shapes) != {"full", "window"} or None in shapes.values():
        return None
    bytes_per_s = peaks.peaks_for(facts["device_kind"])["hbm_bytes_per_s"]
    dev, offset = trace.devices[0], spans.device_clock_offset_ns
    name = next(iter(programs.values()), None)
    runs = sorted((start, own) for mid, start, own in ops.self_ns
                  if mid in found)
    least = spent = 0.0
    i = 0
    for tick, (start, dur) in host_spans.ticks_with_program(
            spans.loop_line(), dev, name, offset):
        need = needed_bytes(tick.args, shapes)
        if need is None:
            return None
        lo, hi = start - offset, start - offset + dur  # the chip's clock
        while i < len(runs) and runs[i][0] < lo:
            i += 1
        while i < len(runs) and runs[i][0] < hi:
            spent += runs[i][1] / 1e9
            i += 1
        least += need / bytes_per_s
    return 100.0 * least / spent if spent > 0 else None


def window_spared_share() -> Optional[float]:
    """1 - (positions the captured ticks needed, every layer of both kinds)
    / (the decoding slots' lengths x all layers), in percent: how much of an
    all-full cache's reads the window spares in this traffic."""
    spans = host_spans.load()
    if spans is None:
        return None
    needed = every = 0
    for tick in spans.named("engine.tick"):
        a = tick.args
        if not a.get("layers_window") or ARGS["window"] not in a:
            continue
        needed += (a[ARGS["full"]] * a["layers_full"]
                   + a[ARGS["window"]] * a["layers_window"])
        every += a["cache_positions"] * (
            a["layers_full"] + a["layers_window"])
    return 100.0 * (1 - needed / every) if every else None


def shared_expert_ns(facts: dict) -> Optional[Dict[str, int]]:
    """Own nanoseconds of chip 0's operations under ``moe.shared``
    (``parallel/moe.py:shared_expert``) inside the decode program, and that
    program's ``total``."""
    ops = op_scopes.load()
    if ops is None:
        return None
    programs = ops.program_ids(facts["decode_program"])
    own = sum(own for mid, _, own in ops.self_ns
              if ops.meta[mid].program_id in programs and op_scopes.scope_of(
                  ops.meta[mid].op_name, ("moe.shared",)))
    if not own:
        return None
    return {"moe.shared": own, "total": _decode_total_ns(ops, facts)}
