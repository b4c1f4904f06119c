"""The state layers' share of a decode tick and of their rooflines, from a
device trace and the engine's own spans.

``ray_tpu/models/granite_hybrid.py`` and ``ray_tpu/models/kv_cache.py`` put a
state layer's device operations under five ``jax.named_scope``s:
``ssm.in_proj``, ``ssm.conv``, ``ssm.scan`` (a block of tokens: prefill) or
``ssm.update`` (one token a slot: decode), ``ssm.gate_norm`` and
``ssm.out_proj``; ``lib/op_scopes.py`` reads each operation's scope from the
trace. The decode step's recurrence is one Pallas custom call a layer, named
``ssm_update`` (``ray_tpu/ops/ssm.py``; the kernel's ``name=``), over the
whole state ``[L, B, H, P, N]``, which is its largest operand: sizes come
from that operand's shape, as ``lib/decode_attn.py`` takes the cache's. The
prefill's recurrence is XLA operations under ``ssm.scan``. What a tick or an
admission NEEDED comes from the spans' arguments: ``state_slot_layers`` of
``engine.tick`` (slots that decode x state layers) and
``ssm_prefill_tokens`` / ``layers_state`` of ``engine.admit`` (real tokens
the admission's scans took, and how many layers scan): the program's
counters. The costs are ``costs/granite_hybrid.py``'s. A trace of a program
without the scopes, the kernel or the arguments (every commit before PR 42,
every model without state layers) gives ``None`` everywhere.
"""
from __future__ import annotations

import os
import re
import statistics
from typing import Dict, List, Optional, Tuple

from benchmarks.lib import costs, host_spans, named, op_scopes, peaks
from benchmarks.lib import trace as T
from benchmarks.lib.cluster import BENCH_DIR

SCOPES = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.update",
          "ssm.gate_norm", "ssm.out_proj")
KERNEL = re.compile(r"\s*(?:ROOT )?%?ssm_update[.\d]* = ")
STATE = re.compile(r"f32\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")
PREFILL_PROGRAM = "jit_prefill"


def _costs():
    return named.load(os.path.join(BENCH_DIR, "costs", "granite_hybrid.py"))


def is_kernel(meta: op_scopes.OpMeta) -> bool:
    return KERNEL.match(meta.text) is not None and "custom-call(" in meta.text


def state_shape(text: str) -> Optional[Tuple[int, ...]]:
    """(L, B, H, P, N) of the state operand of the kernel's instruction."""
    m = STATE.search(text.split("custom-call(", 1)[1])
    return None if m is None else tuple(int(d) for d in m.groups())


def _sizes(shape) -> dict:
    """The costs' keys from the state's shape (one group: the convolution
    takes x and B and C)."""
    _, _, H, P, N = shape
    return {"mamba_n_heads": H, "mamba_d_head": P, "mamba_d_state": N}


def decode_ns(facts: dict) -> Optional[Dict[str, int]]:
    """Own nanoseconds of chip 0's operations inside the decode program
    under any ``ssm.*`` scope (``scopes``), of the ``ssm_update`` kernels
    alone (``kernels``), and that program's ``total``."""
    ops = op_scopes.load()
    if ops is None:
        return None
    programs = ops.program_ids(facts["decode_program"])
    scopes = kernels = 0
    for mid, _, own in ops.self_ns:
        meta = ops.meta[mid]
        if meta.program_id not in programs:
            continue
        if is_kernel(meta):
            kernels += own
            scopes += own
        elif op_scopes.scope_of(meta.op_name, SCOPES):
            scopes += own
    if not scopes:
        return None
    return {"scopes": scopes, "kernels": kernels,
            "total": sum(dur for name, _, dur in ops.modules
                         if facts["decode_program"] in name)}


def live_slots() -> Optional[float]:
    """Slots that decode a tick, averaged over the captured ticks of a model
    with state layers: ``state_slot_layers / layers_state`` of the
    ``engine.tick`` spans."""
    spans = host_spans.load()
    if spans is None:
        return None
    ticks = [s.args for s in spans.named("engine.tick")
             if s.args.get("layers_state")]
    if not ticks:
        return None
    return statistics.fmean(
        t["state_slot_layers"] / t["layers_state"] for t in ticks)


def decode_roofline_share(trace, facts: dict) -> Optional[float]:
    """Least time for the states and tails the captured ticks NEEDED
    (``costs.ssm_update_cost`` of their ``state_slot_layers`` at the chip's
    peaks: memory-bound) over the summed device time of ALL ``ssm_update``
    kernels in those ticks' decode programs, in percent. Ticks and programs
    are paired as ``decode_attn.roofline_share`` pairs them. The kernel
    moves a live slot's whole state in and out and nothing of any other,
    never less than was needed: the share cannot pass 100."""
    spans, ops = host_spans.load(), op_scopes.load()
    if (spans is None or ops is None or trace is None or not trace.devices
            or spans.device_clock_offset_ns is None):
        return None
    programs = ops.program_ids(facts["decode_program"])
    kernels = {mid: meta for mid, meta in ops.meta.items()
               if meta.program_id in programs and is_kernel(meta)}
    shape = next((state_shape(m.text) for m in kernels.values()), None)
    if shape is None:
        return None
    chip = peaks.peaks_for(facts["device_kind"])
    cost = _costs().ssm_update_cost
    dev, offset = trace.devices[0], spans.device_clock_offset_ns
    name = next(iter(programs.values()), None)
    runs = sorted((start, own) for mid, start, own in ops.self_ns
                  if mid in kernels)
    least = spent = 0.0
    i = 0
    for tick, (start, dur) in host_spans.ticks_with_program(
            spans.loop_line(), dev, name, offset):
        if "state_slot_layers" not in tick.args:
            return None
        lo, hi = start - offset, start - offset + dur  # the chip's clock
        while i < len(runs) and runs[i][0] < lo:
            i += 1
        while i < len(runs) and runs[i][0] < hi:
            spent += runs[i][1] / 1e9
            i += 1
        least += costs.roofline_seconds(
            cost(tick.args["state_slot_layers"], _sizes(shape)),
            chip)["seconds"]
    return 100.0 * least / spent if spent > 0 else None


def _prefill_runs(path: Optional[str] = None) -> List[Tuple[int, int, int]]:
    """(start of the enqueue on the host's clock, start on chip 0's clock,
    duration) of every prefill program the capture holds whole, by the
    runtime's ``run_id`` (``lib/request_spans.py`` says why)."""
    from jax.profiler import ProfileData

    try:
        found = T.find_xplane(path or host_spans.TRACE_ROOT)
    except OSError:
        return []
    enqueues: Dict[int, List[int]] = {}
    ran: Dict[int, List[Tuple[str, int, int]]] = {}
    for plane in ProfileData.from_file(found).planes:
        if plane.name == host_spans.CHIP0_PLANE:
            for line in plane.lines:
                if line.name == T.MODULES_LINE:
                    for ev in line.events:
                        run_id = dict(ev.stats).get("run_id")
                        if run_id is not None:
                            ran.setdefault(run_id, []).append((
                                ev.name, int(ev.start_ns),
                                int(ev.duration_ns)))
        elif plane.name == host_spans.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == host_spans.ENQUEUE:
                        stats = dict(ev.stats)
                        if (stats.get("device_ordinal") == 0
                                and "run_id" in stats):
                            enqueues.setdefault(stats["run_id"], []).append(
                                int(ev.start_ns))
    # a number that two runs of the capture share names neither
    return sorted(
        (starts[0], *ran[run_id][0][1:])
        for run_id, starts in enqueues.items()
        if len(starts) == 1 and len(ran.get(run_id, [])) == 1
        and PREFILL_PROGRAM in ran[run_id][0][0])


def prefill_roofline_share(trace, facts: dict) -> Optional[float]:
    """Least time for the recurrence of the real tokens the captured
    admissions prefilled (``costs.ssm_scan_cost`` of ``ssm_prefill_tokens``
    a state layer, times ``layers_state``: the larger of its operations'
    and its bytes' time at the chip's peaks) over the device time under
    ``ssm.scan`` in the prefill programs enqueued inside those
    ``engine.admit`` spans, in percent. An admission whose programs the
    capture does not hold whole counts on neither side."""
    spans, ops = host_spans.load(), op_scopes.load()
    if spans is None or ops is None or trace is None or not trace.devices:
        return None
    admits = [s for s in spans.named("engine.admit")
              if s.args.get("ssm_prefill_tokens")]
    if not admits:
        return None
    programs = ops.program_ids(PREFILL_PROGRAM)
    scans = sorted((start, own) for mid, start, own in ops.self_ns
                   if ops.meta[mid].program_id in programs
                   and op_scopes.scope_of(ops.meta[mid].op_name,
                                          ("ssm.scan",)))
    kernels = [m for m in ops.meta.values() if is_kernel(m)]
    shape = next((state_shape(m.text) for m in kernels), None)
    if not scans or shape is None:
        return None
    chip = peaks.peaks_for(facts["device_kind"])
    cost = _costs().ssm_scan_cost
    runs = _prefill_runs()
    least = spent = 0.0
    for admit in admits:
        inside = [r for r in runs if admit.start_ns <= r[0] < admit.end_ns]
        if len(inside) != admit.args.get("chunks"):
            continue
        for _, start, dur in inside:
            spent += sum(own for at, own in scans
                         if start <= at < start + dur) / 1e9
        least += admit.args["layers_state"] * costs.roofline_seconds(
            cost(admit.args["ssm_prefill_tokens"], _sizes(shape)),
            chip)["seconds"]
    return 100.0 * least / spent if spent > 0 else None
