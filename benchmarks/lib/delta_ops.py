"""The gated delta-rule layers' share of a decode tick, of an admission and
of their rooflines, from a device trace and the engine's own spans.

``ray_tpu/models/olmo_hybrid.py`` and ``ray_tpu/models/kv_cache.py`` put a
state layer's device operations under five ``jax.named_scope``s:
``delta.in_proj``, ``delta.conv``, ``delta.scan`` (a block of tokens:
prefill) or ``delta.update`` (one token a slot: decode), ``delta.gate_norm``
and ``delta.out_proj``; ``lib/op_scopes.py`` reads each operation's scope
from the trace. The decode step's recurrence is one Pallas custom call a
layer, named ``delta_update`` (``ray_tpu/ops/delta_rule.py``; the kernel's
``name=``): its first result is a slot's row ``[B, H, Dv]`` and its second
the whole state ``[L, B, H, Dk, Dv up to whole lane tiles]``, and the sizes
come from those two shapes. The prefill's recurrence is XLA operations under
``delta.scan``. What a tick or an admission NEEDED comes from the spans'
arguments: ``state_slot_layers`` of ``engine.tick`` (slots that decode x
state layers) and ``ssm_prefill_tokens`` / ``layers_state`` / ``chunks`` of
``engine.admit`` (real tokens the admission's scans took, how many layers
scan, how many programs ran): the program's counters, which count any state
layer. The costs are ``costs/olmo_hybrid.py``'s. A trace of a program
without the scopes, the kernel or the arguments (every commit before PR 47,
every model without such layers) gives ``None`` everywhere. What a run of
the prefill programs is, by the runtime's ``run_id``, is
``lib/ssm_ops.py``'s.
"""
from __future__ import annotations

import os
import re
import statistics
from typing import Dict, Optional

from benchmarks.lib import costs, host_spans, named, op_scopes, peaks, ssm_ops
from benchmarks.lib.cluster import BENCH_DIR

SCOPES = ("delta.in_proj", "delta.conv", "delta.scan", "delta.update",
          "delta.gate_norm", "delta.out_proj")
KERNEL = re.compile(r"\s*(?:ROOT )?%?delta_update[.\d]* = ")
# the custom call's two results: a slot's row, the whole state
RESULTS = re.compile(
    r"= \(f32\[(\d+),(\d+),(\d+)\](?:\{[^}]*\})?, "
    r"f32\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")
PREFILL_PROGRAM = ssm_ops.PREFILL_PROGRAM


def _costs():
    return named.load(os.path.join(BENCH_DIR, "costs", "olmo_hybrid.py"))


def is_kernel(meta: op_scopes.OpMeta) -> bool:
    return KERNEL.match(meta.text) is not None and "custom-call(" in meta.text


def sizes(text: str) -> Optional[dict]:
    """The costs' keys from the kernel's instruction: heads and Dv from the
    row ``[B, H, Dv]``, Dk from the state ``[L, B, H, Dk, ..]``."""
    m = RESULTS.search(text)
    if m is None:
        return None
    _, heads, dv, _, _, _, dk, _ = (int(d) for d in m.groups())
    return {"linear_num_key_heads": heads, "linear_key_head_dim": dk,
            "linear_value_head_dim": dv}


def _kernel_sizes(metas) -> Optional[dict]:
    """``sizes`` of the first ``delta_update`` instruction among ``metas``."""
    return next((s for s in (sizes(m.text) for m in metas if is_kernel(m))
                 if s), None)


def program_ns(program: str) -> Optional[Dict[str, int]]:
    """Own nanoseconds of chip 0's operations inside the programs whose name
    holds ``program`` under any ``delta.*`` scope (``scopes``; the
    ``delta_update`` kernels among them), under ``delta.scan`` alone
    (``scan``), and those programs' ``total``."""
    ops = op_scopes.load()
    if ops is None:
        return None
    programs = ops.program_ids(program)
    scopes = scan = 0
    for mid, _, own in ops.self_ns:
        meta = ops.meta[mid]
        if meta.program_id not in programs:
            continue
        scope = op_scopes.scope_of(meta.op_name, SCOPES)
        if scope or is_kernel(meta):
            scopes += own
        if scope == "delta.scan":
            scan += own
    if not scopes:
        return None
    return {"scopes": scopes, "scan": scan,
            "total": sum(dur for name, _, dur in ops.modules
                         if program in name)}


def share_of(program: str) -> Optional[float]:
    ns = program_ns(program)
    if ns is None or not ns["total"]:
        return None
    return 100.0 * ns["scopes"] / ns["total"]


def prefill_share_of_busy(trace) -> Optional[float]:
    """Device time of the prefill programs over the chip's busy time in the
    capture, in percent, for a model whose prefill runs ``delta.*``
    operations."""
    from benchmarks.lib import trace as T

    ns = program_ns(PREFILL_PROGRAM)
    if ns is None or trace is None:
        return None
    busy = T.busy_s(trace)
    return 100.0 * ns["total"] / 1e9 / busy if busy > 0 else None


def chunks_per_admit() -> Optional[float]:
    """Programs an admission ran (``chunks`` of the ``engine.admit`` spans:
    the prompt's pieces of the largest bucket), averaged over the captured
    admissions that scanned a state layer."""
    spans = host_spans.load()
    if spans is None:
        return None
    chunks = [s.args["chunks"] for s in spans.named("engine.admit")
              if s.args.get("ssm_prefill_tokens") and "chunks" in s.args]
    return statistics.fmean(chunks) if chunks else None


def decode_roofline_share(trace, facts: dict) -> Optional[float]:
    """Least time for the states, tails and rows the captured ticks NEEDED
    (``costs.delta_update_cost`` of their ``state_slot_layers`` at the chip's
    peaks: memory-bound) over the summed device time of ALL ``delta_update``
    kernels in those ticks' decode programs, in percent. Ticks and programs
    are paired as ``ssm_ops.decode_roofline_share`` pairs them. The kernel
    moves a live slot's whole state in and out, padding and all, and
    nothing of any other slot: never less than was needed, so the share
    cannot pass 100."""
    spans, ops = host_spans.load(), op_scopes.load()
    if (spans is None or ops is None or trace is None or not trace.devices
            or spans.device_clock_offset_ns is None):
        return None
    programs = ops.program_ids(facts["decode_program"])
    kernels = {mid: meta for mid, meta in ops.meta.items()
               if meta.program_id in programs and is_kernel(meta)}
    model = _kernel_sizes(kernels.values())
    if model is None:
        return None
    chip = peaks.peaks_for(facts["device_kind"])
    cost = _costs().delta_update_cost
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
            cost(tick.args["state_slot_layers"], model), chip)["seconds"]
    return 100.0 * least / spent if spent > 0 else None


def prefill_roofline_share(trace, facts: dict) -> Optional[float]:
    """Least time for the recurrence of the real tokens the captured
    admissions prefilled (``costs.delta_scan_cost`` of ``ssm_prefill_tokens``
    a state layer, one sequence, times ``layers_state``: the larger of its
    operations' and its bytes' time at the chip's peaks) over the device
    time under ``delta.scan`` in the prefill programs enqueued inside those
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
                                          ("delta.scan",)))
    model = _kernel_sizes(ops.meta.values())
    if not scans or model is None:
        return None
    chip = peaks.peaks_for(facts["device_kind"])
    cost = _costs().delta_scan_cost
    runs = ssm_ops._prefill_runs()
    least = spent = 0.0
    for admit in admits:
        inside = [r for r in runs if admit.start_ns <= r[0] < admit.end_ns]
        if len(inside) != admit.args.get("chunks"):
            continue
        for _, start, dur in inside:
            spent += sum(own for at, own in scans
                         if start <= at < start + dur) / 1e9
        least += admit.args["layers_state"] * costs.roofline_seconds(
            cost(admit.args["ssm_prefill_tokens"], model), chip)["seconds"]
    return 100.0 * least / spent if spent > 0 else None
