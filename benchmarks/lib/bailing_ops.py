"""The KDA layers', the latent layer's and the held experts' share of a
decode tick, of an admission and of their rooflines, from a device trace and
the engine's own spans.

``ray_tpu/models/bailing_hybrid.py`` and ``ray_tpu/models/kv_cache.py`` put a
KDA layer's device operations under ``kda.in_proj``, ``kda.conv``,
``kda.scan`` (a block of tokens: prefill) or ``kda.update`` (one token a
slot: decode), ``kda.gate_norm`` and ``kda.out_proj``, and a latent layer's
under ``mla.q``, ``mla.down``, ``mla.up`` (the up-projection: absorbed into
the queries and the result at a decode step, applied to the rows a prefill
chunk sees), ``mla.attend`` and ``mla.out``; ``lib/op_scopes.py`` reads each
operation's scope from the trace. The decode step's recurrence is one Pallas
custom call a layer, named ``kda_update`` (``ray_tpu/ops/kda.py``), the
latent layer's attention one named ``latent_decode_attention``
(``ray_tpu/ops/decode_attention.py``); sizes come from the calls' own shapes.
What a tick or an admission NEEDED comes from the spans' arguments
(``llm/engine.py``): ``state_slot_layers``, ``latent_positions``, ``active``,
``moe_rows`` and ``moe_rows_held`` of ``engine.tick``, ``ssm_prefill_tokens``
/ ``layers_state`` / ``chunks`` of ``engine.admit``: the program's counters.
The costs are ``costs/bailing_hybrid.py``'s. A trace of a program without
the scopes, the kernels or the arguments (every commit before PR 51, every
other model) gives ``None`` everywhere.
"""
from __future__ import annotations

import os
import re
from typing import Callable, Optional

from benchmarks.lib import costs, host_spans, named, op_scopes, peaks, ssm_ops
from benchmarks.lib.cluster import BENCH_DIR

KDA_SCOPES = ("kda.in_proj", "kda.conv", "kda.scan", "kda.update",
              "kda.gate_norm", "kda.out_proj")
MLA_SCOPES = ("mla.q", "mla.down", "mla.up", "mla.attend", "mla.out")
KDA_KERNEL = re.compile(r"\s*(?:ROOT )?%?kda_update[.\d]* = ")
MLA_KERNEL = re.compile(r"\s*(?:ROOT )?%?latent_decode_attention[.\d]* = ")
# ``kda_update``'s two results: a slot's row [B, H, Dv], the whole state
KDA_RESULTS = re.compile(
    r"= \(f32\[(\d+),(\d+),(\d+)\](?:\{[^}]*\})?, "
    r"f32\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")
# ``latent_decode_attention``'s: the heads' sums [B, H, R], the whole cache
# [L, B, 1, R + Dr, S]
MLA_RESULTS = re.compile(
    r"= \(\w+\[(\d+),(\d+),(\d+)\](?:\{[^}]*\})?, "
    r"(\w+)\[(\d+),(\d+),1,(\d+),(\d+)\]")
PREFILL_PROGRAM = ssm_ops.PREFILL_PROGRAM
BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def _costs():
    return named.load(os.path.join(BENCH_DIR, "costs", "bailing_hybrid.py"))


def _is(kernel, meta: op_scopes.OpMeta) -> bool:
    return kernel.match(meta.text) is not None and "custom-call(" in meta.text


def share_of(program: str, scopes, kernel) -> Optional[float]:
    """Own device time of chip 0's operations under any of ``scopes`` (the
    ``kernel`` calls among them) inside the programs whose name holds
    ``program``, over those programs' device time, in percent."""
    ops = op_scopes.load()
    if ops is None:
        return None
    programs = ops.program_ids(program)
    own_ns = sum(
        own for mid, _, own in ops.self_ns
        if ops.meta[mid].program_id in programs and (
            op_scopes.scope_of(ops.meta[mid].op_name, scopes)
            or _is(kernel, ops.meta[mid])))
    total = sum(dur for name, _, dur in ops.modules if program in name)
    if not own_ns or not total:
        return None
    return 100.0 * own_ns / total


def kda_sizes(metas) -> Optional[dict]:
    """The costs' keys from the first ``kda_update`` instruction: heads and
    the head's size from the row ``[B, H, Dv]``."""
    for meta in metas:
        m = KDA_RESULTS.search(meta.text) if _is(KDA_KERNEL, meta) else None
        if m is not None:
            return {"num_heads": int(m.group(2)), "head_dim": int(m.group(3))}
    return None


def mla_sizes(metas) -> Optional[dict]:
    """The costs' keys from the first ``latent_decode_attention``
    instruction: heads and the rank from the sums ``[B, H, R]``, the rotated
    part from the cache's row of ``R + Dr``, and the bytes a value."""
    for meta in metas:
        m = MLA_RESULTS.search(meta.text) if _is(MLA_KERNEL, meta) else None
        if m is not None:
            _, heads, rank, dtype, _, _, row, _ = m.groups()
            return {"num_heads": int(heads), "kv_lora_rank": int(rank),
                    "qk_rope_head_dim": int(row) - int(rank),
                    "bytes_per_value": BYTES[dtype]}
    return None


def _kernel_roofline(trace, facts: dict, kernel, sizes: Callable,
                     least: Callable) -> Optional[float]:
    """``least(tick's arguments, the kernel's sizes)`` (a cost: FLOPs and
    bytes the captured tick NEEDED of this kernel) at the chip's peaks,
    summed over the captured ticks, over the summed device time of ALL
    ``kernel`` calls in those ticks' decode programs, in percent. Ticks and
    programs are paired as ``ssm_ops.decode_roofline_share`` pairs them."""
    spans, ops = host_spans.load(), op_scopes.load()
    if (spans is None or ops is None or trace is None or not trace.devices
            or spans.device_clock_offset_ns is None):
        return None
    programs = ops.program_ids(facts["decode_program"])
    kernels = {mid: meta for mid, meta in ops.meta.items()
               if meta.program_id in programs and _is(kernel, meta)}
    model = sizes(kernels.values())
    if model is None:
        return None
    chip = peaks.peaks_for(facts["device_kind"])
    dev, offset = trace.devices[0], spans.device_clock_offset_ns
    name = next(iter(programs.values()), None)
    runs = sorted((start, own) for mid, start, own in ops.self_ns
                  if mid in kernels)
    needed = spent = 0.0
    i = 0
    for tick, (start, dur) in host_spans.ticks_with_program(
            spans.loop_line(), dev, name, offset):
        cost = least(tick.args, model)
        if cost is None:
            return None
        lo, hi = start - offset, start - offset + dur  # the chip's clock
        while i < len(runs) and runs[i][0] < lo:
            i += 1
        while i < len(runs) and runs[i][0] < hi:
            spent += runs[i][1] / 1e9
            i += 1
        needed += costs.roofline_seconds(cost, chip)["seconds"]
    return 100.0 * needed / spent if spent > 0 else None


def kda_decode_roofline_share(trace, facts: dict) -> Optional[float]:
    """The ``kda_update`` kernels against the states and operands the
    captured ticks needed (``kda_update_cost`` of their
    ``state_slot_layers``: memory-bound). The kernel moves a live slot's
    whole state in and out and nothing of any other: never less than was
    needed, so the share cannot pass 100."""
    cost = _costs().kda_update_cost

    def least(args, model):
        if "state_slot_layers" not in args:
            return None
        return cost(args["state_slot_layers"], model)

    return _kernel_roofline(trace, facts, KDA_KERNEL, kda_sizes, least)


def mla_decode_roofline_share(trace, facts: dict) -> Optional[float]:
    """The ``latent_decode_attention`` kernels against the rows the captured
    ticks needed (``latent_decode_cost`` of their ``latent_positions``, each
    row read once, and a tile written a live slot and latent layer). The
    kernel reads whole chunks of positions, the last one past the slot's
    length: never less than was needed."""
    cost = _costs().latent_decode_cost

    def least(args, model):
        if "latent_positions" not in args:
            return None
        # visits: the slots that decode, a latent layer each; the layers
        # from the positions' own ratio to the slots' (a layer's positions
        # are ``cache_positions``)
        layers = args["latent_positions"] // max(args["cache_positions"], 1)
        return cost(args["latent_positions"], args["active"] * layers, model,
                    model["bytes_per_value"])

    return _kernel_roofline(trace, facts, MLA_KERNEL, mla_sizes, least)


def kda_prefill_roofline_share(trace, facts: dict) -> Optional[float]:
    """Least time for the recurrence of the real tokens the captured
    admissions prefilled (``kda_scan_cost`` of ``ssm_prefill_tokens`` a
    state layer, one sequence, times ``layers_state``) over the device time
    under ``kda.scan`` in the prefill programs enqueued inside those
    ``engine.admit`` spans, in percent: as ``delta_ops.
    prefill_roofline_share``. An admission whose programs the capture does
    not hold whole counts on neither side."""
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
                                          ("kda.scan",)))
    model = kda_sizes(ops.meta.values())
    if not scans or model is None:
        return None
    chip = peaks.peaks_for(facts["device_kind"])
    cost = _costs().kda_scan_cost
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


def held_pairs_share() -> Optional[float]:
    """The captured ticks' (token, expert) pairs that were routed to the
    experts this chip holds, over all their pairs: ``moe_rows_held`` over
    ``moe_rows`` (= ``active`` x top_k x routed layers) of the
    ``engine.tick`` spans, as a fraction: a quarter for 128 of 512 experts
    under a balanced router. A span's ``moe_rows_held`` is of the programs
    read since the span before (the tick before's, as
    ``experts_touched``): over a capture the two sums are off by one tick."""
    spans = host_spans.load()
    if spans is None:
        return None
    ticks = [s.args for s in spans.named("engine.tick")
             if "moe_rows_held" in s.args]
    rows = sum(t["moe_rows"] for t in ticks)
    if not rows:
        return None
    return sum(t["moe_rows_held"] for t in ticks) / rows
