"""The sparse attention's share of a decode tick, of an admission and of
its rooflines (DeepSeek sparse attention: a lightning indexer's scores, the
exact choice of the positions a query keeps, the attention over the choice),
from a device trace and the engine's own spans.

``ray_tpu/ops/index_select.py`` and ``ray_tpu/models/kv_cache.py`` put the
three steps' device operations under ``mla.index`` (the indexer's queries,
key and weights, the scores), ``mla.select`` and ``mla.sparse`` (the
attention over the choice: the ``latent_decode_attention`` kernel in a decode
step, the running softmax over up-projected blocks in a prefill chunk);
``lib/op_scopes.py`` reads each operation's scope from the trace. What a
tick or an admission NEEDED comes from the spans' arguments
(``llm/engine.py``): ``index_positions`` and ``selected_positions`` of
``engine.tick`` and ``engine.admit``: the program's counters. The costs are
``costs/deepseek_v32.py``'s, at the published sizes (its defaults). A trace
of a program without the scopes or the counters (every commit before PR 57,
every other model) gives ``None`` everywhere.
"""
from __future__ import annotations

import bisect
import os
from typing import Callable, Optional

from benchmarks.lib import (
    bailing_ops, costs, host_spans, named, op_scopes, peaks, ssm_ops,
)
from benchmarks.lib.cluster import BENCH_DIR

INDEX, SELECT, SPARSE = "mla.index", "mla.select", "mla.sparse"
SCOPES = (INDEX, SELECT, SPARSE)
PREFILL_PROGRAM = ssm_ops.PREFILL_PROGRAM
# the most tokens one prefill program holds: the queries that can share a
# key or a row (the bytes of a chunk's least time; its FLOPs decide)
CHUNK = 2048


def _costs():
    return named.load(os.path.join(BENCH_DIR, "costs", "deepseek_v32.py"))


def _under(ops, programs, scope: str):
    """(start, own ns) of chip 0's operations under ``scope`` in
    ``programs``, by start; the ``latent_decode_attention`` calls are
    ``mla.sparse``'s, whatever name the call itself carries."""
    return sorted(
        (start, own) for mid, start, own in ops.self_ns
        if ops.meta[mid].program_id in programs and (
            op_scopes.scope_of(ops.meta[mid].op_name, (scope,))
            or (scope == SPARSE and bailing_ops._is(
                bailing_ops.MLA_KERNEL, ops.meta[mid]))))


def share_of(program: str) -> Optional[float]:
    """Own device time of chip 0's operations under the three scopes inside
    the programs whose name holds ``program``, over those programs' device
    time, in percent."""
    ops = op_scopes.load()
    if ops is None:
        return None
    programs = ops.program_ids(program)
    own_ns = sum(own for scope in SCOPES
                 for _, own in _under(ops, programs, scope))
    total = sum(dur for name, _, dur in ops.modules if program in name)
    if not own_ns or not total:
        return None
    return 100.0 * own_ns / total


def selected_share() -> Optional[float]:
    """Positions attention was asked to read over positions the indexer
    scored, of the captured ticks and admissions: ``selected_positions`` /
    ``index_positions``, a ratio (1: nothing was left out)."""
    spans = host_spans.load()
    if spans is None:
        return None
    args = [s.args for name in ("engine.tick", "engine.admit")
            for s in spans.named(name) if s.args.get("index_positions")]
    scored = sum(a["index_positions"] for a in args)
    if not scored:
        return None
    return sum(a["selected_positions"] for a in args) / scored


def _spent(runs, lo: int, hi: int) -> float:
    """Seconds of ``runs`` [(start, own ns)], sorted, that start in [lo,
    hi)."""
    first = bisect.bisect_left(runs, (lo,))
    return sum(own for _, own in runs[first:bisect.bisect_left(
        runs, (hi,))]) / 1e9


def roofline_share(trace, facts: dict, scope: str,
                   of_tick: Optional[Callable],
                   of_admit: Optional[Callable]) -> Optional[float]:
    """Least time for what the captured ticks (``of_tick(args, costs)`` ->
    a cost) and admissions (``of_admit``) needed of one step, at the chip's
    peaks, over the device time of the operations under ``scope`` in those
    ticks' decode programs and those admissions' prefill programs, in
    percent. Ticks pair with their program as ``bailing_ops.
    _kernel_roofline`` pairs them, admissions as ``bailing_ops.
    kda_prefill_roofline_share`` does: one the capture does not hold whole
    counts on neither side."""
    spans, ops = host_spans.load(), op_scopes.load()
    if (spans is None or ops is None or trace is None or not trace.devices
            or spans.device_clock_offset_ns is None):
        return None
    chip = peaks.peaks_for(facts["device_kind"])
    cost = _costs()
    needed = spent = 0.0
    if of_tick is not None:
        programs = ops.program_ids(facts["decode_program"])
        runs = _under(ops, programs, scope)
        dev, offset = trace.devices[0], spans.device_clock_offset_ns
        name = next(iter(programs.values()), None)
        for tick, (start, dur) in host_spans.ticks_with_program(
                spans.loop_line(), dev, name, offset):
            if not tick.args.get("index_positions"):
                continue
            needed += costs.roofline_seconds(
                of_tick(tick.args, cost), chip)["seconds"]
            spent += _spent(runs, start - offset, start - offset + dur)
    if of_admit is not None:
        runs = _under(ops, ops.program_ids(PREFILL_PROGRAM), scope)
        prefills = ssm_ops._prefill_runs()
        for admit in spans.named("engine.admit"):
            if not admit.args.get("index_positions"):
                continue
            inside = [r for r in prefills
                      if admit.start_ns <= r[0] < admit.end_ns]
            if len(inside) != admit.args.get("chunks"):
                continue
            needed += costs.roofline_seconds(
                of_admit(admit.args, cost), chip)["seconds"]
            spent += sum(_spent(runs, start, start + dur)
                         for _, start, dur in inside)
    return 100.0 * needed / spent if spent > 0 else None


def index_roofline_share(trace, facts: dict) -> Optional[float]:
    """The indexer's scores, ticks and admissions: a tick's one query a
    slot reads each visible key for itself (memory-bound), a chunk's tokens
    share them (compute-bound)."""
    return roofline_share(
        trace, facts, INDEX,
        lambda a, c: c.index_scores_cost(a["index_positions"], {}),
        lambda a, c: c.index_scores_cost(a["index_positions"], {}, CHUNK))


def select_roofline_share(trace, facts: dict) -> Optional[float]:
    """The exact choice, ticks and admissions: every score read once."""
    def least(a, c):
        return c.selection_cost(a["index_positions"], a["selected_positions"])

    return roofline_share(trace, facts, SELECT, least, least)


def sparse_decode_roofline_share(trace, facts: dict) -> Optional[float]:
    """The decode steps' attention against the CHOSEN rows (each read once,
    a tile written a live slot and latent layer)."""
    def least(a, c):
        layers = a["latent_positions"] // max(a["cache_positions"], 1)
        return c.selected_decode_cost(
            a["selected_positions"], a["active"] * layers, {})

    return roofline_share(trace, facts, SPARSE, least, None)


def sparse_prefill_roofline_share(trace, facts: dict) -> Optional[float]:
    """The chunks' attention against each query's CHOSEN rows."""
    return roofline_share(
        trace, facts, SPARSE, None,
        lambda a, c: c.selected_prefill_cost(a["selected_positions"], {},
                                             CHUNK))
