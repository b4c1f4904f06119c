"""What a train step of a model with window attention and routed experts
spends where, from a device trace and the trainer's own spans.

- The flash kernels carry their kind in the instruction's name
  (``ops/attention.py`` names its four Pallas calls ``flash_fwd``,
  ``flash_bwd``, ``flash_window_fwd``, ``flash_window_bwd``; XLA keeps the
  name inside the instruction's: ``jvp_flash_window_fwd_.3``). Each call is
  costed by its kind (``costs/smallthinker.py:flash_cost``: a window call by
  the pairs its window leaves, k and v once a kv head) after its first
  result's shape is checked against the configuration's.
- ``parallel/moe.py`` puts its operations under ``moe.route``,
  ``moe.dispatch``, ``moe.experts`` and ``moe.combine``. In the backward
  pass the scopes hold: JAX (0.9) wraps what lies BEFORE a scope in the
  path (``transpose(jvp())/checkpoint/rematted_computation/moe.experts/..``)
  and leaves the scope a path element of its own, so
  ``op_scopes.scope_of`` finds a transposed operation as it finds the
  forward one; the TPU compiler's own names for a ``ragged_dot``
  (``lib/moe_ops.py:COMPILER_NAMED``) are the same in both directions.
- Rows come from the program's counters on the ``train.loss_fetch`` spans
  (``moe_rows_held``, ``moe_rows_max_expert``), measured, never assumed.

Only whole executions of the train program inside the capture are read. A
trace of a program without the names, scopes or counters (the commits before
PR 40, a dense model) gives ``None`` everywhere.
"""
from __future__ import annotations

import os
import re
import statistics
from typing import Dict, List, Optional, Tuple

from benchmarks.lib import costs, host_spans, moe_ops, named, op_scopes, peaks
from benchmarks.lib import trace as T
from benchmarks.lib.cluster import BENCH_DIR

FLASH = re.compile(r"flash_(window_)?(fwd|bwd)")
COUNTERS = ("moe_rows_held", "moe_rows_max_expert")


def _costs():
    return named.load(os.path.join(BENCH_DIR, "costs", "smallthinker.py"))


def flash_calls(dev: T.DeviceTrace) -> Dict[str, Tuple[bool, bool]]:
    """Instruction name -> (window?, backward?) of chip 0's flash kernels."""
    out = {}
    for name, text in dev.op_text.items():
        m = FLASH.search(name)
        if m is not None and T.is_kernel(text):
            out[name] = (m.group(1) is not None, m.group(2) == "bwd")
    return out


def flash_mixed_roofline_share(trace, facts: dict, *, backward: bool
                               ) -> Optional[float]:
    """Least time the chip could take for the forward (or backward) flash
    calls seen on chip 0, each costed by its kind, over their summed device
    time, in percent."""
    if trace is None or not trace.devices or not facts.get("peak_flops_per_s"):
        return None
    dev, model = trace.devices[0], facts["model"]
    calls = {n: window for n, (window, bwd) in flash_calls(dev).items()
             if bwd == backward}
    if not calls or "num_kv_heads" not in model:
        return None
    heads, head_dim = model["num_heads"], model["head_dim"]
    want = f"{facts['batch_per_chip'] * heads},{facts['seq_len']},{head_dim}"
    chip = peaks.peaks_for(facts["device_kind"])
    # least seconds of one call of each kind (window or not)
    least_of = {
        window: costs.roofline_seconds(_costs().flash_cost(
            facts["batch_per_chip"], facts["seq_len"], heads,
            model["num_kv_heads"], head_dim, backward=backward,
            window=model.get("sliding_window") if window else None),
            chip)["seconds"] for window in set(calls.values())}
    least = spent = 0.0
    for name, start, dur in dev.ops:
        if name not in calls:
            continue
        m = re.search(r" = \(?\w+\[([\d,]*)\]", dev.op_text[name])
        if m is None or m.group(1) != want:
            raise ValueError(
                f"{name} is not a flash kernel over [{want}]: "
                f"{dev.op_text[name][:200]}")
        least += least_of[calls[name]]
        spent += dur / 1e9
    return 100.0 * least / spent if spent > 0 else None


def _steps(ops: op_scopes.ScopedOps, facts: dict) -> List[Tuple[int, int]]:
    """(start, end) of the train program's executions on chip 0."""
    return [(start, start + dur) for name, start, dur in ops.modules
            if facts["train_program"] in name]


def step_scope_ns(facts: dict) -> Optional[Dict[str, int]]:
    """Own nanoseconds of chip 0's operations inside the train program's
    executions: under each ``moe.*`` scope, in the flash kernels
    (``flash``), and the executions' ``total`` and count (``steps``)."""
    ops = op_scopes.load()
    if ops is None:
        return None
    steps = _steps(ops, facts)
    if not steps:
        return None
    out = {scope: 0 for scope in moe_ops.SCOPES}
    out.update(flash=0, total=sum(e - s for s, e in steps), steps=len(steps))
    i = 0
    for mid, start, own in ops.self_ns:  # sorted by start, as the steps are
        while i < len(steps) and steps[i][1] <= start:
            i += 1
        if i == len(steps):
            break
        if start < steps[i][0]:
            continue
        meta = ops.meta[mid]
        scope = moe_ops.scope_of(meta)
        if scope is not None:
            out[scope] += own
        elif T.is_kernel(meta.text) and FLASH.search(
                T.instruction_name(meta.text)):
            out["flash"] += own
    return out


def routed_counts() -> Optional[Dict[str, float]]:
    """Mean of each of the program's routing counters over the captured
    steps' ``train.loss_fetch`` spans."""
    spans = host_spans.load()
    if spans is None:
        return None
    fetched = [s.args for s in spans.named("train.loss_fetch")
               if all(key in s.args for key in COUNTERS)]
    if not fetched:
        return None
    return {key: statistics.fmean(float(a[key]) for a in fetched)
            for key in COUNTERS}


def experts_roofline_share(trace, facts: dict) -> Optional[float]:
    """Least time for the grouped products of the rows the program counted
    (``moe_rows_held`` a step, summed over its routed layers; forward and
    backward, ``costs/smallthinker.py:moe_train_experts_cost``) over the
    device time under ``moe.experts`` in the captured steps, in percent.
    The rows of a step's layers are costed as one call's: never more than
    the sum over layers. What remat runs again is in the time and not in
    the cost."""
    counts, ns = routed_counts(), step_scope_ns(facts)
    model = facts["model"]
    if (counts is None or ns is None or not ns["moe.experts"]
            or not facts.get("peak_flops_per_s")):
        return None
    held = model.get("moe_num_held") or model["moe_num_experts"]
    cost = _costs().moe_train_experts_cost(
        counts["moe_rows_held"], held * model["num_layers"],
        model["embed_dim"], model["moe_mlp_dim"])
    least = costs.roofline_seconds(
        cost, peaks.peaks_for(facts["device_kind"]))["seconds"]
    return 100.0 * least * ns["steps"] / (ns["moe.experts"] / 1e9)
