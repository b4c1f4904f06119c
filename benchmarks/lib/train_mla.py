"""What a train step of a model with latent attention in every layer and a
prediction layer behind the trunk spends where, from a device trace and the
trainer's own spans.

- ``ops/attention.py`` names the flash calls whose values are narrower than
  their keys (latent attention's up-projected heads) ``flash_mla_fwd`` and
  ``flash_mla_bwd``; XLA keeps the name inside the instruction's
  (``jvp_flash_mla_fwd_.5``). Each call is costed by
  ``costs/joyai_llm_flash.py:flash_mla_cost`` (the pairs a causal mask
  leaves, whatever blocks an implementation walks) after its first result's
  shape is checked against the configuration's: forward the result
  ``[batch x heads, seq, v_head_dim]``, backward dq ``[batch x heads, seq,
  qk_nope_head_dim + qk_rope_head_dim]``.
- ``models/joyai_llm_flash.py`` and ``models/kv_cache.py`` put a mixer's
  projections under ``mla.q``, ``mla.down``, ``mla.up`` and ``mla.out``, the
  prediction layer under ``mtp.in``, ``mtp.block`` and ``mtp.head``. In a
  train step JAX writes a scope that lies outside the differentiated
  function's innermost call as ``jvp(mla.q)`` or
  ``transpose(jvp(mtp.block))`` and one inside a checkpoint or a branch as a
  path element of its own: ``scoped`` finds either. A kernel in a
  ``custom_vjp``'s backward function carries its call site's scopes too
  (``transpose(jvp(mtp.block))/jvp(mtp.block)/checkpoint/flash_mla_bwd``).
- The router's balance comes from the program's counter on the
  ``train.loss_fetch`` spans (``moe_rows_max_all``), measured.

Only whole executions of the train program inside the capture are read. A
trace of a program without the names, scopes or counters (every commit
before PR 55, every other model) gives ``None`` everywhere.
"""
from __future__ import annotations

import os
import re
import statistics
from typing import Dict, Optional

from benchmarks.lib import costs, host_spans, named, op_scopes, peaks
from benchmarks.lib import trace as T
from benchmarks.lib.cluster import BENCH_DIR

FLASH = re.compile(r"flash_mla_(fwd|bwd)")
MLA = re.compile(r"(?<![\w.])mla\.(q|down|up|out)(?![\w.])")
MTP = re.compile(r"(?<![\w.])mtp\.(in|block|head)(?![\w.])")
FIRST_RESULT = re.compile(r" = \(?\w+\[([\d,]*)\]")


def _costs():
    return named.load(os.path.join(BENCH_DIR, "costs", "joyai_llm_flash.py"))


def scoped(pattern, op_name: str) -> bool:
    """Does ``op_name`` (JAX's, scopes included) lie under a scope that
    ``pattern`` names, written as a path element or inside ``jvp(..)``?"""
    return pattern.search(op_name) is not None


def flash_mla_roofline_share(trace, facts: dict, *, backward: bool
                             ) -> Optional[float]:
    """Least time the chip could take for the forward (or backward)
    ``flash_mla`` calls seen on chip 0 over their summed device time, in
    percent."""
    if trace is None or not trace.devices or not facts.get("peak_flops_per_s"):
        return None
    dev, model = trace.devices[0], facts["model"]
    direction = "bwd" if backward else "fwd"
    calls = {name for name, text in dev.op_text.items()
             if T.is_kernel(text) and (m := FLASH.search(name)) is not None
             and m.group(1) == direction}
    if not calls or "qk_rope_head_dim" not in model:
        return None
    heads = model["num_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    width = qk if backward else model["v_head_dim"]
    want = f"{facts['batch_per_chip'] * heads},{facts['seq_len']},{width}"
    least_a_call = costs.roofline_seconds(_costs().flash_mla_cost(
        facts["batch_per_chip"], facts["seq_len"], heads, backward=backward,
        qk_dim=qk, v_dim=model["v_head_dim"]),
        peaks.peaks_for(facts["device_kind"]))["seconds"]
    least = spent = 0.0
    for name, _, dur in dev.ops:
        if name not in calls:
            continue
        m = FIRST_RESULT.search(dev.op_text[name])
        if m is None or m.group(1) != want:
            raise ValueError(
                f"{name} is not a flash kernel over [{want}]: "
                f"{dev.op_text[name][:200]}")
        least += least_a_call
        spent += dur / 1e9
    return 100.0 * least / spent if spent > 0 else None


def step_ns(facts: dict) -> Optional[Dict[str, int]]:
    """Own nanoseconds of chip 0's operations inside the train program's
    executions: in the ``flash_mla`` kernels (``flash``), under the ``mla.*``
    scopes (``mla``: the projections; a kernel counts as ``flash`` alone),
    under the ``mtp.*`` scopes (``mtp``: everything of the prediction
    layer, its kernels and projections too, so ``mtp`` overlaps the other
    two), and the executions' ``total``."""
    ops = op_scopes.load()
    if ops is None:
        return None
    steps = [(start, start + dur) for name, start, dur in ops.modules
             if facts["train_program"] in name]
    if not steps:
        return None
    out = {"flash": 0, "mla": 0, "mtp": 0,
           "total": sum(e - s for s, e in steps)}
    i = 0
    for mid, start, own in ops.self_ns:  # sorted by start, as the steps are
        while i < len(steps) and steps[i][1] <= start:
            i += 1
        if i == len(steps):
            break
        if start < steps[i][0]:
            continue
        meta = ops.meta[mid]
        if T.is_kernel(meta.text) and FLASH.search(
                T.instruction_name(meta.text)):
            out["flash"] += own
        elif scoped(MLA, meta.op_name):
            out["mla"] += own
        if scoped(MTP, meta.op_name):
            out["mtp"] += own
    return out


def router_load_max_over_mean(facts: dict) -> Optional[float]:
    """The fullest of ALL experts' pairs over the mean an expert gets, a
    routed layer: ``moe_rows_max_all`` (each routed layer's fullest expert,
    summed over the routed layers and the prediction layer) x experts over
    the pairs those layers route (tokens x top_k a layer); 1 is a balanced
    router, which the bias rule is there to hold."""
    spans = host_spans.load()
    model = facts["model"]
    if spans is None or "num_mtp_layers" not in model:
        return None
    fetched = [float(s.args["moe_rows_max_all"])
               for s in spans.named("train.loss_fetch")
               if "moe_rows_max_all" in s.args]
    if not fetched:
        return None
    routed = (model["num_layers"] - model.get("first_k_dense", 1)
              + model["num_mtp_layers"])
    pairs = (facts["batch_per_chip"] * facts["seq_len"] * model["moe_top_k"]
             * routed)
    return statistics.fmean(fetched) * model["moe_num_experts"] / pairs
