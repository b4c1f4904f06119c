"""Device time of the operations under the four ``moe.*`` scopes (route,
dispatch, experts, combine; forward and backward) inside the train step,
over the step's device time, in percent (``benchmarks/lib/train_moe.py``).
Device trace."""
from benchmarks.lib import moe_ops, train_moe


def read(trace, facts):
    ns = train_moe.step_scope_ns(facts)
    if ns is None or not ns["total"] or not any(
            ns[s] for s in moe_ops.SCOPES):
        return None
    return 100.0 * sum(ns[s] for s in moe_ops.SCOPES) / ns["total"]
