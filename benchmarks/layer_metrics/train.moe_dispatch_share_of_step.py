"""Device time of the operations under ``moe.route``, ``moe.dispatch`` and
``moe.combine`` (forward and backward; everything of the routed layer but
the grouped products and their activation, which are ``moe.experts`` and
``moe_train_experts_roofline``'s) inside the train step, over the step's
device time, in percent (``benchmarks/lib/train_moe.py``). Device trace."""
from benchmarks.lib import train_moe

SCOPES = ("moe.route", "moe.dispatch", "moe.combine")


def read(trace, facts):
    ns = train_moe.step_scope_ns(facts)
    if ns is None or not ns["total"] or not any(ns[s] for s in SCOPES):
        return None
    return 100.0 * sum(ns[s] for s in SCOPES) / ns["total"]
