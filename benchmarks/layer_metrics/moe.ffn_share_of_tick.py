"""Device time of the operations under the four ``moe.*`` scopes inside the
decode program, over that program's device time, in percent: whether the
routed layer does most of a tick's work (``benchmarks/lib/moe_ops.py``).
Device trace."""
from benchmarks.lib import moe_ops


def read(trace, facts):
    ns = moe_ops.decode_scope_ns(facts)
    if ns is None or not ns["total"]:
        return None
    return 100.0 * sum(ns.get(s, 0) for s in moe_ops.SCOPES) / ns["total"]
