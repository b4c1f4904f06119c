"""``moe.route`` + ``moe.dispatch`` + ``moe.combine`` over all four
``moe.*`` scopes of the decode program, in percent: what the sparsity costs
(router, sort, gather, un-sort) beside the expert products it saves
(``benchmarks/lib/moe_ops.py``). Device trace."""
from benchmarks.lib import moe_ops


def read(trace, facts):
    ns = moe_ops.decode_scope_ns(facts)
    if ns is None:
        return None
    every = sum(ns.get(s, 0) for s in moe_ops.SCOPES)
    return 100.0 * (every - ns.get("moe.experts", 0)) / every if every else None
