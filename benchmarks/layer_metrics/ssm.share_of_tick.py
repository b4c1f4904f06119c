"""Device time of the state layers' operations inside the decode program
(everything under an ``ssm.*`` scope: the two projections, the convolution,
the ``ssm_update`` kernel, the gate and its norm), over that program's device
time, in percent (``benchmarks/lib/ssm_ops.py``). Device trace."""
from benchmarks.lib import ssm_ops


def read(trace, facts):
    ns = ssm_ops.decode_ns(facts)
    if ns is None or not ns["total"]:
        return None
    return 100.0 * ns["scopes"] / ns["total"]
