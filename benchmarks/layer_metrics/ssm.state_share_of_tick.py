"""Device time of the ``ssm_update`` kernels alone (a decode step's reads and
writes of the slots' states) inside the decode program, over that program's
device time, in percent (``benchmarks/lib/ssm_ops.py``). Device trace."""
from benchmarks.lib import ssm_ops


def read(trace, facts):
    ns = ssm_ops.decode_ns(facts)
    if ns is None or not ns["total"] or not ns["kernels"]:
        return None
    return 100.0 * ns["kernels"] / ns["total"]
