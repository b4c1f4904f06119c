"""Device time of the flash-attention kernels (full and window, forward and
backward) inside the train step, over the step's device time, in percent
(``benchmarks/lib/train_moe.py``). Device trace."""
from benchmarks.lib import train_moe


def read(trace, facts):
    ns = train_moe.step_scope_ns(facts)
    if ns is None or not ns["total"] or not ns["flash"]:
        return None
    return 100.0 * ns["flash"] / ns["total"]
