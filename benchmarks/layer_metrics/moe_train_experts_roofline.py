"""Share of their roofline the routed experts' grouped products reach in the
captured train steps, forward and backward: least time for the rows the
program counted (``moe_rows_held``) over the device time under
``moe.experts`` (``benchmarks/lib/train_moe.py``). What remat computes again
is in the time and not in the cost, so a step that recomputes the forward
products reads at most 75%. Device trace + the program's counter."""
from benchmarks.lib import train_moe


def read(trace, facts):
    return train_moe.experts_roofline_share(trace, facts)
