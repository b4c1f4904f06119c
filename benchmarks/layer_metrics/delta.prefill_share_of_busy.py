"""Device time of the prefill programs over the chip's busy time in the
capture, in percent: how much of the cell's device work is admission, for a
model whose prefill runs ``delta.*`` operations
(``benchmarks/lib/delta_ops.py``). Device trace."""
from benchmarks.lib import delta_ops


def read(trace, facts):
    return delta_ops.prefill_share_of_busy(trace)
