"""Share of its roofline the flash-attention BACKWARD pass reaches on chip 0,
its kernels' times summed (``benchmarks/lib/kernels.py``). Device trace."""
from benchmarks.lib.kernels import flash_roofline_share


def read(trace, facts):
    return flash_roofline_share(trace, facts, backward=True)
