"""Share of its roofline the flash-attention FORWARD kernel reaches on chip
0 (``benchmarks/lib/kernels.py``). Device trace."""
from benchmarks.lib.kernels import flash_roofline_share


def read(trace, facts):
    return flash_roofline_share(trace, facts, backward=False)
