"""Share of their roofline the flash-attention BACKWARD kernels reach on
chip 0 in a step that mixes full and window layers: each call costed by its
kind (``benchmarks/lib/train_moe.py``). Device trace."""
from benchmarks.lib import train_moe


def read(trace, facts):
    return train_moe.flash_mixed_roofline_share(trace, facts, backward=True)
