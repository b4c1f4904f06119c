"""Share of their roofline the flash-attention BACKWARD calls over keys and
values of two widths (latent attention's up-projected heads, 192 / 128)
reach on chip 0: five products over the pairs a causal mask leaves
(``costs/joyai_llm_flash.py:flash_mla_cost``; ``benchmarks/lib/train_mla.py``).
Device trace."""
from benchmarks.lib import train_mla


def read(trace, facts):
    return train_mla.flash_mla_roofline_share(trace, facts, backward=True)
