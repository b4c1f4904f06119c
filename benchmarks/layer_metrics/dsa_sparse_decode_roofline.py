"""Share of its roofline the decode steps' attention over the choice
reaches in the captured ticks: least time for the CHOSEN rows
(``selected_positions`` x 1,152 B read once, a tile written a live slot and
layer, or the heads' absorbed products over them where that is more) over
the device time of the ``latent_decode_attention`` kernels and whatever else
runs under ``mla.sparse`` in those ticks' programs: a form that reads every
visible row and masks shows as a low share (``benchmarks/lib/dsa_ops.py``).
Device trace + the program's span."""
from benchmarks.lib import dsa_ops


def read(trace, facts):
    return dsa_ops.sparse_decode_roofline_share(trace, facts)
