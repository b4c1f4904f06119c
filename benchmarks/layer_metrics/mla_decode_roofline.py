"""Share of its roofline the ``latent_decode_attention`` kernel reaches in
the captured decode ticks: least time for the latent rows those ticks NEEDED
(the program's counter ``latent_positions`` x 1,152 B at the published size,
each row read once for keys and values, and a tile written a live slot) at
the chip's bytes/s, or for the heads' products over them where that is more,
over the device time of ALL kernels named ``latent_decode_attention`` in
those ticks' programs (``benchmarks/lib/bailing_ops.py``). Device trace +
the program's span."""
from benchmarks.lib import bailing_ops


def read(trace, facts):
    return bailing_ops.mla_decode_roofline_share(trace, facts)
