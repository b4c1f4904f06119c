"""Device time of the KDA layers' operations inside the decode program
(everything under a ``kda.*`` scope: the projections, the convolution, the
``kda_update`` kernel, the norm and its gate), over that program's device
time, in percent (``benchmarks/lib/bailing_ops.py``). Device trace."""
from benchmarks.lib import bailing_ops as ops


def read(trace, facts):
    return ops.share_of(facts["decode_program"], ops.KDA_SCOPES,
                        ops.KDA_KERNEL)
