"""Device time of the KDA layers' operations inside the prefill programs
(everything under a ``kda.*`` scope: the projections, the convolution, the
chunked scan, the norm and its gate), over those programs' device time, in
percent (``benchmarks/lib/bailing_ops.py``). Device trace."""
from benchmarks.lib import bailing_ops as ops


def read(trace, facts):
    return ops.share_of(ops.PREFILL_PROGRAM, ops.KDA_SCOPES, ops.KDA_KERNEL)
