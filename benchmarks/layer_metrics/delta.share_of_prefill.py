"""Device time of the gated delta-rule layers' operations inside the prefill
programs (everything under a ``delta.*`` scope: the projections, the
convolution, the chunked scan, the norm and its gate), over those programs'
device time, in percent (``benchmarks/lib/delta_ops.py``). Device trace."""
from benchmarks.lib import delta_ops


def read(trace, facts):
    return delta_ops.share_of(delta_ops.PREFILL_PROGRAM)
