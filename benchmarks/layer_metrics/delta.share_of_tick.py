"""Device time of the gated delta-rule layers' operations inside the decode
program (everything under a ``delta.*`` scope: the projections, the
convolution, the ``delta_update`` kernel, the norm and its gate), over that
program's device time, in percent (``benchmarks/lib/delta_ops.py``). Device
trace."""
from benchmarks.lib import delta_ops


def read(trace, facts):
    return delta_ops.share_of(facts["decode_program"])
