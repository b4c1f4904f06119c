"""Programs an admission ran, averaged over the captured ``engine.admit``
spans that scanned a state layer (their ``chunks``: the prompt's pieces of
the largest prefill bucket, each starting from the state the one before
left): above 1, the carried state is on the timed path
(``benchmarks/lib/delta_ops.py``). The program's span."""
from benchmarks.lib import delta_ops


def read(trace, facts):
    return delta_ops.chunks_per_admit()
