"""Device time of the sparse attention's three steps inside the prefill
programs (everything under ``mla.index``, ``mla.select`` and ``mla.sparse``:
the indexer's projections and a chunk's scores against every cached key, the
exact choice, the attention over the choice), over those programs' device
time, in percent (``benchmarks/lib/dsa_ops.py``). Device trace."""
from benchmarks.lib import dsa_ops


def read(trace, facts):
    return dsa_ops.share_of(dsa_ops.PREFILL_PROGRAM)
