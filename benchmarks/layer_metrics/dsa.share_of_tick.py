"""Device time of the sparse attention's three steps inside the decode
program (everything under ``mla.index``, ``mla.select`` and ``mla.sparse``:
the indexer's projections and scores, the exact choice of the kept
positions, the ``latent_decode_attention`` kernel over the choice), over
that program's device time, in percent (``benchmarks/lib/dsa_ops.py``).
Device trace."""
from benchmarks.lib import dsa_ops


def read(trace, facts):
    return dsa_ops.share_of(facts["decode_program"])
