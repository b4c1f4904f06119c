"""Positions attention was asked to read over positions the indexer scored,
of the captured ticks and admissions: ``selected_positions`` /
``index_positions`` of the ``engine.tick`` and ``engine.admit`` spans, a
ratio: 1 where nothing is left out (contexts up to ``index_topk``), 1/16 at
16 x ``index_topk`` (``benchmarks/lib/dsa_ops.py``). The program's
counters."""
from benchmarks.lib import dsa_ops


def read(trace, facts):
    return dsa_ops.selected_share()
