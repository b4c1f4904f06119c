"""Share of the captured ticks' (token, expert) pairs that were routed to
the experts this chip holds: ``moe_rows_held`` over ``moe_rows`` (``active``
x top_k x routed layers) of the ``engine.tick`` spans, as a fraction: 0.25
for 128 of 512 experts under a balanced router
(``benchmarks/lib/bailing_ops.py``). The program's counter."""
from benchmarks.lib import bailing_ops


def read(trace, facts):
    return bailing_ops.held_pairs_share()
