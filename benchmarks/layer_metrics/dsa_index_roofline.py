"""Share of its roofline the lightning indexer's scoring reaches in the
captured ticks and admissions: least time for the (query, position) pairs
they scored (the program's counter ``index_positions`` x 16.5 kFLOP: 64
heads of 128 channels, a ReLU and a weighted sum; a tick's keys read once a
query, 256 B a position) over the device time of the operations under
``mla.index`` in those ticks' and admissions' programs
(``benchmarks/lib/dsa_ops.py``). Device trace + the program's spans."""
from benchmarks.lib import dsa_ops


def read(trace, facts):
    return dsa_ops.index_roofline_share(trace, facts)
