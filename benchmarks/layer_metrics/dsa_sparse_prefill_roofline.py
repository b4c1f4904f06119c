"""Share of its roofline the prefill chunks' attention over the choice
reaches in the captured admissions: least time for each query's CHOSEN
positions (``selected_positions`` x 82 kFLOP, the heads' up-projected
products; the up-projection itself left out) over the device time of the
operations under ``mla.sparse`` in those admissions' programs: a form that
attends every visible position and masks shows as a low share
(``benchmarks/lib/dsa_ops.py``). Device trace + the program's spans."""
from benchmarks.lib import dsa_ops


def read(trace, facts):
    return dsa_ops.sparse_prefill_roofline_share(trace, facts)
