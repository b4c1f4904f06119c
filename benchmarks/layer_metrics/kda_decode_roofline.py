"""Share of its roofline the ``kda_update`` kernel reaches in the captured
decode ticks: least time for the states and the step's operands
those ticks NEEDED (the program's counter ``state_slot_layers``: each
float32 state read and written once, 2 x 32 x 128 x 128 x 4 B at the
published size, beside its rows of operands) at the chip's bytes/s, over the
device time of ALL kernels named ``kda_update`` in those ticks' programs
(``benchmarks/lib/bailing_ops.py``). Memory-bound. Device trace + the
program's span."""
from benchmarks.lib import bailing_ops


def read(trace, facts):
    return bailing_ops.kda_decode_roofline_share(trace, facts)
