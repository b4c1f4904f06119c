"""Share of its roofline the ``delta_update`` kernel reaches in the captured
decode ticks: least time for the states, convolution rows and q, k, v rows
those ticks NEEDED (the program's counter ``state_slot_layers``: each state
read and written once) at the chip's bytes/s, over the device time of ALL
kernels named ``delta_update`` in those ticks' programs
(``benchmarks/lib/delta_ops.py``). Memory-bound. Device trace + the
program's span."""
from benchmarks.lib import delta_ops


def read(trace, facts):
    return delta_ops.decode_roofline_share(trace, facts)
