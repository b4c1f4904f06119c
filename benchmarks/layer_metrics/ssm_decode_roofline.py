"""Share of its roofline the ``ssm_update`` kernel reaches in the captured
decode ticks: least time for the states and convolution rows those ticks
NEEDED (the program's counter ``state_slot_layers``: each read and written
once) at the chip's bytes/s, over the device time of ALL kernels named
``ssm_update`` in those ticks' programs (``benchmarks/lib/ssm_ops.py``).
Memory-bound. Device trace + the program's span."""
from benchmarks.lib import ssm_ops


def read(trace, facts):
    return ssm_ops.decode_roofline_share(trace, facts)
