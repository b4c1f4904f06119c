"""Share of its roofline the exact choice of the kept positions reaches in
the captured ticks and admissions: least time to read every float32 score
once and write the chosen positions (``index_positions`` x 4 B +
``selected_positions`` x 4 B at the chip's bytes/s) over the device time of
the operations under ``mla.select`` (``benchmarks/lib/dsa_ops.py``). Device
trace + the program's spans."""
from benchmarks.lib import dsa_ops


def read(trace, facts):
    return dsa_ops.select_roofline_share(trace, facts)
