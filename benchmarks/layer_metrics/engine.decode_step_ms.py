"""Median device time of one decode program (a tick over every slot).
Device trace."""
from benchmarks.lib import trace as T


def read(trace, facts):
    if trace is None or not trace.devices:
        return None
    dev = trace.devices[0]
    name = T.dominant_program(dev, facts["decode_program"])
    return None if name is None else T.program_median_ms(dev, name)
