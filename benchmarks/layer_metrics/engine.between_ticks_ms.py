"""Mean time, on the chip, from the end of one decode program to the start
of the next: the host's sampling and bookkeeping for every slot, and any
prefill and insert the scheduler admitted between the two ticks. A token
costs one decode program plus this. Device trace."""
from benchmarks.lib import trace as T


def read(trace, facts):
    if trace is None or not trace.devices:
        return None
    dev = trace.devices[0]
    name = T.dominant_program(dev, facts["decode_program"])
    return None if name is None else T.program_gap_mean_ms(dev, name)
