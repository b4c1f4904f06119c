"""Mean time, on chip 0, from the end of one train-step program to the
start of the next: what the trainer loop, ``report()`` and the next batch's
``device_put`` leave the chip waiting for. Device trace."""
from benchmarks.lib import trace as T


def read(trace, facts):
    if trace is None or not trace.devices:
        return None
    dev = trace.devices[0]
    name = T.dominant_program(dev, facts["train_program"])
    return None if name is None else T.program_gap_mean_ms(dev, name)
