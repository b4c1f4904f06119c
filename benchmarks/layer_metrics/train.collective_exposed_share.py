"""Share of chip 0's busy time in which its op line is held by a collective
(or the wait for an asynchronous one) and no compute runs. Device trace."""
from benchmarks.lib import trace as T


def read(trace, facts):
    if trace is None or not trace.devices:
        return None
    dev = trace.devices[0]
    busy = T.union_ns((s, d) for _, s, d in dev.ops) / 1e9
    if busy <= 0:
        return None
    return 100.0 * T.collective_exposed_s(dev) / busy
