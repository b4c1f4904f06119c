"""Of chip 0's idle time while the loop thread was on record, the share that lies under no
leaf span of the engine's tick loop (the thread that holds ``engine.tick``):
idleness the instrumentation cannot yet explain
(``host_spans.idle_unattributed_share``). One quantity under two names
because a metric names the one end-to-end metric of its cells. The program's
spans against the device trace."""
from benchmarks.lib import host_spans


def read(trace, facts):
    return host_spans.idle_unattributed_share(trace)
