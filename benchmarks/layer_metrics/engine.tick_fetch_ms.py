"""Mean, over the ticks of the capture, of the end of ``engine.tick.fetch``
on the host minus the end of that tick's decode program on chip 0: the
``[B, V]`` logits' way to the host once the device is done. It reads both
clocks, which the trace holds apart by a millisecond or two: the chip's
times are first moved onto the host's clock by the offset the runtime's own
enqueue events give (``host_spans.HostSpans.device_clock_offset_ns``; a
lower bound, so this reads high by the capture's smallest launch latency at
most). The program's span against the device trace."""
import statistics

from benchmarks.lib import host_spans
from benchmarks.lib import trace as T


def read(trace, facts):
    spans = host_spans.load()
    if (spans is None or spans.device_clock_offset_ns is None
            or trace is None or not trace.devices):
        return None
    dev, line = trace.devices[0], spans.loop_line()
    name = T.dominant_program(dev, facts["decode_program"])
    waits = []
    for tick, (start, dur) in host_spans.ticks_with_program(
            line, dev, name, spans.device_clock_offset_ns):
        fetch = [s for s in host_spans.children(line, tick)
                 if s.name == "engine.tick.fetch"]
        if fetch:
            waits.append(fetch[0].end_ns - (start + dur))
    return statistics.fmean(waits) / 1e6 if waits else None
