"""Mean, over the ``engine.admit`` spans the capture holds whole, of chip 0's
device time of the programs enqueued inside the span (the prefill's chunks,
the insert, the empty slot cache's operations), paired by ``run_id`` and by
no shifted time (``lib/request_spans.py``). With ``engine.admit_stall_ms`` it
splits an admission into chip and host. The program's span against the device
trace."""
from benchmarks.lib import host_spans, request_spans


def read(trace, facts):
    spans = host_spans.load()
    if spans is None:
        return None
    return request_spans.admit_device_ms(
        spans.named("engine.admit"), request_spans.enqueued(),
        facts["decode_program"])
