"""Mean ``queued_ms`` over the ``engine.admit`` spans of the capture: from a
request's ``submit`` to the start of its admission. The loop's sleep where the
engine was idle, the read of the tick in flight where it was not, the
admissions ahead of it, and a slot to come free on a full replica. The
program's span (``llm/engine.py``)."""
from benchmarks.lib import request_spans


def read(trace, facts):
    return request_spans.mean("engine.admit", "queued_ms")
