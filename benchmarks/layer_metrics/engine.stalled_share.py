"""Of the lives of the requests that finished in the capture (sum of
``total_ms`` over ``engine.finish``), the percentage their slots stood still
under OTHER requests' admissions (sum of ``stalled_ms``). The estimate it
replaces: request rate x ``engine.admit_stall_ms``. The program's span
(``llm/engine.py``)."""
from benchmarks.lib import host_spans


def read(trace, facts):
    spans = host_spans.load()
    if spans is None:
        return None
    ledgers = [s.args for s in spans.named("engine.finish")
               if "stalled_ms" in s.args]
    lived = sum(a["total_ms"] for a in ledgers)
    if not lived:
        return None
    return 100.0 * sum(a["stalled_ms"] for a in ledgers) / lived
