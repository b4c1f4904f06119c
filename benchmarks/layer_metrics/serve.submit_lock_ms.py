"""Mean ``lock_wait_ms`` over the ``llm.request`` spans of the capture: what
the hand-over (``submit`` / ``submit_stream``) waited for the lock the tick
holds. The request is queued before that wait, which runs beside
``engine.finish``'s ``total_ms`` and not on top of it; where it outlasts the
engine's work, the excess is the request's ``llm.done.after_finish_ms``
(``llm/engine.py:_enqueue``). The program's span (``llm/serving.py``)."""
from benchmarks.lib import serve_spans


def read(trace, facts):
    return serve_spans.mean("llm.request", lambda a: a.get("lock_wait_ms"))
