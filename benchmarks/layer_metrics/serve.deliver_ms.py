"""Mean ``after_finish_ms`` over the ``llm.done`` spans of the capture: from
the engine's ``engine.finish`` to the answer's last piece (a stream's closing
lines and ``[DONE]``) being ready to leave the replica. What follows, the
proxy's pull and the client's read, is another process's. The program's span
(``llm/serving.py``)."""
from benchmarks.lib import request_spans


def read(trace, facts):
    return request_spans.mean("llm.done", "after_finish_ms")
