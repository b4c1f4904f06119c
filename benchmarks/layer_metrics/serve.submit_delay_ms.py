"""Mean ``since_call_ms`` over the ``llm.request`` spans of the capture: from
the replica's call of the deployment to the engine's hearing of the request.
For a stream, whose body runs at the proxy's first pull, that is the reply to
the proxy and the pull's way back, then the tokenizer. The program's span
(``llm/serving.py``)."""
from benchmarks.lib import request_spans


def read(trace, facts):
    return request_spans.mean("llm.request", "since_call_ms")
