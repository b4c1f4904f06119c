"""Mean ``turn_ms`` over the ``serve.replica.pull`` spans of the capture:
from the stream's registration, or its previous reply, to the pull's first
line in the replica: two call legs and the proxy's turn between them. The
program's span (``serve/replica.py``)."""
from benchmarks.lib import serve_spans


def read(trace, facts):
    return serve_spans.mean(
        "serve.replica.pull", lambda a: a.get("turn_ms"))
