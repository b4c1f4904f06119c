"""Mean ``submit_ms`` + ``register_ms`` over the ``serve.proxy.request`` spans
of the capture that got as far: ``handle.remote`` in the executor (the wait
for a thread, the router's pick, the call's send) and the ``handle_request``
round trip, to the stream registered or the unary answer back. The program's
span (``serve/http_proxy.py``)."""
from benchmarks.lib import serve_spans


def read(trace, facts):
    return serve_spans.mean(
        "serve.proxy.request",
        lambda a: a["submit_ms"] + a["register_ms"]
        if a.get("submit_ms", 0) > 0 else None)
