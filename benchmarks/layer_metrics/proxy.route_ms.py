"""Mean ``route_ms`` over the ``serve.proxy.request`` spans of the capture:
the handler's wait for the route (``ProxyBase._route_for`` in the executor:
the wait for a thread, and the controller's ``get_routes`` where the cached
table had lapsed). The program's span (``serve/http_proxy.py``)."""
from benchmarks.lib import serve_spans


def read(trace, facts):
    return serve_spans.mean(
        "serve.proxy.request", lambda a: a.get("route_ms"))
