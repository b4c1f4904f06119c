"""Of the ``serve.proxy.request`` spans of the capture, the fraction with
``route_fetched`` 1: requests that went to the controller for the route
table before anything else of them happened. The cache holds a fetch for one
second, so under Poisson arrivals this is near 1 / (1 + rate). The program's
span (``serve/http_proxy.py``)."""
from benchmarks.lib import serve_spans


def read(trace, facts):
    return serve_spans.mean(
        "serve.proxy.request", lambda a: a.get("route_fetched"))
