"""Median, over the requests the capture holds from the HTTP handler to the
engine, of the handler's ``total_ms`` / ``tokens`` of the request's
``llm.done``: the judged metric as the proxy sees it, from the handler's
first line to the last byte handed to the socket, beside
``engine.request_ms_per_token`` over the same capture. ``per_token_p50_ms``
less this is the client's socket and the load generator's. The program's
spans (``serve/http_proxy.py``, ``llm/serving.py``)."""
import statistics

from benchmarks.lib import serve_spans


def read(trace, facts):
    per_token = [r["total_ms"] / r["tokens"] for r in serve_spans.whole()
                 if r["tokens"]]
    return statistics.median(per_token) if per_token else None
