"""Mean, over the requests the capture holds from the HTTP handler to the
engine (``serve.proxy.request``, ``llm.done`` and ``engine.finish``, joined by
``req`` and then ``rid``), of the handler's ``total_ms`` less the engine's:
what a request spends in the node outside the engine, the route, the handle's
call, the pulls and the reply's way out together. The program's spans
(``serve/http_proxy.py``, ``llm/serving.py``, ``llm/engine.py``)."""
import statistics

from benchmarks.lib import serve_spans


def read(trace, facts):
    outside = [r["outside_engine_ms"] for r in serve_spans.whole()]
    return statistics.fmean(outside) if outside else None
