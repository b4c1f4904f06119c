"""Median, over the ``engine.finish`` spans of the capture, of ``total_ms`` /
``produced``: the judged metric as the engine sees it, from the request's
``submit`` to the end of its answer, a token. ``per_token_p50_ms`` less this
is the service's, the proxy's and the client's. The program's span
(``llm/engine.py``)."""
import statistics

from benchmarks.lib import host_spans


def read(trace, facts):
    spans = host_spans.load()
    if spans is None:
        return None
    per_token = [s.args["total_ms"] / s.args["produced"]
                 for s in spans.named("engine.finish")
                 if s.args.get("produced")]
    return statistics.median(per_token) if per_token else None
