"""Mean ``after_last_pull_ms`` over the ``serve.proxy.request`` spans of the
capture whose stream was pulled to its end: from the return of the pull that
said done to ``write_eof`` returned, the answer's last chunks handed to the
socket. The program's span (``serve/http_proxy.py``)."""
from benchmarks.lib import serve_spans


def read(trace, facts):
    return serve_spans.mean(
        "serve.proxy.request",
        lambda a: a["after_last_pull_ms"] if a.get("pulls") else None)
