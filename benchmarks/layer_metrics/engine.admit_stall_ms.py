"""Mean duration of ``engine.admit``: how long one admission (prefill, its
logits' way to the host, first-token sampling, the insert) holds every
decoding slot. The program's span (``llm/engine.py``)."""
from benchmarks.lib import host_spans


def read(trace, facts):
    return host_spans.mean_duration_ms("engine.admit")
