"""Mean duration of ``engine.tick.sample`` over the ticks of the capture: the
host's per-slot sampling of the ``[B, V]`` logits and the bookkeeping of each
new token, every slot waiting. The program's span (``llm/engine.py``)."""
from benchmarks.lib import host_spans


def read(trace, facts):
    return host_spans.mean_duration_ms("engine.tick.sample")
