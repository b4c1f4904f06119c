"""Mean duration of ``engine.admit.cache``: an admission's empty slot cache,
made op by op before its first chunk (``llm/engine.py:_empty_slot_cache``),
the chip idle but for the fills. The program's span."""
from benchmarks.lib import host_spans


def read(trace, facts):
    return host_spans.mean_duration_ms("engine.admit.cache")
