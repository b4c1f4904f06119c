"""Mean duration of ``train.report`` a step: the ``report()`` call between
two steps, the chip waiting. The program's span (``train/trainer.py``)."""
from benchmarks.lib import host_spans


def read(trace, facts):
    return host_spans.mean_duration_ms("train.report")
