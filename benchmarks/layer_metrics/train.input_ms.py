"""Mean duration of ``train.next_batch`` a step: the numpy draw of the next
batch and its ``device_put``, the chip waiting. The program's span
(``train/trainer.py``)."""
from benchmarks.lib import host_spans


def read(trace, facts):
    return host_spans.mean_duration_ms("train.next_batch")
