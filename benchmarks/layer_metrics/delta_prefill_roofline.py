"""Share of its roofline the prefill's chunked delta-rule scan reaches in the
captured admissions: least time for the recurrence of their real tokens (the
program's counter ``ssm_prefill_tokens``, every state layer: the larger of
its operations' and its bytes' time) over the device time under
``delta.scan`` in those admissions' prefill programs
(``benchmarks/lib/delta_ops.py``). Device trace + the program's span."""
from benchmarks.lib import delta_ops


def read(trace, facts):
    return delta_ops.prefill_roofline_share(trace, facts)
