"""Share of its roofline the prefill's chunked scan reaches in the captured
admissions: least time for the recurrence of their real tokens (the
program's counter ``ssm_prefill_tokens``, every state layer: the larger of
its operations' and its bytes' time) over the device time under
``ssm.scan`` in those admissions' prefill programs
(``benchmarks/lib/ssm_ops.py``). Device trace + the program's span."""
from benchmarks.lib import ssm_ops


def read(trace, facts):
    return ssm_ops.prefill_roofline_share(trace, facts)
