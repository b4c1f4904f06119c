"""Share of its roofline the prefill's chunked KDA scan reaches in the
captured admissions: least time for the recurrence of their real tokens (the
program's counter ``ssm_prefill_tokens``, every state layer: the larger of
its operations' and its bytes' time) over the device time under ``kda.scan``
in those admissions' prefill programs (``benchmarks/lib/bailing_ops.py``).
Device trace + the program's span."""
from benchmarks.lib import bailing_ops


def read(trace, facts):
    return bailing_ops.kda_prefill_roofline_share(trace, facts)
