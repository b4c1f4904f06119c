"""Slots that decode a tick, averaged over the captured ticks:
``state_slot_layers / layers_state`` of the ``engine.tick`` spans
(``llm/engine.py``): how many states a tick's every state layer reads and
writes (``benchmarks/lib/ssm_ops.py``). The program's span."""
from benchmarks.lib import ssm_ops


def read(trace, facts):
    return ssm_ops.live_slots()
