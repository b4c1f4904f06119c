"""Device time of the multi-token-prediction layer inside the train step:
everything under the ``mtp.in``, ``mtp.block`` and ``mtp.head`` scopes (the
two norms and ``eh_proj``, one whole routed block with its flash calls, the
final norm and the second cross entropy over the shared head; forward, remat
and backward), over the step's device time, in percent
(``benchmarks/lib/train_mla.py``). It overlaps ``train.mla_share_of_step``
and ``train.moe_share_of_step`` by the block's own mixer and experts. Device
trace."""
from benchmarks.lib import train_mla


def read(trace, facts):
    ns = train_mla.step_ns(facts)
    if ns is None or not ns["total"] or not ns["mtp"]:
        return None
    return 100.0 * ns["mtp"] / ns["total"]
