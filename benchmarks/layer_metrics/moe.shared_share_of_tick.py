"""Device time of the operations under the ``moe.shared`` scope (the shared
expert every token passes beside its routed ones) inside the decode program,
over that program's device time, in percent
(``benchmarks/lib/decode_attn_mixed.py``). Device trace."""
from benchmarks.lib import decode_attn_mixed


def read(trace, facts):
    ns = decode_attn_mixed.shared_expert_ns(facts)
    if ns is None or not ns["total"]:
        return None
    return 100.0 * ns["moe.shared"] / ns["total"]
