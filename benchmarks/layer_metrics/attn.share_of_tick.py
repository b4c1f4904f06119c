"""Device time of the decode-attention kernels of both kinds of KV cache
inside the decode program, over that program's device time, in percent
(``benchmarks/lib/decode_attn_mixed.py``). Device trace."""
from benchmarks.lib import decode_attn_mixed


def read(trace, facts):
    ns = decode_attn_mixed.decode_kernel_ns(facts)
    if ns is None or not ns["total"]:
        return None
    return 100.0 * (ns["full"] + ns["window"]) / ns["total"]
