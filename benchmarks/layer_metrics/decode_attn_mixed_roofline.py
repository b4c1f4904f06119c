"""Share of its roofline the decode-attention kernel reaches in the captured
decode ticks of a model with two kinds of KV cache: least time for the bytes
those ticks NEEDED of the full layers' cache and of the window layers' rings
(the program's counters ``cache_positions_full`` / ``cache_positions_window``,
and a written tile a decoding slot, every layer of each kind) at the chip's
bytes/s, over the device time of ALL kernels named ``decode_attention`` /
``decode_attention_window`` in those ticks' programs
(``benchmarks/lib/decode_attn_mixed.py``). Memory-bound. Device trace + the
program's span."""
from benchmarks.lib import decode_attn_mixed


def read(trace, facts):
    return decode_attn_mixed.mixed_roofline_share(trace, facts)
