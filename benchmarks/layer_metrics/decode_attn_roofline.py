"""Share of its roofline the decode-attention kernel reaches in the captured
decode ticks: least time for the bytes of KV cache those ticks NEEDED (the
program's counter ``cache_positions``, and a written tile a decoding slot)
at the chip's bytes/s, over the device time of the kernels named
``decode_attention`` in those ticks' programs
(``benchmarks/lib/decode_attn.py``). Memory-bound. Device trace + the
program's span."""
from benchmarks.lib import decode_attn


def read(trace, facts):
    return decode_attn.roofline_share(trace, facts)
