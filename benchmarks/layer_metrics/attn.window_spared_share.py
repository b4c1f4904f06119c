"""How much of an all-full cache's reads the window spares in the captured
decode ticks: 1 - (positions needed of the full layers and of the window
layers, every layer) / (the decoding slots' lengths x all layers), in
percent, from the ``engine.tick`` spans' ``cache_positions_full``,
``cache_positions_window``, ``layers_full`` and ``layers_window``
(``benchmarks/lib/decode_attn_mixed.py``). Near 0 the traffic never leaves
the window. The program's span."""
from benchmarks.lib import decode_attn_mixed


def read(trace, facts):
    return decode_attn_mixed.window_spared_share()
