"""The plain reference of ``granite_hybrid``
(``benchmarks/references/granite_hybrid.py``, the arithmetic the real cell is
held to) at the toy's sizes: what the weights do not carry is the reference's
own to state, and the toy keeps a state of 16 a head channel and scales
``q . k`` by 1/16 where Granite 4.0-H Micro keeps 128 and scales by 1/64. A
copy of the module of its own, so the real one is as it was."""
import os

from benchmarks.lib import named
from benchmarks.lib.cluster import BENCH_DIR

_real = named.load(os.path.join(BENCH_DIR, "references", "granite_hybrid.py"))
_real.D_STATE = 16
_real.ATTENTION_MULTIPLIER = 0.0625
logits = _real.logits
