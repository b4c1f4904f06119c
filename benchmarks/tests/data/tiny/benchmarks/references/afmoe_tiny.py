"""The plain reference of ``afmoe`` (``benchmarks/references/afmoe.py``, the
arithmetic the real cell is held to) at the toy's window: what the weights do
not carry is the reference's own to state, and the toy slides over 16
positions where Trinity-Mini slides over 2048. A copy of the module of its
own, so the real one is as it was."""
import os

from benchmarks.lib import named
from benchmarks.lib.cluster import BENCH_DIR

_real = named.load(os.path.join(BENCH_DIR, "references", "afmoe.py"))
_real.SLIDING_WINDOW = 16
logits = _real.logits
