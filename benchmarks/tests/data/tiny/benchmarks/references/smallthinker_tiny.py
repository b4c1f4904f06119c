"""The plain reference of ``smallthinker``
(``benchmarks/references/smallthinker.py``, the arithmetic the real cell is
held to) at the toy's numbers: what the weights do not carry is the
reference's own to state, and the toy's window is 16 positions and its
router picks 2 where the published model's are 4096 and 6. A copy of the
module of its own, so the real one is as it was."""
import os

from benchmarks.lib import named
from benchmarks.lib.cluster import BENCH_DIR

_real = named.load(os.path.join(BENCH_DIR, "references", "smallthinker.py"))
_real.SLIDING_WINDOW = 16
_real.TOP_K = 2
logits = _real.logits
