"""The plain reference of ``deepseek_v32``
(``benchmarks/references/deepseek_v32.py``, the arithmetic the real cell is
held to) at the toy's sizes: what the weights do not carry is the reference's
own to state, and the toy's indexer keeps 16 positions, its YaRN starts from
a context of 32, and its sequences come 16 rows at a time, where
DeepSeek-V3.2-Exp keeps 2,048, starts from 4,096 and the cell's come 512 at a
time. A copy of the module of its own, so the real one is as it was."""
import os

from benchmarks.lib import named
from benchmarks.lib.cluster import BENCH_DIR

_real = named.load(os.path.join(BENCH_DIR, "references", "deepseek_v32.py"))
_real.INDEX_TOPK = 16
_real.ROPE_ORIGINAL = 32
_real.BLOCK = 16
logits = _real.logits
