"""Share of their roofline the routed experts' grouped products reach in
the captured decode ticks: least time for each tick's rows and the experts
it TOUCHED (the program's counter) over the device time under
``moe.experts`` (``benchmarks/lib/moe_ops.py``). Memory-bound at a decode
tick's 8-128 rows. Device trace + the program's span.

The cost is ``costs/olmoe.py:moe_experts_cost`` (three matrices an expert):
right for the cells this metric lists. The serving runner's ``facts`` do not
carry the configuration's ``costs`` file, so a reader cannot ask for the
cell's own (PERF.md section 7): an architecture whose experts cost otherwise
brings a metric of its own until they do."""
import os

from benchmarks.lib import moe_ops, named
from benchmarks.lib.cluster import BENCH_DIR


def read(trace, facts):
    cost = named.load(os.path.join(BENCH_DIR, "costs", "olmoe.py"))
    return moe_ops.experts_roofline_share(trace, facts, cost.moe_experts_cost)
