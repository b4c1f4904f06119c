"""Distinct experts that received a row, a layer, averaged over the
captured decode ticks: ``experts_touched`` / ``moe_layers`` of the
``engine.tick`` spans (``llm/engine.py``; the decode program counts them on
the chip and the count rides the tick's one fetch). It follows the slots
that decode: 8 of 64 at one, about 56 at sixteen. The program's span."""
import statistics

from benchmarks.lib import host_spans


def read(trace, facts):
    spans = host_spans.load()
    if spans is None:
        return None
    ticks = [s.args for s in spans.named("engine.tick")
             if s.args.get("moe_layers")]
    if not ticks:
        return None
    return statistics.fmean(
        t["experts_touched"] / t["moe_layers"] for t in ticks)
