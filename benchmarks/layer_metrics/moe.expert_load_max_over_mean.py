"""Rows of the fullest held expert over the held experts' mean, a layer:
``moe_rows_max_expert`` (each routed layer's fullest expert, summed over the
layers) over ``moe_rows_held`` / held experts; 1 is a balanced share. The
grouped products wait for the fullest group. The program's counters."""
from benchmarks.lib import train_moe


def read(trace, facts):
    counts, model = train_moe.routed_counts(), facts["model"]
    if counts is None or not counts["moe_rows_held"]:
        return None
    held = model.get("moe_num_held") or model["moe_num_experts"]
    return counts["moe_rows_max_expert"] * held / counts["moe_rows_held"]
