"""Share of a step's (token, expert) pairs that were routed to the experts
this chip holds: ``moe_rows_held`` (the program's counter, summed over the
routed layers, on the ``train.loss_fetch`` span) over tokens x top_k x
layers, in percent; a quarter for 16 of 64 experts under a balanced router.
The program's counter."""
from benchmarks.lib import train_moe


def read(trace, facts):
    counts, model = train_moe.routed_counts(), facts["model"]
    if counts is None:
        return None
    pairs = (facts["batch_per_chip"] * facts["seq_len"] * model["moe_top_k"]
             * model["num_layers"])
    return 100.0 * counts["moe_rows_held"] / pairs
