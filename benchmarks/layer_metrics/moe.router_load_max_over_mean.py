"""Pairs of the fullest of ALL the router's experts over the mean an expert
gets, a routed layer: ``moe_rows_max_all`` (the program's counter on the
``train.loss_fetch`` span: each routed layer's fullest expert of all 256,
summed over the routed layers and the prediction layer) x experts / (tokens
x top_k x those layers); 1 is a balanced router, which the bias rule
(``noaux_tc``) is there to hold (``benchmarks/lib/train_mla.py``). The
program's counter."""
from benchmarks.lib import train_mla


def read(trace, facts):
    return train_mla.router_load_max_over_mean(facts)
