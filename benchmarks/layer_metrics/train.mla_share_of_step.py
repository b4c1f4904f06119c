"""Device time of latent attention inside the train step, the prediction
layer's mixer included: the ``flash_mla`` kernels (forward and backward) and
the operations under the ``mla.*`` scopes (the query rank, the
down-projection, the up-projection to a head's keys and values, the output
projection; forward, remat and backward), over the step's device time, in
percent (``benchmarks/lib/train_mla.py``). Device trace."""
from benchmarks.lib import train_mla


def read(trace, facts):
    ns = train_mla.step_ns(facts)
    if ns is None or not ns["total"] or not ns["flash"]:
        return None
    return 100.0 * (ns["flash"] + ns["mla"]) / ns["total"]
