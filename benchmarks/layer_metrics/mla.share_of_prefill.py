"""Device time of the latent-attention layer's operations inside the
prefill programs (everything under an ``mla.*`` scope: the queries, the
down-projection, the up-projection of the rows a chunk sees, the attention
over them, the gate and the output), over those programs' device time, in
percent (``benchmarks/lib/bailing_ops.py``). Device trace."""
from benchmarks.lib import bailing_ops as ops


def read(trace, facts):
    return ops.share_of(ops.PREFILL_PROGRAM, ops.MLA_SCOPES, ops.MLA_KERNEL)
