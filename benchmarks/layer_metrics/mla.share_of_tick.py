"""Device time of the latent-attention layer's operations inside the decode
program (everything under an ``mla.*`` scope: the queries, the
down-projection, the absorbed up-projection, the
``latent_decode_attention`` kernel, the gate and the output), over that
program's device time, in percent (``benchmarks/lib/bailing_ops.py``).
Device trace."""
from benchmarks.lib import bailing_ops as ops


def read(trace, facts):
    return ops.share_of(facts["decode_program"], ops.MLA_SCOPES,
                        ops.MLA_KERNEL)
