"""Model FLOP/s utilization: the run's tokens per second and chip, times the
FLOPs one trained token requires (``train_flops_per_token`` of the
``benchmarks/costs/<name>.py`` that the configuration's ``costs`` names),
over the chip's published bf16 peak. Host clock of the traced run and a
count from shapes; no peak, no number.

The count of ``costs/gpt2.py`` is 6 x (block matrices + tied head) + 6.L.E.T: CAUSAL ATTENTION IS
COUNTED ONCE, at the half of the score and value products a causal model
needs, not at the 12.L.E.T of full attention that the usual convention (and
ISSUE 23) writes; recomputation is never counted. Against the usual
convention this reads lower: by 6.2% of itself for gpt2-medium (2.272 against 2.423 GFLOP a
token) and 4.6% for gpt2-xl (9.802 against 10.274) at 1024 tokens."""


def read(trace, facts):
    if not facts.get("peak_flops_per_s"):
        return None
    return (100.0 * facts["tokens_per_s_per_chip"] * facts["flops_per_token"]
            / facts["peak_flops_per_s"])
