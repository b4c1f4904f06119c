"""Operations and bytes the algorithms need, computed from shapes.

These are the yardstick's own counts: what the mathematics requires, not
what a particular implementation executes. Recomputation (remat) is never
counted. A multiply-add is 2 FLOPs. What belongs to one architecture (its
parameters, its FLOPs a trained token) is a file of its own,
``costs/<name>.py``, which the configuration file names (``lib/named.py``).
"""
from __future__ import annotations


def flash_attention_cost(batch: int, seq_len: int, heads: int, head_dim: int,
                         *, backward: bool, causal: bool = True,
                         bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of one flash-attention call.

    Forward: S = Q·K^T and O = P·V, 2·B·H·T·T·D FLOPs each, halved when
    causal. Backward: dV = P^T·dO, dP = dO·V^T, dQ = dS·K, dK = dS^T·Q
    plus the recomputed S = Q·K^T that the algorithm itself prescribes
    (flash attention stores no probabilities), five products.
    Bytes: each operand read once and each result written once — forward
    reads q, k, v and writes o (the log-sum-exp row, 4 B a query and head,
    is included); backward reads q, k, v, o, do and lse, writes dq, dk, dv.
    """
    qkvo = batch * seq_len * heads * head_dim * bytes_per_el
    lse = batch * seq_len * heads * 4
    product = 2.0 * batch * heads * seq_len * seq_len * head_dim
    if causal:
        product /= 2.0
    if backward:
        return {"flops": 5.0 * product, "bytes": 8.0 * qkvo + lse}
    return {"flops": 2.0 * product, "bytes": 4.0 * qkvo + lse}


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    """Least time the chip could take for ``cost`` and which peak bounds it."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
