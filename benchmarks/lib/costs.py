"""Operations and bytes the algorithms need, computed from shapes.

These are the yardstick's own counts: what the mathematics requires, not
what a particular implementation executes. Recomputation (remat) is never
counted. A multiply-add is 2 FLOPs.
"""
from __future__ import annotations


def gpt2_param_count(cfg: dict) -> dict:
    """Parameters of a GPT-2 of the given sizes (tied LM head), split into
    the block matrices that every token multiplies and the rest."""
    L, E, V, S = (cfg["num_layers"], cfg["embed_dim"], cfg["vocab_size"],
                  cfg["max_seq_len"])
    M = E * cfg.get("mlp_ratio", 4)
    block_matrices = L * (3 * E * E + E * E + 2 * E * M)
    block_vectors = L * (3 * E + E + M + E + 4 * E)  # biases, 2 layernorms
    return {
        "block_matrices": block_matrices,
        "embedding": V * E,
        "positions": S * E,
        "other": block_vectors + 2 * E,
        "total": block_matrices + block_vectors + V * E + S * E + 2 * E,
    }


def gpt2_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires.

    6·N for the matrices every token multiplies (the block matrices and
    the tied head's [E, V] product: 2 forward, 4 backward each), plus
    attention's score and value products. Each is 2·T·E FLOPs a token and
    layer forward when every query attends the full T keys, 12·L·E·T
    forward + backward; a causal model needs half of that on average,
    6·L·E·T, and that is what is counted (the kernel skips the masked
    half, so counting it whole would credit work nobody does).
    """
    n = gpt2_param_count(cfg)
    dense = 6.0 * (n["block_matrices"] + n["embedding"])
    attention = 6.0 * cfg["num_layers"] * cfg["embed_dim"] * seq_len
    return dense + attention


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
