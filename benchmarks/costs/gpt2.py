"""Parameters and training FLOPs of the GPT-2 architecture (learned
positions, pre-LN blocks, ``mlp_ratio`` x GELU MLP, LM head tied to the token
embedding), computed from shapes: what the mathematics requires, not what an
implementation executes. Recomputation (remat) is never counted. A
multiply-add is 2 FLOPs."""
from __future__ import annotations


def param_count(cfg: dict) -> dict:
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


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires.

    6·N for the matrices every token multiplies (the block matrices and
    the tied head's [E, V] product: 2 forward, 4 backward each), plus
    attention's score and value products. Each is 2·T·E FLOPs a token and
    layer forward when every query attends the full T keys, 12·L·E·T
    forward + backward; a causal model needs half of that on average,
    6·L·E·T, and that is what is counted (the kernel skips the masked
    half, so counting it whole would credit work nobody does).
    """
    n = param_count(cfg)
    dense = 6.0 * (n["block_matrices"] + n["embedding"])
    attention = 6.0 * cfg["num_layers"] * cfg["embed_dim"] * seq_len
    return dense + attention
