"""Parameters and training FLOPs of the dense Llama architecture (no learned
positions, grouped-query attention, gated MLP of three matrices, untied LM
head), computed from shapes: what the mathematics requires, not what an
implementation executes. Recomputation is never counted. A multiply-add is 2
FLOPs."""
from __future__ import annotations


def _mlp_dim(cfg: dict) -> int:
    """``mlp_dim`` where the file states it; else the program's own rule
    (``LlamaConfig.hidden_dim``): 8/3 of the width, rounded up to 128."""
    if cfg.get("mlp_dim") is not None:
        return cfg["mlp_dim"]
    return (int(cfg["embed_dim"] * 8 / 3) + 127) // 128 * 128


def param_count(cfg: dict) -> dict:
    """Parameters of a Llama of the given sizes, split into the block
    matrices that every token multiplies, the head, and the rest."""
    L, E, V = cfg["num_layers"], cfg["embed_dim"], cfg["vocab_size"]
    H, KV = cfg["num_heads"], cfg["num_kv_heads"]
    D, M = E // H, _mlp_dim(cfg)
    # wq and wo [E, H.D]; wk and wv [E, KV.D]; gate, up, down [E, M]
    block_matrices = L * (2 * E * H * D + 2 * E * KV * D + 3 * E * M)
    norms = L * 2 * E + E
    return {
        "block_matrices": block_matrices,
        "embedding": V * E,
        "head": V * E,
        "other": norms,
        "total": block_matrices + 2 * V * E + norms,
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 for every
    parameter of a matrix a token multiplies (the block matrices and the
    head; the embedding is a lookup), plus causal attention's score and value
    products counted once, 6·L·(H·D)·T, as ``costs/gpt2.py`` argues."""
    n = param_count(cfg)
    dense = 6.0 * (n["block_matrices"] + n["head"])
    attention = 6.0 * cfg["num_layers"] * cfg["embed_dim"] * seq_len
    return dense + attention
