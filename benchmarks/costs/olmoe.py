"""Parameters, training FLOPs and the routed experts' cost of the OLMoE
architecture (bias-free attention with q and k norms over the whole projected
vector, ``moe_num_experts`` SwiGLU experts of width ``mlp_dim`` with
``moe_top_k`` a token, untied LM head), computed from shapes: what the
mathematics requires, not what an implementation executes. Recomputation
(remat) is never counted. A multiply-add is 2 FLOPs."""
from __future__ import annotations


def param_count(cfg: dict) -> dict:
    """Parameters of an OLMoE of the given sizes: all of them (``total``),
    and those a token multiplies (``active``: ``moe_top_k`` of the experts,
    the router, attention, norms, the embedding row it reads and the head)."""
    L, E, V, M = (cfg["num_layers"], cfg["embed_dim"], cfg["vocab_size"],
                  cfg["mlp_dim"])
    H, KV = cfg["num_heads"], cfg.get("num_kv_heads") or cfg["num_heads"]
    D = E // H
    X, k = cfg["moe_num_experts"], cfg["moe_top_k"]
    attention = E * H * D + 2 * E * KV * D + H * D * E     # q, k, v, o
    norms = 2 * E + H * D + KV * D           # attn, mlp, q_norm, k_norm
    expert = 3 * E * M                       # gate, up, down
    router = E * X
    layer = attention + norms + router + X * expert
    layer_active = attention + norms + router + k * expert
    outside = 2 * V * E + E                  # embedding, head, final norm
    return {
        "attention_matrices": L * attention,
        "experts": L * X * expert,
        "experts_active": L * k * expert,
        "router": L * router,
        "embedding": V * E,
        "head": V * E,
        "other": L * norms + E,
        "layer": layer,
        "total": L * layer + outside,
        "active": L * layer_active + outside,
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 x the matrices
    it multiplies (attention, router, its ``moe_top_k`` experts, the head; an
    embedding row is read, not multiplied), plus attention's score and value
    products, 6·L·E·T for a causal model (half of 12·L·E·T: the masked half
    is work nobody does; as ``costs/gpt2.py``)."""
    n = param_count(cfg)
    dense = 6.0 * (n["attention_matrices"] + n["router"]
                   + n["experts_active"] + n["head"])
    attention = 6.0 * cfg["num_layers"] * cfg["embed_dim"] * seq_len
    return dense + attention


def moe_experts_cost(rows: int, experts_touched: int, embed_dim: int,
                     mlp_dim: int, bytes_per_weight: int = 2,
                     bytes_per_activation: int = 2) -> dict:
    """FLOPs and least HBM bytes of one layer's expert products over
    ``rows`` routed (token, expert) rows that reached ``experts_touched``
    distinct experts. FLOPs: three products of 2·D·M a row (gate, up, down).
    Bytes: each touched expert's three matrices read once, each row read
    once and its result written once (the [rows, M] intermediates can stay
    on the chip)."""
    return {
        "flops": 2.0 * rows * 3 * embed_dim * mlp_dim,
        "bytes": (experts_touched * 3.0 * embed_dim * mlp_dim
                  * bytes_per_weight
                  + 2.0 * rows * embed_dim * bytes_per_activation),
    }
