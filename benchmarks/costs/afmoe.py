"""Parameters and training FLOPs of Arcee's ``afmoe`` architecture (gated
attention with G query heads a kv head, ``num_dense_layers`` leading SwiGLU
MLPs of ``mlp_dim``, then ``moe_num_experts`` routed SwiGLU experts of
``moe_mlp_dim`` with ``moe_top_k`` a token beside ``num_shared_experts``
shared ones, untied LM head), computed from shapes: what the mathematics
requires, not what an implementation executes. Recomputation (remat) is
never counted. A multiply-add is 2 FLOPs. The routed experts cost what
OLMoE's do (three matrices an expert): ``costs/olmoe.py:moe_experts_cost``."""
from __future__ import annotations


def param_count(cfg: dict) -> dict:
    """Parameters of an afmoe of the given sizes: all of them (``total``),
    and those a token multiplies (``active``)."""
    L, E, V = cfg["num_layers"], cfg["embed_dim"], cfg["vocab_size"]
    H, KV, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    dense_layers = cfg["num_dense_layers"]
    routed_layers = L - dense_layers
    X, k = cfg["moe_num_experts"], cfg["moe_top_k"]
    attention = 3 * E * H * D + 2 * E * KV * D      # q, gate, o; k, v
    norms = 4 * E + 2 * D        # four of the stream, q_norm, k_norm
    expert = 3 * E * cfg["moe_mlp_dim"]             # gate, up, down
    shared = cfg["num_shared_experts"] * expert
    router = E * X + X                              # and expert_bias
    dense = attention + norms + 3 * E * cfg["mlp_dim"]
    routed = attention + norms + shared + router + X * expert
    routed_active = attention + norms + shared + router + k * expert
    outside = 2 * V * E + E                  # embedding, head, final norm
    return {
        "attention_matrices": L * attention,
        "dense_mlp": dense_layers * 3 * E * cfg["mlp_dim"],
        "shared_experts": routed_layers * shared,
        "experts": routed_layers * X * expert,
        "experts_active": routed_layers * k * expert,
        "router": routed_layers * router,
        "embedding": V * E,
        "head": V * E,
        "other": L * norms + E,
        "dense_layer": dense,
        "routed_layer": routed,
        "total": dense_layers * dense + routed_layers * routed + outside,
        "active": (dense_layers * dense + routed_layers * routed_active
                   + outside),
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 x the matrices
    it multiplies (attention, dense MLPs, shared experts, router, its
    ``moe_top_k`` experts, the head; an embedding row is read, not
    multiplied), plus attention's score and value products: 6·H·D·T a full
    layer for a causal model (half of 12: the masked half is work nobody
    does; as ``costs/gpt2.py``), and in a sliding layer no more than the
    window's worth of them, 12·H·D·min(T, window) less the triangle at the
    start, which this leaves in: it counts a sliding layer's token as seeing
    ``min(T / 2, window)`` keys."""
    n = param_count(cfg)
    dense = 6.0 * (n["attention_matrices"] + n["dense_mlp"]
                   + n["shared_experts"] + n["router"] + n["experts_active"]
                   + n["head"])
    width = cfg["num_heads"] * cfg["head_dim"]
    seen = [min(seq_len / 2, cfg["sliding_window"])
            if kind in ("S", "sliding_attention") else seq_len / 2
            for kind in list(cfg["layer_types"])[:cfg["num_layers"]]]
    return dense + 12.0 * width * sum(seen)
