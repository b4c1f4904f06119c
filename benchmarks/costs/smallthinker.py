"""Parameters, training FLOPs and kernel costs of PowerInfer's SmallThinker
architecture on ONE chip's share of a layer (attention with G query heads a
kv head and a head width that is not the model's, a router over
``moe_num_experts``, ``moe_num_held`` ReLU-gated experts of ``moe_mlp_dim``
held of them, ``moe_top_k`` a token, untied LM head over the vocabulary
slice), computed from shapes: what the mathematics requires of THIS chip,
not what an implementation executes. Recomputation (remat) is never
counted. A multiply-add is 2 FLOPs."""
from __future__ import annotations

# config.json's own numbers where the benchmark's cut changed them
PUBLISHED = {"num_layers": 52, "moe_num_held": 64, "vocab_size": 151936}


def _held(cfg: dict) -> int:
    held = cfg.get("moe_num_held")
    return cfg["moe_num_experts"] if held is None else held


def param_count(cfg: dict) -> dict:
    """Parameters of the sizes given: those this chip holds (``total``), and
    the model's as published, every layer, expert and row (``published``)."""
    def count(L, held, V):
        E, X = cfg["embed_dim"], cfg["moe_num_experts"]
        H, KV, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
        attention = 2 * E * H * D + 2 * E * KV * D     # q, o; k, v
        expert = 3 * E * cfg["moe_mlp_dim"]            # gate, up, down
        layer = attention + 2 * E + E * X + held * expert
        return {"attention_matrices": L * attention, "router": L * E * X,
                "experts": L * held * expert, "expert": expert,
                "embedding": V * E, "head": V * E, "layer": layer,
                "total": L * layer + 2 * V * E + E}

    here = count(cfg["num_layers"], _held(cfg), cfg["vocab_size"])
    here["published"] = count(*(PUBLISHED[key] for key in (
        "num_layers", "moe_num_held", "vocab_size")))["total"]
    return here


def keys_seen(seq_len: int, window) -> float:
    """Mean number of keys a token's query meets: itself and what is before
    it, no more than the window's worth."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2.0
    return (window * (window + 1) / 2.0
            + (seq_len - window) * window) / seq_len


def _layout(cfg: dict):
    marks = cfg.get("sliding_window_layout") or [0] * cfg["num_layers"]
    return list(marks)[:cfg["num_layers"]]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires of this chip: 6 x
    the matrices it multiplies here (attention whole, the router, its
    ``moe_top_k x held / routed`` experts a layer, the head over the slice;
    an embedding row is read, not multiplied), plus attention's score and
    value products: 12·H·D x the keys a token sees, ``(T + 1) / 2`` in a
    global layer and in a window layer the mean of ``min(t + 1, window)``."""
    n = param_count(cfg)
    experts = (cfg["moe_top_k"] * _held(cfg) / cfg["moe_num_experts"]
               * n["expert"] * cfg["num_layers"])
    dense = 6.0 * (n["attention_matrices"] + n["router"] + experts
                   + n["head"])
    seen = sum(keys_seen(seq_len, cfg["sliding_window"] if mark else None)
               for mark in _layout(cfg))
    return dense + 12.0 * cfg["num_heads"] * cfg["head_dim"] * seen


def flash_cost(batch: int, seq_len: int, heads: int, kv_heads: int,
               head_dim: int, *, window, backward: bool,
               bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of one flash-attention call over
    ``kv_heads`` kv heads shared by ``heads`` query heads, causal, within
    ``window`` where there is one. The products run over the (query, key)
    pairs the mask leaves: two forward, five backward (``lib/costs.py``).
    Bytes: q, o (and do, dq) once a query head, k, v (and dk, dv) ONCE A KV
    HEAD, the log-sum-exp row 4 B a query and head."""
    pairs = batch * heads * seq_len * keys_seen(seq_len, window)
    q = batch * seq_len * heads * head_dim * bytes_per_el
    kv = batch * seq_len * kv_heads * head_dim * bytes_per_el
    lse = batch * seq_len * heads * 4
    product = 2.0 * pairs * head_dim
    if backward:
        return {"flops": 5.0 * product, "bytes": 4.0 * q + 4.0 * kv + lse}
    return {"flops": 2.0 * product, "bytes": 2.0 * q + 2.0 * kv + lse}


def moe_train_experts_cost(rows: float, held: int, embed_dim: int,
                           mlp_dim: int, bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of one layer's grouped products over
    ``rows`` rows, forward and backward: three products forward, each
    transposed twice backward (nine in all, 2·rows·E·M each). Bytes: the
    held experts' three matrices read forward and backward and their
    gradients written, the rows in and out and the two hidden arrays,
    forward and backward."""
    matrices = 3 * held * embed_dim * mlp_dim * bytes_per_el
    activations = rows * (2 * embed_dim + 3 * mlp_dim) * bytes_per_el
    return {"flops": 18.0 * rows * embed_dim * mlp_dim,
            "bytes": 3.0 * matrices + 2.0 * activations}
