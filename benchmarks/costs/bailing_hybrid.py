"""Parameters, training FLOPs and the mixers' costs of inclusionAI's
``bailing_hybrid`` architecture (Ling-3.0-flash: Kimi Delta Attention state
layers and a multi-head latent attention layer every ``layer_group_size``,
``first_k_dense`` dense MLPs, then routed experts of which this chip holds
``moe_num_held`` beside a shared one, a table and a head of its own),
computed from shapes: what the mathematics requires, not what an
implementation executes. Recomputation is never counted. A multiply-add is 2
FLOPs. The routed experts cost what OLMoE's do (three matrices an expert):
``costs/olmoe.py:moe_experts_cost``."""
from __future__ import annotations


def _kinds(cfg: dict) -> list:
    """"kda" | "latent" a layer, first to last."""
    period = cfg.get("layer_group_size", 6)
    return ["latent" if (i + 1) % period == 0 else "kda"
            for i in range(cfg["num_layers"])]


def param_count(cfg: dict) -> dict:
    """Parameters HELD of a model of the given sizes (the experts: the
    held ones), and those a token multiplies here under a balanced router
    (``active``: its ``moe_top_k`` experts' held share)."""
    E, V, H = cfg["embed_dim"], cfg["vocab_size"], cfg["num_heads"]
    D = cfg.get("head_dim", 128)
    R, Dn, Dr, Dv = (cfg.get("kv_lora_rank", 512),
                     cfg.get("qk_nope_head_dim", 128),
                     cfg.get("qk_rope_head_dim", 64),
                     cfg.get("v_head_dim", 128))
    taps = cfg.get("short_conv_kernel_size", 4)
    X = cfg.get("moe_num_experts", 0)
    held = cfg.get("moe_num_held") or X
    k = cfg.get("moe_top_k", 0)
    dense_layers = cfg.get("first_k_dense", 2) if X else cfg["num_layers"]
    kinds = _kinds(cfg)
    # q, k, v in; the decay's projection; beta and the head gate; out
    kda_matrices = E * 3 * H * D + E * H * D + E * 2 * H + H * D * E
    kda_other = (3 * H * D * taps          # the convolution
                 + H * D + H + D)          # dt_bias, A_log, the norm's gain
    latent_matrices = (E * H * (Dn + Dr) + E * (R + Dr) + R * H * (Dn + Dv)
                       + E * H + H * Dv * E)
    latent_other = R                        # the latent's norm
    expert = 3 * E * cfg.get("moe_mlp_dim", 768)
    shared = cfg.get("num_shared_experts", 1) * expert
    router = E * X + X                      # and expert_bias
    mlp = 3 * E * cfg["mlp_dim"]
    n_dense = min(dense_layers, len(kinds))
    n_routed = len(kinds) - n_dense
    mixers = (kinds.count("kda") * (kda_matrices + kda_other)
              + kinds.count("latent") * (latent_matrices + latent_other))
    return {
        "kda_matrices": kinds.count("kda") * kda_matrices,
        "latent_matrices": kinds.count("latent") * latent_matrices,
        "dense_mlp": n_dense * mlp,
        "shared_experts": n_routed * shared,
        "experts": n_routed * held * expert,
        "experts_active": n_routed * k * expert * (held / X if X else 0),
        "router": n_routed * router,
        "embedding": V * E,
        "head": V * E,
        "other": (kinds.count("kda") * kda_other
                  + kinds.count("latent") * latent_other
                  + len(kinds) * 2 * E + E),
        "kda_mixer": kda_matrices + kda_other,
        "latent_mixer": latent_matrices + latent_other,
        "total": (mixers + len(kinds) * 2 * E + n_dense * mlp
                  + n_routed * (shared + router + held * expert)
                  + 2 * V * E + E),
    }


def kda_scan_cost(tokens: int, cfg: dict, bytes_per_activation: int = 2,
                  sequences: int = 1) -> dict:
    """FLOPs and least HBM bytes of ONE KDA layer's recurrence over
    ``tokens`` real tokens of ``sequences`` sequences (the convolution and
    the projections are not in it). FLOPs, the recurrence as it is defined,
    a token and head of a ``Dk x Dv`` state: its decay (Dk Dv), ``S^T k``
    (2 Dk Dv), the write ``k u^T`` (2 Dk Dv), ``S^T q`` (2 Dk Dv): 7 Dk Dv.
    Bytes: q, k and v read and o written once a token, the decay [H, Dk]
    and beta [H] read in float32; the float32 state read and written once a
    sequence."""
    H, D = cfg["num_heads"], cfg.get("head_dim", 128)
    state = H * D * D
    return {
        "flops": tokens * 7.0 * state,
        "bytes": (tokens * (4 * H * D * bytes_per_activation
                            + 4.0 * (H * D + H))
                  + sequences * 2.0 * 4 * state),
    }


def kda_update_cost(slot_layers: int, cfg: dict) -> dict:
    """FLOPs and least HBM bytes of ``slot_layers`` one-token steps (slots
    that decode x state layers) of the recurrence itself: each reads its
    float32 state and writes it back (2 x 32 x 128 x 128 x 4 B at the
    published size), reads its step's operands in float32 (the decay a
    channel, the key twice over as the step folds its factors, the query:
    [H, Dk] each; the value [H, Dv]) and writes its output [H, Dv].
    The convolution's rows move outside the recurrence and are not in it.
    Memory-bound by two orders of magnitude."""
    H, D = cfg["num_heads"], cfg.get("head_dim", 128)
    state = H * D * D
    return {
        "flops": slot_layers * 7.0 * state,
        "bytes": slot_layers * 4.0 * (2 * state + 6 * H * D),
    }


def latent_decode_cost(positions: int, slot_layers: int, cfg: dict,
                       bytes_per_value: int = 2) -> dict:
    """FLOPs and least HBM bytes of the latent layers' decode attention over
    ``positions`` filled positions (summed over slots and latent layers) of
    ``slot_layers`` visits: each position's row of ``R + Dr`` values is
    read ONCE (it is the key and the value), each visit writes one tile of
    128 positions back, and every head scores the row (2 (R + Dr)) and sums
    its first R (2 R) with the up-projection absorbed."""
    H = cfg["num_heads"]
    R, Dr = cfg.get("kv_lora_rank", 512), cfg.get("qk_rope_head_dim", 64)
    row = (R + Dr) * bytes_per_value
    return {
        "flops": positions * H * 2.0 * (2 * R + Dr),
        "bytes": positions * float(row) + slot_layers * 128.0 * row,
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires of THIS chip: 6 x
    the matrices it multiplies (the mixers, the dense MLPs, the shared
    experts, the router, its held share of ``moe_top_k`` experts, the head),
    plus the latent layers' score and value products for a causal model,
    6 H (Dn + Dr + Dv) T / 2 a layer, plus three times a KDA layer's
    recurrence (``kda_scan_cost``)."""
    n = param_count(cfg)
    kinds = _kinds(cfg)
    H = cfg["num_heads"]
    width = (cfg.get("qk_nope_head_dim", 128) + cfg.get("qk_rope_head_dim", 64)
             + cfg.get("v_head_dim", 128))
    dense = 6.0 * (n["kda_matrices"] + n["latent_matrices"] + n["dense_mlp"]
                   + n["shared_experts"] + n["router"] + n["experts_active"]
                   + n["head"])
    attention = 3.0 * H * width * seq_len * kinds.count("latent")
    recurrence = 3.0 * kda_scan_cost(1, cfg)["flops"] * kinds.count("kda")
    return dense + attention + recurrence
