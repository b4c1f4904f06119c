"""Parameters, training FLOPs and the state layers' costs of IBM's
``granitemoehybrid`` architecture (Granite 4.0-H: Mamba-2 state layers and
grouped-query attention layers as ``layer_types`` names them, a gated MLP of
``mlp_dim`` in every layer, a tied table), computed from shapes: what the
mathematics requires, not what an implementation executes. Recomputation is
never counted. A multiply-add is 2 FLOPs."""
from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    heads, size = cfg.get("mamba_n_heads", 64), cfg.get("mamba_d_head", 64)
    state = cfg.get("mamba_d_state", 128)
    return {"heads": heads, "head_dim": size, "state": state,
            "inner": heads * size, "conv": heads * size + 2 * state,
            "taps": cfg.get("mamba_d_conv", 4)}


def _kinds(cfg: dict) -> list:
    types = cfg.get("layer_types") or ["attention"] * cfg["num_layers"]
    return list(types)[:cfg["num_layers"]]


def param_count(cfg: dict) -> dict:
    """Parameters of a model of the given sizes. The table counts once: it
    is the head too."""
    E, V, M = cfg["embed_dim"], cfg["vocab_size"], cfg["mlp_dim"]
    H, KV = cfg["num_heads"], cfg["num_kv_heads"]
    D = cfg.get("head_dim") or E // H
    s = _sizes(cfg)
    mlp = 3 * E * M                                  # [g, u] and down
    attention = 2 * E * H * D + 2 * E * KV * D
    ssm_matrices = (E * (s["inner"] + s["conv"] + s["heads"])
                    + s["inner"] * E)
    ssm_other = (s["conv"] * s["taps"] + s["conv"]   # the convolution
                 + 3 * s["heads"] + s["inner"])      # dt_bias, A_log, D, gain
    kinds = _kinds(cfg)
    states = kinds.count("mamba")
    attends = len(kinds) - states
    return {
        "attention_matrices": attends * attention,
        "ssm_matrices": states * ssm_matrices,
        "ssm_other": states * ssm_other,
        "mlp": len(kinds) * mlp,
        "embedding": V * E,
        "other": len(kinds) * 2 * E + E,             # the stream's norms
        "state_layer": ssm_matrices + ssm_other + mlp + 2 * E,
        "attention_layer": attention + mlp + 2 * E,
        "total": (attends * attention + states * (ssm_matrices + ssm_other)
                  + len(kinds) * (mlp + 2 * E) + V * E + E),
    }


def ssm_scan_cost(tokens: int, cfg: dict, bytes_per_activation: int = 2
                  ) -> dict:
    """FLOPs and least HBM bytes of ONE state layer's recurrence over
    ``tokens`` real tokens (the convolution and the projections are not in
    it). FLOPs, the recurrence as it is defined, a token and head of P
    channels and N states: the decay of the state (P N), ``dt x (x) B`` and
    its sum into the state (2 P N), ``S C`` (2 P N). Bytes: x, B and C read
    and y written once a token, dt read in float32; the state itself comes
    and goes once a call, which is left out (a lower bound)."""
    s = _sizes(cfg)
    per_head = 5.0 * s["head_dim"] * s["state"]
    return {
        "flops": tokens * s["heads"] * per_head,
        "bytes": tokens * (
            (s["conv"] + s["inner"]) * bytes_per_activation
            + 4.0 * s["heads"]),
    }


def ssm_update_cost(slot_layers: int, cfg: dict) -> dict:
    """FLOPs and least HBM bytes of ``slot_layers`` one-token steps (slots
    that decode x state layers): each reads its float32 state and writes it
    back, and reads and writes the convolution's ``taps - 1`` bf16 rows.
    Memory-bound by two orders of magnitude (5 FLOPs over 8 bytes a state
    element)."""
    s = _sizes(cfg)
    state = s["heads"] * s["head_dim"] * s["state"]
    tail = s["conv"] * (s["taps"] - 1)
    return {
        "flops": slot_layers * 5.0 * state,
        "bytes": slot_layers * 2.0 * (state * 4 + tail * 2),
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 x the matrices
    it multiplies (attention, the state layers' two projections, the MLPs,
    the table as the head; an embedding row is read, not multiplied), plus
    attention's score and value products, 6·H·D·T an attention layer for a
    causal model (``costs/gpt2.py``), plus three times a state layer's
    recurrence (``ssm_scan_cost``: forward, and twice that backward)."""
    n = param_count(cfg)
    kinds = _kinds(cfg)
    D = cfg.get("head_dim") or cfg["embed_dim"] // cfg["num_heads"]
    dense = 6.0 * (n["attention_matrices"] + n["ssm_matrices"] + n["mlp"]
                   + n["embedding"])
    attention = 6.0 * cfg["num_heads"] * D * seq_len * kinds.count("attention")
    recurrence = 3.0 * ssm_scan_cost(1, cfg)["flops"] * kinds.count("mamba")
    return dense + attention + recurrence
