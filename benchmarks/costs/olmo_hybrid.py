"""Parameters, training FLOPs and the state layers' costs of Ai2's
``olmo_hybrid`` architecture (Olmo-Hybrid-7B: gated delta-rule state layers
and full-attention layers as ``layer_types`` names them, a gated MLP of
``mlp_dim`` in every layer, a table and a head of its own), computed from
shapes: what the mathematics requires, not what an implementation executes.
Recomputation is never counted. A multiply-add is 2 FLOPs."""
from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    heads = cfg.get("linear_num_key_heads", 30)
    dk = cfg.get("linear_key_head_dim", 96)
    dv = cfg.get("linear_value_head_dim", 192)
    return {"heads": heads, "dk": dk, "dv": dv, "inner": heads * dv,
            "conv": 2 * heads * dk + heads * dv,
            "taps": cfg.get("linear_conv_kernel_dim", 4)}


def _kinds(cfg: dict) -> list:
    types = cfg.get("layer_types") or ["full_attention"] * cfg["num_layers"]
    return list(types)[:cfg["num_layers"]]


def param_count(cfg: dict) -> dict:
    """Parameters of a model of the given sizes. The table and the head are
    two matrices (untied)."""
    E, V, M = cfg["embed_dim"], cfg["vocab_size"], cfg["mlp_dim"]
    H = cfg["num_heads"]
    KV = cfg.get("num_kv_heads") or H
    D = cfg.get("head_dim") or E // H
    s = _sizes(cfg)
    mlp = 3 * E * M                                  # gate, up and down
    attention = 2 * E * H * D + 2 * E * KV * D
    attention_other = H * D + KV * D                 # the q and k norms
    # q, k, v and the gate in one matrix, beta and the decay in another;
    # the output
    delta_matrices = (E * (s["conv"] + s["inner"] + 2 * s["heads"])
                      + s["inner"] * E)
    delta_other = (s["conv"] * s["taps"]             # the convolution
                   + 2 * s["heads"] + s["dv"])       # dt_bias, A_log, gain
    kinds = _kinds(cfg)
    states = kinds.count("linear_attention")
    attends = len(kinds) - states
    return {
        "attention_matrices": attends * attention,
        "delta_matrices": states * delta_matrices,
        "delta_other": states * delta_other,
        "mlp": len(kinds) * mlp,
        "embedding": V * E,
        "head": V * E,
        "other": (len(kinds) * 2 * E + attends * attention_other
                  + E),                              # the branches' norms
        "state_layer": delta_matrices + delta_other + mlp + 2 * E,
        "attention_layer": attention + attention_other + mlp + 2 * E,
        "total": (attends * (attention + attention_other)
                  + states * (delta_matrices + delta_other)
                  + len(kinds) * (mlp + 2 * E) + 2 * V * E + E),
    }


def delta_scan_cost(tokens: int, cfg: dict, bytes_per_activation: int = 2,
                    sequences: int = 1) -> dict:
    """FLOPs and least HBM bytes of ONE state layer's recurrence over
    ``tokens`` real tokens of ``sequences`` sequences (the convolution and
    the projections are not in it). FLOPs, the recurrence as it is defined,
    a token and head of a ``Dk x Dv`` state: its decay (Dk Dv), ``S^T k``
    (2 Dk Dv), the write ``k u^T`` into it (2 Dk Dv), ``S^T q`` (2 Dk Dv):
    7 Dk Dv. Bytes: q, k and v read and o written once a token, the two
    gates read in float32; the float32 state read and written once a
    sequence."""
    s = _sizes(cfg)
    state = s["heads"] * s["dk"] * s["dv"]
    return {
        "flops": tokens * 7.0 * state,
        "bytes": (tokens * ((s["conv"] + s["inner"]) * bytes_per_activation
                            + 2 * 4.0 * s["heads"])
                  + sequences * 2.0 * 4 * state),
    }


def delta_update_cost(slot_layers: int, cfg: dict,
                      bytes_per_activation: int = 2) -> dict:
    """FLOPs and least HBM bytes of ``slot_layers`` one-token steps (slots
    that decode x state layers): each reads its float32 state and writes it
    back, reads and writes the convolution's ``taps - 1`` rows, and reads
    its q, k, v and gates and writes its output. Memory-bound by two orders
    of magnitude (7 FLOPs over 8 bytes a state element)."""
    s = _sizes(cfg)
    state = s["heads"] * s["dk"] * s["dv"]
    tail = s["conv"] * (s["taps"] - 1)
    return {
        "flops": slot_layers * 7.0 * state,
        "bytes": slot_layers * (
            2.0 * (state * 4 + tail * bytes_per_activation)
            + (s["conv"] + s["inner"]) * bytes_per_activation
            + 2 * 4.0 * s["heads"]),
    }


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 x the matrices
    it multiplies (attention, the state layers' two projections, the MLPs,
    the head; an embedding row is read, not multiplied), plus attention's
    score and value products, 6·H·D·T an attention layer for a causal model
    (``costs/gpt2.py``), plus three times a state layer's recurrence
    (``delta_scan_cost``: forward, and twice that backward)."""
    n = param_count(cfg)
    kinds = _kinds(cfg)
    D = cfg.get("head_dim") or cfg["embed_dim"] // cfg["num_heads"]
    dense = 6.0 * (n["attention_matrices"] + n["delta_matrices"] + n["mlp"]
                   + n["head"])
    attention = (6.0 * cfg["num_heads"] * D * seq_len
                 * kinds.count("full_attention"))
    recurrence = (3.0 * delta_scan_cost(1, cfg)["flops"]
                  * kinds.count("linear_attention"))
    return dense + attention + recurrence
