"""Parameters, training FLOPs and the flash calls' costs of the
``joyai_llm_flash`` architecture (JoyAI-LLM-Flash; DeepSeek-V3's block) on
ONE chip's share of a layer: latent attention with a query rank in every
layer (keys ``qk_nope_head_dim + qk_rope_head_dim`` wide, values
``v_head_dim``), ``first_k_dense`` dense MLPs, then routed layers of which
this chip holds ``moe_num_held`` experts beside a shared one and the whole
router, ``num_mtp_layers`` prediction layers behind the trunk (a routed
layer, ``eh_proj`` and three norms each; the head a second time), a table
and a head over the vocabulary slice. Computed from shapes: what the
mathematics requires of THIS chip, not what an implementation executes.
Recomputation (remat) is never counted. A multiply-add is 2 FLOPs."""
from __future__ import annotations

# config.json's own numbers where the benchmark's cut changed them
PUBLISHED = {"num_layers": 40, "moe_num_held": 256, "vocab_size": 129280}


def _held(cfg: dict) -> int:
    held = cfg.get("moe_num_held")
    return cfg["moe_num_experts"] if held is None else held


def _head_widths(cfg: dict) -> tuple:
    """(keys' and queries' width, values' width) of a head."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def param_count(cfg: dict) -> dict:
    """Parameters of the sizes given: those this chip holds (``total``),
    those of them that are matrices a token multiplies (``multiplied``: its
    ``moe_top_k x held / routed`` experts a routed layer, the head once a
    loss), and the model's as published, every layer, expert and row
    (``published``)."""
    def count(L, held, V):
        E, X, H = cfg["embed_dim"], cfg["moe_num_experts"], cfg["num_heads"]
        Rq, R = cfg["q_lora_rank"], cfg["kv_lora_rank"]
        Dn, Dr, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        mtp = cfg.get("num_mtp_layers", 0)
        dense = min(cfg.get("first_k_dense", 1), L)
        routed = L - dense + mtp            # a prediction layer is routed
        mixer = (E * Rq + Rq * H * (Dn + Dr) + E * (R + Dr)
                 + R * H * (Dn + Dv) + H * Dv * E)
        gains = 2 * E + Rq + R              # a layer's four norms
        expert = 3 * E * cfg["moe_mlp_dim"]
        shared = cfg.get("num_shared_experts", 1) * expert
        router = E * X
        active = cfg["moe_top_k"] * held / X * expert
        multiplied = ((L + mtp) * mixer + dense * 3 * E * cfg["mlp_dim"]
                      + routed * (router + shared + active)
                      + mtp * 2 * E * E + (1 + mtp) * V * E)
        total = ((L + mtp) * (mixer + gains) + dense * 3 * E * cfg["mlp_dim"]
                 + routed * (router + X + shared + held * expert)
                 + mtp * (2 * E * E + 3 * E) + 2 * V * E + E)
        return {"mixer": mixer, "expert": expert, "router": router,
                "routed_layers": routed, "experts": routed * held * expert,
                "embedding": V * E, "head": V * E,
                "multiplied": multiplied, "total": total}

    here = count(cfg["num_layers"], _held(cfg), cfg["vocab_size"])
    here["published"] = count(*(PUBLISHED[key] for key in (
        "num_layers", "moe_num_held", "vocab_size")))["total"]
    return here


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires of this chip: 6 x
    the matrices it multiplies here (``param_count``'s ``multiplied``: the
    mixers whole, the held pairs alone, the prediction layer, the head once
    a loss; an embedding row is read, not multiplied), plus attention's
    score and value products in every mixer: 2 x (keys' + values' width) a
    (query, key) pair forward, three times that with the backward pass, over
    the ``(T + 1) / 2`` keys a causal query sees."""
    dk, dv = _head_widths(cfg)
    mixers = cfg["num_layers"] + cfg.get("num_mtp_layers", 0)
    seen = (seq_len + 1) / 2.0
    return (6.0 * param_count(cfg)["multiplied"]
            + 6.0 * (dk + dv) * cfg["num_heads"] * seen * mixers)


def flash_mla_cost(batch: int, seq_len: int, heads: int, *, backward: bool,
                   qk_dim: int = 192, v_dim: int = 128,
                   bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of one causal flash-attention call over
    ``heads`` heads whose keys (and queries) are ``qk_dim`` wide and whose
    values ``v_dim``. The products run over the (query, key) pairs the mask
    leaves, ``T (T + 1) / 2`` a head: forward the scores (``qk_dim``) and
    the values' sum (``v_dim``); backward the scores again, dP and dV
    (``v_dim`` each), dQ and dK (``qk_dim`` each): five products. Bytes:
    q, k, v read and o written once forward (the log-sum-exp row 4 B a query
    and head); backward q, k, v, o, do and the row read, dq, dk, dv written.
    The keys are counted a head, as the call is given them (the shared
    rotated part repeated into each head's last ``qk_rope_head_dim``)."""
    pairs = batch * heads * seq_len * (seq_len + 1) / 2.0
    wide = batch * seq_len * heads * qk_dim * bytes_per_el     # q, k, dq, dk
    narrow = batch * seq_len * heads * v_dim * bytes_per_el    # v, o, do, dv
    lse = batch * seq_len * heads * 4
    if backward:
        return {"flops": 2.0 * pairs * (3 * qk_dim + 2 * v_dim),
                "bytes": 4.0 * wide + 4.0 * narrow + lse}
    return {"flops": 2.0 * pairs * (qk_dim + v_dim),
            "bytes": 2.0 * wide + 2.0 * narrow + lse}
