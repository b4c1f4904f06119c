"""Parameters and the sparse attention's costs of the ``deepseek_v32``
architecture (DeepSeek-V3.2-Exp: latent attention with a query rank and a
lightning indexer in every layer, ``first_k_dense`` dense MLPs, then routed
experts of which this chip holds ``moe_num_held`` beside a shared one, a
table and a head of its own), computed from shapes: what the mathematics
requires, not what an implementation executes. A multiply-add is 2 FLOPs.
The routed experts cost what OLMoE's do (three matrices an expert:
``costs/olmoe.py:moe_experts_cost``), the dense read of a latent cache what
Ling's does (``costs/bailing_hybrid.py:latent_decode_cost``).

The three steps of the sparse attention are functions of the program's two
counters: ``index_positions`` (positions the indexer scored: every query's
visible positions, summed over queries and latent layers) and
``selected_positions`` (positions attention was then asked to read:
``min(index_topk, visible)`` a query and layer). They count the work the
ARCHITECTURE asks for: the selected read counts the CHOSEN rows, so a form
that reads every visible row and masks shows as a low share of its roofline
and never as one above 100%."""
from __future__ import annotations


def _sizes(cfg: dict) -> dict:
    return {
        "H": cfg.get("num_heads", 128),
        "R": cfg.get("kv_lora_rank", 512),
        "Dn": cfg.get("qk_nope_head_dim", 128),
        "Dr": cfg.get("qk_rope_head_dim", 64),
        "Dv": cfg.get("v_head_dim", 128),
        "Hi": cfg.get("index_n_heads", 64),
        "Di": cfg.get("index_head_dim", 128),
    }


def param_count(cfg: dict) -> dict:
    """Parameters HELD of a model of the given sizes (the experts: the held
    ones), and those a token multiplies here under a balanced router
    (``experts_active``: its ``moe_top_k`` experts' held share)."""
    E, V = cfg["embed_dim"], cfg["vocab_size"]
    s = _sizes(cfg)
    H, R, Dn, Dr, Dv, Hi, Di = (s[k] for k in (
        "H", "R", "Dn", "Dr", "Dv", "Hi", "Di"))
    Rq = cfg.get("q_lora_rank", 1536)
    X = cfg.get("moe_num_experts", 0)
    held = cfg.get("moe_num_held") or X
    k = cfg.get("moe_top_k", 0)
    L = cfg["num_layers"]
    n_dense = min(cfg.get("first_k_dense", 3), L) if X else L
    n_routed = L - n_dense
    latent = (E * Rq + Rq * H * (Dn + Dr) + E * (R + Dr)
              + R * H * (Dn + Dv) + H * Dv * E + Rq + R)
    indexer = Rq * Hi * Di + E * Di + E * Hi + 2 * Di
    expert = 3 * E * cfg.get("moe_mlp_dim", 2048)
    shared = cfg.get("num_shared_experts", 1) * expert
    router = E * X + X                      # and expert_bias
    mlp = 3 * E * cfg["mlp_dim"]
    return {
        "latent_mixer": latent, "indexer": indexer,
        "dense_mlp": n_dense * mlp,
        "shared_experts": n_routed * shared,
        "experts": n_routed * held * expert,
        "experts_active": n_routed * k * expert * (held / X if X else 0),
        "router": n_routed * router,
        "embedding": V * E, "head": V * E,
        "routed_ffn": shared + router + held * expert,
        "total": (L * (latent + indexer + 2 * E) + n_dense * mlp
                  + n_routed * (shared + router + held * expert)
                  + 2 * V * E + E),
    }


def index_scores_cost(index_positions: int, cfg: dict, shared_by: int = 1,
                      bytes_per_value: int = 2) -> dict:
    """The lightning indexer's scores over ``index_positions`` (query,
    position) pairs: ``Hi`` heads' products of ``Di`` channels, a ReLU and a
    weighted sum a pair (2 Hi Di + 2 Hi FLOPs). Bytes: each position's key
    of ``Di`` values read once by the ``shared_by`` queries that can share
    it (1: a decode step's one query a slot; a chunk's tokens share)."""
    s = _sizes(cfg)
    return {
        "flops": index_positions * (2.0 * s["Hi"] * s["Di"] + 2 * s["Hi"]),
        "bytes": index_positions / shared_by * s["Di"] * float(
            bytes_per_value)}


def selection_cost(index_positions: int, selected_positions: int) -> dict:
    """The exact choice of the largest scores: every float32 score read
    once and compared once at the least (4 B, 1 FLOP a scored position), the
    choice written as a position's index (4 B a chosen one)."""
    return {"flops": float(index_positions),
            "bytes": 4.0 * index_positions + 4.0 * selected_positions}


def selected_decode_cost(selected_positions: int, slot_layers: int,
                         cfg: dict, bytes_per_value: int = 2) -> dict:
    """A decode step's attention over the chosen rows alone, the
    up-projection absorbed: each chosen row of ``R + Dr`` values read once
    (1,152 B at the published size), a tile of 128 positions written a slot
    and layer; every head scores the row (2 (R + Dr)) and sums its first R
    (2 R)."""
    s = _sizes(cfg)
    row = (s["R"] + s["Dr"]) * bytes_per_value
    return {
        "flops": selected_positions * s["H"] * 2.0 * (2 * s["R"] + s["Dr"]),
        "bytes": selected_positions * float(row) + slot_layers * 128.0 * row}


def selected_prefill_cost(selected_positions: int, cfg: dict,
                          shared_by: int = 1,
                          bytes_per_value: int = 2) -> dict:
    """A chunk's attention over each query's chosen rows: the cheaper of the
    two exact products a (query, chosen position) pair, a head's keys and
    values up-projected (2 H (Dn + Dr + Dv): 82 kFLOP against the absorbed
    279 kFLOP), the up-projection itself left out (a chunk's queries share a
    row's): a lower bound. Bytes: a chosen row read once by the
    ``shared_by`` queries that can share it."""
    s = _sizes(cfg)
    return {
        "flops": selected_positions * s["H"] * 2.0 * (
            s["Dn"] + s["Dr"] + s["Dv"]),
        "bytes": selected_positions / shared_by * float(
            (s["R"] + s["Dr"]) * bytes_per_value)}


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires of THIS chip: 6 x
    the matrices it multiplies (the mixers and their indexers, the dense
    MLPs, the shared experts, the router, its held share of ``moe_top_k``
    experts, the head), plus a layer's index scores over the T / 2 positions
    a token sees in the mean and its attention's two products over the
    ``index_topk`` of them it keeps (or all), three times each. No cell
    trains this configuration (the file's ``deployment`` says why); the
    file's ``train`` block is what such a cell would state."""
    n = param_count(cfg)
    s = _sizes(cfg)
    L = cfg["num_layers"]
    seen = seq_len / 2.0
    kept = min(seen, cfg.get("index_topk", 2048))
    dense = 6.0 * (L * (n["latent_mixer"] + n["indexer"]) + n["dense_mlp"]
                   + n["shared_experts"] + n["router"] + n["experts_active"]
                   + n["head"])
    index = 3.0 * L * seen * (2.0 * s["Hi"] * s["Di"] + 2 * s["Hi"])
    attention = 3.0 * L * kept * 2.0 * s["H"] * (
        s["Dn"] + s["Dr"] + s["Dv"])
    return dense + index + attention
