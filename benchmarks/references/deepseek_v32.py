"""Plain float32 reference of the ``deepseek_v32`` architecture
(DeepSeek-V3.2-Exp, https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp:
``config.json`` and the repository's ``inference/model.py``): token
embedding; decoder layers ``h += mix(RMSNorm(h))``, ``h += ffn(RMSNorm(h))``;
a final RMSNorm and a head of its own (untied). No bias but the indexer's
LayerNorm.

``mix``, every layer, x = RMSNorm(h): ``cq = RMSNorm(x Wdq)``; ``q = cq Wuq``
a head ``[Dn | Dr]``; ``[c | kr] = x Wdkv``, ``c = RMSNorm(c)``; ``[k_nope |
v] = c Wukv`` a head ``[Dn | Dv]``; INTERLEAVED RoPE (pairs (0, 1), (2, 3),
..) on q's last ``Dr`` and on ``kr``, which every head shares, at
``ROPE_THETA`` under YaRN's frequencies (``_frequencies``). The lightning
indexer: ``qI = cq WqI`` a head of ``Di``; ``kI = LayerNorm(x WkI)`` (gain
and bias), one key a position; HALF-SPLIT RoPE (pairs (i, i + Dr / 2)) on
the first ``Dr`` channels of both, the same frequencies; ``w = (x Ww) x
Hi^-0.5 x Di^-0.5``; ``I[t, s] = sum_h w[t, h] x ReLU(qI[t, h] . kI[s])``.
``S_t`` = the positions of the ``min(INDEX_TOPK, t + 1)`` largest ``I[t,
s]``, s <= t, by a SORT (stable: a tie goes to the lower position), held as a
[T, T] mask. ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}((q_nope . k_nope
+ q_rope . kr) x scale) v``, ``scale = (Dn + Dr)^-0.5 x m^2``, ``m = 0.1 x
MSCALE_ALL_DIM x ln ROPE_FACTOR + 1``; the output projection.

``ffn``: ``(silu(x Wg) * x Wu) Wd`` in a dense layer. In a routed one: ``s =
sigmoid(x Wr)`` over ALL experts; by ``s + bias`` the experts are grouped
(``N_GROUP``), a group scored by the sum of its two largest, the best
``TOPK_GROUP`` groups stay, and the ``TOP_K`` largest of what stays are
chosen; their gates are ``s`` WITHOUT the bias over their sum times
``ROUTE_SCALE``; the held experts' (``FIRST_HELD`` on, as many as the weights
hold) gated SwiGLUs are summed BY A LOOP over them, plus the shared expert.
What the experts held elsewhere would add is left out: the weights are one
chip's share, and so is the result.

Departures from the published code, both in the indexer: it multiplies
``qI`` and ``kI`` by one Hadamard matrix (orthogonal: ``qI . kI`` is the same
without it) and quantises both to FP8 before their product. Neither is done
here: the deployment this reference is held against serves in bfloat16 and
caches the indexer's keys in bfloat16. The multi-token-prediction layer is
not part of the served forward.

Straightforward ``jax.numpy``: no chunk of a prompt, no cache, no absorbed
product, no kernel, no mixed precision. Every matrix product runs in float32
at ``jax.default_matmul_precision("highest")``, which the caller sets. It is
BLOCKED so that 24,000 and, once, 33,279 positions fit one chip beside 9.27
GB of weights: the sequence is padded at its end to whole blocks of
``BLOCK`` (causal: padding reaches no token); rows go through the MLPs, the
experts and the head a block at a time; the index scores, the sort and the
attention a block of queries at a time, one head after another, the chosen
set kept whole as a [T, T] mask of booleans; a weight is widened to float32
where it is used. What the weights do not carry is stated here as constants,
which a test at another size patches; every size comes from the weights'
shapes.

The weights are DATA: the program's own parameter pytree
(``ray_tpu.models.deepseek_v32.init_params`` under the same key): ``wte`` /
``lm_head`` [V, E], ``norm_f`` [E], ``blocks.segments[s][j]`` the j-th layer
of segment s's period, leaves [repeats, ...]: ``mix_norm`` / ``mlp_norm``
[E], ``w_dq`` [E, Rq], ``q_norm`` [Rq], ``w_uq`` [Rq, H, Dn + Dr], ``w_dkv``
[E, R + Dr], ``kv_norm`` [R], ``w_ukv`` [R, H, Dn + Dv], ``wo`` [H, Dv, E],
``w_iq`` [Rq, Hi, Di], ``w_ik`` [E, Di], ``ik_norm`` / ``ik_bias`` [Di],
``w_iw`` [E, Hi]; a dense layer's ``w_gate`` / ``w_up`` / ``w_down``, a
routed layer's ``shared_gate`` / ``shared_up`` / ``shared_down``;
``blocks.experts`` every routed layer's ``router_w`` [L, E, X],
``expert_bias`` [L, X], ``expert_fc`` / ``expert_gate`` [L, held, E, M],
``expert_out`` [L, held, M, E].
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6          # rms_norm_eps
INDEX_NORM_EPS = 1e-6   # the indexer's LayerNorm
ROPE_THETA = 10000.0    # rope_theta
ROPE_FACTOR = 40.0      # rope_scaling.factor
ROPE_ORIGINAL = 4096    # rope_scaling.original_max_position_embeddings
BETA_FAST, BETA_SLOW = 32.0, 1.0
MSCALE_ALL_DIM = 1.0    # rope_scaling.mscale_all_dim (mscale: the same)
INDEX_TOPK = 2048       # index_topk
TOP_K = 8               # num_experts_per_tok
N_GROUP, TOPK_GROUP = 8, 4
ROUTE_SCALE = 2.5       # routed_scaling_factor; norm_topk_prob is true
FIRST_HELD = 0          # the first expert this chip holds
BLOCK = 512             # rows, and queries, at a time


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, gain):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) * gain


def _layer_norm(x, gain, bias):
    centred = x - x.mean(-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        (centred * centred).mean(-1, keepdims=True) + INDEX_NORM_EPS
    ) * gain + bias


def _swiglu(x, w_gate, w_up, w_down):
    w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _in_blocks(fn, *rows):
    """``fn`` over [T, ..] arrays ``BLOCK`` rows at a time, T a multiple."""
    cut = tuple(a.reshape(-1, BLOCK, *a.shape[1:]) for a in rows)
    out = jax.lax.map(lambda blocks: fn(*blocks), cut)
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), out)


def _frequencies(d: int):
    """YaRN's d / 2 frequencies: ``f_i = theta^(-2i/d)``, kept where a pair
    turns more than ``BETA_FAST`` times over the original context, divided
    by the factor where fewer than ``BETA_SLOW``, a linear ramp between."""
    f = ROPE_THETA ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def pair(turns):
        return d * math.log(ROPE_ORIGINAL / (turns * 2 * math.pi)) / (
            2 * math.log(ROPE_THETA))

    low = max(math.floor(pair(BETA_FAST)), 0)
    high = min(math.ceil(pair(BETA_SLOW)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return f / ROPE_FACTOR * ramp + f * (1.0 - ramp)


def _angles(T: int, d: int):
    return jnp.arange(T, dtype=jnp.float32)[:, None] * _frequencies(d)


def _rope_pairs(x):
    """x [T, .., d]: the pair (2i, 2i + 1) turned by its position."""
    T, d = x.shape[0], x.shape[-1]
    angle = _angles(T, d).reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                      a * jnp.sin(angle) + b * jnp.cos(angle)],
                     -1).reshape(x.shape)


def _rope_halves(x, at):
    """x [N, .., d], row n at position ``at[n]``: the pair (i, i + d / 2)
    turned by its position."""
    d = x.shape[-1]
    angle = (at.astype(jnp.float32)[:, None] * _frequencies(d)).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def softmax_scale(width: int) -> float:
    m = 0.1 * MSCALE_ALL_DIM * math.log(ROPE_FACTOR) + 1.0
    return width ** -0.5 * m * m


def chosen_mask(cq, x, p, dr: int):
    """The [T, T] mask of the positions each query attends: its
    ``min(INDEX_TOPK, t + 1)`` highest index scores among s <= t. ``cq``
    [T, Rq] the normed query rank, x [T, E] the normed stream."""
    T = x.shape[0]
    hi, di = p["w_iq"].shape[1:]
    k = _layer_norm(x @ p["w_ik"], p["ik_norm"], p["ik_bias"])     # [T, Di]
    at = jnp.arange(T)
    k = jnp.concatenate([_rope_halves(k[:, :dr], at), k[:, dr:]], -1)
    w = (x @ p["w_iw"]) * (hi ** -0.5 * di ** -0.5)                # [T, Hi]

    def queries(cq, w, at):
        q = (cq @ p["w_iq"].reshape(cq.shape[-1], -1)).reshape(-1, hi, di)
        q = jnp.concatenate([_rope_halves(q[..., :dr], at), q[..., dr:]], -1)

        def head(total, qw):
            q_h, w_h = qw                                  # [BLOCK, Di], [BLOCK]
            return total + w_h[:, None] * jax.nn.relu(q_h @ k.T), None

        score, _ = jax.lax.scan(
            head, jnp.zeros((q.shape[0], T), jnp.float32),
            (jnp.moveaxis(q, 1, 0), w.T))
        score = jnp.where(at[:, None] >= jnp.arange(T)[None, :], score,
                          -jnp.inf)
        # a position's rank among the row's scores, the largest first, a
        # tie to the lower position (the sort is stable)
        order = jnp.argsort(-score, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1)
        return rank < jnp.minimum(INDEX_TOPK, at + 1)[:, None]

    return _in_blocks(queries, cq, w, at)


def _mix(x, p, at):
    """Latent attention over the indexer's choice, x [T, E] (normed) ->
    [T, E]: one head after another, a block of queries at a time. ``p``
    holds the segment's leaves [repeats, ..] and ``at`` this layer's place
    in them: a head's matrices are read where they lie (a layer's slice of
    ``wo`` is a copy of 0.47 GB in float32)."""
    T, E = x.shape
    rq, H, dq = p["w_uq"].shape[1:]
    rank, _, dkv = p["w_ukv"].shape[1:]
    dv = p["wo"].shape[2]
    dn = dkv - dv
    dr = dq - dn
    own = _f32({n: p[n][at] for n in (
        "w_dq", "q_norm", "w_dkv", "kv_norm", "w_iq", "w_ik", "ik_norm",
        "ik_bias", "w_iw")})
    cq = _rms_norm(x @ own["w_dq"], own["q_norm"])                 # [T, Rq]
    c, kr = jnp.split(x @ own["w_dkv"], [rank], -1)
    c, kr = _rms_norm(c, own["kv_norm"]), _rope_pairs(kr)
    mask = chosen_mask(cq, x, own, dr)
    scale = softmax_scale(dq)

    def head(out, h):
        w_uq, w_ukv, wo = _f32((p["w_uq"][at, :, h], p["w_ukv"][at, :, h],
                                p["wo"][at, h]))   # [Rq, dq] [R, dkv] [dv, E]
        q = cq @ w_uq
        q = jnp.concatenate([q[:, :dn], _rope_pairs(q[:, dn:])], -1)
        kv = c @ w_ukv
        keys = jnp.concatenate([kv[:, :dn], kr], -1)               # [T, dq]

        def queries(q, mask):
            att = jnp.where(mask, (q @ keys.T) * scale, -jnp.inf)
            return jax.nn.softmax(att, -1) @ kv[:, dn:]

        return out + _in_blocks(queries, q, mask) @ wo, None

    out, _ = jax.lax.scan(head, jnp.zeros((T, E), jnp.float32), jnp.arange(H))
    return out


def route(x, router_w, expert_bias):
    """x [N, E] -> gates [N, experts] float32, 0 for an expert not chosen."""
    scores = jax.nn.sigmoid(x @ router_w)
    N, X = scores.shape
    by = (scores + expert_bias).reshape(N, N_GROUP, X // N_GROUP)
    group = jax.lax.top_k(by, 2)[0].sum(-1)                    # [N, groups]
    _, best = jax.lax.top_k(group, TOPK_GROUP)
    stays = jnp.zeros((N, N_GROUP), bool).at[
        jnp.arange(N)[:, None], best].set(True)
    by = jnp.where(stays[:, :, None], by, -jnp.inf).reshape(N, X)
    _, chosen = jax.lax.top_k(by, TOP_K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = ROUTE_SCALE * picked / picked.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(N)[:, None], chosen].set(picked)


def _experts(x, gates, moe, layer):
    """sum over the HELD experts of gate x their SwiGLU: a loop. An expert's
    matrices are read where they lie in ``moe``'s leaves [layers, held, ..]
    (a layer's slice of them would be a copy of all its experts)."""
    names = ("expert_gate", "expert_fc", "expert_out")
    held = moe["expert_fc"].shape[1]
    gates = gates[:, FIRST_HELD:FIRST_HELD + held]

    def one(acc, expert):
        e, g = expert
        return acc + g[:, None] * _swiglu(
            x, *(moe[n][layer, e] for n in names)), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(held), gates.T))
    return acc


def _layer(x, p, at, moe, routed):
    """One layer over x [T, E]: ``p`` its segment's leaves [repeats, ..]
    and ``at`` its place in them; ``moe`` every routed layer's router and
    experts, stacked, and ``routed`` its index among them (None: dense)."""
    x = x + _mix(_rms_norm(x, _f32(p["mix_norm"][at])), p, at)
    h = _rms_norm(x, _f32(p["mlp_norm"][at]))
    if routed is None:
        return x + _in_blocks(lambda h: _swiglu(
            h, p["w_gate"][at], p["w_up"][at], p["w_down"][at]), h)

    def share(h):
        gates = route(h, *_f32((moe["router_w"][routed],
                                moe["expert_bias"][routed])))
        return _experts(h, gates, moe, routed) + _swiglu(
            h, p["shared_gate"][at], p["shared_up"][at], p["shared_down"][at])

    return x + _in_blocks(share, h)


def layer_order(blocks: Dict):
    """[(a layer's segment's weights, its place in them, its index among
    the routed layers or None)] first layer to last, from the segments'
    layout: each segment's period ``repeats`` times over."""
    out, routed = [], 0
    for segment in blocks["segments"]:
        repeats = jax.tree.leaves(segment[0])[0].shape[0]
        for r in range(repeats):
            for p in segment:
                dense = "w_gate" in p
                out.append((p, r, None if dense else routed))
                routed += not dense
    return out


def _sequence(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [T] (whole blocks) -> logits [T, V]."""
    x = jnp.asarray(params["wte"], jnp.float32)[tokens]
    for p, at, routed in layer_order(params["blocks"]):
        x = _layer(x, p, at, params["blocks"].get("experts"), routed)
    x = _rms_norm(x, jnp.asarray(params["norm_f"], jnp.float32))
    return _in_blocks(lambda x: x @ _f32(params["lm_head"]).T, x)


def logits(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, T] -> logits [B, T, V], float32: one sequence after
    another, each padded at its end to whole blocks."""
    T = tokens.shape[1]
    padded = jnp.pad(tokens, ((0, 0), (0, -T % BLOCK)))
    return jax.lax.map(lambda t: _sequence(params, t), padded)[:, :T]
