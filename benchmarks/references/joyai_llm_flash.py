"""Plain float32 reference of the ``joyai_llm_flash`` architecture
(JoyAI-LLM-Flash, https://huggingface.co/jdopensource/JoyAI-LLM-Flash:
``config.json``; the block is DeepSeek-V3's, arXiv:2412.19437, whose key
names the config follows): token embedding; decoder layers ``h +=
mix(RMSNorm(h))``, ``h += ffn(RMSNorm(h))``; a final RMSNorm and a head of
its own (untied); one multi-token-prediction layer behind the trunk. No bias
anywhere.

``mix``, every layer, latent attention with a query rank: ``cq = RMSNorm(x
Wdq)``; ``q = cq Wuq`` a head ``[Dn | Dr]``; ``[c | kr] = x Wdkv``, ``c =
RMSNorm(c)``; ``[k_nope | v] = c Wukv`` a head ``[Dn | Dv]``; interleaved
RoPE (pairs (0, 1), (2, 3), ..) at ``ROPE_THETA`` on q's last ``Dr`` and on
``kr``, which every head shares; causal softmax of ``(q_nope . k_nope +
q_rope . kr) / sqrt(Dn + Dr)``, ONE HEAD AFTER ANOTHER (a head's [T, T]
scores are all that is alive at once); the output projection. No gate.

``ffn``: ``(silu(x Wg) * x Wu) Wd`` in a dense layer. In a routed one: ``s
= sigmoid(x Wr)`` over ALL experts; the ``TOP_K`` largest of ``s + bias``
are chosen (no groups); their gates are ``s`` WITHOUT the bias over their
sum times ``ROUTE_SCALE``; the held experts' (``FIRST_HELD`` on, as many as
the weights hold) gated SwiGLUs are summed BY A LOOP over them, plus the
shared expert. What the experts held elsewhere would add is left out: the
weights are one chip's share, and so is the result.

Multi-token prediction at depth 1 (DeepSeek-V3 section 2.2): with ``h_i``
the last trunk layer's output BEFORE the final norm and ``t`` the tokens,
``h'_i = [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))] M``; one whole routed
layer (its own router, bias and held experts; positions 0..T-1); its own
final RMSNorm; the trunk's table and head. Its logits at position i predict
``t_{i+2}``. ``loss_parts`` gives the mean cross entropy of the main head
over every position and of this one over the positions that have a target.

Straightforward ``jax.numpy``: no chunk, no cache, no kernel, no remat, no
mixed precision. Every matrix product runs in float32 at
``jax.default_matmul_precision("highest")``, which the caller sets. What the
weights do not carry is stated here as constants, which a test at another
size patches; every size comes from the weights' shapes.

The weights are DATA: the program's own parameter pytree
(``ray_tpu.models.joyai_llm_flash.init_params`` under the same key):
``wte`` / ``lm_head`` [V, E], ``norm_f`` [E], ``blocks.segments[s][j]`` the
j-th layer of segment s's period, leaves [repeats, ...]: ``mix_norm`` /
``mlp_norm`` [E], ``w_dq`` [E, Rq], ``q_norm`` [Rq], ``w_uq`` [Rq, H, Dn +
Dr], ``w_dkv`` [E, R + Dr], ``kv_norm`` [R], ``w_ukv`` [R, H, Dn + Dv],
``wo`` [H, Dv, E]; a dense layer's ``w_gate`` / ``w_up`` / ``w_down``, a
routed layer's ``shared_gate`` / ``shared_up`` / ``shared_down``;
``blocks.experts`` every routed layer's ``router_w`` [L, E, X],
``expert_bias`` [L, X], ``expert_fc`` / ``expert_gate`` [L, held, E, M],
``expert_out`` [L, held, M, E]; ``mtp``: ``norm_h`` / ``norm_e`` /
``norm_f`` [E], ``eh_proj`` [2 E, E], ``layer`` (a routed layer's leaves
with no leading axis) and ``experts`` (one layer's, no leading axis).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6          # rms_norm_eps
ROPE_THETA = 32e6       # rope_theta; rope_scaling null
TOP_K = 8               # num_experts_per_tok
ROUTE_SCALE = 2.5       # routed_scaling_factor; norm_topk_prob is true
FIRST_HELD = 0          # the first expert this chip holds
MTP_WEIGHT = 0.3        # the second loss's weight in what a step minimises


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, gain):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) * gain


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _rope(x):
    """x [B, T, .., D]: the pair (2i, 2i + 1) turned by ``t / THETA ** (2i /
    D)``, t the position 0..T-1."""
    D = x.shape[-1]
    T = x.shape[1]
    freq = ROPE_THETA ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq     # [T, D / 2]
    angle = angle.reshape((1, T) + (1,) * (x.ndim - 3) + (D // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([a * jnp.cos(angle) - b * jnp.sin(angle),
                        a * jnp.sin(angle) + b * jnp.cos(angle)], -1)
    return turned.reshape(x.shape)


def _attention(q, k, v):
    """q and k [B, T, H, Dq], v [B, T, H, Dv] -> [B, T, H, Dv]: causal, one
    head after another."""
    T, D = q.shape[1], q.shape[-1]
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def head(qkv):
        q, k, v = qkv                                   # [B, T, D]
        att = jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(jnp.float32(D))
        att = jax.nn.softmax(jnp.where(seen[None], att, -jnp.inf), -1)
        return jnp.einsum("bqk,bkd->bqd", att, v)

    out = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return jnp.moveaxis(out, 0, 2)


def _latent(h, p):
    """Latent attention with a query rank over h [B, T, E] (normed)."""
    B, T, E = h.shape
    rq, H, dq = p["w_uq"].shape
    rank, _, dkv = p["w_ukv"].shape
    dv = p["wo"].shape[1]
    dn = dkv - dv
    dr = dq - dn
    cq = _rms_norm(h @ p["w_dq"], p["q_norm"])
    q = (cq @ p["w_uq"].reshape(rq, -1)).reshape(B, T, H, dq)
    c, kr = jnp.split(h @ p["w_dkv"], [rank], -1)
    kv = (_rms_norm(c, p["kv_norm"]) @ p["w_ukv"].reshape(rank, -1)).reshape(
        B, T, H, dkv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:])], -1)
    kr = jnp.broadcast_to(_rope(kr)[:, :, None, :], (B, T, H, dr))
    o = _attention(q, jnp.concatenate([kv[..., :dn], kr], -1), kv[..., dn:])
    return o.reshape(B, T, -1) @ p["wo"].reshape(-1, E)


def route(x, router_w, expert_bias):
    """x [N, E] -> gates [N, experts] float32, 0 for an expert not chosen."""
    scores = jax.nn.sigmoid(x @ router_w)
    _, chosen = jax.lax.top_k(scores + expert_bias, TOP_K)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = ROUTE_SCALE * picked / picked.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(picked)


def _experts(x, gates, moe):
    """sum over the HELD experts of gate x their SwiGLU: a loop over
    ``moe``'s leaves [held, ..]."""
    held = moe["expert_fc"].shape[0]
    gates = gates[:, FIRST_HELD:FIRST_HELD + held]

    def one(acc, expert):
        w_up, w_gate, w_down, g = expert
        return acc + g[:, None] * _swiglu(x, *_f32((w_gate, w_up, w_down))), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        moe["expert_fc"], moe["expert_gate"], moe["expert_out"], gates.T))
    return acc


def _layer(x, p, moe):
    """One layer; ``moe`` its router and experts (leaves [X, ..] / [held,
    ..]), None for a dense one."""
    B, T, E = x.shape
    p = _f32(p)
    x = x + _latent(_rms_norm(x, p["mix_norm"]), p)
    h = _rms_norm(x, p["mlp_norm"]).reshape(B * T, E)
    if moe is None:
        y = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    else:
        gates = route(h, *_f32((moe["router_w"], moe["expert_bias"])))
        y = _experts(h, gates, moe) + _swiglu(
            h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return x + y.reshape(B, T, E)


def layer_order(blocks: Dict):
    """[(a layer's own weights, its router and experts or None)] first layer
    to last, from the segments' layout: each segment's period ``repeats``
    times over, the routed layers' experts in the order the layers come."""
    out, routed = [], 0
    for segment in blocks["segments"]:
        repeats = jax.tree.leaves(segment[0])[0].shape[0]
        for r in range(repeats):
            for p in segment:
                moe = None
                if "w_gate" not in p:
                    moe = {name: w[routed]
                           for name, w in blocks["experts"].items()}
                    routed += 1
                out.append((jax.tree.map(lambda a: a[r], p), moe))
    return out


def _trunk(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, T] -> the last layer's output [B, T, E], not yet normed."""
    x = jnp.asarray(params["wte"], jnp.float32)[tokens]
    for p, moe in layer_order(params["blocks"]):
        x = _layer(x, p, moe)
    return x


def _head(params: Dict, x, gain) -> jax.Array:
    return _rms_norm(x, jnp.asarray(gain, jnp.float32)) @ jnp.asarray(
        params["lm_head"], jnp.float32).T


def logits(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, T] -> the main head's logits [B, T, V], float32."""
    return _head(params, _trunk(params, tokens), params["norm_f"])


def _predicted(params: Dict, h, following) -> jax.Array:
    """The prediction layer's logits [B, T, V] from the trunk's output h
    [B, T, E] (not normed) and ``following`` [B, T], the token after each
    position."""
    mtp = _f32({k: v for k, v in params["mtp"].items() if k != "experts"})
    e = jnp.asarray(params["wte"], jnp.float32)[following]
    x = jnp.concatenate([_rms_norm(h, mtp["norm_h"]),
                         _rms_norm(e, mtp["norm_e"])], -1) @ mtp["eh_proj"]
    x = _layer(x, mtp["layer"], params["mtp"]["experts"])
    return _head(params, x, mtp["norm_f"])


def mtp_logits(params: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [B, S] -> the prediction layer's logits [B, S - 1, V]: row i,
    from the trunk over ``tokens[:, :i + 1]`` and the embedding of
    ``tokens[:, i + 1]``, scores what follows THAT token."""
    return _predicted(params, _trunk(params, tokens[:, :-1]), tokens[:, 1:])


def _xent(lg, targets):
    lp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]


def loss_parts(params: Dict, tokens: jax.Array):
    """tokens [B, T + 1] -> (the main head's mean cross entropy over the T
    positions, the prediction layer's over the T - 1 that have a target),
    both from one pass through the trunk."""
    h = _trunk(params, tokens[:, :-1])
    main = _xent(_head(params, h, params["norm_f"]), tokens[:, 1:]).mean()
    mtp = _xent(_predicted(params, h, tokens[:, 1:])[:, :-1],
                tokens[:, 2:]).mean()
    return main, mtp


def loss(params: Dict, tokens: jax.Array) -> jax.Array:
    """What a step minimises: ``L_main + MTP_WEIGHT x L_mtp``."""
    main, mtp = loss_parts(params, tokens)
    return main + MTP_WEIGHT * mtp
