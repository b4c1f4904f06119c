"""The ``joyai_llm_flash`` family (JoyAI-LLM-Flash: DeepSeek-V3's block)
: what is peculiar to it. The cases every family shares (the logits
against the reference, bfloat16, the refusals, the plan, padded chunks
through the latent cache, idle and reused slots, two slots, speculation) run
over its row of ``tests/families.py``; here, what a step minimises against
the benchmark's plain reference (``benchmarks/references/joyai_llm_flash.py``:
two losses and every leaf's gradient), the faults that comparison must
catch, a block's sixteen experts in four shares, and the balancing rule
under the trainer's step.

CPU, float32, seeded weights, tiny widths that keep every ratio (heads of 24
/ 16, a query rank, 1 dense + 2 routed layers + the prediction layer, 16
experts of which 4 are held, 2 a token); each tolerance is written where it
is used. Nothing timed here is a device number.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import bailing_hybrid, decoder, joyai_llm_flash as family
from ray_tpu.ops import xent
from ray_tpu.parallel import moe
from tests import families

FAMILY = "joyai_llm_flash"
T = 37


def _config(**changes):
    changes.setdefault("dtype", jnp.float32)
    changes.setdefault("attention_impl", "xla")
    return dataclasses.replace(family.JOYAI_FLASH_TINY, **changes)


def _batch(seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, 512, (2, T + 1)).astype(np.int32))


def leaf_gaps(got, want):
    """Two gradients a leaf at a time: leaf's path -> (relative error of its
    norm, 1 - cosine), in float64 on the host. A leaf with no gradient on
    the reference's side (the biases) reads 0 where the program's has none
    either, else inf. ``benchmarks/tests/compare_joyai.py`` reads the chip's
    with it."""
    out = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        a, b = (np.asarray(x, np.float64).ravel() for x in (a, b))
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        if nb == 0.0:
            gap = (0.0 if na == 0.0 else float("inf"), 0.0)
        else:
            gap = (abs(na - nb) / nb,
                   1.0 - a.dot(b) / (na * nb) if na else 1.0)
        out[jax.tree_util.keystr(path)] = gap
    return out


@functools.lru_cache(maxsize=None)
def _plain_side():
    """The reference's logits, two losses and gradients of the moved weights
    on ``_batch``: no fault and no change of configuration below moves a
    weight's shape or the reference, so they are made once."""
    reference = families.reference(FAMILY)

    def plain(params, tokens):
        return (reference.logits(params, tokens[:, :-1]),
                *reference.loss_parts(params, tokens),
                jax.grad(lambda p: reference.loss(p, tokens))(params))

    with jax.default_matmul_precision("highest"):
        return jax.jit(plain)(families._moved(FAMILY, _config()), _batch())


def _readings(cfg, params, tokens):
    """How far the program lies from the reference: the main head's logits
    (max), the two losses, and over the leaves the worst relative error of a
    gradient's norm and the worst 1 - cosine, of what a step minimises; and
    the program's gradients."""
    def program(params, tokens):
        xent_, aux = family.loss_fn(params, {"tokens": tokens}, cfg,
                                    parts=True)
        return (family.forward(params, tokens[:, :-1], cfg)[0], xent_,
                aux["mtp_loss"], jax.grad(lambda p: family.loss_fn(
                    p, {"tokens": tokens}, cfg))(params))

    # each side one program: op by op the same arithmetic takes a minute
    with jax.default_matmul_precision("highest"):
        got, xent_, mtp_, grads = jax.jit(program)(params, tokens)
    want, main, mtp, grads_ref = _plain_side()
    gaps = leaf_gaps(grads, grads_ref).values()
    return {"logits": float(jnp.abs(got - want).max()),
            "main": abs(float(xent_) - float(main)),
            "mtp": abs(float(mtp_) - float(mtp)),
            "grad_norm": max(g[0] for g in gaps),
            "grad_turn": max(g[1] for g in gaps)}, grads


# float32 against float32, the order of the sums alone: logits 6e-7 on 1.4,
# losses 5e-7, a leaf's gradient 4e-6 of its norm and 1e-11 off its direction
# (measured); the faintest fault below reads 30 times a limit
LIMITS = {"logits": 2e-5, "main": 1e-5, "mtp": 1e-5, "grad_norm": 1e-4,
          "grad_turn": 1e-6}


@pytest.mark.parametrize("impl", ["xla", "flash_interpret"])
def test_logits_losses_and_every_leafs_gradient_match_the_reference(impl):
    cfg = _config(attention_impl=impl)
    kinds = decoder.layer_kinds(cfg)
    assert [(k.name, k.routed, k.latent) for k in kinds] == [
        ("dense", False, 24), ("routed", True, 24), ("routed", True, 24)]
    params = families._moved(FAMILY, cfg)
    assert set(params["mtp"]) == {"norm_h", "norm_e", "eh_proj", "layer",
                                  "experts", "norm_f"}
    got, grads = _readings(cfg, params, _batch())
    assert all(got[k] <= LIMITS[k] for k in LIMITS), got
    # the router's bias has no gradient: it moves a choice of indices
    assert not np.asarray(
        grads["blocks"]["experts"]["expert_bias"]).any()
    assert not np.asarray(grads["mtp"]["experts"]["expert_bias"]).any()
    assert float(jnp.abs(grads["mtp"]["eh_proj"]).max()) > 0


def _no_query_norm(monkeypatch, cfg):
    real = family._rms_norm

    def rms_norm(x, gain, *rest):
        return x if gain.shape[-1] == cfg.q_lora_rank else real(x, gain, *rest)
    monkeypatch.setattr(family, "_rms_norm", rms_norm)


def _bias_in_the_gates(monkeypatch, cfg):
    real = moe._route

    def route(params, tokens, config, rng, layer, logits=None):
        probs, gates, chosen = real(params, tokens, config, rng, layer, logits)
        return probs, gates + moe._own(params, "expert_bias", layer)[chosen], \
            chosen
    monkeypatch.setattr(moe, "_route", route)


def _same_positions_embedding(monkeypatch, cfg):
    real = family._mtp_features
    monkeypatch.setattr(
        family, "_mtp_features", lambda config, params, x, following, mesh:
        real(config, params, x, jnp.roll(following, 1, axis=1), mesh))


def _last_position_unmasked(monkeypatch, cfg):
    real = xent.chunked_softmax_xent
    monkeypatch.setattr(
        xent, "chunked_softmax_xent", lambda x, w, targets, mask=None, **kw:
        real(x, w, targets, None, **kw))


def _rope_by_halves(x, pos, theta, yarn=None):
    """Channel i turned with channel i + D / 2 (llama's layout) where the
    configuration pairs (2i, 2i + 1); x [B, T, D] or, as the full forward
    has the queries, [B, H, T, D]. (``yarn``: the rotations' fourth
    argument since PR 57, None in this family.)"""
    D = x.shape[-1]
    angle = pos.astype(jnp.float32)[..., None] / theta ** (
        jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    if x.ndim == 4:
        angle = angle[:, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            a * jnp.sin(angle) + b * jnp.cos(angle)], -1)


def _wrong_scale(monkeypatch, cfg):
    """1 / sqrt(16) where it is 1 / sqrt(24): the queries longer by
    sqrt(24 / 16), which is the same scores."""
    real = decoder._attention_dispatch
    wrong = (cfg.head_dim / cfg.qk_nope_head_dim) ** 0.5
    monkeypatch.setattr(
        decoder, "_attention_dispatch", lambda config, q, *rest, **kw:
        real(config, q * wrong, *rest, **kw))


FAULTS = {
    "no norm on cq": _no_query_norm,
    # the full forward's rotation, which the families' latent layers share
    "rotation by halves": lambda mp, cfg: mp.setattr(
        bailing_hybrid, "_rope_lanes", _rope_by_halves),
    "scale 1 / sqrt(nope width)": _wrong_scale,
    "the bias added to the gates": _bias_in_the_gates,
    "routed_scaling_factor left out": None,     # a configuration's
    "the shared expert left out": lambda mp, cfg: mp.setattr(
        family, "shared_expert", lambda h, *w: jnp.zeros_like(h)),
    "the same position's embedding": _same_positions_embedding,
    "the last position unmasked": _last_position_unmasked,
    "loss weight 0": None,                      # a configuration's
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_fault_is_caught_by_the_comparison(monkeypatch, fault):
    """The program with one fault planted reads past a limit that the sound
    program stays inside (the test above): which reading, and by how much,
    is asserted a fault. A loss weight of 0 moves no loss and no logit: only
    the gradients of the prediction layer's leaves show it."""
    changes = {
        "routed_scaling_factor left out": {"moe": dataclasses.replace(
            family.JOYAI_FLASH_TINY.moe, route_scale=1.0)},
        "loss weight 0": {"mtp_loss_weight": 0.0}}.get(fault, {})
    cfg = _config(**changes)
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch, cfg)
    got, _ = _readings(cfg, families._moved(FAMILY, cfg), _batch())
    over = {k: got[k] / LIMITS[k] for k in LIMITS if got[k] > LIMITS[k]}
    assert over and max(over.values()) > 30, (fault, got)
    if fault == "loss weight 0":
        assert set(over) <= {"grad_norm", "grad_turn"}
    if fault in ("the same position's embedding",
                 "the last position unmasked"):
        assert "mtp" in over and "logits" not in over and "main" not in over


def test_four_shares_and_the_shared_expert_once_are_the_whole_layer():
    """The guide's section 4 for a layer shared four ways (the cell's
    sixteen, at the toy's 16 experts): each share's block gives ``base + its
    experts' part`` (``base``: the stream after the mixer and the shared
    expert, which every chip computes whole); the four parts and one base
    are the uncut reference's layer, the shared expert counted once."""
    cfg = _config()
    whole = _config(moe=dataclasses.replace(cfg.moe, num_held=16))
    params = families._moved(FAMILY, whole)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, T, 64))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (2, T))
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["segments"][1][0])
    experts = jax.tree.map(lambda a: a[0], params["blocks"]["experts"])
    kind = decoder.layer_kinds(cfg)[1]

    def block(cfg, experts):
        # one program a share: op by op a block is five seconds
        return jax.jit(lambda experts: decoder._body(cfg, None, pos, kind)(
            x, layer, None, (experts, None))[0])(experts)

    base = block(whole, {**experts, "expert_out": jnp.zeros_like(
        experts["expert_out"])})
    parts = []
    for first in range(0, 16, 4):
        share = _config(moe=dataclasses.replace(cfg.moe, first_held=first))
        held = {name: w if name in ("router_w", "expert_bias")
                else w[first:first + 4] for name, w in experts.items()}
        parts.append(block(share, held) - base)
    with jax.default_matmul_precision("highest"):
        want = families.reference(FAMILY)._layer(x, layer, experts)
    np.testing.assert_allclose(np.asarray(base + sum(parts)),
                               np.asarray(want), atol=2e-5, rtol=0)
    # and each part is something: no share is the whole
    assert all(float(jnp.abs(p).max()) > 1e-4 for p in parts)


def test_a_step_moves_each_bias_by_the_rule_and_the_optimizer_does_not():
    """``make_train_step``: after one step every ``b_e`` of every routed
    layer and of the prediction layer's router has moved by exactly ``u x
    sign(mean(n) - n_e)``, ``n`` the step's own counts over all 16 experts;
    AdamW's decay (0.1, at a learning rate made large here) has not touched
    it; ``loss`` is the main cross entropy, and the second loss, the counts
    and the rule's counters ride beside it."""
    from ray_tpu.train.step import (
        OptimizerConfig, create_train_state, make_train_step)

    cfg = _config()
    opt = OptimizerConfig(learning_rate=0.1, warmup_steps=0).build()
    state = create_train_state(cfg, opt, jax.random.PRNGKey(0))
    state["params"] = families._moved(FAMILY, cfg)
    batch = {"tokens": _batch()}
    xent_, aux = jax.jit(lambda p, b: family.loss_fn(p, b, cfg, parts=True))(
        state["params"], batch)
    counts = np.asarray(aux["moe_counts"])
    assert counts.shape == (3, 16) and (counts.sum(1) == 2 * T * 2).all()
    new, metrics = make_train_step(cfg, opt, donate=False)(state, batch)
    before = np.concatenate([
        np.asarray(state["params"]["blocks"]["experts"]["expert_bias"]),
        np.asarray(state["params"]["mtp"]["experts"]["expert_bias"])[None]])
    after = np.concatenate([
        np.asarray(new["params"]["blocks"]["experts"]["expert_bias"]),
        np.asarray(new["params"]["mtp"]["experts"]["expert_bias"])[None]])
    want = np.float32(0.001) * np.sign(
        counts.mean(1, keepdims=True) - counts).astype(np.float32)
    assert (np.abs(want) == 0.001).sum() > 40    # 48 biases, few at the mean
    np.testing.assert_array_equal(after, before + want)
    # another leaf did move by the optimizer (decay and all)
    assert float(jnp.abs(new["params"]["norm_f"]
                         - state["params"]["norm_f"]).max()) > 1e-3
    assert abs(float(metrics["loss"]) - float(xent_)) < 1e-5
    assert abs(float(metrics["mtp_loss"]) - float(aux["mtp_loss"])) < 1e-5
    assert float(metrics["aux_loss"]) == 0.0
    assert int(metrics["moe_rows_max_all"]) == counts.max(1).sum()
    assert abs(float(metrics["moe_bias_abs_mean"])
               - np.abs(after).mean()) < 1e-7
    assert int(metrics["moe_rows_held"]) == counts[:, :4].sum()
    assert set(metrics) == {
        "loss", "grad_norm", "step", "aux_loss", "moe_rows_held",
        "moe_rows_max_expert", "mtp_loss", "moe_rows_max_all",
        "moe_bias_abs_mean"}


