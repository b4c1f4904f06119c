"""inclusionAI's ``bailing_hybrid`` (Ling-3.0-flash): what is peculiar to
it. The cases every family shares (the reference and each fault, bfloat16,
the refusals, the plan, the four shares, padded chunks, idle and reused
slots, two slots, speculation refused) run over its row of
``tests/families.py``; here, the kinds of its two periods, the choice among
groups, the latent decode kernel against the XLA step, the counters only a
share with latent layers has, and the scopes a trace's reader finds its
operations by.

CPU, float32, seeded weights, tiny widths; each tolerance is written where
it is used. No device number.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import SamplingParams
from ray_tpu.models import decoder, kv_cache
from ray_tpu.ops import decode_attention, kda
from ray_tpu.parallel import moe
from tests import families

FAMILY = "bailing_hybrid"


def test_two_periods_are_five_kda_layers_and_a_latent_one_each():
    kinds = families.kinds(FAMILY, num_layers=12)
    assert [k.name for k in kinds] == (
        ["kda/dense"] + ["kda/routed"] * 4 + ["latent/routed"]
        + ["kda/routed"] * 5 + ["latent/routed"])
    assert [(k.state, k.recurrence, k.latent) for k in kinds[4:6]] == [
        (16, kda.KDA, None), (None, None, 40)]


def test_a_high_score_in_a_losing_group_is_not_chosen():
    """Eight experts in four groups of two, the best two groups stay, two
    experts a token. Expert 0 has the highest score of all, but its group's
    two scores add up to 1.0; groups 1 (0.6 + 0.6) and 2 (0.7 + 0.55) win,
    and the choice is experts 4 (0.7) and 2 (0.6, the first of a tie)."""
    config = moe.MoEConfig(num_experts=8, top_k=2, score_func="sigmoid",
                           n_group=4, topk_group=2, dropless=True)
    scores = np.array([[0.99, 0.01, 0.6, 0.6, 0.7, 0.55, 0.1, 0.1]])
    logits = jnp.asarray(np.log(scores / (1 - scores)), jnp.float32)
    probs, gates, chosen = moe._route(
        {}, jnp.zeros((1, 4)), config, None, None, logits=logits)
    assert sorted(np.asarray(chosen[0]).tolist()) == [2, 4]
    np.testing.assert_allclose(sorted(np.asarray(gates[0])), [0.6, 0.7],
                               atol=1e-6)
    # among all experts the choice would have been 0 and 4
    free = dataclasses.replace(config, n_group=None, topk_group=None)
    _, _, chosen = moe._route({}, jnp.zeros((1, 4)), free, None, None,
                              logits=logits)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 4]
    # a bias moves the groups' scores and the choice, never the gates:
    # expert 1's lifts group 0 over group 1
    biased = dataclasses.replace(config, expert_bias=True)
    bias = jnp.zeros((8,)).at[1].set(0.3)
    _, gates, chosen = moe._route(
        {"expert_bias": bias}, jnp.zeros((1, 4)), biased, None, None,
        logits=logits)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 4]
    np.testing.assert_allclose(sorted(np.asarray(gates[0])), [0.7, 0.99],
                               atol=1e-6)


@pytest.mark.parametrize("live", [
    [True] * 4, [True, False, True, False], [False] * 4])
def test_latent_decode_kernel_equals_the_xla_step_and_skips_idle_slots(
        monkeypatch, live):
    """``attend_latent`` at T = 1 over layer 1 of a latent cache of two
    layers, four slots at lengths 0, 5, 130 and 255 of 256 (a first column,
    one chunk, two, the last position): the kernel's rows are the XLA
    path's, each filled chunk is read once for keys and values, a slot that
    does not decode keeps every bit of its cache and gets zeros."""
    monkeypatch.setattr(decode_attention, "LATENT_BLOCK_BYTES", 40 * 128 * 4)
    rng = np.random.default_rng(3)
    B, H, R, Dr, Dn, Dv, S = 4, 2, 32, 8, 16, 16, 256
    leaf = jnp.asarray(rng.normal(size=(2, B, 1, R + Dr, S)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dn + Dr)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(B, 1, R + Dr)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(R, H, Dn + Dv)), jnp.float32) * 0.2
    start = jnp.asarray([0, 5, 130, 255], jnp.int32)
    keep = jnp.asarray(live)
    results = {}
    for impl in ("xla", "pallas_interpret"):
        monkeypatch.setattr(kv_cache, "_decode_impl", lambda impl=impl: impl)
        at = kv_cache.step(start, 1, {"latent": leaf}, live=keep)
        with jax.default_matmul_precision("highest"):
            results[impl] = kv_cache.attend_latent(
                {"latent": leaf}, jnp.int32(1), q, rows, up, at,
                (Dn + Dr) ** -0.5)
    (xla_cache, xla_out), (cache, out) = results["xla"], results[
        "pallas_interpret"]
    for slot, on in enumerate(live):
        if on:
            np.testing.assert_allclose(out[slot], xla_out[slot], atol=2e-5)
            np.testing.assert_allclose(cache["latent"][1, slot],
                                       xla_cache["latent"][1, slot], atol=0)
            assert np.array_equal(cache["latent"][1, slot, 0, :, start[slot]],
                                  rows[slot, 0])
        else:
            assert np.array_equal(cache["latent"][:, slot], leaf[:, slot])
            assert not np.asarray(out[slot]).any()
    assert np.array_equal(cache["latent"][0], leaf[0])


def test_the_programs_operations_carry_the_layers_scopes():
    """What a trace's reader finds a KDA layer's and a latent layer's
    operations by (``benchmarks/lib/bailing_ops.py``): every scope is on
    some operation of the compiled decode and prefill programs."""
    decoded, prefilled = families.program_texts(FAMILY)
    for scope in ("kda.in_proj", "kda.conv", "kda.update", "kda.gate_norm",
                  "kda.out_proj", "mla.q", "mla.down", "mla.attend",
                  "mla.up", "mla.out", "moe.route", "moe.shared"):
        assert scope in decoded, scope
    for scope in ("kda.scan", "kda.conv", "mla.attend", "mla.up"):
        assert scope in prefilled, scope


def test_a_shares_engine_counts_held_rows_and_latent_key_positions():
    """Two requests in one batch (37 tokens in three chunks, and 6): the
    counters of what only this family has, a share of group-routed experts
    beside a latent layer."""
    engine = families._engine(FAMILY)
    params = SamplingParams(max_new_tokens=12)
    try:
        futures = [engine.submit(p, params)
                   for p in families.prompts_of(37, 6)]
        assert [len(f.result(timeout=600)) for f in futures] == [12, 12]
    finally:
        engine.shutdown()
    ticks, stats = engine._span.named("engine.tick"), engine.stats
    kinds = decoder.layer_kinds(engine.model_config)
    latent = sum(k.latent is not None for k in kinds)
    routed = sum(k.routed for k in kinds)
    # no layer holds keys and values a head
    assert {t.args["layers_full"] for t in ticks} == {0}
    # a chunk's key positions count the latent layers' (37 tokens: 16, 32
    # and 37 positions seen; 6 tokens: 6)
    assert stats["prefill_key_positions"] == latent * (16 + 32 + 40 + 8)
    # the held experts' rows: a share of the routed pairs (4 of 16 experts
    # held, 2 of 4 groups kept: a quarter under a balanced router), counted
    # by the programs: the ticks' sum is the programs' that were read
    held = sum(t.args["moe_rows_held"] for t in ticks)
    rows = sum(t.args["moe_rows"] for t in ticks)
    assert rows == stats["slot_ticks"] * 4 * routed
    assert 0 < held < rows and held <= stats["moe_rows_held"]
    assert 0.1 < stats["moe_rows_held"] / stats["moe_rows"] < 0.45
