"""DeepSeek-V3.2-Exp (``deepseek_v32``): what is peculiar to it. The cases
every family shares (the reference and each of the serving cell's faults,
bfloat16, the refusals, the plan of two rows a position, the four shares,
padded chunks through the masked kernels, idle and reused slots, two slots,
speculation) run over its row of ``tests/families.py``; here, YaRN's
numbers, the exact choice against a sort, the indexer's scores inside VMEM,
the masked decode kernel against the XLA step, and the engine's two
counters.

CPU, float32, seeded weights, the toy's widths (a dense layer and two routed
ones, 2 heads of 16 | 8, an indexer of 4 heads of 16 that keeps 16
positions, YaRN from a context of 32); each tolerance is written where it is
used. No device number.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.llm import SamplingParams
from ray_tpu.models import bailing_hybrid, decoder, deepseek_v32, kv_cache
from ray_tpu.ops import index_select
from tests import families
from tests.families import _tokens

FAMILY = "deepseek_v32"


def test_the_rows_layers_are_a_dense_lead_and_routed_ones_an_indexer_each():
    """A share of 4 of 16 experts in 4 groups; every layer a latent row of
    32 + 8 values and an indexer that keeps 16 positions."""
    cfg = families.model_config(FAMILY)
    assert cfg.moe.num_held == 4 and cfg.moe.n_group == 4
    kinds = decoder.layer_kinds(cfg)
    assert [k.routed for k in kinds] == [False, True, True]
    assert all(k.latent == 40 and k.index.kept == 16 for k in kinds)


def test_yarn_frequencies_and_scale_by_numbers_worked_out_here():
    """d = 64, theta 10,000, 40 x 4,096: the pairs up to 10 turn more than
    32 times over 4,096 positions and stay, those from 23 on turn less than
    once and are divided by 40, pair 16 lies 6 / 13 along the ramp; m = 0.1
    ln 40 + 1."""
    yarn = bailing_hybrid.Yarn(40.0, 4096, 32.0, 1.0)
    got = np.asarray(bailing_hybrid._inv_freq(64, 10000.0, yarn))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(10000))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(10000))) == 23
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(
        got[16], plain[16] * (7 / 13 + 6 / 13 / 40), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(bailing_hybrid._inv_freq(64, 10000.0)), plain, rtol=1e-6)
    m = 0.1 * math.log(40) + 1
    assert abs(m - 1.36888794) < 1e-8
    cfg = dataclasses.replace(
        deepseek_v32.Config(), moe=None, num_layers=1, first_k_dense=1)
    assert abs(cfg.softmax_scale - 192 ** -0.5 * m * m) < 1e-9
    assert abs(cfg.softmax_scale - 0.1352337) < 1e-6
    # a model whose context is the original one: plain frequencies
    short = dataclasses.replace(cfg, max_seq_len=4096)
    assert short.yarn is None and short.softmax_scale == 192 ** -0.5
    kind, = decoder.layer_kinds(cfg)
    assert kind.scale == cfg.softmax_scale
    assert kind.index == decoder.Index(64, 128, 2048)


# ------------------------------------------------------------ the exact choice


def _by_a_sort(scores, visible, kept):
    """The oracle: a stable sort of each row's visible scores, the largest
    first, a tie to the lower position."""
    out = np.zeros(scores.shape, bool)
    for row, (s, v) in enumerate(zip(scores, visible)):
        seen = np.flatnonzero(v)
        order = seen[np.argsort(-s[seen], kind="stable")]
        out[row, order[:kept]] = True
    return out


@pytest.mark.parametrize("search", ["digits", "kernel"])
@pytest.mark.parametrize("case", [
    "random", "ties", "all_equal", "signed_zeros", "padded_bucket",
    "ties_at_the_threshold", "fewer_than_kept"])
def test_the_chosen_set_is_the_sorts_exactly(monkeypatch, case, search):
    """16 kept of up to 96: rows that see fewer than 16 positions keep them
    all; equal scores straddling the 16th place go to the lower positions;
    -0.0 ties with 0.0; the rows of a padded bucket (queries past the
    prompt's end) are rows like any other; a row whose 16th and 17th
    largest are one value met many times; rows that all see fewer than 16.
    ``kernel``: the threshold from ``kth_largest`` (two tiles of 16 rows,
    counted 32 positions at a time up to a tile's last visible block) in
    place of ``chosen``'s own digits, over scores that hold a NaN wherever a
    row does not see: the same set, bit for bit."""
    rng = np.random.default_rng(5)
    T, S, kept, start = 32, 96, 16, 40
    scores = rng.normal(size=(T, S)).astype(np.float32)
    if case == "ties":
        # a few values, each many times over: every row's 16th place is tied
        scores = rng.integers(-2, 3, (T, S)).astype(np.float32) * 0.25
    elif case == "all_equal":
        scores[:] = 0.75
    elif case == "signed_zeros":
        scores = np.where(rng.random((T, S)) < 0.5, -0.0, 0.0).astype(
            np.float32)
        scores[:, ::7] = -1.0
    elif case == "ties_at_the_threshold":
        # ten above, then one value thirty times: six of the thirty are in
        scores = -np.abs(scores) - 1.0
        scores[:, 3:33] = 0.5
        scores[:, 33:43] = 2.0 + rng.random((T, 10)).astype(np.float32)
    if case == "padded_bucket":
        start = 0       # the first rows see 1, 2, .. positions
    elif case == "fewer_than_kept":
        start, T = 0, 8
        scores = scores[:T]
    pos = start + np.arange(T)
    visible = np.arange(S)[None, :] <= pos[:, None]

    def choose(scores, visible):
        if search == "digits":
            return index_select.chosen(scores, visible, kept)
        kth = index_select.kth_largest(
            scores, jnp.asarray(pos), kept, interpret=True)
        return index_select.chosen(scores, visible, kept, kth)

    given = scores
    if search == "kernel":
        monkeypatch.setattr(index_select, "COUNTED", 32)
        given = np.where(visible, scores, np.nan)
    got = np.asarray(jax.jit(choose)(jnp.asarray(given), jnp.asarray(visible)))
    want = _by_a_sort(scores, visible, kept)
    assert np.array_equal(got, want)
    assert np.array_equal(got.sum(-1), np.minimum(kept, pos + 1))
    assert not (got & ~visible).any()


@pytest.mark.parametrize("lens", [[40, 17, 90], [0, 128, 200]])
def test_the_kernels_threshold_is_the_digit_searchs_for_slots_of_a_tick(
        monkeypatch, lens):
    """A decode step's rows: each sees the positions up to its length (its
    own new key's place among them; a length past the cache's end sees all
    of it), so one tile's rows end in different blocks. The threshold a row
    is ``chosen``'s own ``prefix``: the largest key that
    ``min(kept, visible)`` of the row's keys reach."""
    monkeypatch.setattr(index_select, "COUNTED", 32)
    rng = np.random.default_rng(7)
    S, kept = 128, 16
    scores = rng.integers(-3, 4, (3, S)).astype(np.float32) * 0.5
    lens = np.asarray(lens)
    visible = np.arange(S)[None, :] <= lens[:, None]
    kth = np.asarray(index_select.kth_largest(
        jnp.asarray(np.where(visible, scores, np.nan)), jnp.asarray(lens),
        kept, interpret=True))
    u = np.where(visible, np.asarray(index_select._ordered(
        jnp.asarray(scores))), 0).astype(np.uint64)
    k = np.minimum(visible.sum(-1), kept)
    want = np.stack([np.sort(row)[::-1][n - 1] for row, n in zip(u, k)])
    assert np.array_equal(kth[:, 0], want)
    got = index_select.chosen(
        jnp.asarray(scores), jnp.asarray(visible), kept, jnp.asarray(kth))
    assert np.array_equal(np.asarray(got), _by_a_sort(scores, visible, kept))


# --------------------------------------------------- the scores, inside VMEM


def _indexer(rng, B, T, S, heads=4, width=16):
    """(leaf [2, B, S, Di], q [B, T, Hi, Di], weights [B, T, Hi])."""
    return (jnp.asarray(rng.normal(size=(2, B, S, width)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, T, heads, width)), jnp.float32),
            jnp.asarray(np.abs(rng.normal(size=(B, T, heads))) + 0.1,
                        jnp.float32))


# every place the kernel did not write reads NaN
UNWRITTEN = pltpu.InterpretParams(uninitialized_memory="nan")


@pytest.mark.parametrize("live", [
    [True, False, True], [True, True, True], [False, False, False]],
    ids=["one_idle", "all", "none"])
def test_a_ticks_scores_read_live_slots_filled_blocks_and_nothing_else(
        monkeypatch, live):
    """Three slots at lengths 40 / 17 / 90, blocks of 32 positions: a live
    slot's scores over the positions it has filled are
    ``index_select.scores``'s; its blocks past its length are never written,
    nor is any place of an idle slot's row, and what lies in the leaf there
    (a NaN in every key past a slot's length and in all of an idle slot's)
    changes nothing a query sees."""
    monkeypatch.setattr(index_select, "STEP_POSITIONS", 32)
    rng = np.random.default_rng(11)
    B, S = 3, 128
    leaf, q, w = _indexer(rng, B, 1, S)
    lens = np.asarray([40, 17, 90])
    alive = np.asarray(live)
    filled = (np.arange(S)[None, :] < lens[:, None]) & alive[:, None]
    poisoned = jnp.where(filled[None, :, :, None], leaf, jnp.nan)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(index_select.scores(q, w, leaf[1])[:, 0])
        got = np.asarray(index_select.scores_of_step(
            q[:, 0], w[:, 0], poisoned, 1, jnp.asarray(lens, jnp.int32),
            kv_cache.live_slots(jnp.asarray(alive)), interpret=UNWRITTEN))
    np.testing.assert_allclose(got[filled], want[filled], rtol=1e-5,
                               atol=1e-5)
    visited = (np.arange(S)[None, :] < -(-lens[:, None] // 32) * 32) & (
        alive[:, None])
    assert np.isnan(got[~visited]).all()
    assert not np.isnan(got[filled]).any()


@pytest.mark.parametrize("start, width", [
    (24, 64), (24, None), (112, None), (120, None)],
    ids=["boundary_inside", "whole_cache", "to_the_end", "past_the_end"])
def test_a_chunks_scores_read_what_each_tile_of_queries_sees(
        monkeypatch, start, width):
    """16 queries at ``start ..`` as two tiles of 8 against blocks of 32
    positions: tile 0 of the chunk at 24 ends in block 0 and tile 1 in
    block 1 (a block boundary inside the chunk), the chunk at 112 reaches
    the cache's last position, the one at 120 past it (its tokens past the
    end see all of the cache). Where a query sees, ``index_select.scores``;
    past a tile's last visible block nothing is written, and a NaN in every
    key past the chunk's last position changes nothing."""
    monkeypatch.setattr(index_select, "POSITIONS", 32)
    monkeypatch.setattr(index_select, "QUERIES", 8)
    rng = np.random.default_rng(13)
    T, S = 16, 128
    leaf, q, w = _indexer(rng, 1, T, S)
    pos = start + np.arange(T)
    poisoned = jnp.where(
        (np.arange(S) <= pos[-1])[None, None, :, None], leaf, jnp.nan)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(index_select.scores(q, w, leaf[1])[0])
        got = np.asarray(index_select.scores_of_block(
            q[0], w[0], poisoned, 1, jnp.int32(start), width=width,
            interpret=UNWRITTEN))
    assert got.shape == (T, width or S)
    sees = np.arange(width or S)[None, :] <= pos[:, None]
    np.testing.assert_allclose(got[sees], want[:, :width or S][sees],
                               rtol=1e-5, atol=1e-5)
    last = np.repeat(pos.reshape(-1, 8)[:, -1], 8)     # of a row's tile
    visited = np.arange(width or S)[None, :] < (
        np.minimum(last, S - 1)[:, None] // 32 + 1) * 32
    assert np.isnan(got[~visited]).all()
    assert not np.isnan(got[sees]).any()


def test_the_programs_choice_is_the_references():
    """A layer's indexer through the family's own pieces, its scores and its
    choice (``index_select``), against the reference's [T, T] mask from a
    sort: the same set for every query of 64."""
    cfg, params = families.tiny_params(FAMILY)
    reference = families.reference(FAMILY)
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["segments"][1][0])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 64, 64)),
                    jnp.float32)
    pos = jnp.arange(64)[None]
    def both(x, layer):
        h = reference._rms_norm(x, layer["mix_norm"])
        cq = reference._rms_norm(h @ layer["w_dq"], layer["q_norm"])
        ix = deepseek_v32._indexed(cfg, layer, h, cq, pos)
        found = index_select.scores(ix.q, ix.weights, ix.key)
        seen = jnp.tril(jnp.ones((64, 64), bool))[None]
        return (index_select.chosen(found, seen, ix.kept)[0],
                reference.chosen_mask(cq[0], h[0], layer, 8))

    # one program: op by op it is a minute
    with jax.default_matmul_precision("highest"):
        got, want = jax.jit(both)(x, layer)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got).sum(-1),
                          np.minimum(16, np.arange(64) + 1))
    # and it is no sliding window
    assert not np.array_equal(np.asarray(got)[40], np.arange(64) > 24)


@pytest.mark.parametrize("live", [
    [True, True, True], [False, True, False]], ids=["all", "one"])
def test_masked_decode_kernel_equals_the_xla_step_and_skips_idle_slots(
        monkeypatch, live):
    """One decode step of a layer with an indexer by both routes over the
    same cache of three slots at lengths 40, 17 and 90 (16 of them chosen,
    the new position's own place among them or not): the same heads' sums,
    the new rows at their places in both leaves, an idle slot's cache as it
    was."""
    rng = np.random.default_rng(3)
    B, H, R, Dr, Di, S = 3, 2, 32, 8, 16, 128
    lens = jnp.asarray([40, 17, 90], jnp.int32)
    cache = {
        "latent": jnp.asarray(rng.normal(size=(2, B, 1, R + Dr, S)),
                              jnp.float32),
        "index": jnp.asarray(rng.normal(size=(2, B, S, Di)), jnp.float32)}
    q = jnp.asarray(rng.normal(size=(B, 1, H, 16 + Dr)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(B, 1, R + Dr)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(R, H, 32)), jnp.float32)
    # slot 0's new key scores lowest of all: its own position is not chosen
    key = jnp.asarray(rng.normal(size=(B, 1, Di)), jnp.float32)
    iq = jnp.abs(jnp.asarray(rng.normal(size=(B, 1, 4, Di)), jnp.float32))
    key = key.at[0].set(-10.0 * jnp.ones((1, Di)))
    ix = kv_cache.Indexed(iq, jnp.abs(jnp.asarray(
        rng.normal(size=(B, 1, 4)), jnp.float32)) + 0.1, key, 16)
    alive = jnp.asarray(live)
    outs = {}
    for impl in ("xla", "pallas_interpret"):
        monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
        # one program a route: op by op it is many times as long
        with jax.default_matmul_precision("highest"):
            outs[impl] = jax.jit(lambda cache: kv_cache.attend_latent(
                cache, 1, q, rows, up,
                kv_cache.step(lens, 1, cache, live=alive), 0.2, ix))(cache)
    (got_cache, got), (want_cache, want) = (
        outs["pallas_interpret"], outs["xla"])
    keep = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep],
                               atol=2e-5)
    assert not np.asarray(got)[~keep].any()
    for name in ("latent", "index"):
        new, old = np.asarray(got_cache[name]), np.asarray(cache[name])
        assert np.array_equal(new[0], old[0])
        assert np.array_equal(new[1][~keep], old[1][~keep])
        np.testing.assert_array_equal(new[1][keep],
                                      np.asarray(want_cache[name])[1][keep])
        for b in np.flatnonzero(keep):   # the new row, at its place
            at = (1, b, int(lens[b])) if name == "index" else (
                1, b, 0, slice(None), int(lens[b]))
            assert not np.array_equal(new[at], old[at])
    if live[0]:
        # slot 0 read 16 of its 40 earlier positions and not its own
        picked = index_select.chosen(index_select.scores(
            ix.q, ix.weights, got_cache["index"][1])[:, 0],
            jnp.arange(S)[None] <= lens[:, None], 16)
        assert not bool(picked[0, 40]) and int(picked[0].sum()) == 16


def test_two_slots_of_different_lengths_answer_as_the_full_forward_does():
    """Two requests at once through ``DecodeEngine`` (40 and 9 prompt
    tokens, 12 answer tokens each): the greedy answers are the full
    forward's, and the engine's two counters are the sums worked out here:
    every query's visible positions a latent layer, and 16 of them or
    all."""
    engine = families._engine(FAMILY)
    spans = engine._span
    cfg = engine.model_config
    prompts = [list(_tokens((n,), seed=n)) for n in (40, 9)]
    try:
        futures = [engine.submit([int(t) for t in p],
                                 SamplingParams(max_new_tokens=12))
                   for p in prompts]
        answers = [list(f.result(300)) for f in futures]
        stats = dict(engine.stats)
    finally:
        engine.shutdown()
    params = engine.params
    forward = jax.jit(lambda p, t: deepseek_v32.forward(p, t, cfg)[0])
    for prompt, answer in zip(prompts, answers):
        # teacher-forced: one forward over the prompt and the answer, at
        # one length for both (causal: the padding after reaches nothing)
        tokens = np.zeros((1, 52), np.int32)
        tokens[0, :len(prompt) + 12] = [int(t) for t in prompt] + answer
        greedy = np.asarray(jnp.argmax(forward(
            params, jnp.asarray(tokens))[0], axis=-1))
        assert list(greedy[len(prompt) - 1:len(prompt) + 11]) == answer
    # admissions: chunks of 16 with their padding (40 -> 16 + 16 + 8, 9 ->
    # 16), every padded query counted as the program computes it
    seen = [t + 1 for start, n in ((0, 16), (16, 16), (32, 8), (0, 16))
            for t in range(start, start + n)]
    admits = spans.named("engine.admit")
    assert sum(a.args["index_positions"] for a in admits) == 3 * sum(seen)
    assert sum(a.args["selected_positions"] for a in admits) == 3 * sum(
        min(16, s) for s in seen)
    ticks = spans.named("engine.tick")
    assert ticks and all(
        t.args["index_positions"] == t.args["latent_positions"]
        and 0 < t.args["selected_positions"] <= t.args["index_positions"]
        for t in ticks)
    for name in ("index_positions", "selected_positions"):
        assert stats[name] == sum(
            s.args[name] for s in admits + ticks)
    # a tick's slot at length n sees n + 1 positions and reads 16 of them
    first = ticks[0].args
    assert first["selected_positions"] == 3 * (16 + min(16, 9 + 1))
    cache_span, = {s.args["bytes"] for s in spans.named("engine.admit.cache")}
    assert cache_span == 3 * (40 + 16) * 128 * 4


@pytest.mark.parametrize("scope", ["mla.index", "mla.select", "mla.sparse"])
def test_the_programs_operations_carry_the_three_scopes(scope):
    """What a trace's reader finds the sparse attention's operations by
    (``benchmarks/lib/dsa_ops.py``): each scope is on some operation of the
    compiled decode and prefill programs, beside the latent layer's own."""
    decoded, prefilled = families.program_texts(FAMILY)
    for name in (scope, "mla.q", "mla.down", "mla.up", "mla.out"):
        assert name in decoded, name
    # a chunk's rows are up-projected inside the kernel, under mla.sparse
    for name in (scope, "mla.q", "mla.down", "mla.out"):
        assert name in prefilled, name
    assert "mla.attend" not in prefilled and "mla.up" not in prefilled
