"""DeepSeek-V3.2-Exp (``deepseek_v32``) through the program: the family's
pieces against the benchmark's plain reference
(``benchmarks/references/deepseek_v32.py``: the indexer's scores, the choice
by a sort as a [T, T] mask, latent attention from up-projected keys and
values a head at a time, the held experts by a loop), the exact choice
against a sort, YaRN's numbers, and a slot's two rows a position in the
engine's cache through ``DecodeEngine``: prefill in padded chunks, cached
decoding through the masked kernel, the engine's two counters.

CPU, float32 where logits are compared, seeded weights, the toy's widths (a
dense layer and two routed ones, 2 heads of 16 | 8, an indexer of 4 heads of
16 that keeps 16 positions, YaRN from a context of 32, 16 experts in 4
groups of which experts 4-7 are held, chunks of 16); each tolerance is
written where it is used. Nothing timed here is a device number.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.tests import faults_deepseek_v32 as faults
from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams
from ray_tpu.llm.engine import engine_programs
from ray_tpu.models import bailing_hybrid, decoder, deepseek_v32, kv_cache
from ray_tpu.ops import block_attention, index_select
from tests.test_granite_hybrid import _Spans, _prefill_then_decode

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(
    model_family="deepseek_v32", vocab_size=300, max_seq_len=128,
    num_layers=3, num_heads=2, embed_dim=64, mlp_dim=96, moe_mlp_dim=32,
    rms_eps=1e-6, first_k_dense=1, num_shared_experts=1, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10000.0, rope_factor=40.0, rope_original_max_position=32,
    rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale_all_dim=1.0,
    index_n_heads=4, index_head_dim=16, index_topk=16, moe_num_experts=16,
    moe_top_k=4, moe_norm_topk_prob=True, moe_score_func="sigmoid",
    moe_route_scale=2.5, moe_n_group=4, moe_topk_group=2, moe_num_held=4,
    moe_first_held=4, moe_expert_bias_init_std=0.02, dtype="float32",
    max_batch_slots=3, prefill_buckets=(8, 16),
)


@pytest.fixture(scope="module")
def reference():
    """The plain reference with its constants at the toy's: 16 positions
    kept, YaRN from 32, 4 of 16 experts in 2 of 4 groups, experts 4-7 held,
    16 rows at a time."""
    from benchmarks.lib import named

    ref = named.load(os.path.join(
        CHECKOUT, "benchmarks", "references", "deepseek_v32.py"))
    ref.TOP_K, ref.N_GROUP, ref.TOPK_GROUP, ref.FIRST_HELD = 4, 4, 2, 4
    ref.INDEX_TOPK, ref.ROPE_ORIGINAL, ref.BLOCK = 16, 32, 16
    return ref


def _config(**changes):
    return LLMConfig(**{**TINY, **changes}).model_config()


def _tiny_params(cfg, seed=0):
    """The family's own init with what would hide a fault moved: norm gains
    off 1 and the indexer's LayerNorm bias off 0 (a norm on the wrong
    vector), the matrices times 4 (at 0.02 and 64 channels a router's
    scores all sit at 0.5 and a softmax over a few dozen positions is flat)
    and the router's bias at 0.1 a sigmoid's spread."""
    params = deepseek_v32.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def moved(path, a):
        name = path[-1].key
        if name.endswith("norm") or name == "norm_f":
            return a * jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)
        if name == "ik_bias":
            return a + 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        if name == "expert_bias":
            return a * 5.0
        if name in ("wte", "lm_head"):
            return a
        return a * 4.0

    return jax.tree_util.tree_map_with_path(moved, params)


def _reference_logits(reference, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(reference.logits)(params, jnp.asarray(tokens)))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(2, 300, shape).astype(np.int32)


# ------------------------------------------- the family against the reference


@pytest.mark.parametrize("variant", ("sound",) + faults.VARIANTS)
def test_the_family_matches_the_reference_and_each_fault_does_not(
        reference, monkeypatch, variant):
    """The full forward over 64 tokens (16 of up to 64 positions chosen)
    against the reference in float32: 5e-5, where its own rounding is 4e-6;
    and with each of the cell's faults planted on the program's side
    (``benchmarks/tests/faults_deepseek_v32.py``) it is far from it."""
    config = {"model": dict(TINY)}
    faults.plant(variant, config, monkeypatch.setattr)
    cfg = LLMConfig(**config["model"]).model_config()
    params = _tiny_params(cfg)
    tokens = _tokens((2, 64))
    want = _reference_logits(reference, params, tokens)
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(lambda p, t: deepseek_v32.forward(p, t, cfg))(
            params, jnp.asarray(tokens))
    gap = np.abs(np.asarray(got) - want)
    print(variant, gap.max())
    if variant == "sound":
        assert gap.max() < 5e-5
        return
    assert gap.max() > 1e-2
    # the first 16 tokens see at most 16 positions: every one is chosen,
    # whatever the indexer does
    if variant in ("recent", "ik_unrotated", "no_relu"):
        assert gap[:, :16].max() < 5e-5


def test_bfloat16_activations_stay_near_the_float32_reference(reference):
    cfg = _config(dtype="bfloat16")
    params = _tiny_params(cfg)
    tokens = _tokens((2, 64))
    want = _reference_logits(reference, params, tokens)
    got, _ = jax.jit(lambda p, t: deepseek_v32.forward(p, t, cfg))(
        params, jnp.asarray(tokens))
    # bf16 at 64 channels: most tokens within 0.05, a flipped choice more
    assert np.median(np.abs(np.asarray(got) - want)) < 0.05


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


def test_two_rows_a_position_in_the_cache_plan():
    """A dense lead and the routed layers, each one scan; the cache: a
    latent row of rank + rope values AND the indexer's key a position and
    layer, no keys or values a head; the costs' count is the leaves'."""
    cfg = _config()
    whole = dataclasses.replace(cfg, num_layers=61, first_k_dense=3)
    segments, _ = deepseek_v32.layers(whole, None, cached=True)
    assert [([k.name for k in s.kinds], s.repeats) for s in segments] == [
        (["dense"], 3), (["routed"], 58)]
    cache = jax.eval_shape(
        lambda: decoder.init_kv_cache(cfg, 3, 128, block=16))
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "latent": ((3, 3, 1, 40, 128), jnp.float32),
        "index": ((3, 3, 128, 16), jnp.float32)}
    from benchmarks.lib import named

    costs = named.load(os.path.join(
        CHECKOUT, "benchmarks", "costs", "deepseek_v32.py"))
    for sized in (cfg, dataclasses.replace(cfg, num_layers=5),
                  dataclasses.replace(cfg, first_k_dense=3)):
        params = jax.eval_shape(
            lambda: deepseek_v32.init_params(sized, jax.random.PRNGKey(0)))
        model = {f.name: getattr(sized, f.name)
                 for f in dataclasses.fields(sized)}
        model.update(moe_num_experts=16, moe_num_held=4, moe_top_k=4)
        assert costs.param_count(model)["total"] == sum(
            p.size for p in jax.tree.leaves(params))


@pytest.mark.parametrize("bad, match", [
    (dict(first_k_dense=-1), "first_k_dense -1"),
    (dict(qk_rope_head_dim=32), "rotates its first 32 channels of 16"),
    (dict(moe_dropless=False), "dropless")])
def test_a_configuration_it_cannot_run_is_refused_by_name(bad, match):
    from ray_tpu.models import config_for

    sizes = {k: v for k, v in TINY.items() if k not in (
        "model_family", "max_batch_slots", "prefill_buckets")}
    with pytest.raises(ValueError, match=match):
        config_for("deepseek_v32", **{"moe_dropless": True, **sizes, **bad})


def test_the_four_shares_and_the_shared_expert_once_are_the_whole_layer(
        reference, monkeypatch):
    """The four chips' routed parts, each through the family's own ``ffn``
    with its share of the weights, plus the shared expert ONCE, add up to
    what the uncut reference gives for the layer: the router scores all 16
    experts on every chip, and a pair is computed on exactly one."""
    cfg = _config()
    whole = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_held=None, first_held=0))
    params = _tiny_params(whole)            # all 16 experts' weights
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["segments"][1][0])
    stacked = jax.tree.map(lambda a: a[:1], params["blocks"]["experts"])
    experts = jax.tree.map(lambda a: a[0], stacked)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 24, 64)),
                    jnp.float32)
    h = reference._rms_norm(x, layer["mlp_norm"]).reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(reference, "FIRST_HELD", 0)
        gates = reference.route(h, experts["router_w"], experts["expert_bias"])
        shared = reference._swiglu(h, layer["shared_gate"],
                                   layer["shared_up"], layer["shared_down"])
        uncut = reference._experts(h, gates, stacked, 0) + shared
        parts, rows = jnp.zeros_like(uncut), 0
        for first in (0, 4, 8, 12):
            share = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, first_held=first))
            held = {k: w if k in ("router_w", "expert_bias")
                    else w[first:first + 4] for k, w in experts.items()}
            out, aux, _ = deepseek_v32.ffn(
                share, "routed", layer, x, None, None, (held, None))
            parts += (out - x).reshape(-1, 64) - shared
            rows += int(aux["moe_rows_held"])
    # every (token, expert) pair on exactly one chip
    assert rows == 2 * 24 * 4
    assert float(jnp.abs(uncut - shared).max()) > 0.05
    # float32 sums in another order: 2e-7 measured
    np.testing.assert_allclose(parts + shared, uncut, atol=2e-6)


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


def test_the_programs_choice_is_the_references(reference):
    """A layer's indexer through the family's own pieces, its scores and its
    choice (``index_select``), against the reference's [T, T] mask from a
    sort: the same set for every query of 64."""
    cfg = _config()
    params = _tiny_params(cfg)
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["segments"][1][0])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 64, 64)),
                    jnp.float32)
    pos = jnp.arange(64)[None]
    with jax.default_matmul_precision("highest"):
        h = reference._rms_norm(x, layer["mix_norm"])
        cq = reference._rms_norm(h @ layer["w_dq"], layer["q_norm"])
        ix = deepseek_v32._indexed(cfg, layer, h, cq, pos)
        found = index_select.scores(ix.q, ix.weights, ix.key)
        seen = jnp.tril(jnp.ones((64, 64), bool))[None]
        got = index_select.chosen(found, seen, ix.kept)[0]
        want = reference.chosen_mask(cq[0], h[0], layer, 8)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(got).sum(-1),
                          np.minimum(16, np.arange(64) + 1))
    # and it is no sliding window
    assert not np.array_equal(np.asarray(got)[40], np.arange(64) > 24)


# ---------------------------------------------------- the cache and the engine


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("chunks", [
    [(16, 16)], [(16, 16), (16, 16), (5, 8)]], ids=["one_bucket", "chunks"])
def test_padded_chunks_then_cached_steps_match_the_reference(
        reference, monkeypatch, impl, chunks):
    """A prompt in one bucket (every position chosen), and one of 37 tokens
    as two full chunks of 16 and 5 tokens padded to 8 (each writes both rows
    of its positions and chooses among everything cached), then 16 decode
    steps beside two idle slots, 16 of up to 53 positions chosen. With
    ``pallas_interpret`` every decode step is the
    ``latent_decode_attention`` kernel under the choice's mask, and a chunk
    of 16 scores the filled blocks of its keys and attends the filled blocks
    of its rows through ``selected_block_attention``, 32 positions at a
    time."""
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
    monkeypatch.setattr(kv_cache, "CHOICE_WIDTHS", (32, 64))
    monkeypatch.setattr(index_select, "POSITIONS", 32)
    monkeypatch.setattr(block_attention, "SELECTED_POSITIONS", 32)
    blocks = []
    selected = kv_cache.selected_block_attention
    monkeypatch.setattr(
        kv_cache, "selected_block_attention",
        lambda q, up, leaf, picked, *a, **k: blocks.append(
            (q.shape[0], picked.shape, k["width"]))
        or selected(q, up, leaf, picked, *a, **k))
    cfg = _config()
    params = _tiny_params(cfg)
    prompt = sum(n for n, _ in chunks)
    sequence = _tokens((prompt + 16,), seed=2)
    want = _reference_logits(reference, params, sequence[None])[0]
    with jax.default_matmul_precision("highest"):
        rows, cache = _prefill_then_decode(cfg, params, sequence, chunks)
    # a chunk's choice and attention over the narrowest width that holds it
    assert set(blocks) == (set() if impl == "xla" else {
        (16, (16, 128), w) for w in (32, 64, 128)})
    at = list(np.cumsum([n for n, _ in chunks]) - 1) + list(
        range(prompt, prompt + 16))
    assert len(rows) == len(at)
    # float32 against float32, logits and not tokens: the full forward's
    # own distance from the reference (4e-6)
    assert np.abs(np.stack(rows) - want[at]).max() < 5e-5
    # both rows of every position of slot 1, and nothing of the idle slots
    for name, leaf in cache.items():
        # [layer, slot, position, channel] of either leaf
        leaf = np.asarray(leaf) if name == "index" else np.swapaxes(
            np.asarray(leaf)[:, :, 0], 2, 3)
        assert np.abs(leaf[:, 1, :prompt + 16]).max(axis=-1).min() > 0
        if impl != "xla":   # the XLA step computes every slot
            assert not leaf[:, [0, 2]].any()
        assert not leaf[:, 1, prompt + 16:].any()


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
        at = kv_cache.step(lens, 1, cache, live=alive)
        with jax.default_matmul_precision("highest"):
            outs[impl] = kv_cache.attend_latent(
                cache, 1, q, rows, up, at, 0.2, ix)
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


def _engine(**changes):
    engine = DecodeEngine(LLMConfig(**{**TINY, **changes}))
    engine.params = deepseek_v32.serving_params(
        engine.model_config, _tiny_params(engine.model_config))
    return engine


def test_two_slots_of_different_lengths_answer_as_the_full_forward_does():
    """Two requests at once through ``DecodeEngine`` (40 and 9 prompt
    tokens, 12 answer tokens each): the greedy answers are the full
    forward's, and the engine's two counters are the sums worked out here:
    every query's visible positions a latent layer, and 16 of them or
    all."""
    engine = _engine()
    spans = engine._span = _Spans()
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
        # teacher-forced: one forward over the prompt and the answer
        tokens = [int(t) for t in prompt] + answer
        greedy = np.asarray(jnp.argmax(forward(
            params, jnp.asarray([tokens]))[0], axis=-1))
        assert list(greedy[len(prompt) - 1:-1]) == answer
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


def test_speculation_has_no_quarrel_with_an_indexer_but_the_model_must_take_real():
    """The engine's programs are told which rows are tokens (a router's),
    and count a share's rows: three results."""
    cfg = _config()
    assert cfg.moe.num_held == 4 and cfg.moe.n_group == 4
    kinds = decoder.layer_kinds(cfg)
    assert [k.routed for k in kinds] == [False, True, True]
    assert all(k.latent == 40 and k.index.kept == 16 for k in kinds)


@pytest.mark.parametrize("scope", ["mla.index", "mla.select", "mla.sparse"])
def test_the_programs_operations_carry_the_three_scopes(monkeypatch, scope):
    """What a trace's reader finds the sparse attention's operations by
    (``benchmarks/lib/dsa_ops.py``): each scope is on some operation of the
    compiled decode and prefill programs, beside the latent layer's own."""
    cfg = _config()
    params = jax.eval_shape(lambda: deepseek_v32.serving_params(
        cfg, deepseek_v32.init_params(cfg, jax.random.PRNGKey(0))))
    prefill, _, decode, _ = engine_programs(cfg)
    cache = jax.eval_shape(lambda: decoder.init_kv_cache(cfg, 3, 128, block=16))
    slots = jax.ShapeDtypeStruct((3,), jnp.int32)
    text = decode.lower(params, slots, cache, jax.ShapeDtypeStruct(
        (3, 3), jnp.int32)).compile().as_text()
    for name in (scope, "mla.q", "mla.down", "mla.up", "mla.out"):
        assert name in text, name
    cache1 = jax.eval_shape(lambda: decoder.init_kv_cache(cfg, 1, 128, block=16))
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas_interpret")
    text = prefill.lower(
        params, jax.ShapeDtypeStruct((1, 16), jnp.int32), cache1, one, one,
        rows=one).compile().as_text()
    # a chunk's rows are up-projected inside the kernel, under mla.sparse
    for name in (scope, "mla.q", "mla.down", "mla.out"):
        assert name in text, name
    assert "mla.attend" not in text and "mla.up" not in text
