"""inclusionAI's ``bailing_hybrid`` (Ling-3.0-flash) through the program: the
family's pieces against the benchmark's plain reference
(``benchmarks/references/bailing_hybrid.py``: Kimi Delta Attention one token
after another, latent attention from up-projected keys and values, the held
experts by a loop), and a slot's matrices and latent rows in the engine's
cache through ``DecodeEngine``: prefill in padded chunks that hand on state
and latents, cached decoding through both kernels, a share of the experts
with its counters, speculation refused.

CPU, float32 where logits are compared, seeded weights, the tiny preset's
widths (two periods of five KDA layers and a latent one, 2 heads of 16, 16
experts in 4 groups of which experts 4-7 are held, chunks of 16); each
tolerance is written where it is used. Nothing timed here is a device number.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams
from ray_tpu.llm.engine import engine_programs
from ray_tpu.models import bailing_hybrid, decoder, kv_cache
from ray_tpu.ops import decode_attention, kda
from ray_tpu.parallel import moe
from tests.test_granite_hybrid import _Spans, _prefill_then_decode

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(
    model_family="bailing_hybrid", vocab_size=300, max_seq_len=128,
    num_layers=12, num_heads=2, embed_dim=64, head_dim=16, mlp_dim=96,
    moe_mlp_dim=32, rms_eps=1e-6, first_k_dense=1, num_shared_experts=1,
    layer_group_size=6, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=6e6,
    short_conv_kernel_size=4, kda_lower_bound=-5.0, kda_chunk_size=16,
    state_dtype="float32", moe_num_experts=16, moe_top_k=4,
    moe_norm_topk_prob=True, moe_score_func="sigmoid", moe_route_scale=2.5,
    moe_n_group=4, moe_topk_group=2, moe_num_held=4, moe_first_held=4,
    moe_expert_bias_init_std=0.02, dtype="float32", max_batch_slots=3,
    prefill_buckets=(8, 16),
)


@pytest.fixture(scope="module")
def reference():
    """The plain reference with its constants at the toy's: 4 of 16 experts
    in 2 of 4 groups, experts 4-7 held."""
    from benchmarks.lib import named

    ref = named.load(os.path.join(
        CHECKOUT, "benchmarks", "references", "bailing_hybrid.py"))
    ref.TOP_K, ref.N_GROUP, ref.TOPK_GROUP, ref.FIRST_HELD = 4, 4, 2, 4
    return ref


def _tiny_params(cfg, seed=0):
    """The family's own init with what would hide a fault moved: norm gains
    off 1 (a norm on the wrong vector), the matrices times 4 (at 0.02 and
    64 channels a router's scores all sit at 0.5, beta too, and a head's
    gate: nothing a token says would move them) and the router's bias at
    0.1 a sigmoid's spread (a choice the bias decides)."""
    params = bailing_hybrid.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def moved(path, a):
        name = path[-1].key
        if name.endswith("norm") or name == "norm_f":
            return a * jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)
        if name == "expert_bias":
            return a * 5.0
        if name in ("wte", "lm_head", "conv_w", "dt_bias", "A_log"):
            return a
        return a * 4.0

    return jax.tree_util.tree_map_with_path(moved, params)


def _reference_logits(reference, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits(params, jnp.asarray(tokens)))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(2, 300, shape).astype(np.int32)


def _engine(**changes):
    engine = DecodeEngine(LLMConfig(**{**TINY, **changes}))
    engine.params = bailing_hybrid.serving_params(
        engine.model_config, _tiny_params(engine.model_config))
    return engine


# ------------------------------------------- the family against the reference


def test_the_family_matches_the_reference_and_each_fault_does_not(
        reference, monkeypatch):
    cfg = LLMConfig(**TINY).model_config()
    kinds = decoder.layer_kinds(cfg)
    assert [k.name for k in kinds] == (
        ["kda/dense"] + ["kda/routed"] * 4 + ["latent/routed"]
        + ["kda/routed"] * 5 + ["latent/routed"])
    assert [(k.state, k.recurrence, k.latent) for k in kinds[4:6]] == [
        (16, kda.KDA, None), (None, None, 40)]
    params = _tiny_params(cfg)
    tokens = _tokens((2, 37))          # two chunks and five tokens
    with jax.default_matmul_precision("highest"):
        got = np.asarray(bailing_hybrid.forward(
            params, jnp.asarray(tokens), cfg)[0])
    want = _reference_logits(reference, params, tokens)
    # float32 against float32: the order of the sums (the chunked scan
    # against a token at a time, the sorted dispatch against a loop over
    # experts): 3.7e-6 measured on logits of up to 0.72; the faintest fault
    # below reads 100 times the limit
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 5e-5

    def off(**changes):
        other = dataclasses.replace(cfg, **changes)
        with jax.default_matmul_precision("highest"):
            return np.abs(np.asarray(bailing_hybrid.forward(
                params, jnp.asarray(tokens), other)[0]) - want).max()

    # a chunk of another length is the same recurrence; the lower bound, the
    # rotation's base and the choice among ALL groups are not
    assert off(kda_chunk_size=32) < 5e-5
    assert off(kda_lower_bound=-2.5) > 5e-3
    assert off(rope_theta=1e4) > 5e-3
    assert off(moe=dataclasses.replace(
        cfg.moe, n_group=None, topk_group=None)) > 5e-3
    assert off(moe=dataclasses.replace(cfg.moe, first_held=0)) > 5e-3
    # a layer's own numbers, each a weight moved
    for name, change in (("A_log", lambda a: a + 1.0),
                         ("dt_bias", lambda a: a + 1.0),
                         ("conv_w", lambda a: a.at[..., 0].set(0.0)),
                         ("gate_norm", lambda a: a.at[..., 0].mul(2.0)),
                         ("kv_norm", lambda a: a.at[..., 0].mul(2.0)),
                         ("wz", lambda a: a * 0.0),
                         ("expert_bias", lambda a: a * 0.0)):
        moved = jax.tree_util.tree_map_with_path(
            lambda path, a: change(a) if path[-1].key == name else a, params)
        with jax.default_matmul_precision("highest"):
            assert np.abs(np.asarray(bailing_hybrid.forward(
                moved, jnp.asarray(tokens), cfg)[0]) - want).max() > 5e-5, name

    # the decay's channel vector as its head's mean: Gated DeltaNet, not KDA
    state_in = bailing_hybrid.state_in

    def one_decay_a_head(config, kind, layer, x):
        entering, (g, beta), kept = state_in(config, kind, layer, x)
        return entering, (jnp.broadcast_to(
            g.mean(-1, keepdims=True), g.shape), beta), kept

    monkeypatch.setattr(bailing_hybrid, "state_in", one_decay_a_head)
    assert off() > 5e-3
    monkeypatch.undo()
    # and the reference sees its own constants
    monkeypatch.setattr(reference, "ROUTE_SCALE", 1.0)
    assert np.abs(got - _reference_logits(reference, params, tokens)
                  ).max() > 5e-3


def test_bfloat16_activations_stay_near_the_float32_reference(reference):
    cfg = LLMConfig(**{**TINY, "dtype": "bfloat16"}).model_config()
    params = _tiny_params(cfg)
    tokens = _tokens((2, 37), seed=1)
    got = np.asarray(bailing_hybrid.forward(
        params, jnp.asarray(tokens), cfg)[0])
    want = _reference_logits(reference, params, tokens)
    # bf16's rounding (0.4% a value) through twelve layers of 64 channels,
    # and a router whose fourth and fifth scores change places under it in
    # a few token-layers: a median of 0.031 and a largest of 0.47 measured
    # on logits of up to 0.7. The limits say "the same function", no more;
    # the chip's cell sets its own
    gap = np.abs(got - want)
    assert 1e-4 < np.median(gap) < 0.06 and gap.max() < 1.0


def test_the_stack_is_the_fewer_kinds_and_the_cache_counts_by_kind():
    """The published stack is a lead of six and six periods of six; the
    benchmark's one period is three runs (a dense KDA layer, four routed
    ones in one scan, the latent layer). The cache: a matrix a head and the
    convolution's rows a KDA layer, ONE row of rank + rope values a position
    a latent layer, no keys or values a head."""
    cfg = LLMConfig(**TINY).model_config()
    whole = dataclasses.replace(cfg, num_layers=42, first_k_dense=2)
    segments, _ = bailing_hybrid.layers(whole, None, cached=True)
    assert [(len(s.kinds), s.repeats) for s in segments] == [(6, 1), (6, 6)]
    cut = dataclasses.replace(cfg, num_layers=6)
    segments, _ = bailing_hybrid.layers(cut, None, cached=True)
    assert [([k.name for k in s.kinds], s.repeats) for s in segments] == [
        (["kda/dense"], 1), (["kda/routed"], 4), (["latent/routed"], 1)]
    cache = jax.eval_shape(
        lambda: decoder.init_kv_cache(cfg, 3, 128, block=16))
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "ssm": ((10, 3, 2, 16, 16), jnp.float32),
        "conv": ((10, 3, 3 * 96), jnp.float32),
        "latent": ((2, 3, 1, 40, 128), jnp.float32)}
    # and the costs' count is the leaves'
    from benchmarks.lib import named

    costs = named.load(os.path.join(
        CHECKOUT, "benchmarks", "costs", "bailing_hybrid.py"))
    for sized in (cfg, cut, dataclasses.replace(cfg, num_layers=2)):
        params = jax.eval_shape(
            lambda: bailing_hybrid.init_params(sized, jax.random.PRNGKey(0)))
        model = {f.name: getattr(sized, f.name)
                 for f in dataclasses.fields(sized)}
        model.update(moe_num_experts=16, moe_num_held=4, moe_top_k=4)
        assert costs.param_count(model)["total"] == sum(
            p.size for p in jax.tree.leaves(params))


@pytest.mark.parametrize("bad, match", [
    (dict(state_dtype="bfloat16"), "state_dtype"),
    (dict(kda_lower_bound=-8.0), "kda_lower_bound"),
    (dict(moe_topk_group=5), "groups")])
def test_a_configuration_it_cannot_run_is_refused_by_name(bad, match):
    """A narrower state; a lower bound whose fifteen steps float32 cannot
    hold (``ops/kda.py``); more groups kept than there are."""
    with pytest.raises(ValueError, match=match):
        LLMConfig(**{**TINY, **bad}).model_config()


# ------------------------------------------------- the share and the router


def test_the_four_shares_and_the_shared_expert_once_are_the_whole_layer(
        reference, monkeypatch):
    """The four chips' routed parts, each through the family's own ``ffn``
    with its share of the weights, plus the shared expert ONCE, add up to
    what the uncut reference gives for the layer: the router scores all 16
    experts on every chip, and a pair is computed on exactly one."""
    cfg = LLMConfig(**TINY).model_config()
    whole = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_held=None, first_held=0))
    params = _tiny_params(whole)            # all 16 experts' weights
    segment = params["blocks"]["segments"][1][0]
    layer = jax.tree.map(lambda a: a[0], segment)   # a routed KDA layer
    experts = jax.tree.map(lambda a: a[0], params["blocks"]["experts"])
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 24, 64)),
                    jnp.float32)
    h = reference._rms_norm(x, layer["mlp_norm"]).reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(reference, "FIRST_HELD", 0)
        gates = reference.route(h, experts["router_w"], experts["expert_bias"])
        shared = reference._swiglu(h, layer["shared_gate"],
                                   layer["shared_up"], layer["shared_down"])
        uncut = reference._experts(h, gates, experts) + shared
        parts, rows = jnp.zeros_like(uncut), 0
        for first in (0, 4, 8, 12):
            share = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, first_held=first))
            held = {k: w if k in ("router_w", "expert_bias")
                    else w[first:first + 4] for k, w in experts.items()}
            out, aux, _ = bailing_hybrid.ffn(
                share, "kda/routed", layer, x, None, None, (held, None))
            parts += (out - x).reshape(-1, 64) - shared
            rows += int(aux["moe_rows_held"])
    # every (token, expert) pair on exactly one chip
    assert rows == 2 * 24 * 4
    assert float(jnp.abs(uncut - shared).max()) > 0.05
    # float32 sums in another order: 2e-7 measured
    np.testing.assert_allclose(parts + shared, uncut, atol=2e-6)


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


# ------------------------------------ states and latents in the engine's cache


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("chunks", [
    [(16, 16)], [(16, 16), (16, 16), (5, 8)]], ids=["one_bucket", "chunks"])
def test_padded_chunks_then_cached_steps_match_the_reference(
        reference, monkeypatch, impl, chunks):
    """A prompt in one bucket, and one of 37 tokens as two full chunks of 16
    and 5 tokens padded to 8 (each starts from the states and over the
    latents the one before left; the last one's three padded steps must
    leave the states alone), then 16 decode steps beside two idle slots.
    With ``pallas_interpret`` every decode step is the ``kda_update`` and
    the ``latent_decode_attention`` kernels, and a chunk of 16 up-projects
    the filled blocks of its latent cache (``_latent_blocks``)."""
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
    blocks = []
    latent_blocks = kv_cache._latent_blocks
    monkeypatch.setattr(
        kv_cache, "_latent_blocks",
        lambda leaf, layer, q, *a: blocks.append(q.shape[0])
        or latent_blocks(leaf, layer, q, *a))
    cfg = LLMConfig(**TINY).model_config()
    params = _tiny_params(cfg)
    prompt = sum(n for n, _ in chunks)
    sequence = _tokens((prompt + 16,), seed=2)
    want = _reference_logits(reference, params, sequence[None])[0]
    with jax.default_matmul_precision("highest"):
        rows, _ = _prefill_then_decode(cfg, params, sequence, chunks)
    assert set(blocks) == (set() if impl == "xla" else {16})
    at = list(np.cumsum([n for n, _ in chunks]) - 1) + list(
        range(prompt, prompt + 16))
    assert len(rows) == len(at)
    # float32 against float32, logits and not tokens: the full forward's
    # own distance from the reference (4e-6)
    assert np.abs(np.stack(rows) - want[at]).max() < 5e-5


def test_latents_dropped_at_a_chunk_boundary_show(reference):
    """The third chunk from an empty slot cache: the latent layers see none
    of the first 32 positions, the KDA layers start from no state."""
    cfg = LLMConfig(**TINY).model_config()
    params = _tiny_params(cfg)
    sequence = _tokens((37,), seed=2)
    want = _reference_logits(reference, params, sequence[None])[0]
    prefill = engine_programs(cfg)[0]
    toks = np.zeros((1, 8), np.int32)
    toks[0, :5] = sequence[32:37]
    fresh, _, _ = prefill(
        params, jnp.asarray(toks), decoder.init_kv_cache(cfg, 1, 128, block=16),
        jnp.asarray([32], jnp.int32), jnp.asarray([5], jnp.int32),
        rows=jnp.asarray([4]))
    assert np.abs(np.asarray(fresh[0, 0]) - want[36]).max() > 2e-2


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


def test_an_idle_slots_cache_is_untouched_by_other_slots_ticks(monkeypatch):
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas_interpret")
    cfg = LLMConfig(**TINY).model_config()
    params = _tiny_params(cfg)
    decode = engine_programs(cfg)[2]
    rng = np.random.default_rng(4)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        decoder.init_kv_cache(cfg, 3, 128, block=16))
    before = jax.tree.map(np.asarray, cache)
    packed = np.zeros((3, 3), np.int32)
    packed[:, 0] = 7, 20, 1      # slot 0 decodes at length 20
    ids = jnp.zeros((3,), jnp.int32)
    for _ in range(3):
        ids, _, cache, counts = decode(params, ids, cache, jnp.asarray(packed))
        packed[1, 0] += 1
    # a share's counts: experts touched and rows held a layer; the dense
    # layer none, a routed layer no more rows than the one token's 4 pairs
    assert counts.shape == (12, 2) and not np.asarray(counts[0]).any()
    assert (np.asarray(counts[1:, 1]) <= 4).all()
    for name in ("ssm", "conv", "latent"):
        after = np.asarray(cache[name])
        assert (after[:, 1:] == before[name][:, 1:]).all(), name
        assert np.abs(after[:, 0] - before[name][:, 0]).max() > 1e-3, name


def test_two_slots_of_different_lengths_answer_as_the_full_forward_does(
        reference):
    """``model_family: bailing_hybrid`` through ``LLMConfig`` and
    ``DecodeEngine``: two requests in one batch, a prompt of 37 (three
    chunks) and one of 6: every answer token's log-probability is the
    reference's full forward over prompt + answer on the same share. And
    the counters of what only this family has."""
    engine = _engine()
    engine._span = _Spans()
    prompts = [[int(t) for t in _tokens((n,), seed=n)] for n in (37, 6)]
    params = SamplingParams(max_new_tokens=12, logprobs=1)
    futures = [engine.submit(p, params) for p in prompts]
    for prompt, future in zip(prompts, futures):
        out = future.result(timeout=600)
        want = jax.nn.log_softmax(_reference_logits(
            reference, engine.params, np.asarray([prompt + list(out)])
        )[0], axis=-1)
        got = np.array([lp["logprob"] for lp in out.logprobs])
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + 12)
        # float32 logits of up to 0.7 through a log-softmax over 300
        assert np.abs(got - np.asarray(want)[at, list(out)]).max() < 1e-4
    engine.shutdown()
    admits = engine._span.named("engine.admit")
    ticks = engine._span.named("engine.tick")
    stats = engine.stats
    assert [a.args["chunks"] for a in admits] == [3, 1]
    assert stats["ssm_prefill_tokens"] == 43
    assert {a.args["layers_state"] for a in admits} == {10}
    assert stats["state_slot_layers"] == 10 * stats["slot_ticks"] == sum(
        t.args["state_slot_layers"] for t in ticks)
    # two latent layers, none that holds keys and values a head: a tick's
    # latent positions are its slots' lengths and columns, a layer
    assert {t.args["layers_full"] for t in ticks} == {0}
    assert all(t.args["latent_positions"] == 2 * t.args["cache_positions"]
               for t in ticks)
    assert stats["latent_positions"] == 2 * stats["cache_positions"] == sum(
        t.args["latent_positions"] for t in ticks)
    # a chunk's key positions count the latent layers' (37 tokens: 16, 32
    # and 37 positions seen; 6 tokens: 6), two layers each
    assert stats["prefill_key_positions"] == 2 * (16 + 32 + 40 + 8)
    # the held experts' rows: a share of the routed pairs (4 of 16 experts
    # held, 2 of 4 groups kept: a quarter under a balanced router), counted
    # by the programs: the ticks' sum is the programs' that were read
    held = sum(t.args["moe_rows_held"] for t in ticks)
    rows = sum(t.args["moe_rows"] for t in ticks)
    assert rows == stats["slot_ticks"] * 4 * 11
    assert 0 < held < rows and held <= stats["moe_rows_held"]
    assert 0.1 < stats["moe_rows_held"] / stats["moe_rows"] < 0.45


def test_a_reused_slot_answers_as_a_fresh_engine_does():
    """Five requests through three slots, chunked and not: each answer is
    what an engine that has seen nothing else gives, so no slot starts from
    its last tenant's matrices or reads its latents."""
    prompts = [[int(t) for t in _tokens((n,), seed=10 + n)]
               for n in (30, 5, 21, 40, 3)]
    params = SamplingParams(max_new_tokens=8)
    want = []
    for prompt in prompts:
        fresh = _engine()
        want.append(list(fresh.generate(prompt, params)))
        fresh.shutdown()
    engine = _engine()
    futures = [engine.submit(p, params) for p in prompts]
    assert [list(f.result(timeout=600)) for f in futures] == want
    engine.shutdown()


def test_speculation_is_refused_with_the_reason():
    with pytest.raises(ValueError, match="cannot be rolled back"):
        DecodeEngine(LLMConfig(**{**TINY, "speculative_ngram_k": 2}))


def test_the_programs_operations_carry_the_layers_scopes(monkeypatch):
    """What a trace's reader finds a KDA layer's and a latent layer's
    operations by (``benchmarks/lib/bailing_ops.py``): every scope is on
    some operation of the compiled decode and prefill programs."""
    cfg = LLMConfig(**TINY).model_config()
    params = jax.eval_shape(lambda: bailing_hybrid.serving_params(
        cfg, bailing_hybrid.init_params(cfg, jax.random.PRNGKey(0))))
    prefill, _, decode, _ = engine_programs(cfg)
    cache = jax.eval_shape(lambda: decoder.init_kv_cache(cfg, 3, 128, block=16))
    slots = jax.ShapeDtypeStruct((3,), jnp.int32)
    text = decode.lower(params, slots, cache, jax.ShapeDtypeStruct(
        (3, 3), jnp.int32)).compile().as_text()
    for scope in ("kda.in_proj", "kda.conv", "kda.update", "kda.gate_norm",
                  "kda.out_proj", "mla.q", "mla.down", "mla.attend",
                  "mla.up", "mla.out", "moe.route", "moe.shared"):
        assert scope in text, scope
    cache1 = jax.eval_shape(lambda: decoder.init_kv_cache(cfg, 1, 128, block=16))
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: "pallas_interpret")
    text = prefill.lower(
        params, jax.ShapeDtypeStruct((1, 16), jnp.int32), cache1, one, one,
        rows=one).compile().as_text()
    for scope in ("kda.scan", "kda.conv", "mla.attend", "mla.up"):
        assert scope in text, scope
