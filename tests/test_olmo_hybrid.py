"""Ai2's ``olmo_hybrid`` (Olmo-Hybrid-7B) through the program: the family's
pieces against the benchmark's plain reference
(``benchmarks/references/olmo_hybrid.py``: the gated delta rule one token
after another), and the state layers' matrices in the engine's cache beside
the attention layers' keys and values through ``DecodeEngine``: prefill in
padded chunks that hand the state on, cached decoding, two slots of
different lengths in one batch, speculation refused.

CPU, float32 where logits are compared, seeded weights, the tiny preset's
widths (two periods of three state layers and an attention layer, 2 heads of
8 x 16, chunks of 4); each tolerance is written where it is used. Nothing
timed here is a device number.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams
from ray_tpu.llm.engine import engine_programs
from ray_tpu.models import decoder, kv_cache, olmo_hybrid
from ray_tpu.ops import delta_rule
from ray_tpu.ops.block_attention import block_attention
from tests.test_granite_hybrid import _Spans, _prefill_then_decode

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, F = olmo_hybrid.LINEAR, olmo_hybrid.FULL

TINY = dict(
    model_family="olmo_hybrid", vocab_size=300, max_seq_len=128,
    num_layers=8, num_heads=2, num_kv_heads=2, embed_dim=64, head_dim=32,
    mlp_dim=96, rms_eps=1e-6, layer_types=(L, L, L, F) * 2,
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, linear_chunk_size=4,
    state_dtype="float32", dtype="float32", max_batch_slots=3,
    prefill_buckets=(8, 16),
)


@pytest.fixture(scope="module")
def reference():
    from benchmarks.lib import named

    return named.load(os.path.join(
        CHECKOUT, "benchmarks", "references", "olmo_hybrid.py"))


def _tiny_params(cfg, seed=0):
    """The family's own init with what would hide a fault moved: norm gains
    of all ones (a norm on the wrong vector), and a mixer whose matrices of
    0.02 leave ``beta`` at 1 and the decay where ``dt_bias`` put it whatever
    the token (every matrix doubled: beta from 0.8 to 1.2, the decays
    apart). Not further: every branch is normed to the stream's size and a
    head's output is normed over its own 16 channels, so where a query
    nearly cancels against the keys the state holds (a sequence's first
    tokens) float32's rounding of that sum is what the norm scales up. At
    matrices times 6 two float32 forwards of the same equations (the
    family's with ``delta_rule.recurrence`` in the scan's place, and the
    reference) read 7e-3 apart on one seed of three."""
    params = olmo_hybrid.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def moved(path, a):
        name = path[-1].key
        if name.endswith("norm") or name == "norm_f":
            return a * jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)
        if name in ("wte", "lm_head", "conv_w", "dt_bias", "A_log"):
            return a
        return a * 2.0

    return jax.tree_util.tree_map_with_path(moved, params)


def _reference_logits(reference, params, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits(params, jnp.asarray(tokens)))


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(2, 300, shape).astype(np.int32)


def _engine(**changes):
    engine = DecodeEngine(LLMConfig(**{**TINY, **changes}))
    engine.params = olmo_hybrid.serving_params(
        engine.model_config, _tiny_params(engine.model_config))
    return engine


# ------------------------------------------- the family against the reference


def test_the_family_matches_the_reference_and_each_fault_does_not(
        reference, monkeypatch):
    cfg = LLMConfig(**TINY).model_config()
    assert cfg == dataclasses.replace(
        olmo_hybrid.PRESETS["olmo-hybrid-tiny"], vocab_size=300,
        dtype=jnp.float32, attention_impl="xla", head_dim=32,
        layer_types=cfg.layer_types)
    assert [(k.state, k.recurrence) for k in decoder.layer_kinds(cfg)] == [
        (4, delta_rule.GATED_DELTA)] * 3 + [(None, None)] + [
        (4, delta_rule.GATED_DELTA)] * 3 + [(None, None)]
    params = _tiny_params(cfg)
    tokens = _tokens((2, 37))          # nine chunks and one token
    got = np.asarray(olmo_hybrid.forward(
        params, jnp.asarray(tokens), cfg)[0])
    want = _reference_logits(reference, params, tokens)
    # float32 against float32: the order of the sums (the chunked scan
    # against a token at a time) under the norms ``_tiny_params`` speaks of:
    # 1.0e-5 to 1.9e-5 measured over three seeds on logits of up to 0.8, and
    # the same with the token-by-token recurrence in the scan's place; the
    # faintest fault below reads 100 times the limit
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 1e-4

    def off(**changes):
        other = dataclasses.replace(cfg, **changes)
        return np.abs(np.asarray(olmo_hybrid.forward(
            params, jnp.asarray(tokens), other)[0]) - want).max()

    # beta without its factor 2 reads far above that; a chunk of another
    # length is the same recurrence
    assert off(linear_allow_neg_eigval=False) > 1e-2
    assert off(linear_chunk_size=16) < 1e-4
    # a state layer's own numbers, each a weight moved
    for name, change in (("A_log", lambda a: a + 1.0),
                         ("dt_bias", lambda a: a + 1.0),
                         ("conv_w", lambda a: a.at[..., 0].set(0.0)),
                         ("gate_norm", lambda a: a.at[..., 0].mul(2.0)),
                         ("q_norm", lambda a: a.at[..., 0].mul(2.0))):
        moved = jax.tree_util.tree_map_with_path(
            lambda path, a: change(a) if path[-1].key == name else a, params)
        assert np.abs(np.asarray(olmo_hybrid.forward(
            moved, jnp.asarray(tokens), cfg)[0]) - want).max() > 1e-4, name
    # the gate before the norm and not after it (the order Mamba-2 has)
    def gate_first(config, layer, x, y, gate):
        y = y * jax.nn.silu(gate).reshape(y.shape)
        y = olmo_hybrid._rms_norm(y, layer["gate_norm"], config.rms_eps)
        out = y.reshape(*y.shape[:2], -1) @ layer["delta_out"]
        return olmo_hybrid._branch(config, x, out, layer["mix_norm"])

    monkeypatch.setattr(olmo_hybrid, "state_out", gate_first)
    assert off() > 1e-2
    monkeypatch.undo()
    # and the reference sees its own constants
    monkeypatch.setattr(reference, "BETA_SCALE", 1.0)
    assert np.abs(got - _reference_logits(reference, params, tokens)
                  ).max() > 1e-2


def test_bfloat16_activations_stay_near_the_float32_reference(reference):
    cfg = LLMConfig(**{**TINY, "dtype": "bfloat16"}).model_config()
    params = _tiny_params(cfg)
    tokens = _tokens((2, 37), seed=1)
    got = np.asarray(olmo_hybrid.forward(
        params, jnp.asarray(tokens), cfg)[0])
    want = _reference_logits(reference, params, tokens)
    # bf16's rounding (0.4% a value) through eight layers of 64 channels
    # whose every branch is normed to the stream's own size, so that nothing
    # damps what a layer adds: a median of 0.03 and a largest of 0.23
    # measured on logits of up to 0.8 (Granite's branches enter at 0.22 and
    # read 4e-3). The limits say "the same function", no more; the served
    # path's stream is float32, and the chip's cell sets its own limits
    gap = np.abs(got - want)
    assert 1e-4 < np.median(gap) < 0.06 and gap.max() < 0.5


def test_the_stack_is_the_period_and_the_cache_counts_by_kind(reference):
    published = (L, L, L, F) * 8
    cfg = LLMConfig(**{**TINY, "num_layers": 32,
                       "layer_types": published}).model_config()
    segments, experts = olmo_hybrid.layers(cfg, None, cached=True)
    assert experts is None and len(segments) == 1
    assert segments[0].repeats == 8
    assert [k.name for k in segments[0].kinds] == [L, L, L, F]
    cache = jax.eval_shape(
        lambda: decoder.init_kv_cache(cfg, 3, 128, block=16))
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        "k": ((8, 3, 2, 32, 128), jnp.float32),
        "v": ((8, 3, 2, 32, 128), jnp.float32),
        # a head's [8, 16] with its values up to a lane tile of 128
        "ssm": ((24, 3, 2, 8, 128), jnp.float32),
        "conv": ((24, 3, 3 * 64), jnp.float32)}
    assert set(olmo_hybrid.state_leaves(cfg)) == set(kv_cache.STATE)
    # the layers there are, where fewer are asked for: the first of them
    two = dataclasses.replace(cfg, num_layers=2)
    assert [k.name for k in decoder.layer_kinds(two)] == [L, L]
    # and the costs' count is the leaves'
    from benchmarks.lib import named

    costs = named.load(os.path.join(
        CHECKOUT, "benchmarks", "costs", "olmo_hybrid.py"))
    for sized in (cfg, two, LLMConfig(**TINY).model_config()):
        params = jax.eval_shape(
            lambda: olmo_hybrid.init_params(sized, jax.random.PRNGKey(0)))
        model = {**{f.name: getattr(sized, f.name)
                    for f in dataclasses.fields(sized)}}
        assert costs.param_count(model)["total"] == sum(
            p.size for p in jax.tree.leaves(params))


@pytest.mark.parametrize("bad, match", [
    (dict(layer_types=(L, "sliding_attention", L, F) * 2), "layer_types"),
    (dict(linear_num_value_heads=4), "linear_num_value_heads"),
    (dict(state_dtype="bfloat16"), "state_dtype"),
    (dict(state_dtype="float16"), "state_dtype")])
def test_a_configuration_it_cannot_run_is_refused_by_name(bad, match):
    """A narrower state among them: the cache holds a state in float32 and
    the kernel steps nothing else."""
    with pytest.raises(ValueError, match=match):
        LLMConfig(**{**TINY, **bad}).model_config()
    with pytest.raises(ValueError, match=match):
        DecodeEngine(LLMConfig(**{**TINY, **bad}))


# --------------------------------------------- the state in the engine's cache


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_three_padded_chunks_then_cached_steps_match_the_reference(
        reference, monkeypatch, impl):
    """A prompt of 37 tokens as two full chunks of 16 and 5 tokens padded
    to 8 (each starts from the state the one before left, the last one's
    three padded steps must leave it alone), then 16 decode steps, beside
    two idle slots; with ``pallas_interpret`` every decode step's state
    update is the ``delta_update`` kernel, and the two chunks of 16 attend
    through the ``block_attention`` kernel (the third is no whole tile of
    tokens: XLA's)."""
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
    blocks = []
    monkeypatch.setattr(
        kv_cache, "block_attention",
        lambda q, *a, **kw: blocks.append(q.shape[1])
        or block_attention(q, *a, **kw))
    cfg = LLMConfig(**TINY).model_config()
    params = _tiny_params(cfg)
    sequence = _tokens((53,), seed=2)
    want = _reference_logits(reference, params, sequence[None])[0]
    rows, _ = _prefill_then_decode(
        cfg, params, sequence, [(16, 16), (16, 16), (5, 8)])
    assert set(blocks) == (set() if impl == "xla" else {16})
    at = [15, 31, 36] + list(range(37, 53))
    assert len(rows) == len(at) == 19
    # float32 against float32, logits and not tokens: 2.0e-5 measured, the
    # full forward's own distance from the reference (``_tiny_params`` says
    # what the norms do to float32's rounding); the fault below reads
    # 1,000 times that
    assert np.abs(np.stack(rows) - want[at]).max() < 1e-4

    # a state dropped at the chunk boundary reads far above that
    prefill = engine_programs(cfg)[0]
    toks = np.zeros((1, 8), np.int32)
    toks[0, :5] = sequence[32:37]
    fresh, _, _ = prefill(
        params, jnp.asarray(toks), decoder.init_kv_cache(cfg, 1, 128, block=16),
        jnp.asarray([32], jnp.int32), jnp.asarray([5], jnp.int32),
        rows=jnp.asarray([4]))
    assert np.abs(np.asarray(fresh[0, 0]) - want[36]).max() > 2e-2


def test_padded_steps_leave_state_and_tail_as_the_last_real_token_did():
    cfg = LLMConfig(**TINY).model_config()
    params = _tiny_params(cfg)
    prefill = engine_programs(cfg)[0]
    sequence = _tokens((16,), seed=3)
    empty = decoder.init_kv_cache(cfg, 1, 128, block=16)

    def state_after(n, bucket):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = sequence[:n]
        _, cache, _ = prefill(
            params, jnp.asarray(toks), empty, jnp.zeros((1,), jnp.int32),
            jnp.asarray([n], jnp.int32), rows=jnp.asarray([n - 1]))
        return cache

    padded, exact = state_after(5, 16), state_after(5, 8)
    for name in ("ssm", "conv"):
        # two programs of two shapes: the same sums, perhaps not fused alike
        np.testing.assert_allclose(padded[name], exact[name], atol=1e-6)
    assert float(jnp.abs(padded["ssm"]).max()) > 1e-3
    # the tail is the last three rows that entered: not what padding made
    longer = state_after(8, 8)
    assert float(jnp.abs(longer["conv"] - exact["conv"]).max()) > 1e-3


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_an_idle_slots_state_is_untouched_by_other_slots_ticks(
        monkeypatch, impl):
    monkeypatch.setattr(kv_cache, "_decode_impl", lambda: impl)
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
        ids, _, cache, _ = decode(params, ids, cache, jnp.asarray(packed))
        packed[1, 0] += 1
    for name in ("ssm", "conv"):
        after = np.asarray(cache[name])
        assert (after[:, 1:] == before[name][:, 1:]).all(), name
        assert np.abs(after[:, 0] - before[name][:, 0]).max() > 1e-3, name


def test_two_slots_of_different_lengths_answer_as_the_full_forward_does(
        reference):
    """Two requests in one batch, a prompt of 37 (three chunks) and one of
    6: every answer token's log-probability is the reference's full forward
    over prompt + answer, so neither slot reads the other's state and each
    is at its own position."""
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
        # float32 logits of up to 1.9 through a log-softmax over 300
        assert np.abs(got - np.asarray(want)[at, list(out)]).max() < 5e-5
    engine.shutdown()
    # the counters count any state layer: six of the eight layers
    admits = engine._span.named("engine.admit")
    ticks = engine._span.named("engine.tick")
    assert [a.args["chunks"] for a in admits] == [3, 1]
    assert engine.stats["ssm_prefill_tokens"] == 43 == sum(
        a.args["ssm_prefill_tokens"] for a in admits)
    assert {a.args["layers_state"] for a in admits} == {6}
    assert engine.stats["state_slot_layers"] == (
        6 * engine.stats["slot_ticks"]) == sum(
        t.args["state_slot_layers"] for t in ticks)
    assert {t.args["layers_full"] for t in ticks} == {2}


def test_a_reused_slot_answers_as_a_fresh_engine_does():
    """Five requests through three slots, chunked and not: each answer is
    what an engine that has seen nothing else gives, so no slot starts from
    its last tenant's matrices."""
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


def test_the_cache_holds_a_state_in_float32_whatever_the_activations():
    engine = _engine(dtype="bfloat16")
    assert engine._cache["ssm"].dtype == jnp.float32
    assert engine._cache["ssm"].shape == (6, 3, 2, 8, 128)
    assert engine._cache["conv"].dtype == jnp.bfloat16
    engine.shutdown()


def test_the_trainer_builds_the_family_from_a_files_keys():
    """``models.config_for``, the trainer's way in: the flat keys of a
    configuration file, ``layer_types`` as a JSON list."""
    from ray_tpu import models

    cfg = models.config_for("olmo_hybrid", **{
        k: list(v) if isinstance(v, tuple) else v for k, v in TINY.items()
        if k not in ("model_family", "max_batch_slots", "prefill_buckets")})
    assert models.module_for(cfg) is olmo_hybrid
    params = olmo_hybrid.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(_tokens((2, 17), seed=5))
    loss, grads = jax.value_and_grad(olmo_hybrid.loss_fn)(
        params, {"tokens": tokens}, cfg)
    assert np.isfinite(float(loss)) and 5.0 < float(loss) < 6.5  # ln 300
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert all(bool(jnp.isfinite(g).all()) for _, g in flat)
    # every weight of a state layer is reached by the loss
    for path, g in flat:
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)
