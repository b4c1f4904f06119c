"""OLMoE through the program: the sorted, grouped expert dispatch of
``parallel/moe.py`` against a plain per-token loop, the ``llama`` family's
OLMoE flags against the benchmark's plain reference
(``benchmarks/references/olmoe.py``), and both through ``DecodeEngine``:
prefill, cached decode, the row mask, the counters and their spans.

CPU, float32 unless a test says otherwise, seeded weights, tiny widths; each
tolerance is written where it is used, with its reason. One profiler capture
(module fixture ``served``), read back with ``benchmarks/lib/host_spans.py``.
Nothing timed here is a device number.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import DecodeEngine, LLMConfig, SamplingParams
from ray_tpu.models import llama
from ray_tpu.parallel.moe import (
    MoEConfig, init_moe_params, moe_layer, moe_layer_counted,
)
from tests import families

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _benchmark_file(kind, name):
    from benchmarks.lib import named

    return named.load(os.path.join(CHECKOUT, "benchmarks", kind, name))


# ------------------------------------------------ (a) - (c): the dispatch


def _loop(params, x, cfg, row_mask=None):
    """The layer as a loop over tokens and their chosen experts, in jnp so
    that it has a gradient: route, then for every token add gate x
    expert(token) for each of its k experts."""
    tokens = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(tokens @ params["router_w"], axis=-1)
    out = []
    for t in range(tokens.shape[0]):
        gates, chosen = jax.lax.top_k(probs[t], cfg.top_k)
        if cfg.norm_topk_prob:
            gates = gates / gates.sum()
        acc = jnp.zeros_like(tokens[t])
        for g, e in zip(gates, np.asarray(chosen)):
            h = tokens[t] @ params["expert_fc"][e]
            if cfg.activation == "swiglu":
                h = jax.nn.silu(tokens[t] @ params["expert_gate"][e]) * h
            else:
                h = jax.nn.gelu(h)
            acc = acc + g * (h @ params["expert_out"][e])
        real = True if row_mask is None else bool(row_mask.reshape(-1)[t])
        out.append(acc if real else jnp.zeros_like(acc))
    return jnp.stack(out).reshape(x.shape)


def _layer(experts, top_k, norm, activation="swiglu", seed=0, tokens=(2, 9)):
    cfg = MoEConfig(num_experts=experts, top_k=top_k, activation=activation,
                    norm_topk_prob=norm, dropless=True)
    params = init_moe_params(jax.random.PRNGKey(seed), 16, 24, cfg)
    # weights of 0.02 make every expert's output ~1e-4: scale them so that
    # a wrong gate or a wrong expert is far above the tolerance
    params = jax.tree.map(lambda a: a * 20.0, params)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (*tokens, 16))
    return cfg, params, x


@pytest.mark.parametrize("norm", [True, False], ids=["norm", "raw_gates"])
@pytest.mark.parametrize("experts, top_k", [(4, 2), (64, 8)])
def test_grouped_dispatch_matches_a_per_token_loop(experts, top_k, norm):
    cfg, params, x = _layer(experts, top_k, norm)
    out, aux, touched = moe_layer_counted(params, x, cfg)
    want = _loop(params, x, cfg)
    # float32 both, sums in another order: 1e-5 on outputs of 0.1 to 3
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(out, want, atol=1e-5)
    assert int(touched) == len(set(np.asarray(jax.lax.top_k(
        jax.nn.softmax(x.reshape(-1, 16) @ params["router_w"]), top_k)[1]
    ).ravel()))
    # what renormalising is worth: the two settings are far apart
    other, _, _ = moe_layer_counted(
        params, x, dataclasses.replace(cfg, norm_topk_prob=not norm))
    assert float(jnp.abs(other - out).max()) > 1e-2


def test_grouped_dispatch_gelu_experts():
    cfg, params, x = _layer(4, 2, True, activation="gelu")
    out, _ = moe_layer(params, x, cfg)
    np.testing.assert_allclose(out, _loop(params, x, cfg), atol=1e-5)


def test_masked_rows_reach_no_expert():
    cfg, params, x = _layer(64, 8, False)
    mask = jnp.asarray(np.random.default_rng(3).random((2, 9)) < 0.4)
    out, _, touched = moe_layer_counted(params, x, cfg, row_mask=mask)
    np.testing.assert_allclose(out, _loop(params, x, cfg, mask), atol=1e-5)
    assert (np.asarray(out)[~np.asarray(mask)] == 0).all()
    # only the real rows' experts count, and the real rows' results do not
    # depend on what the others hold
    probs = jax.nn.softmax(x.reshape(-1, 16) @ params["router_w"])
    chosen = np.asarray(jax.lax.top_k(probs, 8)[1])[np.asarray(mask).ravel()]
    assert int(touched) == len(set(chosen.ravel())) < 64
    noise = jnp.where(mask[..., None], x, 1e3)
    other, _, _ = moe_layer_counted(params, noise, cfg, row_mask=mask)
    np.testing.assert_array_equal(np.asarray(other), np.asarray(out))
    none, _, none_touched = moe_layer_counted(
        params, x, cfg, row_mask=jnp.zeros((2, 9), bool))
    assert int(none_touched) == 0 and not np.asarray(none).any()


def test_every_token_sent_to_one_expert():
    cfg, params, x = _layer(4, 2, False)
    # a router that prefers experts 2 then 0 for every token, by a margin
    x = jnp.abs(x)
    params["router_w"] = jnp.zeros_like(params["router_w"]).at[:, 2].set(
        5.0).at[:, 0].set(2.0)
    out, _, touched = moe_layer_counted(params, x, cfg)
    assert int(touched) == 2
    np.testing.assert_allclose(out, _loop(params, x, cfg), atol=1e-5)
    one = dataclasses.replace(cfg, top_k=1)
    out, _, touched = moe_layer_counted(params, x, one)
    assert int(touched) == 1
    np.testing.assert_allclose(out, _loop(params, x, one), atol=1e-5)


@pytest.mark.parametrize("norm", [True, False], ids=["norm", "raw_gates"])
def test_grouped_dispatch_gradient_matches_the_loops(norm):
    cfg, params, x = _layer(4, 2, norm, tokens=(1, 6))

    def loss(fn):
        return lambda p, x: (fn(p, x) ** 2).sum()

    got = jax.grad(loss(lambda p, x: moe_layer(p, x, cfg)[0]),
                   argnums=(0, 1))(params, x)
    want = jax.grad(loss(lambda p, x: _loop(p, x, cfg)), argnums=(0, 1))(
        params, x)
    # float32, sums in another order, gradients of 0.1 to 50
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(w).max()) > 1e-2
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_no_array_has_a_token_and_an_expert_by_width_extent():
    """No [T, E, M] or [T, E, D] array exists: no value of the traced
    computation holds a token extent (T or T x k) together with the expert
    extent AND a width."""
    experts, top_k, T, D, M = 64, 8, 18, 16, 24
    cfg, params, x = _layer(experts, top_k, False)
    jaxpr = jax.make_jaxpr(
        lambda p, x: moe_layer_counted(p, x, cfg))(params, x)

    def shapes(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield eqn.primitive.name, tuple(v.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = list(shapes(jaxpr.jaxpr))
    assert any(name == "ragged_dot_general" for name, _ in seen)
    for name, shape in seen:
        tokens = T in shape or T * top_k in shape
        assert not (tokens and experts in shape
                    and (D in shape or M in shape)), (name, shape)
    assert "td,edm->tem" not in open(os.path.join(
        CHECKOUT, "ray_tpu", "parallel", "moe.py")).read()


# ------------------ (d): the family against the reference: the ``llama`` row
# of ``tests/families.py`` (sound, a key left un-normed, gates renormalised)

# the row's keys with this file's engine: four slots, a bucket of 32
OLMOE = {**families.TINY["llama"], "max_seq_len": 64, "max_batch_slots": 4,
         "prefill_buckets": (8, 16, 32)}


@pytest.fixture(scope="module")
def reference():
    return families.reference("llama")


def _weights(config):
    return families._moved("llama", config.model_config())


# ------------------------------------- (e) - (g): through the engine


def _prefill_into(engine, slot, prompt):
    """The engine's own programs, as ``_prefill_locked`` and
    ``_activate_slot_locked`` call them: the logits of every prompt
    position, with the prompt's cache in ``slot``."""
    bucket = engine._bucket(len(prompt))
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    logits, cache1, touched = engine._prefill(
        engine.params, jnp.asarray(toks), engine._empty_slot_cache(),
        jnp.zeros((1,), jnp.int32), *engine._real([len(prompt)]))
    engine._cache = engine._insert(engine._cache, cache1, slot)
    return np.asarray(logits)[0, :len(prompt)], np.asarray(touched)


def _decode(engine, sequences, active, garbage=0):
    """One tick: the slots of ``active`` decode the last token of their
    sequence, the others are idle and hold ``garbage``."""
    B = len(engine._slots)
    toks = np.full((B, 1), garbage, np.int32)
    lens = np.zeros((B,), np.int32)
    real = np.zeros((B,), np.int32)
    for b in active:
        toks[b, 0], lens[b], real[b] = (
            sequences[b][-1], len(sequences[b]) - 1, 1)
    _, logits, engine._cache, touched = engine._decode(
        engine.params, engine._ids, engine._cache,
        jnp.asarray(np.stack([toks[:, 0], lens, real])))
    return np.asarray(logits), np.asarray(touched)


@pytest.mark.parametrize("dtype, tol", [
    # float32 against float32: the largest difference of a logit (of 0.7)
    # is the order of the sums, 1e-6 measured; gates renormalised read 0.15,
    # a key left un-normed 0.61
    ("float32", 2e-5),
    # weights, activations and cache in bf16 (the reference reads the same
    # bf16 weights in float32): the differences' RMS over the logits' own,
    # each row centred. 0.012-0.064 measured over four seeds, an un-normed
    # key reads 0.47-0.59. Not the largest difference: where a token's 8th
    # and 9th expert tie within bf16's rounding the program picks the other
    # one, and one logit then reads up to 0.33 of 0.65. Gates renormalised
    # (0.045-0.075 at 8 of 16 experts) are below bf16 at this size: the
    # float32 case finds them.
    ("bfloat16", 0.1),
], ids=["float32", "bf16_weights"])
def test_engine_prefill_and_cached_decode_match_the_reference(
        reference, dtype, tol):
    config = LLMConfig(**{**OLMOE, "dtype": dtype, "param_dtype": dtype})
    engine = DecodeEngine(config, params=_weights(config))
    assert {a.dtype for a in jax.tree.leaves(engine.params)} == {
        jnp.dtype(dtype)}
    assert engine._cache["k"].dtype == jnp.dtype(dtype)
    rng = np.random.default_rng(5)
    sequences = {b: list(rng.integers(2, 258, n)) for b, n in
                 ((0, 5), (1, 13), (2, 29), (3, 8))}
    got, want = [], []

    def full(b):
        # one length, so one program (causal, a token at a time: what lies
        # after a position does not reach it)
        padded = np.zeros((1, 40), np.int32)
        padded[0, :len(sequences[b])] = sequences[b]
        return families._reference_logits(
            reference, engine.params, padded)[0, :len(sequences[b])]

    for b, seq in sequences.items():
        logits, touched = _prefill_into(engine, b, seq)
        got.append(logits), want.append(full(b))
        assert touched.shape == (2,) and (touched <= 16).all()
        sequences[b] = seq + [int(logits[-1].argmax())]
    # an idle slot's row is written at its position 0, as in the engine: a
    # slot that has idled once holds nothing and is not read again
    for active in ((0, 1, 2, 3), (0, 1, 3), (0, 1, 3), (1,)):
        logits, touched = _decode(engine, sequences, active)
        assert (touched <= min(16, 8 * len(active))).all()
        assert (touched >= 8).all()
        for b in active:
            got.append(logits[b][None]), want.append(full(b)[-1:])
            sequences[b].append(int(logits[b].argmax()))
    got, want = np.concatenate(got), np.concatenate(want)
    if dtype == "float32":
        assert np.abs(got - want).max() < tol
    else:
        centred = [a - a.mean(-1, keepdims=True) for a in (got, want)]
        assert (np.sqrt(((centred[0] - centred[1]) ** 2).mean())
                / np.sqrt((centred[1] ** 2).mean())) < tol
    if dtype == "float32":
        # one slot's logits are the same whatever the other slots hold:
        # idle slots are routed to no expert and attend nothing of slot 1
        before = jax.tree.map(jnp.copy, engine._cache)
        alone, touched_alone = _decode(engine, sequences, (1,))
        engine._cache = jax.tree.map(
            lambda c: c.at[:, jnp.asarray([0, 2, 3])].set(7.0), before)
        other, touched_other = _decode(engine, sequences, (1,), garbage=299)
        np.testing.assert_array_equal(alone[1], other[1])
        np.testing.assert_array_equal(touched_alone, touched_other)
    engine.shutdown()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Four requests on the tiny OLMoE engine's loop, under one capture."""
    from benchmarks.lib import host_spans

    config = LLMConfig(**OLMOE)
    engine = DecodeEngine(config, params=_weights(config))
    logdir = str(tmp_path_factory.mktemp("olmoe_spans"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    rng = np.random.default_rng(9)
    prompts = [list(rng.integers(2, 258, n)) for n in (3, 11, 20, 7, 30)]
    bf16 = LLMConfig(**{**OLMOE, "dtype": "bfloat16",
                        "param_dtype": "bfloat16"})
    bf16_given = _weights(bf16)
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        # a replica that takes bf16 weights while the capture runs
        bf16_engine = DecodeEngine(bf16, params=bf16_given)
        # host rows (``logprobs``): each tick is read in its own span, so a
        # span's count of touched experts is its own program's
        futures = [engine.submit(p, SamplingParams(
            max_new_tokens=n, logprobs=1))
            for p, n in zip(prompts, (6, 3, 9, 1, 5))]
        answers = [list(f.result(timeout=120)) for f in futures]
    finally:
        jax.profiler.stop_trace()
    stats = dict(engine.stats)
    engine.shutdown()
    return {"spans": host_spans.load(logdir), "stats": stats,
            "prompts": prompts, "answers": answers, "engine": engine,
            "bf16_given": bf16_given, "bf16_engine": bf16_engine}


def test_bf16_weights_are_held_as_they_were_given(served):
    """OLMoE's weights are bf16 as the checkpoint's are: the engine holds
    the very arrays it was given (no program runs over 7.1 GB of experts),
    and ``engine.weights`` says that nothing was rounded."""
    given = jax.tree.leaves(served["bf16_given"])
    held = jax.tree.leaves(served["bf16_engine"].params)
    assert len(held) == len(given) and all(
        h is g for g, h in zip(given, held))
    (span,) = served["spans"].named("engine.weights")
    assert span.args["leaves_rounded"] == 0
    assert span.args["held_bytes"] == span.args["given_bytes"] == sum(
        a.size * 2 for a in given)


def test_moe_counters_equal_the_spans_arguments(served):
    spans, stats = served["spans"], served["stats"]
    ticks, admits = spans.named("engine.tick"), spans.named("engine.admit")
    assert len(admits) == 5 and len(ticks) == stats["ticks"] > 0
    for name in ("moe_rows", "moe_experts_touched"):
        assert stats[name] > 0
    assert stats["moe_rows"] == sum(
        s.args["moe_rows"] for s in ticks + admits)
    assert stats["moe_experts_touched"] == sum(
        s.args["experts_touched"] for s in ticks + admits)
    for t in ticks:
        # real rows x k x layers, and every layer touches between k experts
        # and all of them
        assert t.args["moe_layers"] == 2
        assert t.args["moe_rows"] == t.args["active"] * 8 * 2
        assert 2 * 8 <= t.args["experts_touched"] <= 2 * min(
            16, 8 * t.args["active"])
    for a in admits:
        assert a.args["moe_rows"] == a.args["prompt_tokens"] * 8 * 2
    assert stats["moe_rows"] == 8 * 2 * (
        stats["slot_ticks"] + sum(len(p) for p in served["prompts"]))


def test_experts_touched_is_what_the_reference_routes(served, reference):
    """The prefill of each request touched, in each layer, exactly the
    experts the reference's router picks for the prompt's own tokens (not
    for the bucket's padding)."""
    engine = served["engine"]
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          engine.params)
    admits = {a.args["prompt_tokens"]: a
              for a in served["spans"].named("engine.admit")}
    for prompt in served["prompts"]:
        seen = []

        def spy(x, router_w, top_k=reference.TOP_K, _route=reference.route):
            gates = _route(x, router_w, top_k)
            jax.debug.callback(lambda g: seen.append(np.asarray(g)), gates)
            return gates

        reference.route, honest = spy, reference.route
        try:
            # op by op: a program traced before the spy would not call it
            with jax.default_matmul_precision("highest"):
                reference.logits(params, jnp.asarray([prompt], jnp.int32))
        finally:
            reference.route = honest
        jax.effects_barrier()
        assert len(seen) == 2  # one routing a layer
        want = sum(int((g > 0).any(axis=0).sum()) for g in seen)
        assert admits[len(prompt)].args["experts_touched"] == want


def test_a_dense_model_routes_nothing():
    engine = DecodeEngine(LLMConfig(
        vocab_size=300, max_seq_len=64, num_layers=1, num_heads=2,
        embed_dim=32, dtype="float32", max_batch_slots=2,
        prefill_buckets=(16,)))
    assert len(engine.generate([5, 6, 7], SamplingParams(max_new_tokens=4))
               ) == 4
    assert engine.stats["moe_rows"] == engine.stats[
        "moe_experts_touched"] == 0
    engine.shutdown()


# ------------------------------------------- (h) and the configuration


def test_llm_config_fields_reach_the_family_by_name():
    cfg = LLMConfig(**OLMOE).model_config()
    assert (cfg.hidden_dim, cfg.rope_theta, cfg.rms_eps) == (32, 10000, 1e-5)
    assert cfg.param_dtype == jnp.float32  # not stated: the family's own
    assert LLMConfig(**{**OLMOE, "param_dtype": "bfloat16"}
                     ).model_config().param_dtype == jnp.bfloat16
    # a field the gpt2 family does not take is an error by its name
    for field in ("rope_theta", "rms_eps", "qk_norm", "mlp_dim"):
        with pytest.raises(TypeError, match=field):
            LLMConfig(model_family="gpt2", **{field: OLMOE[field]}
                      ).model_config()
    with pytest.raises(TypeError, match="num_kv_heads"):
        LLMConfig(model_family="gpt2", num_kv_heads=2).model_config()
    with pytest.raises(ValueError, match="qk_norm"):
        LLMConfig(**{**OLMOE, "qk_norm": "head"}).model_config()
    with pytest.raises(ValueError, match="unknown model_family 'mamba'"):
        LLMConfig(model_family="mamba").model_config()


def test_router_init_std_reaches_the_routers_weights_and_nothing_else():
    """0.02 unless stated; stated, the router's weights have that scale and
    every other leaf is what it was (same key)."""
    base = LLMConfig(**OLMOE).model_config()
    assert base.moe.router_init_std == 0.02
    peaked = LLMConfig(**OLMOE, moe_router_init_std=0.1).model_config()
    assert peaked.moe.router_init_std == 0.1
    a, b = (llama.init_params(c, jax.random.PRNGKey(0))
            for c in (base, peaked))
    ra, rb = a["blocks"]["moe"].pop("router_w"), b["blocks"]["moe"].pop(
        "router_w")
    np.testing.assert_allclose(rb, ra * 5.0, rtol=1e-6)
    assert abs(float(rb.std()) - 0.1) < 0.01
    jax.tree.map(np.testing.assert_array_equal, a, b)


def test_stacked_experts_are_cast_once_outside_the_layer_loop():
    """float32 weights under bf16 activations (the family's default): the
    cached forward casts every layer's experts once a call
    (``moe.stacked_for``), and the grouped products refuse a stack that would
    have to be cast inside the layer loop. The logits come out float32,
    straight from the head's float32 sums."""
    from ray_tpu.parallel.moe import stacked_for

    cfg = LLMConfig(**{**OLMOE, "dtype": "bfloat16"}).model_config()
    assert (cfg.dtype, cfg.param_dtype) == (jnp.bfloat16, jnp.float32)
    params = families._moved("llama", cfg)
    tokens = jnp.asarray(np.random.default_rng(1).integers(2, 258, (2, 8)),
                         jnp.int32)
    cache = llama.init_kv_cache(cfg, 2, 16)
    run = jax.jit(lambda p, t, c: llama.forward_cached(
        p, t, c, jnp.zeros((2,), jnp.int32), cfg,
        real=jnp.asarray([8, 5], jnp.int32)))
    logits, _, touched = run(params, tokens, cache)
    assert logits.dtype == jnp.float32 and touched.shape == (2,)
    # the full forward (bf16 residual stream, a layer's own experts) agrees
    # within bf16: logits of 0.7, differences of 0.02 measured
    full, _ = llama.forward(params, tokens, cfg)
    assert np.abs(np.asarray(logits[0]) - np.asarray(full[0])).max() < 0.06
    # exactly one cast of each expert matrix in the whole program, and it
    # is of the stack [L, E, ..], not of a layer's [E, ..]
    text = run.lower(params, tokens, cache).as_text()
    E, D, M = 16, 64, 32
    for shape in (f"{E}x{D}x{M}", f"{E}x{M}x{D}"):
        converts = [l for l in text.splitlines() if "stablehlo.convert" in l
                    and f"{shape}xf32>) -> tensor" in l]
        assert all(f"2x{shape}xf32" in l for l in converts), converts
        assert 1 <= len(converts) <= 2  # expert_fc and expert_gate share one
    moe = params["blocks"]["moe"]
    x = jnp.zeros((1, 4, D), jnp.bfloat16)
    with pytest.raises(TypeError, match="stacked_for"):
        moe_layer_counted(moe, x, cfg.moe, layer=jnp.int32(0))
    out, _, _ = moe_layer_counted(stacked_for(moe, x.dtype), x, cfg.moe,
                                  layer=jnp.int32(0))
    assert out.dtype == jnp.bfloat16


def test_the_configuration_file_holds_the_published_numbers():
    with open(os.path.join(CHECKOUT, "benchmarks", "configs",
                           "olmoe-1b-7b.json")) as f:
        config = json.load(f)
    # config.json of allenai/OLMoE-1B-7B-0125-Instruct, as the catalog of
    # the model-configs guide has it
    published = {
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "intermediate_size": 1024,
        "vocab_size": 50304, "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "norm_topk_prob": False, "max_position_embeddings": 4096,
        "num_hidden_layers": 16, "tie_word_embeddings": False,
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    }
    differs = {k for k, v in published.items() if config[k] != v}
    assert differs == {"num_hidden_layers"} == set(config["changed"])
    assert config["published"] == {"num_hidden_layers": 16}
    model = config["model"]
    for ours, theirs in (
            ("embed_dim", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"),
            ("moe_num_experts", "num_experts"),
            ("moe_top_k", "num_experts_per_tok"),
            ("mlp_dim", "intermediate_size"), ("vocab_size", "vocab_size"),
            ("rms_eps", "rms_norm_eps"), ("rope_theta", "rope_theta"),
            ("moe_norm_topk_prob", "norm_topk_prob"),
            ("max_seq_len", "max_position_embeddings"),
            ("num_layers", "num_hidden_layers")):
        assert model[ours] == config[theirs], ours
    assert model["qk_norm"] == "full"
    assert config["serve"]["param_dtype"] == "bfloat16"
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "olmoe-1b-7b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]


def test_param_count_is_the_leaves_of_init_params():
    costs = _benchmark_file("costs", "olmoe.py")
    model = {k: v for k, v in OLMOE.items() if k in (
        "vocab_size", "max_seq_len", "num_layers", "num_heads",
        "num_kv_heads", "embed_dim", "mlp_dim", "moe_num_experts",
        "moe_top_k")}
    cfg = LLMConfig(**OLMOE).model_config()
    leaves = jax.tree.leaves(llama.init_params(cfg, jax.random.PRNGKey(0)))
    count = costs.param_count(model)
    assert count["total"] == sum(a.size for a in leaves)
    experts = 2 * 16 * 3 * 64 * 32
    assert count["experts"] == experts
    assert count["total"] - count["active"] == experts // 2  # 8 of 16
    # the published model, from the same function: 6.92B, 419.6M a layer
    full = costs.param_count({
        "vocab_size": 50304, "num_layers": 16, "num_heads": 16,
        "num_kv_heads": 16, "embed_dim": 2048, "mlp_dim": 1024,
        "moe_num_experts": 64, "moe_top_k": 8})
    assert round(full["total"] / 1e9, 2) == 6.92
    assert round(full["layer"] / 1e6, 1) == 419.6
    assert costs.train_flops_per_token(model, 32) > 6 * count[
        "experts_active"]
    cost = costs.moe_experts_cost(128, 40, 2048, 1024)
    assert cost["flops"] == 2 * 128 * 3 * 2048 * 1024
    assert cost["bytes"] == 40 * 3 * 2048 * 1024 * 2 + 2 * 128 * 2048 * 2
