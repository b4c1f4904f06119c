"""Llama model family: RoPE/RMSNorm/SwiGLU/GQA correctness + train/decode.

Reference capability analog: the model families the reference serves via
vLLM passthrough (SURVEY.md §2.4 Ray LLM); here the family is in-framework,
so these tests pin down numerics (cache-consistency, GQA grouping) the way
the reference relies on vLLM's own tests to do.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import get_preset, llama, module_for
from ray_tpu.models.llama import LLAMA_TINY, LlamaConfig


@pytest.fixture(scope="module")
def tiny_params():
    return llama.init_params(LLAMA_TINY, jax.random.PRNGKey(0))


def test_registry_dispatch():
    assert module_for(LLAMA_TINY) is llama
    assert get_preset("llama-tiny") is LLAMA_TINY
    with pytest.raises(KeyError):
        get_preset("nope")


def test_forward_shapes(tiny_params):
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits, aux = llama.forward(tiny_params, tokens, LLAMA_TINY)
    assert logits.shape == (2, 16, LLAMA_TINY.vocab_size)
    assert logits.dtype == jnp.float32
    assert float(aux) == 0.0


def test_param_axes_match_params(tiny_params):
    axes = llama.param_axes(LLAMA_TINY)
    flat_p = jax.tree.leaves(tiny_params)
    flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_p) == len(flat_a)
    for p, a in zip(flat_p, flat_a):
        assert p.ndim == len(a), (p.shape, a)


def test_causality(tiny_params):
    """Changing a future token must not change past logits."""
    rng = np.random.RandomState(0)
    t1 = rng.randint(0, 512, (1, 12)).astype(np.int32)
    t2 = t1.copy()
    t2[0, -1] = (t2[0, -1] + 7) % 512
    l1, _ = llama.forward(tiny_params, jnp.asarray(t1), LLAMA_TINY)
    l2, _ = llama.forward(tiny_params, jnp.asarray(t2), LLAMA_TINY)
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], rtol=2e-3, atol=2e-3)
    assert not np.allclose(l1[0, -1], l2[0, -1], atol=1e-3)


# Cached decoding against the full forward (GQA, and g = 1 as plain MHA):
# cases "llama_gqa" and "llama_mha" of tests/test_kv_cache.py.


def test_train_step_loss_decreases():
    from ray_tpu.train.step import (
        OptimizerConfig,
        create_train_state,
        make_train_step,
    )

    config = LlamaConfig(
        vocab_size=256, max_seq_len=32, num_layers=2, num_heads=4,
        num_kv_heads=2, embed_dim=64, dtype=jnp.float32,
    )
    opt = OptimizerConfig(learning_rate=1e-2, warmup_steps=1).build()
    state = create_train_state(config, opt, jax.random.PRNGKey(0))
    step = make_train_step(config, opt)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, 256, (4, 33)), jnp.int32)}
    state, m0 = step(state, batch)
    for _ in range(10):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])


def test_train_step_sharded_mesh():
    """dp x tp mesh on the virtual 8-device CPU mesh."""
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.train.step import (
        OptimizerConfig,
        create_train_state,
        make_train_step,
    )

    mesh = MeshConfig(data=2, tensor=4).build(jax.devices()[:8])
    config = LlamaConfig(
        vocab_size=256, max_seq_len=32, num_layers=2, num_heads=8,
        num_kv_heads=4, embed_dim=64, dtype=jnp.float32,
    )
    opt = OptimizerConfig().build()
    state = create_train_state(config, opt, jax.random.PRNGKey(0), mesh)
    step = make_train_step(config, opt, mesh)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, 256, (4, 33)), jnp.int32)}
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_jax_trainer_llama(rt_start, tmp_path):
    """The public Trainer path trains a llama model (family dispatch)."""
    import math

    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    result = JaxTrainer(
        train_loop_config={
            "model": {
                "family": "llama", "vocab_size": 128, "max_seq_len": 32,
                "num_layers": 2, "num_heads": 4, "num_kv_heads": 2,
                "embed_dim": 32, "dtype": "float32",
                "attention_impl": "xla",
            },
            "mesh": {"data": -1},
            "num_steps": 3,
            "batch_size": 8,
            "seq_len": 16,
            "checkpoint_every": 0,
            "optimizer": {"warmup_steps": 1, "total_steps": 3},
        },
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="llama_e2e", storage_path=str(tmp_path)
        ),
    ).fit()
    assert math.isfinite(result.metrics["loss"])


def test_decode_engine_llama():
    from ray_tpu.llm.config import LLMConfig
    from ray_tpu.llm.engine import DecodeEngine, SamplingParams

    cfg = LLMConfig(
        model_id="llama-test", model_family="llama", vocab_size=300,
        max_seq_len=128, num_layers=2, num_heads=4, num_kv_heads=2,
        embed_dim=64, dtype="float32", max_batch_slots=2,
        prefill_buckets=(16, 32),
    )
    eng = DecodeEngine(cfg, seed=0)
    try:
        text = eng.generate_text("hello", SamplingParams(max_new_tokens=4))
        assert isinstance(text, str)
        ids = eng.generate(
            eng.tokenizer.encode("hi"), SamplingParams(max_new_tokens=3)
        )
        assert len(ids) == 3
    finally:
        eng.shutdown()


# ------------------------------------------------------------------ MoE


def test_llama_moe_forward_and_axes():
    """Mixtral-style llama: SwiGLU routed experts replace the dense FFN."""
    from ray_tpu.parallel.moe import MoEConfig

    config = LlamaConfig(
        vocab_size=256, max_seq_len=32, num_layers=2, num_heads=4,
        num_kv_heads=2, embed_dim=64, dtype=jnp.float32,
        moe=MoEConfig(num_experts=4, top_k=2, activation="swiglu"),
    )
    params = llama.init_params(config, jax.random.PRNGKey(0))
    assert "moe" in params["blocks"]
    assert "expert_gate" in params["blocks"]["moe"]  # swiglu experts
    assert "w_gate" not in params["blocks"]          # dense FFN dropped
    axes = llama.param_axes(config)
    flat_p = jax.tree.leaves(params)
    flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_p) == len(flat_a)
    for p, a in zip(flat_p, flat_a):
        assert p.ndim == len(a), (p.shape, a)

    tokens = jnp.zeros((2, 8), jnp.int32)
    logits, aux = llama.forward(params, tokens, config)
    assert logits.shape == (2, 8, 256)
    assert float(aux) > 0.0  # load-balancing loss is active


def test_llama_moe_trains_on_expert_mesh():
    """EP: expert axis sharded over the virtual mesh; loss decreases."""
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.moe import MoEConfig
    from ray_tpu.train.step import (
        OptimizerConfig,
        create_train_state,
        make_train_step,
    )

    mesh = MeshConfig(data=2, expert=4).build(jax.devices()[:8])
    config = LlamaConfig(
        vocab_size=256, max_seq_len=32, num_layers=2, num_heads=4,
        num_kv_heads=2, embed_dim=64, dtype=jnp.float32,
        moe=MoEConfig(num_experts=4, top_k=2, activation="swiglu"),
    )
    opt = OptimizerConfig(learning_rate=1e-2, warmup_steps=1).build()
    state = create_train_state(config, opt, jax.random.PRNGKey(0), mesh)
    step = make_train_step(config, opt, mesh)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, 256, (4, 33)), jnp.int32)}
    state, m0 = step(state, batch)
    for _ in range(8):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])
