"""Every configuration of ``BENCHMARK.json`` and of the rehearsal's toy: the
three names its file gives resolve, the program's configuration builds from
it, and the program agrees with the plain reference it names. And a file that
leaves a name out, or names a file that is not there, fails by that key's
name before anything is started."""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import named, program, reference

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")


def _configurations():
    for root in (run.CHECKOUT, TINY):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        for c in bench["configs"]:
            cell = next(w["name"] for w in bench["workloads"]
                        if w["config"] == c["name"])
            yield pytest.param(root, cell, id=c["name"])


@pytest.mark.parametrize("root, cell", _configurations())
def test_a_configuration_names_its_family_reference_and_costs(root, cell):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import module_for

    _, _, config, _, _ = run.load_cell(cell, root)
    logits = reference.logits_of(config)
    costs = named.load(config["files"]["costs"])
    # the trainer's and the engine's constructors both take the file's keys
    assert program.trainer_model(config)["family"] == config["family"]
    cfg = program.model_config(config)
    assert type(cfg).__module__ == "ray_tpu.models." + config["family"]
    for key, value in config["model"].items():
        assert getattr(cfg, key) == value, key

    # the published widths at a depth of 2, so that GPT-2 XL's 25 heads of 64
    # are compared and not a toy's; activations in float32 like the reference
    cut = {**config["model"], "num_layers": 2}
    cfg = dataclasses.replace(cfg, num_layers=2, dtype=jnp.float32)
    model = module_for(cfg)
    params = jax.jit(model.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    assert costs.param_count(cut)["total"] == sum(
        p.size for p in jax.tree.leaves(params))
    assert costs.train_flops_per_token(cut, 32) > 6 * 2 * cfg.embed_dim ** 2
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, cfg.vocab_size, (2, 32), dtype=np.int32))
    got = model.forward(params, tokens, cfg)
    got = got[0] if isinstance(got, tuple) else got  # (logits, aux loss)
    want = reference.in_blocks(logits, params, tokens, 2)
    assert want.shape == (2, 32, cfg.vocab_size) and want.dtype == np.float32
    # float32 against float32 on the CPU: the two differ in the order of
    # their sums only, measured 1.8e-7 to 4.4e-6 on logits of 0.7 to 4.1 over
    # the four configurations. At the toy's size a key left unrotated reads
    # 3.8e-3 and query heads grouped in the wrong order 5.1e-3 (weights of
    # 0.02 make attention nearly flat, so these are the faintest faults)
    assert np.abs(np.asarray(got) - want).max() < 1e-4


@pytest.mark.parametrize("key", ["family", "reference", "costs"])
def test_a_name_left_out_fails_by_its_key(toy, key):
    with pytest.raises(KeyError, match=f"llama-tiny.json has no '{key}'"):
        run.load_cell("llama-tiny.train-steady", toy(**{key: None}))


@pytest.mark.parametrize("key", ["reference", "costs"])
def test_a_file_that_is_not_there_fails_by_its_key(toy, key):
    with pytest.raises(FileNotFoundError,
                       match=f"llama-tiny.json: '{key}' names 'mamba'"):
        run.load_cell("llama-tiny.train-steady", toy(**{key: "mamba"}))


def test_a_family_the_program_does_not_know_fails_by_its_name(toy):
    _, _, config, _, _ = run.load_cell(
        "llama-tiny.serve-chat", toy(family="mamba"))
    with pytest.raises(ValueError, match="unknown model_family 'mamba'"):
        program.model_config(config)


def test_a_key_the_program_does_not_take_fails_by_its_name(toy):
    root = toy(model={"vocab_size": 512, "state_size": 16})
    _, _, config, _, _ = run.load_cell("llama-tiny.serve-chat", root)
    with pytest.raises(TypeError, match="state_size"):
        program.llm_config(config)
