"""Ai2's ``olmo_hybrid`` (Olmo-Hybrid-7B): what is peculiar to it. The cases
every family shares (the reference and each fault, bfloat16, the refusals,
the plan, padded chunks, idle and reused slots, two slots, speculation
refused) run over its row of ``tests/families.py``; here, that the row is the
tiny preset and its state layers the gated delta rule's, the state's dtype
under bfloat16 activations, and the trainer's way in.

CPU, float32, seeded weights, tiny widths: no device number.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import decoder, kv_cache, olmo_hybrid
from ray_tpu.ops import delta_rule
from tests import families
from tests.families import _tokens

FAMILY = "olmo_hybrid"


def test_the_rows_keys_are_the_tiny_preset_and_its_states_the_delta_rules():
    cfg = families.model_config(FAMILY)
    assert cfg == dataclasses.replace(
        olmo_hybrid.PRESETS["olmo-hybrid-tiny"], vocab_size=300,
        dtype=jnp.float32, attention_impl="xla", head_dim=32,
        layer_types=cfg.layer_types)
    assert [(k.state, k.recurrence) for k in decoder.layer_kinds(cfg)] == [
        (4, delta_rule.GATED_DELTA)] * 3 + [(None, None)] + [
        (4, delta_rule.GATED_DELTA)] * 3 + [(None, None)]
    assert set(olmo_hybrid.state_leaves(cfg)) == set(kv_cache.STATE)


def test_the_cache_holds_a_state_in_float32_whatever_the_activations():
    engine = families._engine(FAMILY, dtype="bfloat16")
    assert engine._cache["ssm"].dtype == jnp.float32
    assert engine._cache["ssm"].shape == (6, 3, 2, 8, 128)
    assert engine._cache["conv"].dtype == jnp.bfloat16
    engine.shutdown()


def test_the_trainer_builds_the_family_from_a_files_keys():
    """``models.config_for``, the trainer's way in: the flat keys of a
    configuration file, ``layer_types`` as a JSON list."""
    from ray_tpu import models

    cfg = models.config_for("olmo_hybrid", **{
        k: list(v) if isinstance(v, tuple) else v
        for k, v in families.TINY[FAMILY].items()
        if k not in families.ENGINE_KEYS})
    assert models.module_for(cfg) is olmo_hybrid
    params = olmo_hybrid.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(_tokens((2, 17), seed=5))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: olmo_hybrid.loss_fn(p, {"tokens": tokens}, cfg)))(params)
    assert np.isfinite(float(loss)) and 5.0 < float(loss) < 6.5  # ln 300
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert all(bool(jnp.isfinite(g).all()) for _, g in flat)
    # every weight of a state layer is reached by the loss
    for path, g in flat:
        assert float(jnp.abs(g).max()) > 0, jax.tree_util.keystr(path)
