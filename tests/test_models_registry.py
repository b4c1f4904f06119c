"""The model zoo's seam: one decoder, families as pieces, one registry.

``models/decoder.py`` owns the layer stack, the cached forward, the pipeline
and the loss; a family module owns what differs and is listed once, in
``models.FAMILIES``. These tests hold the seam where it is: a family that
grows its own scan, or a decoder that asks which family it serves, fails
here. What a family's forward computes is held over the table of
``tests/families.py`` (``test_family_reference.py``, ``test_family_cached.py``,
``test_family_engine.py``), its ``serving_params`` and heads-major pieces by
``test_family_weights.py``.
"""
import ast
import dataclasses
import inspect
import json

import jax
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.llm.config import LLMConfig
from ray_tpu.models import (
    FAMILIES, config_for, decoder, family_module, get_preset, module_for,
)
from ray_tpu.parallel.moe import MoEConfig
from tests import families

# what the decoder calls on a family, what a server calls once as it takes
# its weights, and what callers outside ask of one
PIECES = ("layers", "embed", "at_input", "qkv", "attn_out", "ffn",
          "final_norm", "head", "head_weight", "serving_params",
          "second_loss", "step_rule")
# the pieces the decoder gives a default: a family has one, its own or that
DEFAULTED = ("at_input", "second_loss", "step_rule")
# what a family with state layers has beside them (``decoder.Layer.state``)
STATE_PIECES = ("state_in", "state_out", "state_leaves")
OWN = ("Config", "PRESETS", "EXPERT_ACTIVATION", "init_params", "param_axes")
SHARED = ("forward_features", "forward", "init_kv_cache", "forward_cached",
          "forward_pipelined", "loss_fn", "count_params")
GATED = ("swiglu", "reglu")   # activations with a gate matrix


def _source(module) -> ast.Module:
    return ast.parse(inspect.getsource(module))


def _called_names(tree: ast.Module) -> set:
    """Every name a call goes through: ``a.b.c(...)`` gives a, b and c."""
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            for part in ast.walk(node.func):
                if isinstance(part, ast.Name):
                    names.add(part.id)
                elif isinstance(part, ast.Attribute):
                    names.add(part.attr)
    return names


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_family_is_the_pieces_and_nothing_of_the_skeleton(family):
    module = family_module(family)
    public = {n for n, v in vars(module).items()
              if not n.startswith("_") and inspect.isfunction(v)
              and v.__module__ == module.__name__}
    stateful = any(kind.state is not None for kind in decoder.layer_kinds(
        next(iter(module.PRESETS.values()))))
    assert public | set(DEFAULTED) == set(PIECES) | {
        "init_params", "param_axes"} | (
        set(STATE_PIECES) if stateful else set())
    for name in set(DEFAULTED) - public:
        assert getattr(module, name) is getattr(decoder, name), name
    for name in OWN:
        assert hasattr(module, name), name
    # the shared functions resolve, through the family, to the one definition
    for name in SHARED:
        assert getattr(module, name) is getattr(decoder, name), name
    assert not _called_names(_source(module)) & {
        "scan", "checkpoint", "remat", "pipeline_apply", "kv_cache", "attend",
        "step", "while_loop", "fori_loop"}
    # one signature each across families
    first = family_module(next(iter(FAMILIES)))
    for name in PIECES + ("init_params", "param_axes"):
        assert (list(inspect.signature(getattr(module, name)).parameters)
                == list(inspect.signature(getattr(first, name)).parameters)
                ), name


def test_the_decoder_names_no_family_and_probes_nothing():
    tree = _source(decoder)
    assert not _called_names(tree) & {"isinstance", "hasattr", "getattr",
                                      "type"}
    identifiers = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    identifiers |= {n.attr for n in ast.walk(tree)
                    if isinstance(n, ast.Attribute)}
    imported = {a.name for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names}
    for family in FAMILIES:
        assert family not in identifiers | imported, family
    # and each shared function is defined here, once
    defined = [n.name for n in tree.body if isinstance(n, ast.FunctionDef)]
    for name in SHARED:
        assert defined.count(name) == 1, name


def test_the_registry_is_the_one_list_of_families():
    presets = {}
    for family in FAMILIES:
        module = family_module(family)
        assert module.__name__ == FAMILIES[family]
        for name, cfg in module.PRESETS.items():
            assert name not in presets, name
            presets[name] = cfg
            assert get_preset(name) is cfg
            assert module_for(cfg) is module
            assert isinstance(cfg, module.Config)
        # a config built from keywords is the family's own class
        assert type(config_for(family, num_layers=1)) is module.Config
    with pytest.raises(ValueError) as err:
        config_for("mamba")
    assert all(family in str(err.value) for family in FAMILIES)
    with pytest.raises(KeyError) as err:
        get_preset("nope")
    assert all(name in str(err.value) for name in presets)
    with pytest.raises(TypeError, match="unknown model config type"):
        module_for(object())
    # nothing else in the package enumerates them
    tree = _source(models)
    strings = [n.value for n in ast.walk(tree)
               if isinstance(n, ast.Constant) and isinstance(n.value, str)
               and n.value in FAMILIES]
    assert sorted(strings) == sorted(FAMILIES)


@pytest.mark.parametrize("family", list(FAMILIES))
@pytest.mark.parametrize("experts", [0, 4], ids=["dense", "routed"])
def test_llm_config_builds_every_family(family, experts):
    """What ``LLMConfig.model_config`` used to branch on by family name: kv
    heads not stated are as many as the query heads, and routed experts get
    the family's activation."""
    module = family_module(family)
    if experts and families.variants(family)["routed"] is None:
        # no routed form: experts are refused by the key's name
        with pytest.raises(ValueError, match="moe"):
            LLMConfig(model_family=family, num_heads=4, embed_dim=64,
                      moe_num_experts=experts).model_config()
        return
    cfg = LLMConfig(model_family=family, num_heads=4, embed_dim=64,
                    moe_num_experts=experts).model_config()
    assert isinstance(cfg, module.Config)
    assert cfg.num_kv_heads == cfg.num_heads == 4
    if experts:
        assert cfg.moe.num_experts == 4 and cfg.moe.dropless
        assert cfg.moe.activation == module.EXPERT_ACTIVATION
        blocks = module.init_params(cfg, jax.random.PRNGKey(0))["blocks"]
        # a family of one kind of layer keeps them with the layer, one of
        # several in a stack of their own
        gated = "expert_gate" in (blocks.get("moe") or blocks["experts"])
        assert gated == (module.EXPERT_ACTIVATION in GATED)
    else:
        # dense, or, for a family with no dense form, its own experts
        assert cfg.moe == module.Config().moe
    # stated: the family's config takes it under its own name, or refuses
    # it by that name
    stated = dict(model_family=family, num_heads=4, embed_dim=64,
                  num_kv_heads=2, moe_num_experts=experts)
    if "num_kv_heads" in module.Config.__dataclass_fields__:
        stated = LLMConfig(**stated)
        assert stated.model["num_kv_heads"] == 2
        assert stated.model_config().num_kv_heads == 2
        assert decoder.init_kv_cache(
            stated.model_config(), 3, 16)["k"].shape == (4, 3, 2, 16, 16)
    else:
        with pytest.raises(TypeError, match="num_kv_heads"):
            LLMConfig(**stated)
        cache = decoder.init_kv_cache(cfg, 3, 16)
        if "ssm" in cache:
            # four layers short of the family's period: every one keeps a
            # state, none keys and values a head
            assert cache["k"].shape[0] == 0
            assert cache["ssm"].shape[:3] == (4, 3, 4)
        elif "latent" in cache:
            # every layer attends a latent row a position, none keys and
            # values a head
            # and beside it, where the layers choose what they attend,
            # their indexer's key
            assert set(cache) - {"index"} == {"latent"}
            assert cache["latent"].shape == (4, 3, 1, cfg.latent_dim, 16)
            assert ("index" in cache) == any(
                k.index is not None for k in decoder.layer_kinds(cfg))
        else:
            assert cache["k"].shape == (4, 3, 4, 16, 16)


# ------------------------------------------- a configuration's flat keys
# ``LLMConfig`` names no family's field: what it does not read goes to
# ``models.config_for`` as it was stated, the function the trainer calls too.

@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_engine_and_the_trainer_build_one_config_from_one_set_of_keys(
        family):
    flat = families.flat_keys(families.preset(family))
    routed = bool(flat.get("moe_num_experts"))
    assert routed == (families.preset(family).moe is not None)
    if routed:
        assert families.preset(family).moe.activation == family_module(
            family).EXPERT_ACTIVATION
    served = LLMConfig(model_family=family, **flat).model_config()
    # the trainer's ``config_for(model.pop("family"), **model)``
    trained = config_for(family, **{
        **flat, "attention_impl": "xla",
        **({"moe_dropless": True} if routed else {})})
    assert served == trained and type(served) is type(trained)
    # and the keys said what the preset is
    preset = dataclasses.replace(families.preset(family),
                                 attention_impl="xla")
    if routed:
        preset = dataclasses.replace(
            preset, moe=dataclasses.replace(preset.moe, dropless=True))
    assert served == preset
    # what the llm layer does not name it keeps as it was stated
    config = LLMConfig(model_family=family, **flat)
    own = {f.name for f in dataclasses.fields(LLMConfig)}
    assert config.model == {k: v for k, v in flat.items() if k not in own}
    assert not set(config.model) & own
    # and hands on to a replica, on a wire that has no tuple
    again = LLMConfig.from_dict(json.loads(json.dumps(config.to_dict())))
    assert again.model == {k: list(v) if isinstance(v, tuple) else v
                           for k, v in config.model.items()}
    assert repr(again.model_config()).replace("[", "(").replace(
        "]", ")") == repr(served)
    assert dataclasses.replace(config, max_batch_slots=3).model == (
        config.model)


@pytest.mark.parametrize("loaded", [True, False], ids=["loaded", "on_disk"])
def test_a_new_family_reaches_the_engine_config_with_no_edit_outside_it(
        monkeypatch, tmp_path, loaded):
    """A family is its module and one line of ``FAMILIES``: a field only its
    ``Config`` has goes through ``LLMConfig`` under its own name. Its
    ``Config`` has a base, so its source does not show its fields and its
    module is asked (``config_keys``), loaded already or not."""
    import importlib
    import sys

    name = f"toy_family_{'loaded' if loaded else 'on_disk'}"
    (tmp_path / f"{name}.py").write_text(families.TOY_FAMILY)
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setitem(FAMILIES, "toy", name)
    # the test's end takes out of ``sys.modules`` whatever is there by then
    monkeypatch.setitem(sys.modules, name, None)
    del sys.modules[name]
    assert models._declared_fields(name) is None
    if loaded:
        importlib.import_module(name)
    config = LLMConfig(model_family="toy", num_heads=4, num_kv_heads=2,
                       embed_dim=64, toy_gain=3.0, toy_layout=(1, 0),
                       moe_num_experts=4)
    assert config.model == {"num_kv_heads": 2, "toy_gain": 3.0,
                            "toy_layout": (1, 0), "moe_num_experts": 4}
    cfg = config.model_config()
    ToyConfig = sys.modules[name].Config
    assert type(cfg) is ToyConfig
    assert (cfg.toy_gain, cfg.toy_layout, cfg.num_kv_heads) == (3.0, (1, 0), 2)
    assert cfg.moe.num_experts == 4 and cfg.moe.dropless
    # as a replica gets it: through ``to_dict`` and a wire that has no tuple
    wire = json.loads(json.dumps(config.to_dict()))
    assert wire["model"]["toy_layout"] == [1, 0]
    again = LLMConfig.from_dict(wire).model_config()
    assert again == dataclasses.replace(cfg, toy_layout=[1, 0])
    # the other families do not take it, and it takes nothing of theirs
    with pytest.raises(TypeError, match="toy_gain"):
        LLMConfig(model_family="llama", toy_gain=3.0)
    with pytest.raises(TypeError, match="mamba_d_state"):
        LLMConfig(model_family="toy", mamba_d_state=16)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_keyword_the_family_does_not_take_fails_by_name_as_it_is_stated(
        family):
    module = family_module(family)
    fields = {f.name for f in dataclasses.fields(module.Config)}
    # one no family takes; one this family does not take and another does
    others = set().union(*(
        {f.name for f in dataclasses.fields(family_module(other).Config)}
        for other in FAMILIES)) - fields
    for name in ["state_size"] + sorted(others)[:1]:
        assert name not in fields
        with pytest.raises(TypeError, match=name):
            LLMConfig(model_family=family, **{name: 1})
        with pytest.raises(TypeError, match=name):
            LLMConfig(model_family=family, model={name: 1})
        # None: not stated, so nothing to refuse
        assert LLMConfig(model_family=family, **{name: None}).model == {}
    # a router's flat names are taken for every family (``config_for``
    # drops them where they describe no router), a field of the family's is
    assert LLMConfig(model_family=family, moe_num_experts=0,
                     moe_top_k=2).model == {"moe_num_experts": 0,
                                            "moe_top_k": 2}
    assert LLMConfig(model_family=family, remat=False).model_config(
        ).remat is False
    # what the engine decides is not the configuration's to state
    assert LLMConfig(model_family=family, attention_impl="flash"
                     ).model_config().attention_impl == "xla"


def test_an_unknown_family_fails_as_the_configuration_is_made():
    with pytest.raises(ValueError, match="unknown model_family 'mamba'"):
        LLMConfig(model_family="mamba")
    with pytest.raises(ValueError, match="unknown model_family 'mamba'"):
        models.config_keys("mamba")


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_familys_fields_read_off_its_source_are_its_configs_fields(family):
    """``config_keys`` reads a family's source without running it: what it
    reads there is what ``dataclasses.fields`` says."""
    fields = [f.name for f in dataclasses.fields(family_module(family).Config)]
    assert models._declared_fields(FAMILIES[family]) == fields
    assert models.config_keys(family) == set(fields) | set(models.MOE_KEYS)


def test_stating_a_configuration_loads_no_jax():
    """A serving driver builds an ``LLMConfig`` and runs no model: the check
    of its keys must not cost it jax's import (3.6 s of a ``setup_s`` of
    32 s on the chip's host, PERF.md PR 45)."""
    import subprocess
    import sys

    code = """
import sys
from ray_tpu.llm import LLMConfig
from ray_tpu.models import FAMILIES
for family in FAMILIES:
    LLMConfig(model_family=family, num_layers=2, moe_top_k=2)
    try:
        LLMConfig(model_family=family, state_size=16)
    except TypeError as e:
        assert "state_size" in str(e), e
    else:
        raise AssertionError(family)
config = LLMConfig(model_family="granite_hybrid", mamba_d_state=16)
assert LLMConfig.from_dict(config.to_dict()).model == {"mamba_d_state": 16}
assert "jax" not in sys.modules
"""
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr


# a router stated as a training configuration states it (capacity queues:
# ``dropless`` false), under each of the three forms ``config_for`` takes
TRAINED_ROUTERS = {
    "flat": {"moe_num_experts": 4, "moe_top_k": 2},
    "nested": {"moe": {"num_experts": 4, "top_k": 2}},
    "object": {"moe": MoEConfig(num_experts=4, top_k=2,
                                activation="swiglu")},
}


@pytest.mark.parametrize("form", list(TRAINED_ROUTERS))
def test_a_bundle_trained_with_capacity_queues_is_served_dropless(form):
    stated = dict(vocab_size=512, max_seq_len=128, num_layers=2, num_heads=4,
                  num_kv_heads=2, embed_dim=64, **TRAINED_ROUTERS[form])
    trained = config_for("llama", **stated)
    assert not trained.moe.dropless and trained.moe.activation == "swiglu"
    bundle = {"family": "llama", "config": stated}
    # the bundle's family and sizes win over the configuration's own
    served = LLMConfig(model_family="gpt2", num_layers=7).model_config(bundle)
    assert served == dataclasses.replace(
        trained, moe=dataclasses.replace(trained.moe, dropless=True))
    assert config_for("llama", **stated) == trained   # ``stated`` untouched
    # a bundle that states no architecture: the configuration's
    assert LLMConfig(num_layers=7).model_config({"params": None}
                                                ).num_layers == 7


def test_a_flat_number_goes_over_the_nested_block_and_needs_a_router():
    nested = {"num_experts": 4, "top_k": 2, "activation": "gelu"}
    cfg = config_for("llama", moe=nested, moe_top_k=1, moe_dropless=True)
    assert cfg.moe == MoEConfig(num_experts=4, top_k=1, activation="gelu",
                                dropless=True)
    assert config_for("llama", moe=MoEConfig(**nested), moe_top_k=1,
                      moe_dropless=True) == cfg
    # no block and no experts: a router's numbers describe nothing
    assert config_for("llama", moe_top_k=1, moe_dropless=True).moe is None
    assert config_for("llama", moe_num_experts=0, moe_top_k=1).moe is None
    assert config_for("llama", moe=None, moe_dropless=True).moe is None
    # the bias exists where its deviation is stated
    assert config_for("llama", moe_num_experts=4,
                      moe_expert_bias_init_std=0.02).moe.expert_bias
    assert not config_for("llama", moe_num_experts=4).moe.expert_bias


def test_the_engine_serves_a_trained_bundle_dropless(tmp_path):
    import pickle

    from ray_tpu.llm.engine import DecodeEngine, SamplingParams
    from ray_tpu.models import llama

    stated = dict(vocab_size=512, max_seq_len=64, num_layers=2, num_heads=4,
                  num_kv_heads=2, embed_dim=64, dtype="float32",
                  **TRAINED_ROUTERS["nested"])
    trained = config_for("llama", **stated)
    params = llama.init_params(trained, jax.random.PRNGKey(0))
    path = tmp_path / "bundle.pkl"
    with open(path, "wb") as f:
        pickle.dump({"family": "llama", "config": stated,
                     "params": jax.tree.map(np.asarray, params)}, f)
    knobs = dict(max_batch_slots=2, prefill_buckets=(16,))
    engine = DecodeEngine(LLMConfig(model_source=str(path), **knobs))
    try:
        cfg = engine.model_config
        assert type(cfg) is llama.Config and cfg.moe.dropless
        assert cfg == dataclasses.replace(
            trained, moe=dataclasses.replace(trained.moe, dropless=True))
        assert engine._moe_top_k == 2 and engine._moe_layers == 2
        got = engine.generate([5, 9, 11], SamplingParams(max_new_tokens=4))
    finally:
        engine.shutdown()
    # what a configuration that states the same model serves on the same
    # weights
    flat = {k: v for k, v in stated.items() if k != "moe"}
    direct = DecodeEngine(
        LLMConfig(model_family="llama", moe_num_experts=4, moe_top_k=2,
                  **flat, **knobs), params=params)
    try:
        assert direct.model_config == dataclasses.replace(
            cfg, attention_impl="xla")
        assert direct.generate(
            [5, 9, 11], SamplingParams(max_new_tokens=4)) == got
    finally:
        direct.shutdown()


# ------------------------------------------------------- kinds of layer


@pytest.mark.parametrize("family", ["gpt2", "llama"])
@pytest.mark.parametrize("experts", [0, 4], ids=["dense", "routed"])
def test_a_family_of_one_kind_of_layer_is_one_scan(family, experts):
    """``gpt2`` and ``llama`` state one kind of layer: one segment over the
    blocks as they are stacked, no window, one cache."""
    module = family_module(family)
    cfg = families.variants(family)["routed" if experts else "dense"]
    params = module.init_params(cfg, jax.random.PRNGKey(0))
    for cached in (False, True):
        segments, stack = module.layers(cfg, params["blocks"], cached)
        (kinds, (blocks,), repeats), = segments
        assert kinds == (decoder.Layer(routed=bool(experts) and cached),)
        assert repeats == cfg.num_layers
        # routed experts leave the scan of the cached forward alone
        assert (stack is not None) == (bool(experts) and cached)
        assert ("moe" in blocks) == (bool(experts) and not cached)
    assert set(decoder.layer_kinds(cfg)) == {
        decoder.Layer(routed=bool(experts))}
    assert sorted(decoder.init_kv_cache(cfg, 2, 32)) == ["k", "v"]
