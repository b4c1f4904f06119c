"""The model zoo's seam: one decoder, families as pieces, one registry.

``models/decoder.py`` owns the layer stack, the cached forward, the pipeline
and the loss; a family module owns what differs and is listed once, in
``models.FAMILIES``. These tests hold the seam where it is: a family that
grows its own scan, or a decoder that asks which family it serves, fails
here.
"""
import ast
import inspect

import jax
import pytest

from ray_tpu import models
from ray_tpu.llm.config import LLMConfig
from ray_tpu.models import (
    FAMILIES, config_for, decoder, family_module, get_preset, module_for,
)

# what the decoder calls on a family, and what callers outside ask of one
PIECES = ("embed", "qkv", "attn_out", "ffn", "final_norm", "head",
          "head_weight")
OWN = ("Config", "PRESETS", "EXPERT_ACTIVATION", "init_params", "param_axes")
SHARED = ("forward_features", "forward", "init_kv_cache", "forward_cached",
          "forward_pipelined", "loss_fn", "count_params")


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
    assert public == set(PIECES) | {"init_params", "param_axes"}
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
    cfg = LLMConfig(model_family=family, num_heads=4, embed_dim=64,
                    moe_num_experts=experts).model_config()
    assert isinstance(cfg, module.Config)
    assert cfg.num_kv_heads == cfg.num_heads == 4
    if experts:
        assert cfg.moe.num_experts == 4 and cfg.moe.dropless
        assert cfg.moe.activation == module.EXPERT_ACTIVATION
        params = module.init_params(cfg, jax.random.PRNGKey(0))
        gated = "expert_gate" in params["blocks"]["moe"]
        assert gated == (module.EXPERT_ACTIVATION == "swiglu")
    else:
        assert cfg.moe is None
    # stated: the family's config takes it under its own name, or refuses
    # it by that name
    stated = LLMConfig(model_family=family, num_heads=4, embed_dim=64,
                       num_kv_heads=2, moe_num_experts=experts)
    if "num_kv_heads" in module.Config.__dataclass_fields__:
        assert stated.model_config().num_kv_heads == 2
        assert decoder.init_kv_cache(
            stated.model_config(), 3, 16)["k"].shape == (4, 3, 2, 16, 16)
    else:
        with pytest.raises(TypeError, match="num_kv_heads"):
            stated.model_config()
        assert decoder.init_kv_cache(cfg, 3, 16)["k"].shape == (
            4, 3, 4, 16, 16)
