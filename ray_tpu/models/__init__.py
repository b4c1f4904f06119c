"""Model zoo: model families as pieces over one decoder.

``decoder.py`` holds what every family shares, each written once: the layer
stack (``forward_features``, ``forward``), the cached forward
(``init_kv_cache``, ``forward_cached``), the pipeline
(``forward_pipelined``), ``loss_fn``, ``count_params``. A family's module
(``FAMILIES``) holds the pieces that differ: its ``Config`` dataclass and
``PRESETS``, ``init_params`` and ``param_axes``, and the pieces the decoder
calls (``layers``, ``embed``, ``qkv``, ``attn_out``, ``ffn``, ``final_norm``,
``head``, ``head_weight``, ``at_input`` where its feed-forward reads the
block's input (the decoder's default is None), and ``state_in`` /
``state_out`` / ``state_leaves`` where a layer keeps a state (Mamba-2's in
``granite_hybrid``, the gated delta rule's in ``olmo_hybrid``, Kimi Delta
Attention's in ``bailing_hybrid``: the kind's ``Layer.recurrence`` says
which; a latent layer's ``qkv`` gives a row a position and the
up-projection, ``bailing_hybrid``'s, ``joyai_llm_flash``'s and, the ninth
family, ``deepseek_v32``'s, whose fourth piece is its lightning indexer at
the call's tokens, ``Layer.index``); ``decoder.py`` gives each one's signature) and the one a server calls once
(``serving_params``), and it hands the decoder's functions on under its own
name, so ``module_for(cfg).loss_fn`` is the one definition. A new
architecture is a family module, or a piece of one, and one line of
``FAMILIES``: its numbers are the fields of its ``Config`` and are named
nowhere else.

Train/LLM layers find a config's family via :func:`module_for`, and build a
family's config from a configuration's flat keys via :func:`config_for`: the
trainer from its ``model`` dictionary, ``LLMConfig`` from its sizes and its
``model`` (checked against :func:`config_keys`). ``MOE_KEYS`` is the one
table of a router's flat names, read here alone.
The KV cache is ``{"k", "v"}``, each ``[L, B, KV, D, S]``, and for a model with
window layers their rings, with state layers their states, with latent
layers their rows (and an indexer's keys), beside it
(``kv_cache.py``): callers outside this
package rely on the slot being axis 1 of every leaf and on nothing else.
"""
from __future__ import annotations

import ast
import dataclasses
import functools
import importlib.util
from typing import Any

# family name -> its module (imported when asked for: they import jax)
FAMILIES = {
    "gpt2": "ray_tpu.models.gpt2",
    "llama": "ray_tpu.models.llama",
    "afmoe": "ray_tpu.models.afmoe",
    "smallthinker": "ray_tpu.models.smallthinker",
    "granite_hybrid": "ray_tpu.models.granite_hybrid",
    "olmo_hybrid": "ray_tpu.models.olmo_hybrid",
    "bailing_hybrid": "ray_tpu.models.bailing_hybrid",
    "joyai_llm_flash": "ray_tpu.models.joyai_llm_flash",
    "deepseek_v32": "ray_tpu.models.deepseek_v32",
}


def _module_name(family: str) -> str:
    if family not in FAMILIES:
        raise ValueError(
            f"unknown model_family {family!r} ({' | '.join(FAMILIES)})")
    return FAMILIES[family]


def family_module(family: str):
    """The module of the family called ``family``."""
    return importlib.import_module(_module_name(family))


def module_for(config: Any):
    """Return the model module that owns this config object."""
    for family in FAMILIES:
        module = family_module(family)
        if isinstance(config, module.Config):
            return module
    raise TypeError(f"unknown model config type: {type(config).__name__}")


# a router's numbers under the flat names a configuration gives them -> the
# ``MoEConfig`` field each one is
MOE_KEYS = {
    "moe_num_experts": "num_experts", "moe_top_k": "top_k",
    "moe_norm_topk_prob": "norm_topk_prob",
    "moe_router_init_std": "router_init_std",
    "moe_score_func": "score_func", "moe_route_scale": "route_scale",
    "moe_expert_bias_init_std": "expert_bias_init_std",
    "moe_num_held": "num_held", "moe_first_held": "first_held",
    "moe_dropless": "dropless",
    "moe_n_group": "n_group", "moe_topk_group": "topk_group",
    "moe_bias_update_rate": "bias_update_rate",
    "moe_aux_loss_weight": "aux_loss_weight",
}


def config_keys(family: str) -> frozenset:
    """The keywords :func:`config_for` takes for ``family``: the fields of
    its ``Config`` and the router's flat names. The fields are read off the
    module's source where it shows them: a serving driver states a
    configuration and runs no model, and importing a family brings jax (3.6 s
    on the chip's host, a tenth of a cell's ``setup_s``; PERF.md, PR 45)."""
    fields = _declared_fields(_module_name(family))
    if fields is None:
        fields = [
            f.name for f in dataclasses.fields(family_module(family).Config)]
    return frozenset(MOE_KEYS) | set(fields)


@functools.lru_cache(maxsize=None)
def _declared_fields(module: str):
    """The field names of the class that ``module``'s source binds to
    ``Config``, without running it; None where the source does not show them
    all (the class has a base)."""
    with open(importlib.util.find_spec(module).origin) as f:
        body = ast.parse(f.read()).body
    bound = next((n.value.id for n in body if isinstance(n, ast.Assign)
                  and isinstance(n.value, ast.Name)
                  and getattr(n.targets[0], "id", None) == "Config"), "Config")
    for node in body:
        if isinstance(node, ast.ClassDef) and node.name == bound:
            return None if node.bases else [
                a.target.id for a in node.body if isinstance(a, ast.AnnAssign)]
    return None


def config_for(family: str, **kwargs):
    """The ``family``'s own config object from keyword arguments as a file or
    a bundle states them: ``dtype`` / ``param_dtype`` may be names
    ("bfloat16"), ``moe`` a ``MoEConfig`` or a dictionary of its fields
    (experts with no ``activation`` stated get the family's), or the router's
    numbers flat (``MOE_KEYS``; a stated ``moe_expert_bias_init_std`` is a
    router with ``expert_bias``). A flat number goes over a nested block's;
    with no block and ``moe_num_experts`` 0 or absent the flat numbers are
    dropped (a dense model, or the family's own experts). A keyword the
    family's config does not take is its ``TypeError``, by that name."""
    import jax.numpy as jnp

    from ray_tpu.parallel.moe import MoEConfig

    module = family_module(family)
    for key in ("dtype", "param_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = jnp.dtype(kwargs[key]).type
    flat = {MOE_KEYS[key]: kwargs.pop(key) for key in list(kwargs)
            if key in MOE_KEYS}
    if "expert_bias_init_std" in flat:
        flat["expert_bias"] = True
    moe = kwargs.get("moe")
    if moe is None and flat.get("num_experts"):
        moe = {}
    if isinstance(moe, MoEConfig):
        kwargs["moe"] = dataclasses.replace(moe, **flat)
    elif moe is not None:
        kwargs["moe"] = MoEConfig(
            **{"activation": module.EXPERT_ACTIVATION, **moe, **flat})
    return module.Config(**kwargs)


def narrowed(tree, dtype, as_given=()):
    """``tree`` with every leaf that is held wider than ``dtype`` cast to it,
    but for the leaves whose own name (their last key) is in ``as_given``.
    Every other leaf of the result IS the leaf given, so where nothing is
    wider no operation runs and no byte is copied. What a family's
    ``serving_params`` is made of: the family says which names its cached
    forward reads as they are."""
    import jax
    import jax.numpy as jnp

    width = jnp.dtype(dtype).itemsize

    def held(path, leaf):
        if path[-1].key in as_given or leaf.dtype.itemsize <= width:
            return leaf
        return leaf.astype(dtype)

    return jax.tree_util.tree_map_with_path(held, tree)


def get_preset(name: str):
    """Look up a preset config by name across all families."""
    presets = {}
    for family in FAMILIES:
        presets.update(family_module(family).PRESETS)
    if name not in presets:
        raise KeyError(
            f"unknown model preset {name!r}; known: {sorted(presets)}")
    return presets[name]
