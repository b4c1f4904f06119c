"""Model zoo: model families as pieces over one decoder.

``decoder.py`` holds what every family shares, each written once: the layer
stack (``forward_features``, ``forward``), the cached forward
(``init_kv_cache``, ``forward_cached``), the pipeline
(``forward_pipelined``), ``loss_fn``, ``count_params``. A family's module
(``FAMILIES``) holds the pieces that differ: its ``Config`` dataclass and
``PRESETS``, ``init_params`` and ``param_axes``, and the pieces the decoder
calls (``layers``, ``embed``, ``qkv``, ``attn_out``, ``ffn``, ``final_norm``,
``head``, ``head_weight``, and ``state_in`` / ``state_out`` / ``state_leaves``
where a layer keeps a state; ``decoder.py`` gives each one's signature) and
the one a server calls once (``serving_params``), and it hands the decoder's
functions on under its own name, so ``module_for(cfg).loss_fn`` is the one
definition. A new architecture is a family module, or a piece of one, and
one line of ``FAMILIES``.

Train/LLM layers find a config's family via :func:`module_for`, and build a
family's config from plain keyword arguments via :func:`config_for`.
The KV cache is ``{"k", "v"}``, each ``[L, B, KV, D, S]``, and for a model with
window layers their rings, with state layers their states, beside it
(``kv_cache.py``): callers outside this
package rely on the slot being axis 1 of every leaf and on nothing else.
"""
from __future__ import annotations

import importlib
from typing import Any

# family name -> its module (imported when asked for: they import jax)
FAMILIES = {
    "gpt2": "ray_tpu.models.gpt2",
    "llama": "ray_tpu.models.llama",
    "afmoe": "ray_tpu.models.afmoe",
    "smallthinker": "ray_tpu.models.smallthinker",
    "granite_hybrid": "ray_tpu.models.granite_hybrid",
}


def family_module(family: str):
    """The module of the family called ``family``."""
    if family not in FAMILIES:
        raise ValueError(
            f"unknown model_family {family!r} ({' | '.join(FAMILIES)})")
    return importlib.import_module(FAMILIES[family])


def module_for(config: Any):
    """Return the model module that owns this config object."""
    for family in FAMILIES:
        module = family_module(family)
        if isinstance(config, module.Config):
            return module
    raise TypeError(f"unknown model config type: {type(config).__name__}")


# a router's numbers under the flat names a configuration file and
# ``LLMConfig`` give them -> the ``MoEConfig`` field each one is
MOE_KEYS = {
    "moe_num_experts": "num_experts", "moe_top_k": "top_k",
    "moe_norm_topk_prob": "norm_topk_prob",
    "moe_router_init_std": "router_init_std",
    "moe_score_func": "score_func", "moe_route_scale": "route_scale",
    "moe_expert_bias_init_std": "expert_bias_init_std",
    "moe_num_held": "num_held", "moe_first_held": "first_held",
    "moe_dropless": "dropless",
}


def config_for(family: str, **kwargs):
    """The ``family``'s own config object from keyword arguments as a file or
    a bundle states them: ``dtype`` / ``param_dtype`` may be names
    ("bfloat16"), ``moe`` a dictionary of ``MoEConfig`` fields (experts with
    no ``activation`` stated get the family's), or the router's numbers flat
    (``MOE_KEYS``; ``moe_num_experts`` 0 is a dense model; a stated
    ``moe_expert_bias_init_std`` is a router with ``expert_bias``). A keyword
    the family's config does not take is its ``TypeError``, by that name."""
    import jax.numpy as jnp

    from ray_tpu.parallel.moe import MoEConfig

    module = family_module(family)
    for key in ("dtype", "param_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = jnp.dtype(kwargs[key]).type
    flat = {MOE_KEYS[key]: kwargs.pop(key) for key in list(kwargs)
            if key in MOE_KEYS}
    if flat.get("num_experts"):
        flat["expert_bias"] = "expert_bias_init_std" in flat
        kwargs["moe"] = {**flat, **(kwargs.get("moe") or {})}
    if isinstance(kwargs.get("moe"), dict):
        kwargs["moe"] = MoEConfig(
            **{"activation": module.EXPERT_ACTIVATION, **kwargs["moe"]})
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
