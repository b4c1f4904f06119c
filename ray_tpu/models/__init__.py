"""Model zoo: functional JAX model families sharing one interface.

Each model module exposes: a frozen ``*Config`` dataclass, ``PRESETS``,
``init_params``, ``param_axes``, ``forward``, ``forward_cached``,
``init_kv_cache``, ``loss_fn``, ``count_params``, ``flops_per_token`` (and
optionally ``forward_pipelined``). Train/LLM layers dispatch on the config
type via :func:`module_for`, and build a family's config from plain keyword
arguments via :func:`config_for` — adding a family means adding a module
here.
The KV cache is ``{"k", "v"}``, each ``[L, B, KV, D, S]`` (``kv_cache.py``):
callers outside a model module rely on the slot being axis 1 and on nothing
else.
"""
from __future__ import annotations

from typing import Any


def module_for(config: Any):
    """Return the model module that owns this config object."""
    from ray_tpu.models import gpt2, llama

    if isinstance(config, llama.LlamaConfig):
        return llama
    if isinstance(config, gpt2.GPT2Config):
        return gpt2
    raise TypeError(f"unknown model config type: {type(config).__name__}")


FAMILIES = ("gpt2", "llama")


def config_for(family: str, **kwargs):
    """The ``family``'s own config object from keyword arguments as a file or
    a bundle states them: ``dtype`` / ``param_dtype`` may be names
    ("bfloat16"), ``moe`` a dictionary of ``MoEConfig`` fields. A keyword the
    family's config does not take is its ``TypeError``, by that name."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt2, llama
    from ray_tpu.parallel.moe import MoEConfig

    if family not in FAMILIES:
        raise ValueError(
            f"unknown model_family {family!r} ({' | '.join(FAMILIES)})")
    for key in ("dtype", "param_dtype"):
        if isinstance(kwargs.get(key), str):
            kwargs[key] = jnp.dtype(kwargs[key]).type
    if isinstance(kwargs.get("moe"), dict):
        kwargs["moe"] = MoEConfig(**kwargs["moe"])
    cls = llama.LlamaConfig if family == "llama" else gpt2.GPT2Config
    return cls(**kwargs)


def get_preset(name: str):
    """Look up a preset config by name across all families."""
    from ray_tpu.models import gpt2, llama

    for mod in (gpt2, llama):
        if name in mod.PRESETS:
            return mod.PRESETS[name]
    known = sorted(
        list(gpt2.PRESETS) + list(llama.PRESETS)
    )
    raise KeyError(f"unknown model preset {name!r}; known: {known}")
