"""Model zoo: functional JAX model families sharing one interface.

Each model module exposes: a frozen ``*Config`` dataclass, ``PRESETS``,
``init_params``, ``param_axes``, ``forward``, ``forward_cached``,
``init_kv_cache``, ``loss_fn``, ``count_params``, ``flops_per_token`` (and
optionally ``forward_pipelined``). Train/LLM layers dispatch on the config
type via :func:`module_for` — adding a family means adding a module here.
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
