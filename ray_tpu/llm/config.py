"""LLM layer configuration.

Reference analog: ``python/ray/llm/_internal/common/models.py`` /
``serve/engines/vllm/vllm_models.py`` — ``LLMConfig`` carrying model id,
engine kwargs (tensor_parallel_size etc.), and serving knobs. The reference
delegates the engine to vLLM; here the engine is in-framework
(``ray_tpu/llm/engine.py`` — jitted JAX prefill/decode on a model of
``ray_tpu/models``). ``LLMConfig`` names what this layer reads or defaults;
whatever else a family's ``Config`` takes is the family's to name: here it
is a key of ``model``, handed to ``models.config_for`` as it was stated (the
function and the keys of the trainer's ``model`` dictionary).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence


@dataclass(init=False)
class LLMConfig:
    model_id: str = "gpt2-scratch"
    # Model: either explicit architecture numbers (fresh weights) or a path
    # to a pickled {"family": ..., "config": config kwargs, "params": pytree}
    # bundle ("family" defaults to gpt2 for old bundles).
    model_source: Optional[str] = None
    model_family: str = "gpt2"  # a name of ``models.FAMILIES``
    vocab_size: int = 512
    max_seq_len: int = 1024
    num_layers: int = 4
    num_heads: int = 4
    embed_dim: int = 256
    dtype: str = "bfloat16"
    # Whatever else the family's ``Config`` takes, under the family's own
    # names (``models.config_keys``); not stated = the family's default. The
    # constructor takes these as plain keywords too and files them here
    # (None = not stated); one the family does not take is its ``TypeError``.
    model: Dict[str, Any] = field(default_factory=dict)

    # Engine knobs (reference: engine_kwargs tensor_parallel_size etc.)
    max_batch_slots: int = 8
    prefill_buckets: Sequence[int] = (64, 128, 256)
    tensor_parallel_size: int = 1  # reserved: mesh "tensor" axis size
    # Prompt-lookup speculative decoding (vLLM spec-decode "[ngram]"
    # parity, TPU-first rationale: each verify step amortizes one program
    # dispatch over up to k tokens). OPT-IN; greedy requests only
    # (temperature 0 — rejection-sampling equivalence for stochastic
    # requests is out of scope and those requests fall back to 1-token
    # ticks). 0 disables; k = max draft tokens proposed per step.
    speculative_ngram_k: int = 0
    # Automatic prefix caching (vLLM-APC parity): completed prompt prefills
    # are kept in an LRU; identical prompts skip prefill entirely and
    # shared prefixes (system prompts) prefill only their tail. OPT-IN
    # (0 disables): each entry pins a full [L, 1, max_seq_len, ...] KV
    # pytree on device — size it against your HBM budget.
    prefix_cache_size: int = 0

    # Serving
    max_new_tokens_default: int = 64
    tokenizer: str = "byte"  # "byte" | local HF tokenizer dir

    # serve.deployment(...) keyword arguments for the replica. A replica
    # that must hold a chip asks for it here:
    # {"ray_actor_options": {"num_tpus": 1}}.
    deployment_config: Dict[str, Any] = field(default_factory=dict)

    def __init__(self, **stated):
        from ray_tpu.models import config_keys

        for f in dataclasses.fields(self):
            default = (f.default if f.default_factory is dataclasses.MISSING
                       else f.default_factory())
            setattr(self, f.name, stated.pop(f.name, default))
        # what is left is the family's
        self.model = {k: v for k, v in {**(self.model or {}), **stated}.items()
                      if v is not None}
        unknown = set(self.model) - config_keys(self.model_family)
        if unknown:
            raise TypeError(
                f"LLMConfig(model_family={self.model_family!r}) got an "
                f"unexpected keyword argument {sorted(unknown)[0]!r}")

    def model_config(self, bundle: Optional[dict] = None):
        """The family's own config object of the model stated here, or of a
        loaded ``bundle`` that states its own (``config``, ``family``): a
        mismatch would allocate a KV cache with the wrong layout."""
        from ray_tpu.models import config_for

        if bundle is not None and "config" in bundle:
            family = bundle.get("family", self.model_family)
            stated = bundle["config"]
        else:
            family = self.model_family
            stated = {
                "vocab_size": self.vocab_size,
                "max_seq_len": self.max_seq_len,
                "num_layers": self.num_layers, "num_heads": self.num_heads,
                "embed_dim": self.embed_dim, "dtype": self.dtype,
                **self.model, "attention_impl": "xla"}
        # Inference routes dropless, whatever the model was trained with:
        # capacity-queue drops depend on the rest of the batch, so prefill
        # and per-step decode would disagree (and with the full forward).
        return config_for(family, **{**stated, "moe_dropless": True})

    def to_dict(self) -> dict:
        d = dict(self.__dict__)
        d["prefill_buckets"] = list(self.prefill_buckets)
        d["model"] = {k: list(v) if isinstance(v, tuple) else v
                      for k, v in self.model.items()}
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "LLMConfig":
        return cls(**d)


class ByteTokenizer:
    """Self-contained UTF-8 byte tokenizer (ids = byte + 2; 0=pad, 1=eos).

    Stands in for a model tokenizer in environments with no downloadable
    vocab; real checkpoints bring their own tokenizer dir (``tokenizer``
    config field pointing at local HF files).
    """

    pad_id = 0
    eos_id = 1
    vocab_floor = 258

    def encode(self, text: str):
        return [b + 2 for b in text.encode("utf-8")]

    def decode(self, ids) -> str:
        # Total over any model vocab: ids beyond the byte range (the model
        # may have vocab_size > 258) decode to nothing rather than raising.
        return bytes(
            i - 2 for i in ids if 2 <= i <= 257
        ).decode("utf-8", errors="replace")


def load_tokenizer(config: LLMConfig):
    if config.tokenizer == "byte":
        return ByteTokenizer()
    from transformers import AutoTokenizer

    return AutoTokenizer.from_pretrained(config.tokenizer)
