"""LLM layer configuration.

Reference analog: ``python/ray/llm/_internal/common/models.py`` /
``serve/engines/vllm/vllm_models.py`` — ``LLMConfig`` carrying model id,
engine kwargs (tensor_parallel_size etc.), and serving knobs. The reference
delegates the engine to vLLM; here the engine is in-framework
(``ray_tpu/llm/engine.py`` — jitted JAX prefill/decode on a model of
``ray_tpu/models``), so engine kwargs map onto that family's config
(``models.config_for``) + mesh axes instead of vLLM arguments.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence


@dataclass
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
    num_kv_heads: Optional[int] = None  # llama GQA; None = num_heads (MHA)
    embed_dim: int = 256
    dtype: str = "bfloat16"
    # Architecture numbers only some families take; None = not stated, the
    # family's own default. One that is stated goes to the family's config
    # under its own name, and a family that does not take it refuses it by
    # that name (gpt2 has no ``rope_theta``).
    mlp_dim: Optional[int] = None        # llama: MLP / expert width
    rope_theta: Optional[float] = None   # llama: rotary base
    rms_eps: Optional[float] = None      # llama: RMSNorm epsilon
    qk_norm: Optional[str] = None        # llama: "none" | "full" (OLMoE)
    head_dim: Optional[int] = None       # afmoe: a head's size, stated
    moe_mlp_dim: Optional[int] = None    # afmoe: one expert's width
    num_dense_layers: Optional[int] = None    # afmoe: leading dense layers
    num_shared_experts: Optional[int] = None  # afmoe: beside the routed
    # afmoe: "sliding_attention" | "full_attention" a layer, and the window;
    # granite_hybrid: "mamba" | "attention" a layer
    layer_types: Optional[Any] = None
    sliding_window: Optional[int] = None
    mup_enabled: Optional[bool] = None   # afmoe: embedding x sqrt(embed_dim)
    # smallthinker: 1 a layer with the window (and RoPE), 0 a global one
    sliding_window_layout: Optional[Any] = None
    # granite_hybrid (IBM granitemoehybrid), under config.json's own names:
    # a state layer (Mamba-2) keeps ``mamba_n_heads`` heads of
    # ``mamba_d_head`` channels (``mamba_expand`` x embed_dim in all) with a
    # state of ``mamba_d_state`` each, B and C in ``mamba_n_groups`` groups
    # (1), behind a causal convolution of ``mamba_d_conv`` taps; a prefill
    # scans in chunks of ``mamba_chunk_size``; whether the convolution and
    # the two projections carry a bias
    mamba_d_state: Optional[int] = None
    mamba_d_conv: Optional[int] = None
    mamba_expand: Optional[int] = None
    mamba_n_heads: Optional[int] = None
    mamba_d_head: Optional[int] = None
    mamba_n_groups: Optional[int] = None
    mamba_chunk_size: Optional[int] = None
    mamba_conv_bias: Optional[bool] = None
    mamba_proj_bias: Optional[bool] = None
    # granite_hybrid's four stated factors: on the embedding, on q . k (in
    # place of head_dim ** -0.5), on each branch before the residual sum,
    # and the divisor of the logits
    embedding_multiplier: Optional[float] = None
    attention_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None
    # what the engine's cache holds a state layer's state in: "float32" is
    # the one value the family takes (decays near 1 over thousands of steps
    # gather a narrower rounding); stated so that a configuration says it
    ssm_state_dtype: Optional[str] = None
    # dtype the weights are made (fresh) or loaded (a bundle) in; None = the
    # family's (float32). What a replica HOLDS follows from it and ``dtype``:
    # the engine keeps each weight its family's forward rounds to ``dtype``
    # on use rounded once, at load (``DecodeEngine``)
    param_dtype: Optional[str] = None
    # Routed experts in place of the MLP (``parallel/moe.py``): their number
    # (0 = dense) and how many a token reaches. GELU experts under gpt2,
    # SwiGLU under llama (Mixtral: 8 / 2; OLMoE: 64 / 8 and
    # ``moe_norm_topk_prob=False``, the k gates as the softmax gives them).
    # Served models route dropless: every token reaches its top-k experts.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk_prob: bool = True
    # Scale of the router's initial weights where the weights are fresh
    # (no ``model_source``); None = ``MoEConfig``'s, where training starts.
    # Random weights served in place of a trained model's state a larger
    # one (``MoEConfig.router_init_std`` says why).
    moe_router_init_std: Optional[float] = None
    # The router's score ("softmax" | "sigmoid"), a factor on the k gates,
    # and the deviation fresh weights draw ``expert_bias`` with (stated: the
    # k are chosen under that bias, which the gates do not carry); None =
    # ``MoEConfig``'s (softmax, 1, no bias)
    moe_score_func: Optional[str] = None
    moe_route_scale: Optional[float] = None
    moe_expert_bias_init_std: Optional[float] = None
    # This replica's share of every layer's experts: ``moe_num_held`` of the
    # ``moe_num_experts`` the router scores, from ``moe_first_held`` on (one
    # chip of an expert-parallel group; ``MoEConfig.num_held``). None = all.
    moe_num_held: Optional[int] = None
    moe_first_held: Optional[int] = None

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

    def model_config(self):
        from ray_tpu.models import config_for

        kwargs: Dict[str, Any] = dict(
            vocab_size=self.vocab_size,
            max_seq_len=self.max_seq_len,
            num_layers=self.num_layers,
            num_heads=self.num_heads,
            embed_dim=self.embed_dim,
            dtype=self.dtype,
            attention_impl="xla",
        )
        for name in ("num_kv_heads", "mlp_dim", "rope_theta", "rms_eps",
                     "qk_norm", "param_dtype", "head_dim", "moe_mlp_dim",
                     "num_dense_layers", "num_shared_experts", "layer_types",
                     "sliding_window", "mup_enabled",
                     "sliding_window_layout", "mamba_d_state",
                     "mamba_d_conv", "mamba_expand", "mamba_n_heads",
                     "mamba_d_head", "mamba_n_groups", "mamba_chunk_size",
                     "mamba_conv_bias", "mamba_proj_bias",
                     "embedding_multiplier", "attention_multiplier",
                     "residual_multiplier", "logits_scaling",
                     "ssm_state_dtype"):
            if getattr(self, name) is not None:
                kwargs[name] = getattr(self, name)
        if self.moe_num_experts:
            kwargs["moe"] = dict(
                num_experts=self.moe_num_experts,
                top_k=self.moe_top_k,
                norm_topk_prob=self.moe_norm_topk_prob,
                # Inference routes dropless: capacity-queue drops depend on
                # the rest of the batch, so prefill and per-step decode
                # would disagree (and with the full forward).
                dropless=True,
            )
            for stated, name in (
                    (self.moe_router_init_std, "router_init_std"),
                    (self.moe_score_func, "score_func"),
                    (self.moe_route_scale, "route_scale"),
                    (self.moe_expert_bias_init_std, "expert_bias_init_std"),
                    (self.moe_num_held, "num_held"),
                    (self.moe_first_held, "first_held")):
                if stated is not None:
                    kwargs["moe"][name] = stated
            kwargs["moe"]["expert_bias"] = (
                self.moe_expert_bias_init_std is not None)
        return config_for(self.model_family, **kwargs)

    def to_dict(self) -> dict:
        d = dict(self.__dict__)
        d["prefill_buckets"] = list(self.prefill_buckets)
        for key in ("layer_types", "sliding_window_layout"):
            if isinstance(d[key], tuple):
                d[key] = list(d[key])
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
