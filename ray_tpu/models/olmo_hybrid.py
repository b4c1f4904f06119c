"""Ai2's ``olmo_hybrid`` decoder (Olmo-Hybrid-7B) as pieces over the one
decoder: the sixth family, and the second whose state layers are no
attention, with another recurrence than the first's.

What the published ``config.json`` gives, and what it leaves to the family's
convention and to Gated DeltaNet as published (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464; the flash-linear-attention layer whose key names the
config follows), each of the latter stated as ``assumed`` in a configuration
file:

- the stream is ``wte[tokens]``; the head is a table of its own (untied);
  RMSNorm with a gain everywhere;
- every layer norms each branch's OUTPUT, not its input (OLMo 2's and OLMo
  3's order): ``h = h + norm(mix(h))``, then ``h = h + norm(mlp(h))`` with
  ``mlp(x) = (silu(x Wg) * x Wu) Wd``, no bias; a last norm before the head;
- ``layer_types`` says which layers attend (``full_attention``) and which
  keep a state (``linear_attention``); published: three of these and one of
  those, eight times;
- a ``full_attention`` layer: as many kv heads as heads, bias-free q, k, v,
  o; q and k each normed over ALL their channels before the heads are split;
  no rotation (``rope_theta: null``): order comes from the state layers;
  causal softmax over ``q . k / sqrt(head_dim)``;
- a ``linear_attention`` layer (the gated delta rule, ``ops/delta_rule.py``):
  ``[q, k, v]`` through a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps, no bias, and a SiLU; each head's q and k
  to unit length; ``beta = sigmoid(x Wb)``, doubled where
  ``linear_allow_neg_eigval``; the log decay ``g = -exp(A_log) softplus(x Wa
  + dt_bias)``; the recurrence over a ``[linear_key_head_dim,
  linear_value_head_dim]`` matrix a head; each head's output normed over its
  own channels (one gain, shared by the heads) and THEN gated by ``silu(x
  Wg)``; the output projection.

What a sequence carries through a ``linear_attention`` layer is its matrices
and the convolution's last rows (``state_leaves``; ``models/kv_cache.py:
recur``, which the skeleton runs between ``state_in`` and ``state_out``).
The stack is ``granite_hybrid``'s: the shortest period of kinds, each
compiled once.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import narrowed
from ray_tpu.models.decoder import *  # noqa: F401,F403 — what families share
from ray_tpu.models.decoder import Layer, Segment, periods
from ray_tpu.models.llama import _rms_norm
from ray_tpu.ops import delta_rule

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    max_seq_len: int = 65536
    num_layers: int = 32
    num_heads: int = 30
    num_kv_heads: Optional[int] = None   # None = as many as ``num_heads``
    embed_dim: int = 3840
    head_dim: Optional[int] = None       # None = embed_dim / num_heads
    mlp_dim: int = 11008
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    remat_policy: str = "dots"
    seq_axis: str = "seq"
    moe: Optional[Any] = None            # no layer is routed: refused
    # a layer's mixer, first to last, as ``config.json`` names it:
    # "linear_attention" | "full_attention"; the first ``num_layers`` of
    # them count. None = every layer attends. Held as given, a JSON file's
    # list too, so out of the hash (``mixer_types`` is what the code reads)
    layer_types: Optional[Sequence[str]] = field(default=None, hash=False)
    # the state layers' sizes, under their published names
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30      # as many as key heads, or refused
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True  # beta in (0, 2), not (0, 1)
    linear_chunk_size: int = 64           # tokens a chunk of the scan
    # what the cache holds a state in: float32 is the one value taken
    # (stated, so that a file says it), as ``granite_hybrid``'s
    state_dtype: str = "float32"

    def __post_init__(self):
        if self.num_kv_heads is None:
            object.__setattr__(self, "num_kv_heads", self.num_heads)
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.embed_dim // self.num_heads)
        types = self.mixer_types
        if len(types) != self.num_layers or set(types) - {LINEAR, FULL}:
            raise ValueError(
                f"OlmoHybridConfig.layer_types must name {self.num_layers} "
                f"layers or more {LINEAR!r} or {FULL!r}, got "
                f"{self.layer_types!r}")
        if self.moe is not None:
            raise ValueError(
                "OlmoHybridConfig.moe: no layer is routed here")
        if self.linear_num_value_heads != self.linear_num_key_heads:
            raise ValueError(
                "OlmoHybridConfig.linear_num_value_heads: a value head has "
                "a key head of its own here (as published: 30 and 30)")
        if self.state_dtype != "float32":
            raise ValueError(
                "OlmoHybridConfig.state_dtype: the cache holds a state in "
                "float32 and nothing narrower (a step corrects what the "
                "state holds, so its rounding is read back and written "
                f"again), got {self.state_dtype!r}")

    @property
    def mixer_types(self) -> Tuple[str, ...]:
        """``layer_types`` of the ``num_layers`` layers there are."""
        given = self.layer_types or (FULL,) * self.num_layers
        return tuple(given[:self.num_layers])

    @property
    def linear_key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels through the convolution: q, k and v side by side."""
        return 2 * self.linear_key_dim + self.linear_value_dim


Config = OlmoHybridConfig
EXPERT_ACTIVATION = "swiglu"

OLMO_HYBRID_TINY = OlmoHybridConfig(     # test size: two periods of 3 + 1
    vocab_size=512, max_seq_len=128, num_layers=8, num_heads=2, embed_dim=64,
    mlp_dim=96, layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2,
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_chunk_size=4,
)

PRESETS = {"olmo-hybrid-tiny": OLMO_HYBRID_TINY}


def _kinds(config: Config) -> Tuple[Layer, ...]:
    return tuple(
        Layer(kind, state=config.linear_chunk_size,
              recurrence=delta_rule.GATED_DELTA)
        if kind == LINEAR else Layer(kind) for kind in config.mixer_types)


def state_leaves(config: Config) -> Dict[str, tuple]:
    """A slot's share of a state layer's cache: the matrix a head (its
    value columns up to whole lane tiles: ``delta_rule.held_shape``), and
    the rows the convolution still needs."""
    return {
        "ssm": (delta_rule.held_shape(
            config.linear_num_key_heads, config.linear_key_head_dim,
            config.linear_value_head_dim), jnp.float32),
        "conv": (((config.linear_conv_kernel_dim - 1)
                  * config.linear_conv_dim,), config.dtype),
    }


def init_params(config: Config, key: jax.Array) -> Dict[str, Any]:
    """Matrices at 0.02 (into the residual stream at 0.02 / sqrt(2 L)),
    gains 1; a state layer's own as Gated DeltaNet is published: ``A_log =
    ln U(0, 16)``, ``dt_bias`` the inverse softplus of ``exp U[ln 1e-3, ln
    1e-1]``, the convolution U[-1/sqrt(K), 1/sqrt(K)] (torch's ``Conv1d``
    at a fan-in of K)."""
    E, H, KV, D, V, M = (config.embed_dim, config.num_heads,
                         config.num_kv_heads, config.head_dim,
                         config.vocab_size, config.mlp_dim)
    C, K = config.linear_conv_dim, config.linear_conv_kernel_dim
    heads, inner = config.linear_num_key_heads, config.linear_value_dim
    pd = config.param_dtype
    std = 0.02
    res_std = std / (2 * config.num_layers) ** 0.5
    k_wte, k_head, k_layers = jax.random.split(key, 3)

    def layer(key, kind: Layer, n: int):
        k = jax.random.split(key, 10)

        def normal(key, shape, s=std):
            return (jax.random.normal(key, (n,) + shape) * s).astype(pd)

        def uniform(key, shape, lo, hi):
            return jax.random.uniform(key, (n,) + shape, jnp.float32, lo, hi)

        out = {
            "mix_norm": jnp.ones((n, E), pd),
            "mlp_norm": jnp.ones((n, E), pd),
            "w_gate": normal(k[0], (E, M)),
            "w_up": normal(k[1], (E, M)),
            "w_down": normal(k[2], (M, E), res_std),
        }
        if kind.state is None:
            out.update({
                "wq": normal(k[3], (E, H, D)), "wk": normal(k[4], (E, KV, D)),
                "wv": normal(k[5], (E, KV, D)),
                "wo": normal(k[6], (H, D, E), res_std),
                "q_norm": jnp.ones((n, H * D), pd),
                "k_norm": jnp.ones((n, KV * D), pd)})
            return out
        dt = jnp.exp(uniform(k[7], (heads,), jnp.log(1e-3), jnp.log(1e-1)))
        out.update({
            # q, k, v (what the convolution takes) and the gate; beta and
            # the decay apart: 60 columns more would leave the matrix's
            # width no multiple of the chip's 128 lanes, and its compiler
            # then lays the whole matrix out anew in every program that
            # reads it (1.6 GB of copies a decode tick at the published size)
            "delta_in": normal(k[3], (E, C + inner)),
            "delta_gates": normal(k[9], (E, 2 * heads)),
            "delta_out": normal(k[4], (inner, E), res_std),
            "conv_w": uniform(k[5], (C, K), -K ** -0.5, K ** -0.5).astype(pd),
            # softplus(dt_bias) = dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            # U(0, 16): the open end is the smallest float32 above 0
            "A_log": jnp.log(uniform(
                k[8], (heads,), jnp.finfo(jnp.float32).tiny, 16.0)),
            "gate_norm": jnp.ones((n, config.linear_value_head_dim), pd),
        })
        return out

    segments = tuple(
        tuple(layer(jax.random.fold_in(jax.random.fold_in(k_layers, s), j),
                    kind, repeats) for j, kind in enumerate(kinds))
        for s, (kinds, repeats) in enumerate(periods(_kinds(config))))
    return {
        "wte": (jax.random.normal(k_wte, (V, E)) * std).astype(pd),
        "blocks": {"segments": segments},
        "norm_f": jnp.ones((E,), pd),
        "lm_head": (jax.random.normal(k_head, (V, E)) * std).astype(pd),
    }


def param_axes(config: Config) -> Dict[str, Any]:
    def layer(kind: Layer):
        axes = {"mix_norm": ("stage", "norm"), "mlp_norm": ("stage", "norm"),
                "w_gate": ("stage", "embed", "mlp"),
                "w_up": ("stage", "embed", "mlp"),
                "w_down": ("stage", "mlp", "embed")}
        if kind.state is None:
            axes.update({"wq": ("stage", "embed", "heads", "head_dim"),
                         "wk": ("stage", "embed", "kv", "head_dim"),
                         "wv": ("stage", "embed", "kv", "head_dim"),
                         "wo": ("stage", "heads", "head_dim", "embed"),
                         "q_norm": ("stage", "norm"),
                         "k_norm": ("stage", "norm")})
            return axes
        axes.update({"delta_in": ("stage", "embed", "mlp"),
                     "delta_gates": ("stage", "embed", None),
                     "delta_out": ("stage", "mlp", "embed"),
                     "conv_w": ("stage", "mlp", None),
                     "gate_norm": ("stage", None),
                     "dt_bias": ("stage", None), "A_log": ("stage", None)})
        return axes

    return {"wte": ("vocab", "embed"),
            "blocks": {"segments": tuple(
                tuple(layer(kind) for kind in kinds)
                for kinds, _ in periods(_kinds(config)))},
            "norm_f": ("norm",),
            "lm_head": ("vocab", "embed")}


def serving_params(config: Config, params):
    """The projections, the MLPs and ``lm_head`` are read through
    ``.astype(config.dtype)`` alone. Read as they are: ``wte`` (the cached
    forward's stream is float32, as llama's), every RMSNorm gain, a state
    layer's ``dt_bias`` and ``A_log`` (float32: they set decays near 1) and
    its convolution (summed in float32 from the weights as held)."""
    return narrowed(params, config.dtype, as_given=(
        "wte", "mix_norm", "mlp_norm", "q_norm", "k_norm", "gate_norm",
        "norm_f", "dt_bias", "A_log", "conv_w"))


def layers(config: Config, blocks, cached: bool):
    """The period's segments over ``blocks["segments"]``; no layer is
    routed."""
    plan = periods(_kinds(config))
    held = [(None,) * len(kinds) for kinds, _ in plan] if blocks is None \
        else blocks["segments"]
    return ([Segment(kinds, params, repeats)
             for (kinds, repeats), params in zip(plan, held)], None)


def embed(config: Config, params, tokens, pos, cached: bool):
    """Token embeddings; no position enters anywhere. The cached forward
    sums its stream in float32, as llama's."""
    return params["wte"][tokens].astype(
        jnp.float32 if cached else config.dtype)


def _branch(config: Config, x, out, gain):
    """``x + norm(out)``: a branch's OUTPUT is normed, and the stream it
    joins is not."""
    return x + _rms_norm(out, gain, config.rms_eps, x.dtype)


def qkv(config: Config, kind, layer, x, pos, heads_major: bool = False):
    """x [B, T, E] as the stream has it (no norm before a branch) -> q, k
    and v [B, T, H, D]: q and k normed over all their channels together,
    then split into heads; nothing is rotated. That norm is written
    positions-major only: where ``heads_major`` q and k are transposed
    behind it, [B, H, T, D], and v's product writes that order itself."""
    B, T = x.shape[:2]
    h = x.astype(config.dtype)
    q = jnp.einsum("bte,ehd->bthd", h, layer["wq"].astype(h.dtype))
    k = jnp.einsum("bte,ehd->bthd", h, layer["wk"].astype(h.dtype))
    v = heads_in(h, layer["wv"].astype(h.dtype), heads_major)
    q = _rms_norm(q.reshape(B, T, -1), layer["q_norm"],
                  config.rms_eps).reshape(B, T, -1, config.head_dim)
    k = _rms_norm(k.reshape(B, T, -1), layer["k_norm"],
                  config.rms_eps).reshape(k.shape)
    return (*swapped(q, k), v) if heads_major else (q, k, v)


def attn_out(config: Config, layer, x, attn, heads_major: bool = False):
    out = heads_out(attn, layer["wo"].astype(attn.dtype), heads_major)
    return _branch(config, x, out, layer["mix_norm"])


def state_in(config: Config, kind, layer, x):
    """A ``linear_attention`` layer up to its recurrence: x [B, T, E]
    projected -> (q, k and v side by side [B, T, C], the gates (log decay g
    <= 0, beta) [B, T, H] float32 each, the output's gate [B, T, inner])."""
    C = config.linear_conv_dim
    h = x.astype(config.dtype)
    with jax.named_scope("delta.in_proj"):
        qkv_, gate = jnp.split(
            jnp.einsum("bte,ef->btf", h, layer["delta_in"].astype(h.dtype)),
            [C], axis=-1)
        b, a = jnp.split(jnp.einsum(
            "bte,ef->btf", h, layer["delta_gates"].astype(h.dtype),
            preferred_element_type=jnp.float32), 2, axis=-1)
        beta = jax.nn.sigmoid(b)
        if config.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(layer["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a + layer["dt_bias"])
    return qkv_, (g, beta), gate


def state_out(config: Config, layer, x, y, gate):
    """What the recurrence gave, y [B, T, H, Dv] float32: each head normed
    over its own channels, THEN gated by ``silu(gate)``; the output
    projection; the branch's norm and the residual."""
    with jax.named_scope("delta.gate_norm"):
        y = _rms_norm(y, layer["gate_norm"], config.rms_eps)
        y = (y.reshape(*y.shape[:2], -1)
             * jax.nn.silu(gate.astype(jnp.float32))).astype(config.dtype)
    with jax.named_scope("delta.out_proj"):
        out = jnp.einsum("btf,fe->bte", y, layer["delta_out"].astype(y.dtype))
    return _branch(config, x, out, layer["mix_norm"])


def ffn(config: Config, kind, layer, x, rng, row_mask, stacked,
        from_input=None):
    """The gated MLP of the stream as it is, its output normed, the
    residual -> (x, no aux loss, no expert touched)."""
    h = x.astype(config.dtype)
    gate = jnp.einsum("bte,em->btm", h, layer["w_gate"].astype(h.dtype))
    up = jnp.einsum("bte,em->btm", h, layer["w_up"].astype(h.dtype))
    y = jnp.einsum("btm,me->bte", jax.nn.silu(gate) * up,
                   layer["w_down"].astype(h.dtype))
    return (_branch(config, x, y, layer["mlp_norm"]), jnp.float32(0.0),
            jnp.int32(0))


def final_norm(config: Config, params, x):
    return _rms_norm(x, params["norm_f"], config.rms_eps, config.dtype)


def head_weight(params):
    return params["lm_head"]


def head(config: Config, params, x):
    """Final features [B, T, E] -> logits [B, T, V] float32, straight from
    the product's float32 sums (``llama.head`` says why)."""
    return jnp.einsum("bte,ve->btv", x, head_weight(params).astype(x.dtype),
                      preferred_element_type=jnp.float32)
