"""IBM's ``granitemoehybrid`` decoder (Granite 4.0-H) as pieces over the one
decoder: the fifth family, and the first with layers that are no attention.

What the published ``config.json`` and the ``transformers`` modeling code of
``granitemoehybrid`` / ``bamba`` describe (Mamba-2 as ``mamba_ssm`` has it):

- the stream is ``embedding_multiplier * wte[tokens]``; the head is the same
  table (tied), its logits divided by ``logits_scaling``; RMSNorm with a
  gain everywhere;
- every layer: ``h = h + residual_multiplier * mix(input_norm(h))``, then
  ``h = h + residual_multiplier * mlp(post_norm(h))`` with ``mlp(x) =
  (silu(g) * u) Wo``, ``[g, u] = x Wi`` split in halves, no bias;
- ``layer_types`` says which layers attend and which keep a state;
- an ``attention`` layer: bias-free q, k, v with G query heads a kv head, no
  q/k norm and NO position signal (``position_embedding_type: "nope"``);
  causal softmax over ``q . k * attention_multiplier`` (a stated number, not
  ``head_dim ** -0.5``: the attention everywhere here divides by
  ``sqrt(head_dim)``, so ``qkv`` hands it ``q * attention_multiplier *
  sqrt(head_dim)``, a power of two at the published sizes and exact);
- a ``mamba`` layer (Mamba-2): ``[z, xBC, dt] = x Win``; ``xBC`` through a
  depthwise causal convolution of ``mamba_d_conv`` taps and a SiLU, then
  split into ``x`` (``mamba_n_heads`` heads of ``mamba_d_head``), ``B`` and
  ``C`` (``mamba_d_state`` each, shared by the heads: one group); ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence of
  ``ops/ssm.py`` and ``+ D x``; ``RMSNorm(y * silu(z))`` over all heads'
  channels together (the gate BEFORE the norm); the output projection.

What a sequence carries through a ``mamba`` layer is its state and the
convolution's last rows (``state_leaves``; ``models/kv_cache.py:recur``, which
the skeleton runs between ``state_in`` and ``state_out``), whatever its
length. The stack is ``afmoe``'s skeleton: the shortest period of kinds
(published: ``[m, m, m, m, m, a, m, m, m, m]`` four times), each kind
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
from ray_tpu.ops import ssm

MAMBA, ATTENTION = "mamba", "attention"


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    max_seq_len: int = 4096
    num_layers: int = 40
    num_heads: int = 32
    num_kv_heads: Optional[int] = None   # None = as many as ``num_heads``
    embed_dim: int = 2048
    head_dim: Optional[int] = None       # None = embed_dim / num_heads
    mlp_dim: Optional[int] = None        # ``shared_intermediate_size``
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"
    remat: bool = True
    remat_policy: str = "dots"
    seq_axis: str = "seq"
    moe: Optional[Any] = None            # no layer is routed: refused
    # a layer's mixer, first to last, as ``config.json`` names it: "mamba" |
    # "attention"; the first ``num_layers`` of them count. None = every
    # layer attends. Held as given, a JSON file's list too, so out of the
    # hash (``mixer_types`` is what the code reads)
    layer_types: Optional[Sequence[str]] = field(default=None, hash=False)
    # the state layers' sizes, under their published names
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_heads: Optional[int] = None   # None = inner / ``mamba_d_head``
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False         # as published; True is refused
    # the four stated factors
    embedding_multiplier: float = 12.0
    attention_multiplier: Optional[float] = None   # None = head_dim ** -0.5
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    # what the cache holds a state in: a recurrence of thousands of steps
    # whose decays lie near 1 gathers a narrower type's rounding, so
    # float32 is the one value taken (stated, so that a file says it)
    ssm_state_dtype: str = "float32"

    def __post_init__(self):
        if self.num_kv_heads is None:
            object.__setattr__(self, "num_kv_heads", self.num_heads)
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.embed_dim // self.num_heads)
        if self.mlp_dim is None:
            object.__setattr__(self, "mlp_dim", 4 * self.embed_dim)
        if self.attention_multiplier is None:
            object.__setattr__(
                self, "attention_multiplier", self.head_dim ** -0.5)
        types = self.mixer_types
        if len(types) != self.num_layers or set(types) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"GraniteHybridConfig.layer_types must name "
                f"{self.num_layers} layers or more {MAMBA!r} or "
                f"{ATTENTION!r}, got {self.layer_types!r}")
        if self.moe is not None:
            raise ValueError(
                "GraniteHybridConfig.moe: no layer is routed here (the "
                "published num_local_experts is 0)")
        if self.mamba_n_heads is None:
            object.__setattr__(
                self, "mamba_n_heads",
                self.mamba_expand * self.embed_dim // self.mamba_d_head)
        if self.mamba_n_groups != 1:
            raise ValueError(
                "GraniteHybridConfig.mamba_n_groups: B and C are shared by "
                "every head here (one group)")
        if self.mamba_proj_bias:
            raise ValueError(
                "GraniteHybridConfig.mamba_proj_bias: the state layers' two "
                "projections carry no bias here (as published)")
        if self.ssm_state_dtype != "float32":
            raise ValueError(
                "GraniteHybridConfig.ssm_state_dtype: the cache holds a "
                "state in float32 and nothing narrower (decays as near 1 as "
                f"exp(-0.001) gather its rounding), got "
                f"{self.ssm_state_dtype!r}")
        if (self.mamba_expand * self.embed_dim
                != self.mamba_n_heads * self.mamba_d_head):
            raise ValueError(
                "GraniteHybridConfig: mamba_expand x embed_dim is not "
                "mamba_n_heads x mamba_d_head")

    @property
    def mixer_types(self) -> Tuple[str, ...]:
        """``layer_types`` of the ``num_layers`` layers there are."""
        given = self.layer_types or (ATTENTION,) * self.num_layers
        return tuple(given[:self.num_layers])

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """Channels through the convolution: x, B and C side by side."""
        return self.mamba_d_inner + 2 * self.mamba_d_state


Config = GraniteHybridConfig
EXPERT_ACTIVATION = "swiglu"

GRANITE_HYBRID_TINY = GraniteHybridConfig(  # test size: one period of 2+1+1
    vocab_size=512, max_seq_len=128, num_layers=4, num_heads=4,
    num_kv_heads=2, embed_dim=64, head_dim=16, mlp_dim=96,
    layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA),
    mamba_d_state=16, mamba_n_heads=8, mamba_d_head=16, mamba_chunk_size=8,
    attention_multiplier=0.0625,
)

PRESETS = {"granite-hybrid-tiny": GRANITE_HYBRID_TINY}


def _kinds(config: Config) -> Tuple[Layer, ...]:
    return tuple(
        Layer(kind, state=config.mamba_chunk_size, recurrence=ssm.MAMBA2)
        if kind == MAMBA else Layer(kind) for kind in config.mixer_types)


def state_leaves(config: Config) -> Dict[str, tuple]:
    """A slot's share of a state layer's cache: the state a head, and the
    rows the convolution still needs."""
    return {
        "ssm": ((config.mamba_n_heads, config.mamba_d_head,
                 config.mamba_d_state), jnp.float32),
        "conv": (((config.mamba_d_conv - 1) * config.mamba_conv_dim,),
                 config.dtype),
    }


def init_params(config: Config, key: jax.Array) -> Dict[str, Any]:
    """Matrices at 0.02 (into the residual stream at 0.02 / sqrt(2 L)),
    gains 1; a state layer's own as Mamba-2 is published: ``A_log = ln
    U[1, 16]``, ``dt_bias`` the inverse softplus of ``exp U[ln 1e-3, ln
    1e-1]``, ``D = 1``, the convolution U[-1/sqrt(K), 1/sqrt(K)]."""
    E, H, KV, D, V, M = (config.embed_dim, config.num_heads,
                         config.num_kv_heads, config.head_dim,
                         config.vocab_size, config.mlp_dim)
    inner, C, K = (config.mamba_d_inner, config.mamba_conv_dim,
                   config.mamba_d_conv)
    heads = config.mamba_n_heads
    pd = config.param_dtype
    std = 0.02
    res_std = std / (2 * config.num_layers) ** 0.5
    k_wte, k_layers = jax.random.split(key)

    def layer(key, kind: Layer, n: int):
        k = jax.random.split(key, 10)

        def normal(key, shape, s=std):
            return (jax.random.normal(key, (n,) + shape) * s).astype(pd)

        def uniform(key, shape, lo, hi):
            return jax.random.uniform(key, (n,) + shape, jnp.float32, lo, hi)

        out = {
            "input_norm": jnp.ones((n, E), pd),
            "post_norm": jnp.ones((n, E), pd),
            "w_in": normal(k[0], (E, 2 * M)),
            "w_out": normal(k[1], (M, E), res_std),
        }
        if kind.state is None:
            out.update({
                "wq": normal(k[2], (E, H, D)), "wk": normal(k[3], (E, KV, D)),
                "wv": normal(k[4], (E, KV, D)),
                "wo": normal(k[5], (H, D, E), res_std)})
            return out
        dt = jnp.exp(uniform(k[6], (heads,), jnp.log(1e-3), jnp.log(1e-1)))
        out.update({
            "ssm_in": normal(k[2], (E, inner + C + heads)),
            "ssm_out": normal(k[3], (inner, E), res_std),
            "conv_w": uniform(k[4], (C, K), -K ** -0.5, K ** -0.5).astype(pd),
            "conv_b": (uniform(k[5], (C,), -K ** -0.5, K ** -0.5)
                       if config.mamba_conv_bias
                       else jnp.zeros((n, C))).astype(pd),
            # softplus(dt_bias) = dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(uniform(k[7], (heads,), 1.0, 16.0)),
            "D": jnp.ones((n, heads), jnp.float32),
            "gate_norm": jnp.ones((n, inner), pd),
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
    }


def param_axes(config: Config) -> Dict[str, Any]:
    def layer(kind: Layer):
        axes = {"input_norm": ("stage", "norm"),
                "post_norm": ("stage", "norm"),
                "w_in": ("stage", "embed", "mlp"),
                "w_out": ("stage", "mlp", "embed")}
        if kind.state is None:
            axes.update({"wq": ("stage", "embed", "heads", "head_dim"),
                         "wk": ("stage", "embed", "kv", "head_dim"),
                         "wv": ("stage", "embed", "kv", "head_dim"),
                         "wo": ("stage", "heads", "head_dim", "embed")})
            return axes
        axes.update({"ssm_in": ("stage", "embed", "mlp"),
                     "ssm_out": ("stage", "mlp", "embed"),
                     "conv_w": ("stage", "mlp", None),
                     "conv_b": ("stage", "mlp"),
                     "gate_norm": ("stage", "mlp"),
                     **{name: ("stage", None)
                        for name in ("dt_bias", "A_log", "D")}})
        return axes

    return {"wte": ("vocab", "embed"),
            "blocks": {"segments": tuple(
                tuple(layer(kind) for kind in kinds)
                for kinds, _ in periods(_kinds(config)))},
            "norm_f": ("norm",)}


def serving_params(config: Config, params):
    """The projections and the MLPs are read through
    ``.astype(config.dtype)`` alone. Read as they are: every RMSNorm gain, a
    state layer's ``dt_bias``, ``A_log`` and ``D`` (float32: they set decays
    near 1), its convolution (summed in float32 from the weights as held)
    and the table: the cached forward's stream takes a row as it is held
    (float32 sums, as llama's), and the head multiplies by the same leaf,
    so a table held wider than ``config.dtype`` is rounded there on every
    call; the published checkpoint is bfloat16 and nothing is."""
    return narrowed(params, config.dtype, as_given=(
        "wte", "input_norm", "post_norm", "gate_norm", "norm_f", "dt_bias",
        "A_log", "D", "conv_w", "conv_b"))


def layers(config: Config, blocks, cached: bool):
    """The period's segments over ``blocks["segments"]``; no layer is
    routed."""
    plan = periods(_kinds(config))
    held = [(None,) * len(kinds) for kinds, _ in plan] if blocks is None \
        else blocks["segments"]
    return ([Segment(kinds, params, repeats)
             for (kinds, repeats), params in zip(plan, held)], None)


def embed(config: Config, params, tokens, pos, cached: bool):
    """Token embeddings times ``embedding_multiplier``; no position enters
    anywhere. The cached forward sums its stream in float32, as llama's."""
    x = params["wte"][tokens].astype(jnp.float32 if cached else config.dtype)
    return x * config.embedding_multiplier


def qkv(config: Config, kind, layer, x, pos, heads_major: bool = False):
    """x [B, T, E] normed -> q [B, T, KV, G, D], k and v [B, T, KV, D] (q
    [B, H, T, D], k and v [B, KV, T, D] where ``heads_major``): no norm, no
    rotation. q carries the stated scale over the ``sqrt(D)`` the attention
    divides by."""
    B, T = x.shape[:2]
    h = _rms_norm(x, layer["input_norm"], config.rms_eps, config.dtype)
    q, k, v = (heads_in(h, layer[w].astype(h.dtype), heads_major)
               for w in ("wq", "wk", "wv"))
    q = q * jnp.asarray(
        config.attention_multiplier * config.head_dim ** 0.5, q.dtype)
    if heads_major:
        return q, k, v
    return q.reshape(B, T, config.num_kv_heads, -1, config.head_dim), k, v


def attn_out(config: Config, layer, x, attn, heads_major: bool = False):
    out = heads_out(attn, layer["wo"].astype(attn.dtype), heads_major)
    return x + config.residual_multiplier * out


def state_in(config: Config, kind, layer, x):
    """A ``mamba`` layer up to its recurrence: x [B, T, E] normed and
    projected -> (xBC [B, T, C], dt [B, T, H] float32 after the softplus,
    the gate z [B, T, inner])."""
    inner, C = config.mamba_d_inner, config.mamba_conv_dim
    h = _rms_norm(x, layer["input_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("ssm.in_proj"):
        zxbcdt = jnp.einsum("bte,ef->btf", h, layer["ssm_in"].astype(h.dtype))
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + C], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
    return xbc, dt, z


def state_out(config: Config, layer, x, y, z):
    """What the recurrence gave, y [B, T, H, P] float32, gated by
    ``silu(z)`` and THEN normed over all heads' channels; the output
    projection; the residual."""
    with jax.named_scope("ssm.gate_norm"):
        y = y.reshape(*y.shape[:2], -1) * jax.nn.silu(z.astype(jnp.float32))
        y = _rms_norm(y, layer["gate_norm"], config.rms_eps, config.dtype)
    with jax.named_scope("ssm.out_proj"):
        out = jnp.einsum("btf,fe->bte", y, layer["ssm_out"].astype(y.dtype))
    return x + config.residual_multiplier * out


def at_input(config: Config, kind, layer, x, stacked):
    """Nothing of a block's input is kept for its feed-forward."""
    return None


def ffn(config: Config, kind, layer, x, rng, row_mask, stacked,
        from_input=None):
    """post_norm, the gated MLP (gate and up one matrix, split in halves),
    the residual -> (x, no aux loss, no expert touched)."""
    h = _rms_norm(x, layer["post_norm"], config.rms_eps, config.dtype)
    gate, up = jnp.split(
        jnp.einsum("bte,em->btm", h, layer["w_in"].astype(h.dtype)), 2,
        axis=-1)
    y = jnp.einsum("btm,me->bte", jax.nn.silu(gate) * up,
                   layer["w_out"].astype(h.dtype))
    return (x + config.residual_multiplier * y, jnp.float32(0.0),
            jnp.int32(0))


def final_norm(config: Config, params, x):
    """The last norm, and ``1 / logits_scaling`` with it: the features are
    what the table multiplies, in ``head`` and in the chunked cross entropy
    alike (``decoder.loss_fn`` reads ``head_weight`` and no ``head``)."""
    x = _rms_norm(x, params["norm_f"], config.rms_eps, jnp.float32)
    return (x / config.logits_scaling).astype(config.dtype)


def head_weight(params):
    return params["wte"]


def head(config: Config, params, x):
    """Final features [B, T, E] (``final_norm``'s: scaled) -> logits
    [B, T, V] float32 over the tied table."""
    return jnp.einsum("bte,ve->btv", x, head_weight(params).astype(x.dtype),
                      preferred_element_type=jnp.float32)
