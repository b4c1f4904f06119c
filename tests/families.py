"""The one place a model family is described for the tests: a row of ``ROWS``
for every name of ``models.FAMILIES`` (its tiny configuration, how its
initial weights are moved, its plain reference and constants, its faults,
refusals, plan and tolerances, as data), and the helpers the families' tests
share. No test lives here. ``test_family_reference.py``, ``_cached.py`` and
``_engine.py`` run each shared case on every family that has the property it
is about, read off ``decoder.layer_kinds`` and the configuration (``has``),
never off a name. A new family is one row here and a file of its own cases.

CPU, float32 where logits are compared, seeded weights, tiny widths; a
tolerance stands in its row, the measured value beside it. No device number.
"""
import contextlib
import dataclasses
import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.lib import named
from benchmarks.tests import faults_deepseek_v32
from ray_tpu import models
from ray_tpu.llm import DecodeEngine, LLMConfig
from ray_tpu.llm import engine as engine_module
from ray_tpu.llm.engine import engine_programs
from ray_tpu.models import (
    afmoe, bailing_hybrid, decoder, family_module, granite_hybrid, kv_cache,
    olmo_hybrid,
)
from ray_tpu.ops import block_attention, index_select
from ray_tpu.parallel.moe import MoEConfig

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
M, A = granite_hybrid.MAMBA, granite_hybrid.ATTENTION
L, F = olmo_hybrid.LINEAR, olmo_hybrid.FULL
S = afmoe.SLIDING

# what every row's engine has: three slots, buckets that pad, float32
ENGINE = dict(dtype="float32", max_batch_slots=3, prefill_buckets=(8, 16))
# the keys of a row that are the engine's and not the model's
ENGINE_KEYS = ("model_family", "max_batch_slots", "prefill_buckets")


def preset(family):
    """The family's own tiny preset: the one of its ``PRESETS`` named so."""
    (name,) = [n for n in family_module(family).PRESETS if n.endswith("-tiny")]
    return models.get_preset(name)


def flat_keys(cfg) -> dict:
    """A config object as a configuration file states one: dtypes by name,
    how many experts and the router's numbers that are not ``MoEConfig``'s
    own under their flat names, no ``attention_impl``."""
    flat = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name not in ("moe", "attention_impl")
            and getattr(cfg, f.name) is not None}
    for key in ("dtype", "param_dtype"):
        flat[key] = jnp.dtype(flat[key]).name
    if cfg.moe is not None:
        flat.update({key: getattr(cfg.moe, name)
                     for key, name in models.MOE_KEYS.items()
                     if name == "num_experts"
                     or getattr(cfg.moe, name) != getattr(MoEConfig, name)})
    return flat


def _of_preset(family):
    return dict(model_family=family, **{**flat_keys(preset(family)), **ENGINE})


# One tiny configuration a family, as ``LLMConfig`` takes it.
TINY = {
    "gpt2": _of_preset("gpt2"),
    # the OLMoE flags: every layer routed, 8 of 16 experts, raw gates
    "llama": dict(
        model_family="llama", vocab_size=300, max_seq_len=128, num_layers=2,
        num_heads=4, num_kv_heads=4, embed_dim=64, mlp_dim=32,
        rope_theta=10000, rms_eps=1e-5, qk_norm="full", moe_num_experts=16,
        moe_top_k=8, moe_norm_topk_prob=False, **ENGINE),
    # a dense sliding lead, then one period of three sliding layers and a
    # full one, all routed: the benchmark's cut at toy widths, window 8
    "afmoe": dict(
        model_family="afmoe", vocab_size=300, max_seq_len=64, num_layers=5,
        num_heads=8, num_kv_heads=2, embed_dim=64, head_dim=16, mlp_dim=96,
        moe_mlp_dim=32, rope_theta=10000, rms_eps=1e-5, num_dense_layers=1,
        num_shared_experts=1, sliding_window=8, layer_types=(S, S, S, S, F),
        mup_enabled=True, moe_num_experts=16, moe_top_k=4,
        moe_norm_topk_prob=True, moe_score_func="sigmoid",
        moe_route_scale=2.826, moe_router_init_std=0.3,
        moe_expert_bias_init_std=0.05,
        **{**ENGINE, "prefill_buckets": (4, 8)}),
    # 7 query heads a kv head, a head width that is not the model's, a
    # window shorter than the sequence, one global layer and three window
    "smallthinker": _of_preset("smallthinker"),
    # one period of the published stack in small: state layers around one
    # attention layer (G = 2), chunks of 8
    "granite_hybrid": dict(_of_preset("granite_hybrid"), vocab_size=300),
    # two periods of three state layers and an attention layer, 2 heads of
    # 8 x 16, chunks of 4
    "olmo_hybrid": dict(_of_preset("olmo_hybrid"), vocab_size=300),
    # the benchmark's one period: a dense KDA layer, four routed ones in one
    # scan, the latent layer; 16 experts in 4 groups of which 4-7 are held
    "bailing_hybrid": dict(
        _of_preset("bailing_hybrid"), vocab_size=300, num_layers=6),
    # heads of 24 / 16, a query rank, 1 dense + 2 routed layers + the
    # prediction layer, 16 experts of which 4 are held, 2 a token
    "joyai_llm_flash": _of_preset("joyai_llm_flash"),
    # a dense layer and two routed ones, 2 heads of 16 | 8, an indexer of 4
    # heads of 16 that keeps 16 positions, YaRN from a context of 32
    "deepseek_v32": dict(
        _of_preset("deepseek_v32"), vocab_size=300, mlp_dim=96,
        moe_mlp_dim=32, kv_lora_rank=32, moe_first_held=4),
}


# ---------------------------------------------------------------- the rows


@dataclasses.dataclass(frozen=True)
class Moved:
    """How ``_tiny_params`` moves initial weights so that a fault shows: a
    leaf with ``norm`` in its name off 1 by a factor in [0.5, 1.5); a leaf
    of ``leaves`` by its rule (a factor, ``GAIN``: as a norm's, or
    ``("noise", std)`` added); every other by ``factor``. ``splits``: the
    keys the gains are drawn from (the weights a row was measured on)."""
    factor: float
    leaves: dict
    splits: int = 128


GAIN = "gain"


@dataclasses.dataclass(frozen=True)
class Fault:
    """One change that the comparison with the reference must read: fields
    of the model's config (``config``) or of its router's (``moe``), one
    weight leaf moved (``leaf``: name, change), a piece patched (``patch``:
    called with ``monkeypatch.setattr`` and the row's keys before the config
    is built), or a constant of the REFERENCE (``constant``). The largest
    difference of a logit is then ``over`` its limit, or, for a change that
    is the same function (another chunk length), still ``under`` it.
    ``quiet``: the first so many tokens stay inside the sound limit."""
    id: str
    config: dict = dataclasses.field(default_factory=dict)
    moe: dict = dataclasses.field(default_factory=dict)
    leaf: Optional[tuple] = None
    patch: Optional[Callable] = None
    constant: dict = dataclasses.field(default_factory=dict)
    over: Optional[float] = None
    under: Optional[float] = None
    quiet: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Row:
    reference: str                  # benchmarks/references/<reference>.py
    # what the weights do not carry, at the toy's values
    constants: dict = dataclasses.field(default_factory=dict)
    moved: Optional[Moved] = None   # None: the family's init as it is
    tokens: tuple = (2, 37)         # of the full forward's comparison
    # the full forward against the reference, float32 both: the largest
    # difference of a logit is under ``sound`` on logits over ``logits``
    sound: float = 2e-5
    logits: float = 0.1
    faults: tuple = ()
    # bfloat16 activations against the float32 reference: (low, high) of the
    # largest difference and of the median (None: no bound)
    bf16: dict = dataclasses.field(default_factory=dict)
    # (changes, the error's words) of configurations refused by name
    refused: tuple = ()
    # the stack and the cache: [(changes, [(kind names, repeats), ..])],
    # the cache's leaves {name: (shape, dtype)} of 3 slots of 128 at the
    # row's keys with ``cache_at`` over them, the configurations whose
    # ``benchmarks/costs`` count is the leaves' (with the keys that count
    # takes beside the config's fields)
    stacks: tuple = ()
    cache: Optional[dict] = None
    cache_at: dict = dataclasses.field(default_factory=dict)
    costs: tuple = ()
    costs_keys: dict = dataclasses.field(default_factory=dict)
    # padded chunks and cached steps; the last chunk alone reads ``dropped``
    cached: float = 2e-5
    dropped: float = 1e-3
    # watches a kernel as the chunks are traced: (monkeypatch, implementation,
    # what was seen so far: a list that outlives the case, since a trace is
    # made once) -> what to call with the chunks and the final cache
    watch: Optional[Callable] = None
    # an answer token's log-probability by the engine and by the reference
    logprob: float = 5e-5


def _zero_first(a):
    return a.at[..., 0].set(0.0)


def _double_first(a):
    return a.at[..., 0].mul(2.0)


def _gate_before_the_norm(setattr, keys):
    """The gate before the norm and not after it (the order Mamba-2 has)."""
    def gate_first(config, layer, x, y, gate):
        y = y * jax.nn.silu(gate).reshape(y.shape)
        y = olmo_hybrid._rms_norm(y, layer["gate_norm"], config.rms_eps)
        out = y.reshape(*y.shape[:2], -1) @ layer["delta_out"]
        return olmo_hybrid._branch(config, x, out, layer["mix_norm"])

    setattr(olmo_hybrid, "state_out", gate_first)


def _one_decay_a_head(setattr, keys):
    """The decay's channel vector as its head's mean: Gated DeltaNet."""
    state_in = bailing_hybrid.state_in

    def averaged(config, kind, layer, x):
        entering, (g, beta), kept = state_in(config, kind, layer, x)
        return entering, (jnp.broadcast_to(
            g.mean(-1, keepdims=True), g.shape), beta), kept

    setattr(bailing_hybrid, "state_in", averaged)


def _planted(variant):
    """One of the serving cell's own controls (``faults_deepseek_v32``)."""
    return lambda setattr, keys: faults_deepseek_v32.plant(
        variant, {"model": keys}, setattr)


def _spy(monkeypatch, name, seen, what):
    """``kv_cache.<name>`` as it is, its calls' ``what`` kept in ``seen``."""
    real = getattr(kv_cache, name)
    monkeypatch.setattr(kv_cache, name, lambda *a, **k: seen.append(
        what(*a, **k)) or real(*a, **k))


def blocks_are(seen, want):
    assert set(seen) == want, (seen, want)


def _watch_block_attention(monkeypatch, impl, seen):
    """The chunks of 16 attend through ``block_attention`` (the last: XLA)."""
    _spy(monkeypatch, "block_attention", seen, lambda q, *a, **k: q.shape[1])
    return lambda chunks, cache: blocks_are(
        seen, set() if impl == "xla" else {16})


def _watch_latent_blocks(monkeypatch, impl, seen):
    """A chunk of 16 up-projects its latent cache's filled blocks."""
    _spy(monkeypatch, "_latent_blocks", seen,
         lambda leaf, layer, q, *a: q.shape[0])
    return lambda chunks, cache: blocks_are(
        seen, set() if impl == "xla" else {16})


def _watch_selected_blocks(monkeypatch, impl, seen):
    """A chunk of 16 scores and attends the filled blocks of its two rows
    through ``selected_block_attention``, 32 positions at a time, over the
    narrowest width that holds it; slot 1's rows are written, no other's."""
    monkeypatch.setattr(kv_cache, "CHOICE_WIDTHS", (32, 64))
    monkeypatch.setattr(index_select, "POSITIONS", 32)
    monkeypatch.setattr(block_attention, "SELECTED_POSITIONS", 32)
    _spy(monkeypatch, "selected_block_attention", seen,
         lambda q, up, leaf, picked, *a, **k: (q.shape[0], picked.shape))

    def check(chunks, cache):
        # one trace holds every width's branch
        blocks_are(seen, set() if impl == "xla" else {
            (16, (16, w)) for w in (32, 64, 128)})
        filled = sum(n for n, _ in chunks) + 16
        for name, leaf in cache.items():
            # [layer, slot, position, channel] of either leaf
            leaf = np.asarray(leaf) if name == "index" else np.swapaxes(
                np.asarray(leaf)[:, :, 0], 2, 3)
            assert np.abs(leaf[:, 1, :filled]).max(axis=-1).min() > 0
            if impl != "xla":   # the XLA step computes every slot
                assert not leaf[:, [0, 2]].any()
            assert not leaf[:, 1, filled:].any()

    return check


_HELD_ROUTER = dict(moe_num_experts=16, moe_num_held=4, moe_top_k=4)

ROWS = {
    "gpt2": Row(
        reference="gpt2",
        moved=Moved(6.0, {"ln1_g": GAIN, "ln2_g": GAIN, "ln_f_g": GAIN,
                          "wte": 1.0, "wpe": 1.0}),
        # 4e-7 measured on logits of up to 0.3 (PR 59)
        faults=(
            # no position at all: the set of tokens before, in any order
            Fault("no_positions", leaf=("wpe", lambda a: a * 0.0), over=1e-3),
        ),
        bf16={"median": (None, 0.05)}),
    "llama": Row(
        reference="olmoe",
        moved=Moved(1.0, {"expert_fc": 8.0, "expert_gate": 8.0,
                          "expert_out": 8.0, "router_w": 8.0}),
        tokens=(2, 24),
        # the order of the sums, 2e-7 to 2e-6 measured on logits of 0.5 to 2
        sound=1e-5, logits=0.5,
        faults=(
            # the faintest: a key left un-normed, gates renormalised
            Fault("qk_norm_none", config=dict(qk_norm="none"), over=1e-3),
            Fault("gates_renormalised", moe=dict(norm_topk_prob=True),
                  over=1e-3),
            Fault("k_norm_of_ones", leaf=("k_norm", jnp.ones_like),
                  over=1e-3),
        ),
        bf16={"median": (None, 0.05)},
        refused=((dict(qk_norm="head"), "qk_norm"),)),
    "afmoe": Row(
        reference="afmoe",
        constants=dict(SLIDING_WINDOW=8, TOP_K=4),
        # norm gains of all ones hide a norm on the wrong vector, matrices
        # of 0.02 leave attention nearly flat and experts of 1e-4
        moved=Moved(6.0, {"router_w": 1.0, "expert_bias": 1.0, "wte": 1.0,
                          "lm_head": 1.0}, splits=256),
        tokens=(2, 24),          # three windows long
        # the order of the sums, 1e-6 measured on logits of 0.5 to 3
        sound=2e-5, logits=0.5,
        faults=(
            # the faintest read far above the limit; RoPE on the full layer
            # too: every layer sliding, the window too long to cut
            Fault("window_ignored", config=dict(sliding_window=64),
                  over=1e-2),
            Fault("rope_on_the_full_layer", config=dict(
                layer_types=(S,) * 5, sliding_window=64), over=1e-2),
            Fault("no_rope", config=dict(layer_types=(F,) * 5), over=1e-2),
            Fault("no_mup", config=dict(mup_enabled=False), over=1e-2),
            Fault("no_bias", moe=dict(expert_bias=False), over=1e-3),
            Fault("no_route_scale", moe=dict(route_scale=1.0), over=1e-3),
            Fault("gates_not_renormalised", moe=dict(norm_topk_prob=False),
                  over=1e-3),
            Fault("softmax_scores", moe=dict(score_func="softmax"),
                  over=1e-3),
            Fault("shared_expert_twice",
                  leaf=("shared_down", lambda a: a * 2), over=1e-2),
            Fault("reference_window", constant=dict(SLIDING_WINDOW=64),
                  over=1e-2),
        ),
        bf16={"median": (None, 0.1)},
        refused=((dict(layer_types=(S, S, S, S, "S")), "layer_types"),),
        stacks=(
            (dict(num_layers=32, num_dense_layers=2,
                  layer_types=(S, S, S, F) * 8),
             [(("sliding/dense", "sliding/dense", "sliding/routed",
                "full/routed"), 1),
              (("sliding/routed",) * 3 + ("full/routed",), 7)]),
            # the cell's cut: five kinds, nothing repeats, nothing scanned
            ({}, [(("sliding/dense",) + ("sliding/routed",) * 3
                   + ("full/routed",), 1)]),
        ),
        cache={"k": ((1, 3, 2, 16, 128), jnp.float32),
               "v": ((1, 3, 2, 16, 128), jnp.float32),
               "k_window": ((4, 3, 2, 16, 24), jnp.float32),
               "v_window": ((4, 3, 2, 16, 24), jnp.float32)},
        # float32 all through: the order of the sums (2e-6 measured)
        cached=5e-5, logprob=5e-5),
    "smallthinker": Row(
        reference="smallthinker",
        constants=dict(SLIDING_WINDOW=8, TOP_K=2, Q_BLOCK=16),
        tokens=(2, 32),          # two blocks of queries
        # within 2e-5 on logits of 0.3 (its cached forward's own limit)
        sound=2e-5, logits=0.1,
        faults=(
            Fault("window_ignored", config=dict(sliding_window=64),
                  over=1e-3),
        ),
        bf16={"median": (None, 0.05)}),
    "granite_hybrid": Row(
        reference="granite_hybrid",
        constants=dict(D_STATE=16, ATTENTION_MULTIPLIER=0.0625),
        # norm gains of all ones (a norm on the wrong vector) and matrices
        # of 0.02 (a mixer that adds a thousandth to the stream)
        moved=Moved(6.0, {"wte": 1.0, "conv_w": 1.0, "conv_b": 1.0,
                          "dt_bias": 1.0, "A_log": 1.0, "D": GAIN},
                    splits=64),
        # four chunks and five tokens; the order of the sums, 2e-7 measured
        # on logits of up to 0.16
        sound=2e-5, logits=0.1,
        faults=(
            # the four factors (the attention's: head_dim ** -0.5 in place
            # of the stated number); another chunk is the same recurrence
            Fault("embedding_multiplier",
                  config=dict(embedding_multiplier=1.0), over=1e-2),
            Fault("residual_multiplier",
                  config=dict(residual_multiplier=1.0), over=1e-2),
            Fault("logits_scaling", config=dict(logits_scaling=1.0),
                  over=1e-2),
            Fault("attention_multiplier",
                  config=dict(attention_multiplier=16 ** -0.5), over=1e-4),
            Fault("another_chunk", config=dict(mamba_chunk_size=5),
                  under=2e-5),
            # a state layer's own, each a weight moved
            Fault("D", leaf=("D", lambda a: a * 0), over=1e-4),
            Fault("conv_b", leaf=("conv_b", lambda a: a * 0), over=1e-4),
            Fault("A_log", leaf=("A_log", lambda a: a + 1.0), over=1e-4),
            Fault("dt_bias", leaf=("dt_bias", lambda a: a + 1.0), over=1e-4),
            # and the reference sees its own factors
            Fault("reference_residual",
                  constant=dict(RESIDUAL_MULTIPLIER=0.2), over=1e-3),
        ),
        # bf16's rounding through four layers: 4e-3 measured on logits of 0.6
        bf16={"max": (1e-5, 3e-2)},
        refused=(
            (dict(layer_types=(M, "sliding_attention", A, M)), "layer_types"),
            (dict(mamba_n_groups=2), "mamba_n_groups"),
            (dict(mamba_expand=3), "mamba_expand")),
        stacks=((dict(num_layers=40,
                      layer_types=((M,) * 5 + (A,) + (M,) * 4) * 4),
                 [((M,) * 5 + (A,) + (M,) * 4, 4)]),),
        cache={"k": ((4, 3, 2, 16, 128), jnp.float32),
               "v": ((4, 3, 2, 16, 128), jnp.float32),
               "ssm": ((36, 3, 8, 16, 16), jnp.float32),
               "conv": ((36, 3, 3 * 160), jnp.float32)},
        cache_at=dict(num_layers=40,
                      layer_types=((M,) * 5 + (A,) + (M,) * 4) * 4),
        # float32 against float32 (1e-7 measured)
        cached=2e-5, dropped=1e-3, logprob=5e-5),
    "olmo_hybrid": Row(
        reference="olmo_hybrid",
        # norm gains off 1, and every matrix doubled (at 0.02 ``beta`` sits at
        # 1 and the decay where ``dt_bias`` put it whatever the token). Not
        # further: every branch is normed to the stream's size and a head's
        # output over its own 16 channels, so where a query nearly cancels
        # against the keys the state holds float32's rounding of that sum is
        # what the norm scales up: at matrices times 6 two float32 forwards of
        # the same equations read 7e-3 apart on one seed of three.
        moved=Moved(2.0, {"wte": 1.0, "lm_head": 1.0, "conv_w": 1.0,
                          "dt_bias": 1.0, "A_log": 1.0}, splits=64),
        # nine chunks and one token. The order of the sums (the chunked scan
        # against a token at a time) under the norms above: 1.0e-5 to 1.9e-5
        # measured over three seeds on logits of up to 0.8, and the same
        # with the token-by-token recurrence in the scan's place; the
        # faintest fault below reads 100 times the limit
        sound=1e-4, logits=0.5,
        faults=(
            Fault("beta_without_its_factor",
                  config=dict(linear_allow_neg_eigval=False), over=1e-2),
            Fault("another_chunk", config=dict(linear_chunk_size=16),
                  under=1e-4),
            # a state layer's own numbers, each a weight moved
            Fault("A_log", leaf=("A_log", lambda a: a + 1.0), over=1e-4),
            Fault("dt_bias", leaf=("dt_bias", lambda a: a + 1.0), over=1e-4),
            Fault("conv_w", leaf=("conv_w", _zero_first), over=1e-4),
            Fault("gate_norm", leaf=("gate_norm", _double_first), over=1e-4),
            Fault("q_norm", leaf=("q_norm", _double_first), over=1e-4),
            Fault("gate_before_the_norm", patch=_gate_before_the_norm,
                  over=1e-2),
            # and the reference sees its own constants
            Fault("reference_beta", constant=dict(BETA_SCALE=1.0), over=1e-2),
        ),
        # bf16's rounding (0.4% a value) through eight layers whose every
        # branch is normed to the stream's own size, so that nothing damps
        # what a layer adds: a median of 0.03 and a largest of 0.23 measured
        # on logits of up to 0.8 (Granite: 4e-3). The limits say "the same
        # function", no more; the chip's cell sets its own
        bf16={"median": (1e-4, 0.06), "max": (None, 0.5)},
        refused=(
            (dict(layer_types=(L, "sliding_attention", L, F) * 2),
             "layer_types"),
            (dict(linear_num_value_heads=4), "linear_num_value_heads"),
            # the cache holds a state in float32, the kernel steps no other
            (dict(state_dtype="bfloat16"), "state_dtype"),
            (dict(state_dtype="float16"), "state_dtype")),
        stacks=((dict(num_layers=32, layer_types=(L, L, L, F) * 8),
                 [((L, L, L, F), 8)]),),
        cache={"k": ((8, 3, 2, 32, 128), jnp.float32),
               "v": ((8, 3, 2, 32, 128), jnp.float32),
               # a head's [8, 16] with its values up to a lane tile of 128
               "ssm": ((24, 3, 2, 8, 128), jnp.float32),
               "conv": ((24, 3, 3 * 64), jnp.float32)},
        cache_at=dict(num_layers=32, layer_types=(L, L, L, F) * 8),
        costs=(dict(num_layers=32, layer_types=(L, L, L, F) * 8),
               dict(num_layers=2), {}),
        # logits and not tokens: 2.0e-5 measured, the full forward's own
        # distance from the reference; a state dropped at the chunk boundary
        # reads 1,000 times that
        cached=1e-4, dropped=2e-2, watch=_watch_block_attention,
        # float32 logits of up to 1.9 through a log-softmax over 300
        logprob=5e-5),
    "bailing_hybrid": Row(
        reference="bailing_hybrid",
        # 4 of 16 experts in 2 of 4 groups, experts 4-7 held
        constants=dict(TOP_K=4, N_GROUP=4, TOPK_GROUP=2, FIRST_HELD=4),
        # norm gains off 1, the matrices times 4 (at 0.02 and 64 channels a
        # router's scores all sit at 0.5, beta too, and a head's gate:
        # nothing a token says would move them) and the router's bias at 0.1
        # a sigmoid's spread (a choice the bias decides)
        moved=Moved(4.0, {"expert_bias": 5.0, "wte": 1.0, "lm_head": 1.0,
                          "conv_w": 1.0, "dt_bias": 1.0, "A_log": 1.0}),
        # two chunks and five tokens. The order of the sums (the chunked
        # scan against a token at a time, the sorted dispatch against a loop
        # over experts): 1.6e-6 to 2.5e-6 measured over three seeds on logits
        # of up to 0.66 to 0.81 at six layers (PR 59; 3.7e-6 at twelve)
        sound=5e-5, logits=0.5,
        faults=(
            # another chunk is the same recurrence; the lower bound, the
            # rotation's base and the choice among ALL groups are not
            Fault("another_chunk", config=dict(kda_chunk_size=32),
                  under=5e-5),
            Fault("lower_bound", config=dict(kda_lower_bound=-2.5),
                  over=5e-3),
            Fault("rope_theta", config=dict(rope_theta=1e4), over=5e-3),
            Fault("no_groups", moe=dict(n_group=None, topk_group=None),
                  over=5e-3),
            Fault("another_share", moe=dict(first_held=0), over=5e-3),
            # a layer's own numbers, each a weight moved
            Fault("A_log", leaf=("A_log", lambda a: a + 1.0), over=5e-5),
            Fault("dt_bias", leaf=("dt_bias", lambda a: a + 1.0), over=5e-5),
            Fault("conv_w", leaf=("conv_w", _zero_first), over=5e-5),
            Fault("gate_norm", leaf=("gate_norm", _double_first), over=5e-5),
            Fault("kv_norm", leaf=("kv_norm", _double_first), over=5e-5),
            Fault("wz", leaf=("wz", lambda a: a * 0.0), over=5e-5),
            Fault("expert_bias", leaf=("expert_bias", lambda a: a * 0.0),
                  over=5e-5),
            Fault("one_decay_a_head", patch=_one_decay_a_head, over=5e-3),
            # and the reference sees its own constants
            Fault("reference_route_scale", constant=dict(ROUTE_SCALE=1.0),
                  over=5e-3),
        ),
        # bf16's rounding (0.4% a value) through the layers of 64 channels,
        # and a router whose fourth and fifth scores change places under it
        # in a few token-layers: a median of 0.0059 to 0.0063 and a largest
        # of 0.10 to 0.19 measured over three seeds on logits of up to 0.8 at
        # six layers (PR 59; 0.031 and 0.47 at twelve, under 0.06 and 1.0).
        # The limits say "the same function", no more; the cell sets its own
        bf16={"median": (1e-4, 0.02), "max": (None, 0.6)},
        refused=(
            # a lower bound whose fifteen steps float32 cannot hold
            (dict(state_dtype="bfloat16"), "state_dtype"),
            (dict(kda_lower_bound=-8.0), "kda_lower_bound"),
            (dict(moe_topk_group=5), "groups")),
        # the published lead and periods of six; the benchmark's three runs
        stacks=(
            (dict(num_layers=42, first_k_dense=2),
             [(("kda/dense",) * 2 + ("kda/routed",) * 3 + ("latent/routed",),
               1),
              (("kda/routed",) * 5 + ("latent/routed",), 6)]),
            ({}, [(("kda/dense",), 1), (("kda/routed",), 4),
                  (("latent/routed",), 1)]),
        ),
        # two periods: a matrix a head and the convolution's rows a KDA
        # layer, ONE row of rank + rope values a position a latent layer
        cache={"ssm": ((10, 3, 2, 16, 16), jnp.float32),
               "conv": ((10, 3, 3 * 96), jnp.float32),
               "latent": ((2, 3, 1, 40, 128), jnp.float32)},
        cache_at=dict(num_layers=12),
        costs=(dict(num_layers=12), {}, dict(num_layers=2)),
        costs_keys=_HELD_ROUTER,
        # the full forward's own distance from the reference (4e-6)
        cached=5e-5, dropped=2e-2, watch=_watch_latent_blocks,
        # float32 logits of up to 0.7 through a log-softmax over 300
        logprob=1e-4),
    "joyai_llm_flash": Row(
        reference="joyai_llm_flash",
        # 2 of 16 experts a token, experts 0-3 held
        constants=dict(TOP_K=2, FIRST_HELD=0),
        # norm gains off 1 (a norm left out or put on the wrong vector), the
        # matrices times 4 (at 0.02 and 64 channels a router's scores all
        # sit at 0.5 and a softmax over 37 positions is flat), the embedding
        # at 0.3 (at 1.0 no layer shows in a logit) and the router's bias at
        # 0.1, a sigmoid's spread (a choice the bias decides)
        moved=Moved(4.0, {"expert_bias": 5.0, "wte": 0.3, "lm_head": 1.0}),
        # the order of the sums alone: 6e-7 on logits of 1.4
        sound=2e-5, logits=0.5,
        faults=(
            # the gradients' and the second loss's faults are the family's
            # own (``test_joyai_llm_flash.py``); a logit reads this one
            Fault("no_route_scale", moe=dict(route_scale=1.0), over=1e-3),
        ),
        bf16={"median": (None, 0.1)},
        refused=(
            (dict(moe_dropless=False), "dropless"),
            (dict(num_mtp_layers=2), "num_mtp_layers"),
            (dict(first_k_dense=4), "first_k_dense")),
        # a position's row of 16 + 8 values a trunk layer; the prediction
        # layer is held and not run
        cache={"latent": ((3, 3, 1, 24, 128), jnp.float32)},
        # within 2e-5 on logits of 1.4
        cached=2e-5, logprob=1e-4),
    "deepseek_v32": Row(
        reference="deepseek_v32",
        # 16 positions kept, YaRN from 32, 4 of 16 experts in 2 of 4 groups,
        # experts 4-7 held, 16 rows at a time
        constants=dict(TOP_K=4, N_GROUP=4, TOPK_GROUP=2, FIRST_HELD=4,
                       INDEX_TOPK=16, ROPE_ORIGINAL=32, BLOCK=16),
        # norm gains off 1 and the indexer's LayerNorm bias off 0 (a norm on
        # the wrong vector), the matrices times 4 (a router's scores all at
        # 0.5, a softmax over a few dozen positions flat) and the router's
        # bias at 0.1 a sigmoid's spread
        moved=Moved(4.0, {"ik_bias": ("noise", 0.3), "expert_bias": 5.0,
                          "wte": 1.0, "lm_head": 1.0}),
        # 64 tokens (16 of up to 64 positions chosen): 5e-5, where its own
        # rounding is 4e-6; each of the cell's faults planted on the
        # program's side is far from it, and the first 16 tokens see at most
        # 16 positions: every one is chosen, whatever the indexer does
        tokens=(2, 64), sound=5e-5, logits=0.1,
        faults=tuple(
            Fault(variant, patch=_planted(variant), over=1e-2,
                  quiet=16 if variant in (
                      "recent", "ik_unrotated", "no_relu") else None)
            for variant in faults_deepseek_v32.VARIANTS),
        # bf16 at 64 channels: most tokens within 0.05, a flipped choice more
        bf16={"median": (None, 0.05)},
        refused=(
            (dict(first_k_dense=-1), "first_k_dense -1"),
            (dict(qk_rope_head_dim=32), "rotates its first 32 channels of 16"),
            (dict(moe_dropless=False), "dropless")),
        # a dense lead and the routed layers, each one scan; a latent row of
        # rank + rope values AND the indexer's key a position and layer
        stacks=((dict(num_layers=61, first_k_dense=3),
                 [(("dense",), 3), (("routed",), 58)]),),
        cache={"latent": ((3, 3, 1, 40, 128), jnp.float32),
               "index": ((3, 3, 128, 16), jnp.float32)},
        costs=({}, dict(num_layers=5), dict(first_k_dense=3)),
        costs_keys=_HELD_ROUTER,
        # the full forward's own distance from the reference (4e-6)
        cached=5e-5, dropped=1e-3, watch=_watch_selected_blocks,
        logprob=1e-4),
}


# ------------------------------------------------- what a family has


def model_config(family, **changes):
    return LLMConfig(**{**TINY[family], **changes}).model_config()


def kinds(family, **changes):
    return decoder.layer_kinds(model_config(family, **changes))


def has(family, what) -> bool:
    """A property a shared case is about, read off the layer kinds and the
    configuration of the family's row: ``state`` / ``latent`` / ``window`` /
    ``index`` / ``routed`` layers, ``held`` experts (a share), a ``shared``
    expert; a leading ``~`` for "has not", ``a|b`` for either."""
    if what.startswith("~"):
        return not has(family, what[1:])
    if "|" in what:
        return any(has(family, one) for one in what.split("|"))
    cfg = model_config(family)
    if what == "held":
        return cfg.moe is not None and cfg.moe.num_held is not None
    if what == "shared":
        return bool(getattr(cfg, "num_shared_experts", 0))
    if what == "routed":
        return any(k.routed for k in decoder.layer_kinds(cfg))
    return any(getattr(k, what) is not None for k in decoder.layer_kinds(cfg))


def shared_case(*properties, names="family", rows=lambda family: [family],
                ids=None):
    """Marks a case of the shared files: it runs on every family of the
    table that has ``properties`` (on each of its ``rows``, where a case has
    more to a row than the family), and keeps them (``.properties``): the
    one statement of what a case is about, which the table's own test reads
    to say what a new row collects."""
    def mark(function):
        function.properties = properties
        return pytest.mark.parametrize(names, [
            row for family in ROWS if all(has(family, p) for p in properties)
            for row in rows(family)], ids=ids)(function)

    return mark


def variants(family) -> dict:
    """The family's tiny preset ``dense`` and ``routed`` (4 experts, 2 a
    token), as the code finds them: a family that refuses experts has no
    routed form, and one that refuses to be without keeps its own."""
    base, module = preset(family), family_module(family)
    # no deeper than the row: a period more shows nothing of a leaf
    depth = min(base.num_layers, TINY[family]["num_layers"])
    base = dataclasses.replace(base, num_layers=depth)
    try:
        dense = dataclasses.replace(base, moe=None)
    except ValueError:
        dense = base                # every layer routed, whatever is stated
    try:
        routed = dataclasses.replace(dense, moe=MoEConfig(
            num_experts=4, top_k=2, dropless=True,
            activation=module.EXPERT_ACTIVATION))
    except ValueError:
        routed = None               # experts are refused by the key's name
    return {"dense": dense, "routed": routed}


# --------------------------------------------------------------- helpers


def _tokens(shape, seed=0):
    return np.random.default_rng(seed).integers(2, 300, shape).astype(np.int32)


def _tiny_params(family, cfg, seed=0):
    """The family's own init with what would hide a fault moved, by the
    row's rules (``Moved``); one program (op by op, every leaf's RNG call
    and product compiles on its own)."""
    module, rule = family_module(family), ROWS[family].moved

    def made(key, moving):
        params = module.init_params(cfg, key)
        if rule is None:
            return params
        keys = iter(jax.random.split(moving, rule.splits))

        def moved(path, a):
            name = path[-1].key
            how = GAIN if "norm" in name else rule.leaves.get(
                name, rule.factor)
            if how == GAIN:
                return a * jax.random.uniform(
                    next(keys), a.shape, a.dtype, 0.5, 1.5)
            if isinstance(how, tuple):      # ("noise", std)
                return a + how[1] * jax.random.normal(
                    next(keys), a.shape, a.dtype)
            return a if how == 1.0 else a * how

        return jax.tree_util.tree_map_with_path(moved, params)

    return jax.jit(made)(
        jax.random.PRNGKey(seed), jax.random.PRNGKey(seed + 1))


_MOVED = {}


def _moved(family, cfg):
    """``_tiny_params`` once a model configuration and process."""
    if repr(cfg) not in _MOVED:
        _MOVED[repr(cfg)] = _tiny_params(family, cfg)
    return _MOVED[repr(cfg)]


def tiny_params(family):
    """(config, moved weights) of the row."""
    cfg = model_config(family)
    return cfg, _moved(family, cfg)


def load_reference(family, **constants):
    """The family's plain reference, a module of its own every call, with
    the row's constants (and ``constants`` over them) set on it."""
    row = ROWS[family]
    module = named.load(os.path.join(
        CHECKOUT, "benchmarks", "references", f"{row.reference}.py"))
    for name, value in {**row.constants, **constants}.items():
        assert hasattr(module, name), name
        setattr(module, name, value)
    return module


@functools.lru_cache(maxsize=None)
def reference(family):
    return load_reference(family)


@functools.lru_cache(maxsize=None)
def _reference_program(module):
    return jax.jit(module.logits)


def _reference_logits(reference, params, tokens):
    """One program a reference module: op by op the same arithmetic takes
    many times as long."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_reference_program(reference)(
            params, jnp.asarray(tokens)))


@functools.lru_cache(maxsize=None)
def _forward_program(family, cfg):
    module = family_module(family)
    return jax.jit(lambda p, tokens: module.forward(p, tokens, cfg)[0])


def forward_logits(family, cfg, params, tokens, fresh=False):
    """The family's full forward as one program, compiled once a config
    (``fresh``: traced again, for a piece that was patched)."""
    program = _forward_program.__wrapped__ if fresh else _forward_program
    with jax.default_matmul_precision("highest"):
        return np.asarray(program(family, cfg)(params, jnp.asarray(tokens)))


def gap_from_the_reference(family, fault=None, setattr=None, seed=0):
    """|program - reference| of every logit of the row's tokens, with
    ``fault`` (a ``Fault`` of the row, None: sound) planted."""
    row = ROWS[family]
    fault = fault or Fault("sound")
    keys = dict(TINY[family])
    if fault.patch is not None:
        fault.patch(setattr, keys)
    cfg = LLMConfig(**keys).model_config()
    if fault.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, **fault.moe))
    cfg = dataclasses.replace(cfg, **fault.config)
    _, params = tiny_params(family)
    if fault.leaf is not None:
        name, change = fault.leaf
        assert any(path[-1].key == name for path, _ in
                   jax.tree_util.tree_leaves_with_path(params)), name
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: change(a) if path[-1].key == name else a, params)
    tokens = _tokens(row.tokens, seed)
    plain = load_reference(family, **fault.constant) if fault.constant else (
        reference(family))
    want = _reference_logits(plain, tiny_params(family)[1], tokens)
    got = forward_logits(family, cfg, params, tokens,
                         fresh=fault.patch is not None)
    return np.abs(got - want), want


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: every span's name and
    arguments, with no capture."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **args):
        span = _Span(name, args)
        self.seen.append(span)
        return span

    def named(self, name):
        return [s for s in self.seen if s.name == name]


class _Span(contextlib.nullcontext):
    def __init__(self, name, args):
        super().__init__(self)      # ``with`` gives the span itself
        self.name, self.args = name, dict(args)

    def set_metadata(self, **args):
        self.args.update(args)


def _engine(family, **changes):
    """An engine on the row's keys and moved weights (an engine given none
    compiles its own initialisation, every time), its spans kept."""
    config = LLMConfig(**{**TINY[family], **changes})
    engine = DecodeEngine(
        config, params=_moved(family, config.model_config()))
    engine._span = _Spans()
    return engine


@contextlib.contextmanager
def one_compile():
    """Every engine built inside shares the programs of the first one of its
    configuration: ``engine_programs`` hands out fresh jitted closures, and
    a second engine would trace and compile the same four again."""
    made = {}

    def programs(cfg, own_cache=False):
        key = (repr(cfg), own_cache)
        if key not in made:
            made[key] = engine_programs(cfg, own_cache)
        return made[key]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(engine_module, "engine_programs", programs)
        yield


def submit_together(engine, prompts, params):
    """Every prompt queued, in order, before the loop's next turn (a caller
    that submits one by one races the loop for its lock, and a fast engine
    has answered the first before it sees the third): the futures."""
    with ThreadPoolExecutor(len(prompts)) as pool, engine._lock:
        handed = []
        for n, prompt in enumerate(prompts):
            handed.append(pool.submit(engine.submit, prompt, params))
            # queued, and waiting for the lock (or refused: ``result``)
            while engine._pending.qsize() <= n and not handed[-1].done():
                time.sleep(0.001)
    return [h.result() for h in handed]


def prompts_of(*lengths, seed=0):
    return [[int(t) for t in _tokens((n,), seed=seed + n)] for n in lengths]


def _takes_real(cfg):
    return any(k.routed or k.state is not None
               for k in decoder.layer_kinds(cfg))


def prefill_once(program, cfg, params, tokens, at, n, bucket, cache=None):
    """One padded chunk through the engine's prefill ``program``: ``n`` real
    ``tokens`` in a ``bucket`` from position ``at`` of a slot cache (None:
    an empty one) -> (the last real token's logits, the slot cache)."""
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = tokens[:n]
    if cache is None:
        cache = decoder.init_kv_cache(cfg, 1, 128, block=16)
    real = (jnp.asarray([n], jnp.int32),) if _takes_real(cfg) else ()
    logits, cache, *_ = program(
        params, jnp.asarray(toks), cache, jnp.asarray([at], jnp.int32),
        *real, rows=jnp.asarray([n - 1]))
    return np.asarray(logits[0, 0]), cache


def _prefill_then_decode(cfg, params, sequence, chunks, programs=None):
    """The engine's own programs by hand: ``sequence``'s first tokens in
    padded ``chunks`` (real length, bucket) into a slot cache, inserted into
    slot 1 of 3, then one decode step a token: logits at every position
    from the first chunk's last on."""
    prefill, insert, decode, _ = programs or engine_programs(cfg)
    cache1, rows, at = None, [], 0
    for n, bucket in chunks:
        row, cache1 = prefill_once(prefill, cfg, params, sequence[at:at + n],
                                   at, n, bucket, cache1)
        rows.append(row)
        at += n
    cache = insert(decoder.init_kv_cache(cfg, 3, 128, block=16), cache1, 1)
    ids = jnp.zeros((3,), jnp.int32)
    for t in range(at, len(sequence)):
        packed = np.zeros((3, 3), np.int32)
        packed[:, 1] = sequence[t], t, 1
        ids, logits, cache, *_ = decode(params, ids, cache,
                                        jnp.asarray(packed))
        rows.append(np.asarray(logits[1]))
    return rows, cache


@functools.lru_cache(maxsize=None)
def program_texts(family):
    """The compiled text of the row's decode program (3 slots) and of its
    prefill of a chunk of 16 through the block kernels
    (``pallas_interpret``), once a process: what a trace's reader finds a
    family's operations by is the scopes on them."""
    cfg = model_config(family)
    module = family_module(family)
    params = jax.eval_shape(lambda: module.serving_params(
        cfg, module.init_params(cfg, jax.random.PRNGKey(0))))
    prefill, _, decode, _ = engine_programs(cfg)
    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    cache, cache1 = (jax.eval_shape(
        lambda: decoder.init_kv_cache(cfg, slots, 128, block=16))
        for slots in (3, 1))
    decoded = decode.lower(
        params, shaped(3), cache, shaped(3, 3)).compile().as_text()
    impl, kv_cache._decode_impl = kv_cache._decode_impl, (
        lambda: "pallas_interpret")
    try:
        prefilled = prefill.lower(
            params, shaped(1, 16), cache1, shaped(1), shaped(1),
            rows=shaped(1)).compile().as_text()
    finally:
        kv_cache._decode_impl = impl
    return decoded, prefilled


def state_counters(engine, admits, ticks, state_layers):
    """The counters of any state layer: the spans' arguments sum to the
    engine's."""
    stats = engine.stats
    assert {a.args["layers_state"] for a in admits} == {state_layers}
    assert stats["ssm_prefill_tokens"] == sum(
        a.args["ssm_prefill_tokens"] for a in admits)
    assert stats["state_slot_layers"] == (
        state_layers * stats["slot_ticks"]) == sum(
        t.args["state_slot_layers"] for t in ticks)


def _scattered(count, B, seed):
    """``live_slots``' [B + 1] for ``count`` slots (None: all) in no order:
    the walk takes them as they are named."""
    slots = np.random.default_rng(seed).permutation(B)[
        :B if count is None else count]
    live = np.zeros(B + 1, np.int32)
    live[:len(slots)], live[B] = slots, len(slots)
    mask = np.zeros(B, bool)
    mask[slots] = True
    return jnp.asarray(live), jnp.asarray(mask)


# the TPU interpreter runs a DMA when it is WAITED for: a piece computed on
# before its read's wait, or a ring entry written over before its write's,
# shows as wrong numbers
LATE = pltpu.InterpretParams()


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations
    (branches, bodies, checkpoints)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


# A toy family as a file on disk would hold it: llama's pieces under a
# ``Config`` of its own (a base, so its source does not show its fields).
TOY_FAMILY = """
from dataclasses import dataclass
from typing import Sequence

from ray_tpu.models.llama import *  # noqa: F401,F403 — llama's pieces


@dataclass(frozen=True)
class Config(LlamaConfig):
    toy_gain: float = 1.0
    toy_layout: Sequence[int] = ()


PRESETS = {"toy-tiny": Config(vocab_size=300, num_layers=2, embed_dim=64)}
"""
