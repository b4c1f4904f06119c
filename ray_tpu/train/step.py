"""SPMD train step construction: state, shardings, jitted update.

This is the compute heart of the Train layer (reference analog: the user
train_fn a JaxTrainer runs, ``train/v2/jax/jax_trainer.py:20`` — except the
reference ships no model/step code; here the framework provides it).
Everything is one jit: forward+backward (remat'd), gradient psum over
data/fsdp (inserted by XLA from shardings), adamw update with sharded
optimizer state (ZeRO via the same param shardings).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import decoder, module_for
from ray_tpu.parallel.moe import aux_loss_of
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    named_sharding,
    spec_from_logical,
)


@dataclass
class OptimizerConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0

    def build(self) -> optax.GradientTransformation:
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, self.learning_rate, self.warmup_steps,
            max(self.total_steps, self.warmup_steps + 1),
        )
        return optax.chain(
            optax.clip_by_global_norm(self.grad_clip),
            optax.adamw(
                schedule, b1=self.b1, b2=self.b2,
                weight_decay=self.weight_decay,
            ),
        )


def param_shardings(mesh: Mesh, config, rules=None):
    """``config`` may be any model family's config (GPT2Config,
    LlamaConfig, ...); dispatch goes through ``models.module_for``."""
    axes = module_for(config).param_axes(config)
    # a leaf is a tuple of axis names; a family of several kinds of layer
    # also holds its segments in tuples
    return jax.tree.map(
        lambda a: named_sharding(mesh, a, rules),
        axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x),
    )


def opt_state_shardings(opt, opt_state, p_shard, mesh: Mesh):
    """Shardings for an optimizer state: its parameter-shaped trees (adam's
    mu/nu) take the params' shardings, its counters are replicated.
    ``opt_state`` gives the structure only (arrays, tracers or shapes)."""
    replicated = NamedSharding(mesh, P())
    return optax.tree_utils.tree_map_params(
        opt, lambda _, sharding: sharding, opt_state, p_shard,
        transform_non_params=lambda _: replicated,
    )


def create_train_state(
    config,
    opt: optax.GradientTransformation,
    key: jax.Array,
    mesh: Optional[Mesh] = None,
    rules=None,
) -> Dict[str, Any]:
    """Initialize {params, opt_state, step} directly sharded on the mesh
    (init under jit with out_shardings: no host-memory detour)."""
    model = module_for(config)
    if mesh is None:
        params = model.init_params(config, key)
        return {"params": params, "opt_state": opt.init(params), "step": 0}

    p_shard = param_shardings(mesh, config, rules)

    def init_fn(key):
        params = model.init_params(config, key)
        return params

    params = jax.jit(init_fn, out_shardings=p_shard)(key)

    # Left to jit the optimizer state lands whole on device 0 — zeros depend
    # on no sharded input — and that device's memory then bounds the model.
    opt_shard = opt_state_shardings(
        opt, jax.eval_shape(opt.init, params), p_shard, mesh
    )
    opt_state = jax.jit(opt.init, out_shardings=opt_shard)(params)
    step = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
    return {"params": params, "opt_state": opt_state, "step": step}


def make_train_step(
    config,
    opt: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    rules=None,
    pipeline_microbatches: Optional[int] = None,
    donate: bool = True,
    seed: int = 0,
) -> Callable:
    """Build the jitted SPMD train step: (state, batch) → (state, metrics).

    ``rules`` override the logical-axis→mesh-axis sharding rules: when given
    (with a mesh), the step constrains params to those shardings so custom
    layouts are honored even if the input state arrived differently sharded.
    Stochastic layers (MoE router jitter) draw from a per-step key folded
    from ``seed`` and ``state["step"]``.

    ``metrics["loss"]`` is the next-token cross entropy. A model with routed
    experts is trained on that plus its auxiliary loss, which rides beside
    it as ``metrics["aux_loss"]``, with whatever else its layers counted (a
    share of the experts: ``moe_rows_held``, ``moe_rows_max_expert``), a
    family's second loss (``mtp_loss``, weighted into what is minimised:
    ``moe.aux_loss_of``) and its ``step_rule``'s own counters.
    """
    moe = getattr(config, "moe", None)
    needs_rng = moe is not None and moe.router_jitter > 0
    # a family's leaves that a rule moves and the optimizer does not (a
    # router's bias under a balancing rule: ``models/joyai_llm_flash.py``)
    rule = module_for(config).step_rule
    p_shard = (
        param_shardings(mesh, config, rules) if mesh is not None else None
    )

    def loss(params, batch, rng):
        if moe is None or pipeline_microbatches:
            return decoder.loss_fn(
                params, batch, config, mesh,
                pipeline_microbatches=pipeline_microbatches, rng=rng,
            ), {}
        xent, aux = decoder.loss_fn(
            params, batch, config, mesh, rng=rng, parts=True)
        total = xent + aux_loss_of(aux)
        if not isinstance(aux, dict):
            aux = {"aux_loss": aux}
        aux = {k: v for k, v in aux.items() if k != "second_loss"}
        return total, {"loss": xent, **aux}

    def step_fn(state, batch):
        params = state["params"]
        if p_shard is not None and rules is not None:
            params = jax.lax.with_sharding_constraint(params, p_shard)
        rng = (
            jax.random.fold_in(jax.random.PRNGKey(seed), state["step"])
            if needs_rng else None
        )
        (loss_val, beside), grads = jax.value_and_grad(
            loss, has_aux=True)(params, batch, rng)
        state = dict(state, params=params)
        updates, new_opt = opt.update(
            grads, state["opt_state"], state["params"]
        )
        updates, beside = rule(config, state["params"], updates, beside)
        new_params = optax.apply_updates(state["params"], updates)
        if p_shard is not None:
            # Pin the new state to the layout create_train_state gave it:
            # left free, GSPMD re-shards some leaves (layer-norm biases
            # over fsdp), the state comes back changed and the second step
            # compiles again.
            new_params = jax.lax.with_sharding_constraint(new_params, p_shard)
            new_opt = jax.lax.with_sharding_constraint(
                new_opt, opt_state_shardings(opt, new_opt, p_shard, mesh)
            )
        metrics = {
            "loss": loss_val,
            "grad_norm": optax.global_norm(grads),
            "step": state["step"] + 1,
            **beside,
        }
        return (
            {
                "params": new_params,
                "opt_state": new_opt,
                "step": state["step"] + 1,
            },
            metrics,
        )

    return jax.jit(step_fn, donate_argnums=(0,) if donate else ())


def make_eval_step(config, mesh=None) -> Callable:
    def eval_fn(params, batch):
        return decoder.loss_fn(params, batch, config, mesh)

    return jax.jit(eval_fn)
