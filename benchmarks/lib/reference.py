"""What the comparison with a plain reference needs, whatever the
architecture, written once over any ``logits(params, tokens) -> [B, T, V]``.

The architecture's own arithmetic is one file, ``references/<name>.py``,
which the configuration file names (``"reference"``; ``lib/named.py``):
straightforward float32 ``jax.numpy`` that shares nothing with the program.
Every matrix product runs at ``jax.default_matmul_precision("highest")``
(``in_blocks``; on a TPU a float32 product is otherwise computed in bf16
passes).

The weights are DATA here: the comparison needs the very weights the
program initialised, so ``program_initial_weights`` builds the program's own
configuration from the configuration file and calls its family's
``init_params`` under the program's key.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

from benchmarks.lib import named, program


def logits_of(config: dict) -> Callable:
    """The ``logits`` of the reference the configuration file names."""
    return named.load(config["files"]["reference"]).logits


def token_logprobs(logits: Callable, params: Dict,
                   tokens: jax.Array) -> jax.Array:
    """log p(tokens[:, t+1] | tokens[:, :t+1]) for every t: [B, T-1]."""
    lp = jax.nn.log_softmax(logits(params, tokens[:, :-1]), axis=-1)
    return jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]


def greedy_gaps(logits: Callable, params: Dict,
                tokens: jax.Array) -> jax.Array:
    """How far tokens[:, t+1] lies under the reference's own greedy choice
    after tokens[:, :t+1], in log-probability, for every t: [B, T-1]; 0
    where it IS that choice."""
    lp = jax.nn.log_softmax(logits(params, tokens[:, :-1]), axis=-1)
    chosen = jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1)[..., 0]
    return lp.max(axis=-1) - chosen


def loss(logits: Callable, params: Dict, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross entropy of tokens [B, T+1]."""
    return -token_logprobs(logits, params, tokens).mean()


def program_initial_weights(config: dict) -> Dict:
    """The weights the program starts from (trainer and engine both
    initialise from ``PRNGKey(0)``), made by the program's own ``init_params``
    in one jitted call on this process's default device."""
    from ray_tpu.models import module_for

    cfg = program.model_config(config)
    return jax.jit(module_for(cfg).init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))


def in_blocks(fn, params: Dict, tokens, block: int):
    """``fn(params, tokens[i:i+block])`` over the batch in blocks, jitted
    once, results concatenated on the host: the reference must fit beside
    nothing else on one chip, not be fast."""
    import numpy as np

    with jax.default_matmul_precision("highest"):
        jitted = jax.jit(fn)
        parts = [
            np.asarray(jitted(params, jnp.asarray(tokens[i:i + block])))
            for i in range(0, len(tokens), block)
        ]
    return np.concatenate([np.atleast_1d(p) for p in parts])
