"""Train-layer config dataclasses.

Reference analogs: ``python/ray/air/config.py`` (ScalingConfig :inline,
RunConfig, FailureConfig, CheckpointConfig) and the JAX trainer's TPU
extensions (``python/ray/train/v2/jax/jax_trainer.py:57-64`` — ``use_tpu``,
``topology``, ``accelerator_type``). TPU-first differences: ``topology`` is a
typed field that resolves to a :class:`ray_tpu.parallel.mesh.TpuSliceSpec`,
and elasticity bounds live here (the reference splits them into
``ScalingPolicy`` constructor args).
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, Optional


@dataclass
class ScalingConfig:
    """How many train workers to run and what each needs.

    ``num_workers`` is the target world size (one worker per TPU host in
    multi-host SPMD). ``min_workers`` < ``num_workers`` enables elastic
    training: on failure the group may restart smaller.
    """

    num_workers: int = 1
    use_tpu: bool = False
    topology: Optional[str] = None          # e.g. "2x2" / "4x4" (v5e chips)
    accelerator_type: Optional[str] = None  # e.g. "TPU-v5e"
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"
    min_workers: Optional[int] = None       # elastic lower bound

    def worker_resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker or {})
        res.setdefault("CPU", 1.0)
        if self.use_tpu and "TPU" not in res:
            # One worker per TPU host: it takes every chip its host
            # advertises (1 on a one-chip VM, 4 or 8 on a full host).
            import ray_tpu

            per_host = max(
                (n["resources"].get("TPU", 0) for n in ray_tpu.nodes()
                 if n["alive"]),
                default=0,
            )
            if per_host <= 0:
                raise ValueError(
                    "ScalingConfig(use_tpu=True) but no alive node "
                    "advertises a TPU resource"
                )
            res["TPU"] = float(per_host)
        return res

    @property
    def elastic(self) -> bool:
        return self.min_workers is not None and self.min_workers < self.num_workers


@dataclass
class FailureConfig:
    """How many worker-group failures to tolerate before giving up.

    ``max_failures=-1`` retries forever (reference semantics:
    ``air/config.py FailureConfig``).
    """

    max_failures: int = 0


@dataclass
class CheckpointConfig:
    """Top-K checkpoint retention (reference: ``air/config.py
    CheckpointConfig``; manager behavior ``train/v2/_internal/execution/
    checkpoint/checkpoint_manager.py:93``)."""

    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"  # "max" | "min"

    def __post_init__(self):
        if self.checkpoint_score_order not in ("max", "min"):
            raise ValueError("checkpoint_score_order must be 'max' or 'min'")
        if self.num_to_keep is not None and self.num_to_keep <= 0:
            raise ValueError("num_to_keep must be positive or None")


@dataclass
class RunConfig:
    """Where results/checkpoints go and the failure/checkpoint policies."""

    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)

    def resolved_storage_path(self) -> str:
        return os.path.expanduser(
            self.storage_path
            or os.environ.get("RAY_TPU_STORAGE_PATH", "~/ray_tpu_results")
        )


@dataclass
class JaxConfig:
    """Per-worker JAX process setup (reference: ``train/v2/jax/config.py:24``
    ``_JaxBackend`` — sets JAX_PLATFORMS + MEGASCALE coordinator env and calls
    ``jax.distributed.initialize``).

    On a real multi-host slice each train worker is one TPU host;
    ``distributed_init=True`` makes workers call
    ``jax.distributed.initialize(coordinator, num_processes, process_id)``.
    In single-host (and CPU-test) runs leave it False — the worker just sees
    its locally attached devices.
    """

    jax_platforms: Optional[str] = None
    distributed_init: bool = False
    coordinator_address: Optional[str] = None  # default: rank 0's host IP
    coordinator_port: int = 8476
    env_vars: Dict[str, str] = field(default_factory=dict)
