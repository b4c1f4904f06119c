"""User-facing trainers.

Reference analogs: ``python/ray/train/v2/api/data_parallel_trainer.py``
(``DataParallelTrainer``) and ``train/v2/jax/jax_trainer.py:20``
(``JaxTrainer`` — the SPMD/TPU trainer). ``JaxTrainer`` here goes further
than the reference: since the framework owns the model/step layer
(``ray_tpu.train.step``), it can run a complete sharded GPT-2 training loop
from config alone via :func:`default_jax_train_loop`.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from ray_tpu.train.config import JaxConfig, RunConfig, ScalingConfig
from ray_tpu.train.controller import TrainController
from ray_tpu.train.result import Result


class DataParallelTrainer:
    """Runs ``train_loop_per_worker`` on a rank-ordered worker group."""

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        backend_config: Optional[JaxConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
    ):
        self._train_loop = train_loop_per_worker
        self._train_loop_config = train_loop_config
        self._scaling_config = scaling_config or ScalingConfig()
        self._run_config = run_config or RunConfig()
        self._backend_config = backend_config
        self._datasets = datasets or {}

    def fit(self) -> Result:
        config = self._train_loop_config
        if self._datasets:
            # Dataset sharding (reference: train/_internal/data_config.py):
            # each worker iterates its rank's split via get_dataset_shard.
            config = dict(config or {})
            config["_datasets"] = self._datasets
        controller = TrainController(
            self._train_loop,
            config,
            self._scaling_config,
            self._run_config,
            self._backend_config,
        )
        return controller.run()


class JaxTrainer(DataParallelTrainer):
    """SPMD trainer for JAX on TPU (reference: ``jax_trainer.py:20``).

    Each worker is one JAX process (one TPU host). ``backend_config``
    controls platform selection and ``jax.distributed.initialize``.
    """

    def __init__(
        self,
        train_loop_per_worker: Optional[Callable] = None,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        jax_config: Optional[JaxConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
    ):
        super().__init__(
            train_loop_per_worker or default_jax_train_loop,
            train_loop_config=train_loop_config,
            scaling_config=scaling_config,
            run_config=run_config,
            backend_config=jax_config or JaxConfig(),
            datasets=datasets,
        )


def default_jax_train_loop(config: Dict[str, Any]):
    """Complete sharded-GPT-2 training loop driven purely by config.

    config keys: ``model`` (GPT2Config kwargs), ``mesh`` (MeshConfig kwargs),
    ``optimizer`` (OptimizerConfig kwargs), ``num_steps``, ``batch_size``,
    ``seq_len``, ``checkpoint_every`` (0 = only at end), ``data_seed``.
    Reports ``{loss, step, tokens_per_sec, compiles, platform, device_kind,
    device_count}`` each step (a worker whose node was granted ``TPU`` fails
    with ``AcceleratorMismatchError`` rather than train on another
    platform), and beside the loss what a model with routed experts counted
    in it (``FETCHED``: they ride the loss's one fetch and are the
    ``train.loss_fetch`` span's arguments); saves orbax checkpoints; resumes
    from ``get_checkpoint()`` after failures.

    Each step is a ``train.step`` in the profiler's own trace (a
    ``StepTraceAnnotation``, so xprof draws step boundaries) with what the
    host does as its children: ``train.next_batch``, ``train.dispatch``,
    ``train.loss_fetch`` (the wait for the step), ``train.report`` or
    ``train.checkpoint``. All inert unless a capture runs.
    """
    import os
    import tempfile
    import time

    import jax
    import numpy as np
    from jax.profiler import StepTraceAnnotation, TraceAnnotation as span

    from ray_tpu._private.accelerators.tpu import local_device_info
    from ray_tpu.models import config_for, get_preset
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.train import checkpoint as ckpt_mod
    from ray_tpu.train.context import get_checkpoint, get_context, report
    from ray_tpu.train.step import (
        OptimizerConfig,
        create_train_state,
        make_train_step,
    )
    from ray_tpu.util.debug import compile_count

    # what is fetched from a step's metrics, in one transfer
    FETCHED = ("loss", "aux_loss", "moe_rows_held", "moe_rows_max_expert",
               "mtp_loss", "moe_rows_max_all", "moe_bias_abs_mean")
    ctx = get_context()
    device_info = local_device_info()
    model = config.get("model", {})
    if isinstance(model, str):  # zoo preset, e.g. "gpt2-small" / "llama-1b"
        model_cfg = get_preset(model)
    else:
        model = dict(model)
        model_cfg = config_for(model.pop("family", "gpt2"), **model)
    mesh = MeshConfig(**config.get("mesh", {"data": -1})).build()
    opt_cfg = OptimizerConfig(**config.get("optimizer", {}))
    opt = opt_cfg.build()
    num_steps = int(config.get("num_steps", 10))
    batch_size = int(config.get("batch_size", 8))
    seq_len = int(config.get("seq_len", model_cfg.max_seq_len))
    ckpt_every = int(config.get("checkpoint_every", 0))

    state = create_train_state(model_cfg, opt, jax.random.PRNGKey(0), mesh)
    start_step = 0
    prev = get_checkpoint()
    if prev is not None:
        with prev.as_directory() as d:
            state = ckpt_mod.load_pytree(d, target=state)
        start_step = int(state["step"])

    step_fn = make_train_step(model_cfg, opt, mesh)
    rng = np.random.default_rng(int(config.get("data_seed", 0)))

    from jax.sharding import NamedSharding, PartitionSpec as P

    batch_sharding = NamedSharding(mesh, P(("data", "fsdp"), None))

    def next_batch(step: int) -> dict:
        toks = rng.integers(
            0, model_cfg.vocab_size, (batch_size, seq_len + 1), dtype=np.int32
        )
        return jax.device_put({"tokens": toks}, {"tokens": batch_sharding})

    def save(state, step, metrics):
        if ctx.get_world_rank() != 0:
            return
        with tempfile.TemporaryDirectory(prefix="rt_local_ckpt_") as d:
            ckpt_mod.save_pytree(state, d)
            report(metrics, checkpoint=ckpt_mod.Checkpoint(d))

    t0 = time.monotonic()
    for step in range(start_step, num_steps):
        with StepTraceAnnotation("train.step", step_num=step):
            with span("train.next_batch"):
                batch = next_batch(step)
            with span("train.dispatch"):
                state, metrics = step_fn(state, batch)
            if ctx.should_stop():
                break
            with span("train.loss_fetch") as fetch:
                fetched = jax.device_get(
                    {k: metrics[k] for k in FETCHED if k in metrics})
                loss = float(fetched.pop("loss"))
                routed = {k: float(v) for k, v in fetched.items()}
                if routed:
                    fetch.set_metadata(**routed)
            dt = max(time.monotonic() - t0, 1e-9)
            t0 = time.monotonic()
            m = {
                "loss": loss,
                **routed,
                "step": step + 1,
                "tokens_per_sec": batch_size * seq_len / dt,
                # a rise between two steps: a program was built in between
                "compiles": compile_count(),
                **device_info,
            }
            is_ckpt_step = ckpt_every and (step + 1) % ckpt_every == 0
            if is_ckpt_step or step + 1 == num_steps:
                with span("train.checkpoint"):
                    save(state, step + 1, m)
            else:
                with span("train.report"):
                    report(m)
    return {"final_step": int(state["step"])}


def get_dataset_shard(name: str = "train"):
    """This rank's split of a dataset passed to the trainer (reference:
    ``ray.train.get_dataset_shard``)."""
    from ray_tpu.train.context import get_context

    ctx = get_context()
    ds = (getattr(ctx, "_datasets", None) or {}).get(name)
    if ds is None:
        return None
    rank, world = ctx.get_world_rank(), ctx.get_world_size()
    if hasattr(ds, "split"):  # ray_tpu.data.Dataset
        # equal shards: unequal row counts would desync SPMD step loops
        return ds.split(world, equal=True)[rank]
    if isinstance(ds, (list, tuple)):
        return list(ds[rank::world])
    return ds
