"""The registered ``"xla"`` collective backend: reductions lowered to
jitted XLA collectives under ``shard_map`` over the group's mesh.

This is the SNIPPETS retrieval target ([1]–[3]) and the NCCL-replacement
half of the ROADMAP device-plane item: ``ray.util.collective`` groups
whose allreduce/allgather/reduce_scatter/broadcast execute as
``jax.lax.psum`` / ``lax.all_gather`` / ``lax.psum_scatter`` inside ONE
compiled program per (op, shape, dtype) — the math rides the accelerator
interconnect (ICI on a slice), not a Python loop over host buffers.

Two movement regimes share the one lowering:

- **Single-controller / CPU mesh (tier-1)**: rank tensors are exchanged
  once over the control plane (the coordinator actor, inherited from
  :class:`HostCollectiveGroup`), stacked onto the group mesh axis with
  ``jax.device_put``, and reduced by the jitted ``shard_map`` program.
  Results match the host backend bit-for-bit for exact float32 inputs —
  the parity contract ``tests/test_devstore.py`` pins.
- **Multi-controller SPMD (TPU pods)**: each process's addressable
  devices are already members of the global mesh, so the same jitted
  program IS the ICI collective and no host exchange happens — that path
  is the ``ici_*`` helpers' in-jit regime
  (``xla_collective_group.ici_allreduce`` et al.), usable today under
  ``pjit``/``shard_map``.

Fallback: a group wider than the local device count (or a jax-less
process) delegates to the host-staged parent — correctness never depends
on mesh availability.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, Optional

import numpy as np

from ray_tpu.util.collective.backend_registry import register_collective_backend
from ray_tpu.util.collective.collective_group.xla_collective_group import (
    XlaCollectiveGroup,
    _like,
    _to_host,
)
from ray_tpu.util.collective.types import (
    AllGatherOptions,
    AllReduceOptions,
    Backend,
    BroadcastOptions,
    ReduceOp,
    ReduceScatterOptions,
)

logger = logging.getLogger(__name__)

_AXIS = "col"  # the group mesh axis every lowered collective reduces over


@register_collective_backend(Backend.XLA)
class XlaBackendGroup(XlaCollectiveGroup):
    """``backend="xla"`` group. Collectives compile to ``shard_map``-ed
    ``jax.lax`` ops over a ``world_size``-device mesh; the host-staged
    parent is the explicit fallback when no such mesh exists locally."""

    def __init__(self, world_size: int, rank: int, group_name: str):
        super().__init__(world_size, rank, group_name)
        self._mesh = None
        self._mesh_tried = False
        self._jitted: Dict[tuple, Any] = {}
        # Pinned by the parity tests: how many collectives actually took
        # the lowered path (vs the host fallback).
        self.stats = {"shard_map_calls": 0, "host_fallbacks": 0}

    # ------------------------------------------------------------ mesh

    def _group_mesh(self):
        """One-axis mesh with a device per rank, built lazily and cached;
        None when this process cannot host it (the fallback signal)."""
        if self._mesh_tried:
            return self._mesh
        self._mesh_tried = True
        try:
            import jax
            from jax.sharding import Mesh

            devs = jax.devices()
            if self._world_size <= len(devs):
                self._mesh = Mesh(
                    np.array(devs[: self._world_size]), (_AXIS,)
                )
            else:
                logger.debug(
                    "collective group '%s': world_size %d exceeds local "
                    "device count %d; staying on the host backend",
                    self._group_name, self._world_size, len(devs),
                )
        except Exception as e:  # jax missing/broken: host path serves
            logger.debug("xla collective mesh unavailable: %s", e)
        return self._mesh

    def _stacked(self, values):
        """Host-exchanged per-rank tensors → one device array sharded a
        rank per mesh device along the group axis."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        stacked = np.stack([np.asarray(v) for v in values])
        return jax.device_put(
            stacked, NamedSharding(self._mesh, PartitionSpec(_AXIS))
        )

    def _lowered(self, key: tuple, build):
        fn = self._jitted.get(key)
        if fn is None:
            fn = self._jitted[key] = build()
        self.stats["shard_map_calls"] += 1
        return fn

    # ------------------------------------------------------ collectives

    def allreduce(self, tensor, opts: Optional[AllReduceOptions] = None):
        opts = opts or AllReduceOptions()
        if self._group_mesh() is None:
            self.stats["host_fallbacks"] += 1
            return super().allreduce(tensor, opts)
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        values = self._exchange(_to_host(tensor))
        op = opts.reduce_op

        def build():
            def f(x):  # block: [1, *shape]
                if op == ReduceOp.SUM:
                    r = jax.lax.psum(x, _AXIS)
                elif op == ReduceOp.AVERAGE:
                    r = jax.lax.pmean(x, _AXIS)
                elif op == ReduceOp.MAX:
                    r = jax.lax.pmax(x, _AXIS)
                elif op == ReduceOp.MIN:
                    r = jax.lax.pmin(x, _AXIS)
                else:  # PRODUCT: no pprod primitive — gather then prod
                    g = jax.lax.all_gather(x, _AXIS, axis=0, tiled=True)
                    r = jax.numpy.prod(g, axis=0, keepdims=True)
                return r[0]

            return jax.jit(shard_map(
                f, mesh=self._mesh, in_specs=P(_AXIS), out_specs=P(),
                check_vma=False,
            ))

        key = ("allreduce", op, np.asarray(values[0]).shape,
               str(np.asarray(values[0]).dtype))
        out = self._lowered(key, build)(self._stacked(values))
        return _like(np.asarray(out), tensor)

    def allgather(self, tensor, opts: Optional[AllGatherOptions] = None):
        opts = opts or AllGatherOptions()
        if self._group_mesh() is None:
            self.stats["host_fallbacks"] += 1
            return super().allgather(tensor, opts)
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        values = self._exchange(_to_host(tensor))

        def build():
            def f(x):  # block: [1, *shape] → [world, *shape] replicated
                return jax.lax.all_gather(x, _AXIS, axis=0, tiled=True)

            return jax.jit(shard_map(
                f, mesh=self._mesh, in_specs=P(_AXIS), out_specs=P(),
                check_vma=False,
            ))

        key = ("allgather", np.asarray(values[0]).shape,
               str(np.asarray(values[0]).dtype))
        out = np.asarray(self._lowered(key, build)(self._stacked(values)))
        return [_like(out[i], tensor) for i in range(self._world_size)]

    def reducescatter(self, tensor,
                      opts: Optional[ReduceScatterOptions] = None):
        opts = opts or ReduceScatterOptions()
        host = np.asarray(_to_host(tensor))
        mesh_ok = (
            self._group_mesh() is not None
            and opts.reduce_op == ReduceOp.SUM
            and host.ndim >= 1
            and host.shape[0] % self._world_size == 0
        )
        if not mesh_ok:
            # psum_scatter is a SUM over equal tiles by construction;
            # other ops / ragged splits keep host semantics exactly.
            self.stats["host_fallbacks"] += 1
            return super().reducescatter(tensor, opts)
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        values = self._exchange(host)

        def build():
            def f(x):  # block: [1, s0, ...] → [1, s0/world, ...]
                return jax.lax.psum_scatter(
                    x, _AXIS, scatter_dimension=1, tiled=True
                )

            return jax.jit(shard_map(
                f, mesh=self._mesh, in_specs=P(_AXIS), out_specs=P(_AXIS),
                check_vma=False,
            ))

        key = ("reducescatter", host.shape, str(host.dtype))
        out = np.asarray(self._lowered(key, build)(self._stacked(values)))
        # Device i's tile is chunk i of the reduced tensor; this rank
        # keeps its own chunk (host parity: array_split[rank]).
        return _like(out[self._rank], tensor)

    def broadcast(self, tensor, opts: Optional[BroadcastOptions] = None):
        opts = opts or BroadcastOptions()
        if self._group_mesh() is None:
            self.stats["host_fallbacks"] += 1
            return super().broadcast(tensor, opts)
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        root = opts.root_rank
        payload = _to_host(tensor) if self._rank == root else None
        values = self._exchange(payload)
        filled = [
            np.asarray(v) if v is not None else
            np.zeros_like(np.asarray(values[root])) for v in values
        ]

        def build():
            def f(x):  # mask-psum: root's block survives, replicated out
                idx = jax.lax.axis_index(_AXIS)
                masked = jax.numpy.where(
                    idx == root, x, jax.numpy.zeros_like(x)
                )
                return jax.lax.psum(masked, _AXIS)[0]

            return jax.jit(shard_map(
                f, mesh=self._mesh, in_specs=P(_AXIS), out_specs=P(),
                check_vma=False,
            ))

        key = ("broadcast", root, np.asarray(values[root]).shape,
               str(np.asarray(values[root]).dtype))
        out = self._lowered(key, build)(self._stacked(filled))
        return _like(np.asarray(out), tensor)
