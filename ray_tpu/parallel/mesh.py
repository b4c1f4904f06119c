"""Device mesh construction: the TPU-native resource model for parallelism.

This replaces the reference's delegation of TP/PP/EP to engine kwargs
(reference: ``python/ray/llm/_internal/serve/engines/vllm/vllm_models.py:231``
reads tensor/pipeline_parallel_size and hands them to vLLM; SURVEY.md §2.3
notes SP/CP are absent entirely). Here every parallelism strategy is a named
mesh axis; XLA inserts the collectives (psum/all_gather/reduce_scatter/
ppermute) over ICI according to shardings.

Axes (any may be 1):
    data   — data parallelism (gradient psum)
    fsdp   — parameter/optimizer sharding a la ZeRO-3 (all_gather on use)
    tensor — tensor/model parallelism (Megatron-style column/row splits)
    seq    — sequence/context parallelism (ring attention over ICI ring)
    expert — MoE expert parallelism (all_to_all routing)
    stage  — pipeline stages (microbatch loop with ppermute handoff)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("data", "fsdp", "stage", "tensor", "seq", "expert")


@dataclass(frozen=True)
class MeshConfig:
    """Declarative parallelism layout. -1 on exactly one axis = "fill with
    remaining devices" (like a reshape wildcard)."""

    data: int = -1
    fsdp: int = 1
    stage: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {
            "data": self.data,
            "fsdp": self.fsdp,
            "stage": self.stage,
            "tensor": self.tensor,
            "seq": self.seq,
            "expert": self.expert,
        }
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"only one wildcard axis allowed, got {wild}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices but {n_devices} available"
            )
        return sizes

    def build(self, devices: Optional[List] = None) -> Mesh:
        return make_mesh(self, devices)


def make_mesh(config: MeshConfig, devices: Optional[List] = None) -> Mesh:
    """Build a jax Mesh laid out so the innermost axes (tensor/seq/expert —
    the chattiest collectives) map to adjacent devices: on a real slice those
    are ICI neighbors (same recipe as jax.experimental.mesh_utils; on v4/v5p
    3D tori jax's create_device_mesh does the topology-aware assignment)."""
    if devices is None:
        devices = jax.devices()
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXES)
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except Exception:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXES)


def single_device_mesh() -> Mesh:
    return make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


def mesh_shape(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


@dataclass(frozen=True)
class TpuSliceSpec:
    """Typed TPU slice description — a first-class scheduler concept (the
    reference encodes this as string resources + labels from GCE metadata,
    ``python/ray/_private/accelerators/tpu.py:475-588``; we promote it to a
    typed object as SURVEY.md §7 prescribes)."""

    generation: str = "v5e"        # v4 | v5e | v5p | v6e ...
    topology: Tuple[int, ...] = (2, 2)   # chip grid, e.g. (4, 4) = v5e-16
    hosts: int = 1
    chips_per_host: int = 4

    @property
    def num_chips(self) -> int:
        return int(math.prod(self.topology))

    @property
    def name(self) -> str:
        return f"{self.generation}-{self.num_chips}"

    def head_resource(self) -> str:
        """Resource name the scheduler uses to reserve a whole ICI slice
        (semantics of the reference's TPU-{pod}-head resource,
        ``tpu.py:634``)."""
        return f"TPU-{self.name}-head"
