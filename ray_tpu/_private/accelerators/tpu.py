"""TPU accelerator manager: env/metadata detection + slice resources.

Reference analog: ``python/ray/_private/accelerators/tpu.py`` —
``TPUAcceleratorManager`` (:316): chip autodetect (:343), visibility env
``TPU_VISIBLE_CHIPS`` (:432), pod type/topology from GCE instance metadata
(:475-588), and the extra ``TPU-{pod}-head`` resource on worker 0 (:634)
that lets the scheduler reserve an ICI-connected slice atomically.

Chips are counted from what this machine exposes to the TPU runtime — the
device files libtpu opens — before anything that merely describes the host
(``TPU_*`` env vars, GCE metadata): a one-chip VM carved out of a four-chip
host exports the host's ``TPU_CHIPS_PER_HOST_BOUNDS=2,2,1`` but only one
``/dev/vfio/<n>``, so that variable cannot lower or raise a count the device
files give. A vfio group that sysfs attributes to another vendor (a GPU or
NIC passed through) is not a chip. Detection never touches JAX (the process
that detects is the driver, which must stay off the chip) and never needs
the network: the metadata server is the last resort and is given up on
after its first failed lookup. Every layer is injectable for tests (the
reference mocks the same seams in ``tests/accelerators/test_tpu.py``).
"""
from __future__ import annotations

import glob
import logging
import math
import os
import re
from typing import Dict, List, Optional

from ray_tpu._private.accelerators.accelerator import (
    AcceleratorManager,
    register_accelerator_manager,
)

logger = logging.getLogger(__name__)

_GCE_METADATA_URL = (
    "http://metadata.google.internal/computeMetadata/v1/instance/attributes/"
)

# chips per host by generation (v4/v5p: 4 chips, v5e/v6e: up to 8)
_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4, "v5litepod": 8,
                   "v5e": 8, "v6e": 8}


_metadata_cache: Dict[str, Optional[str]] = {}
_metadata_unreachable = False


def _fetch_metadata(key: str, timeout: float = 1.0) -> Optional[str]:
    """GCE metadata attribute (None off-GCE), cached per process. One
    failed lookup marks the server unreachable for the life of the process,
    so a sealed machine pays at most one timeout. Patched in tests (patched
    versions bypass the cache)."""
    global _metadata_unreachable
    if key in _metadata_cache:
        return _metadata_cache[key]
    if _metadata_unreachable:
        return None
    import urllib.error
    import urllib.request

    try:
        req = urllib.request.Request(
            _GCE_METADATA_URL + key, headers={"Metadata-Flavor": "Google"}
        )
        with urllib.request.urlopen(req, timeout=timeout) as r:
            value = r.read().decode()
    except urllib.error.HTTPError:
        value = None  # server answered: this attribute is not set
    except OSError:
        _metadata_unreachable = True
        return None
    _metadata_cache[key] = value
    return value


_GOOGLE_PCI_VENDOR = "0x1ae0"


def _vfio_group_vendors(group: str) -> List[str]:
    """PCI vendor ids of the devices in vfio/iommu group ``group``, as
    sysfs gives them; empty where sysfs does not say."""
    vendors = []
    pattern = f"/sys/kernel/iommu_groups/{group}/devices/*/vendor"
    for path in glob.glob(pattern):
        with open(path) as f:
            vendors.append(f.read().strip().lower())
    return vendors


def _chip_device_files() -> List[str]:
    """Device files the TPU runtime opens, one per chip: ``/dev/accel<n>``
    (v4 and older VM images) or ``/dev/vfio/<n>`` (v5e/v6e;
    ``/dev/vfio/vfio`` is the container control node, not a chip). A vfio
    group counts unless sysfs names its devices and none is Google's."""
    chips = glob.glob("/dev/accel*")
    if chips:
        return chips
    for path in glob.glob("/dev/vfio/*"):
        group = os.path.basename(path)
        if group == "vfio":
            continue
        vendors = _vfio_group_vendors(group)
        if not vendors or _GOOGLE_PCI_VENDOR in vendors:
            chips.append(path)
    return chips


def local_device_info() -> Dict[str, object]:
    """Platform, kind and count of the JAX devices this process computes
    on, for callers to report. Raises :class:`AcceleratorMismatchError`
    when the node was granted ``TPU`` and JAX came up on anything else —
    computing on the CPU there would only look like success."""
    import jax

    from ray_tpu._private import worker as worker_mod
    from ray_tpu.exceptions import AcceleratorMismatchError

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    w = worker_mod.global_worker
    granted = w.node_resources.get("TPU", 0) if w is not None else 0
    if granted > 0 and info["platform"] != "tpu":
        raise AcceleratorMismatchError(
            f"node {w.node_id[:8]} was granted TPU={granted:g} but JAX runs "
            f"on {info['platform']!r} ({info['device_kind']})"
        )
    return info


@register_accelerator_manager
class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    # ---------------------------------------------------------- detection

    @staticmethod
    def _accelerator_type() -> Optional[str]:
        """e.g. "v5e-16": env first, then GCE metadata."""
        for var in ("TPU_ACCELERATOR_TYPE", "ACCELERATOR_TYPE"):
            v = os.environ.get(var)
            if v:
                return v
        return _fetch_metadata("accelerator-type")

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        n = len(_chip_device_files())
        if n:
            return n
        v = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")
        if v:  # "2,2,1" style bounds
            try:
                return math.prod(int(x) for x in v.split(","))
            except ValueError:
                pass
        acc = TPUAcceleratorManager._accelerator_type()
        if acc:
            gen = acc.split("-")[0]
            per_host = _CHIPS_PER_HOST.get(gen, 4)
            total = TPUAcceleratorManager._num_chips_in_slice(acc) or per_host
            return min(per_host, total)
        return 0

    @staticmethod
    def _num_chips_in_slice(acc_type: str) -> int:
        m = re.match(r"v\w+-(\d+)$", acc_type or "")
        return int(m.group(1)) if m else 0

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        acc = TPUAcceleratorManager._accelerator_type()
        return f"TPU-{acc.split('-')[0].upper()}" if acc else None

    @staticmethod
    def _worker_id() -> int:
        v = os.environ.get("TPU_WORKER_ID")
        if v is not None:
            try:
                return int(v)
            except ValueError:
                pass
        v = _fetch_metadata("agent-worker-number")
        return int(v) if v and v.isdigit() else 0

    @staticmethod
    def get_current_node_additional_resources() -> Dict[str, float]:
        """Worker 0 of a slice advertises ``TPU-{type}-head: 1`` so a single
        bundle can reserve the whole ICI slice (reference: ``tpu.py:634``)."""
        acc = TPUAcceleratorManager._accelerator_type()
        if acc and TPUAcceleratorManager._worker_id() == 0:
            return {f"TPU-{acc}-head": 1.0}
        return {}

    @staticmethod
    def get_current_node_labels() -> Dict[str, str]:
        acc = TPUAcceleratorManager._accelerator_type()
        if not acc:
            return {}
        labels = {
            "ray_tpu.accelerator_type": acc,
            "ray_tpu.tpu_worker_id": str(TPUAcceleratorManager._worker_id()),
        }
        name = os.environ.get("TPU_NAME") or _fetch_metadata("instance-id")
        if name:
            labels["ray_tpu.slice_name"] = str(name)
        topo = os.environ.get("TPU_TOPOLOGY")
        if not topo:
            # tpu-env is a multi-line "KEY: 'value'" blob; extract TOPOLOGY
            blob = _fetch_metadata("tpu-env")
            if blob:
                m = re.search(r"TOPOLOGY:\s*'?([0-9x]+)'?", blob)
                topo = m.group(1) if m else None
        if topo:
            labels["ray_tpu.topology"] = topo.strip()
        return labels

    # ---------------------------------------------------------- visibility

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> Optional[str]:
        return "TPU_VISIBLE_CHIPS"

    @staticmethod
    def set_visible_accelerators(ids: List[str], env: Dict[str, str]):
        """Reference ``tpu.py:432``: scope a worker to a subset of local
        chips. Bounds are narrowed only for the single-chip case — for
        multi-chip grants the physical grid (e.g. v4's 2x2x1) must stay the
        default or libtpu rejects the topology (matches the reference)."""
        env["TPU_VISIBLE_CHIPS"] = ",".join(ids)
        if len(ids) == 1:
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"
