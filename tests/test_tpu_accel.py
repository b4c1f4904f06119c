"""TPU accelerator manager + slice placement groups.

Reference analog: ``python/ray/tests/accelerators/test_tpu.py`` (metadata
lookups patched) and ``python/ray/tests/test_tpu.py`` slice-PG coverage.
"""
import pytest

import ray_tpu
from ray_tpu._private.accelerators import (
    TPUAcceleratorManager,
    detect_node_accelerators,
    detect_node_labels,
)
from ray_tpu._private.accelerators import tpu as tpu_mod
from ray_tpu.util.tpu import (
    get_tpu_coordinator_env_vars,
    slice_placement_group,
)

_real_chip_device_files = tpu_mod._chip_device_files
_real_fetch_metadata = tpu_mod._fetch_metadata


@pytest.fixture(autouse=True)
def _no_gce(monkeypatch):
    monkeypatch.setattr(tpu_mod, "_fetch_metadata", lambda *a, **k: None)
    monkeypatch.setattr(tpu_mod, "_chip_device_files", lambda: [])
    for var in ("TPU_ACCELERATOR_TYPE", "ACCELERATOR_TYPE", "TPU_WORKER_ID",
                "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_NAME", "TPU_TOPOLOGY"):
        monkeypatch.delenv(var, raising=False)


def test_no_tpu_detected():
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 0
    assert detect_node_accelerators() == {}
    assert detect_node_labels() == {}


def test_device_files_win_over_the_hosts_description(monkeypatch):
    """What the v5e chip machine exposes: ONE vfio group (plus the control
    node) on a VM carved out of a four-chip host whose env still describes
    the whole host. The chips this machine can open are the device files."""
    monkeypatch.setattr(tpu_mod, "_chip_device_files", _real_chip_device_files)
    monkeypatch.setattr(tpu_mod.glob, "glob", lambda pat: {
        "/dev/accel*": [],
        "/dev/vfio/*": ["/dev/vfio/3", "/dev/vfio/vfio"],
    }.get(pat, []))
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    assert tpu_mod._chip_device_files() == ["/dev/vfio/3"]
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 1
    assert detect_node_accelerators()["TPU"] == 1.0


def test_a_vfio_group_of_another_vendor_is_not_a_chip(monkeypatch):
    """A GPU or NIC passed through with vfio has a ``/dev/vfio/<n>`` too.
    Where sysfs names a group's devices, only Google's count; a group sysfs
    says nothing about (group 5) still counts."""
    monkeypatch.setattr(tpu_mod, "_chip_device_files", _real_chip_device_files)
    monkeypatch.setattr(tpu_mod.glob, "glob", lambda pat: {
        "/dev/accel*": [],
        "/dev/vfio/*": ["/dev/vfio/3", "/dev/vfio/4", "/dev/vfio/5",
                        "/dev/vfio/vfio"],
    }.get(pat, []))
    monkeypatch.setattr(tpu_mod, "_vfio_group_vendors", lambda group: {
        "3": ["0x1ae0"], "4": ["0x10de"], "5": [],
    }[group])
    assert tpu_mod._chip_device_files() == ["/dev/vfio/3", "/dev/vfio/5"]
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 2


def test_metadata_lookup_cannot_stall_a_sealed_machine(monkeypatch):
    """No network: the first failed lookup is the last one attempted."""
    import urllib.request

    calls = []

    def no_route(req, timeout=None):
        calls.append(req.full_url)
        raise OSError("network unreachable")

    monkeypatch.setattr(urllib.request, "urlopen", no_route)
    monkeypatch.setattr(tpu_mod, "_fetch_metadata", _real_fetch_metadata)
    monkeypatch.setattr(tpu_mod, "_metadata_cache", {})
    monkeypatch.setattr(tpu_mod, "_metadata_unreachable", False)
    assert detect_node_accelerators() == {} and detect_node_labels() == {}
    assert detect_node_accelerators() == {} and detect_node_labels() == {}
    assert len(calls) == 1


def test_detection_from_env(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-16")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("TPU_NAME", "my-slice")
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 4
    res = detect_node_accelerators()
    assert res["TPU"] == 4.0
    assert res["TPU-v5e-16-head"] == 1.0  # worker 0 carries the head token
    labels = detect_node_labels()
    assert labels["ray_tpu.accelerator_type"] == "v5e-16"
    assert labels["ray_tpu.slice_name"] == "my-slice"


def test_non_head_worker_has_no_head_resource(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-16")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    res = detect_node_accelerators()
    assert res["TPU"] == 4.0
    assert "TPU-v5e-16-head" not in res


def test_single_host_slice_from_type(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-8")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    # 8 chips, v5e packs 8/host -> single host owns the whole slice
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 8


def test_visibility_env():
    env = {}
    TPUAcceleratorManager.set_visible_accelerators(["0", "1"], env)
    assert env["TPU_VISIBLE_CHIPS"] == "0,1"
    # multi-chip grants keep default bounds (physical grid must win)
    assert "TPU_CHIPS_PER_PROCESS_BOUNDS" not in env
    solo = {}
    TPUAcceleratorManager.set_visible_accelerators(["2"], solo)
    assert solo["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"


def test_coordinator_env_vars():
    env = get_tpu_coordinator_env_vars("10.0.0.1:8080", 4, 2)
    assert env == {
        "MEGASCALE_COORDINATOR_ADDRESS": "10.0.0.1:8080",
        "MEGASCALE_NUM_SLICES": "4",
        "MEGASCALE_SLICE_ID": "2",
    }


def test_slice_placement_group_reserves_hosts():
    """v5e-16 = 2 hosts x 8 chips; the PG lands only when both hosts exist
    and the head token pins host 0."""
    ray_tpu.init(num_cpus=2)
    try:
        cluster = ray_tpu._internal_cluster()
        cluster.add_node({"CPU": 1, "TPU": 8, "TPU-v5e-16-head": 1})
        cluster.add_node({"CPU": 1, "TPU": 8})
        cluster.wait_for_nodes(3)
        spg = slice_placement_group("v5e-16")
        assert spg.ready(timeout=30)
        assert spg.num_workers == 2
        assert spg.chips_per_host == 8
        r0 = spg.worker_resources(0)
        assert r0["TPU"] == 8.0 and "TPU-v5e-16-head" in r0
        r1 = spg.worker_resources(1)
        assert r1 == {"TPU": 8.0}
    finally:
        ray_tpu.shutdown()


def test_slice_placement_group_never_split():
    """Slice atomicity: while one SlicePlacementGroup holds a slice, a
    second group can neither take the slice's head token nor poach its
    non-head hosts — it stays pending until the first group releases
    (reference behavior: ``util/tpu.py`` head-resource reservation)."""
    from ray_tpu.util.placement_group import remove_placement_group

    ray_tpu.init(num_cpus=2)
    try:
        cluster = ray_tpu._internal_cluster()
        cluster.add_node({"CPU": 1, "TPU": 8, "TPU-v5e-16-head": 1})
        cluster.add_node({"CPU": 1, "TPU": 8})
        cluster.wait_for_nodes(3)
        spg1 = slice_placement_group("v5e-16")
        assert spg1.ready(timeout=30)
        # The whole slice (head token on host 0 + every host's chips) is
        # reserved: a second slice group must not place anywhere.
        spg2 = slice_placement_group("v5e-16", timeout=2)
        assert not spg2.ready(timeout=3)
        # Release slice 1 -> the pending group takes the whole slice.
        remove_placement_group(spg1.placement_group)
        assert spg2.ready(timeout=30)
    finally:
        ray_tpu.shutdown()


def test_slice_placement_group_unsatisfiable():
    from ray_tpu.train import ScalingConfig

    ray_tpu.init(num_cpus=2)
    try:
        spg = slice_placement_group("v5e-16", timeout=2)
        assert not spg.ready(timeout=2)
        # a lease nothing can grant is refused up front, not left hanging
        with pytest.raises(ValueError, match="advertises a TPU"):
            ScalingConfig(use_tpu=True).worker_resources()
    finally:
        ray_tpu.shutdown()


def test_slice_placement_group_bad_type():
    with pytest.raises(ValueError, match="v5e-16"):
        slice_placement_group("v5e")


def test_chips_per_host_from_live_nodes():
    """A 4-host x 4-chip v5e-16 (differs from the generation table's 8)
    must be reserved with the observed per-host chip count."""
    ray_tpu.init(num_cpus=2)
    try:
        cluster = ray_tpu._internal_cluster()
        cluster.add_node({"CPU": 1, "TPU": 4, "TPU-v5e-16-head": 1},
                         labels={"ray_tpu.accelerator_type": "v5e-16"})
        for _ in range(3):
            cluster.add_node({"CPU": 1, "TPU": 4},
                             labels={"ray_tpu.accelerator_type": "v5e-16"})
        cluster.wait_for_nodes(5)
        spg = slice_placement_group("v5e-16")
        assert spg.chips_per_host == 4
        assert spg.num_workers == 4
        assert spg.ready(timeout=30)
    finally:
        ray_tpu.shutdown()


def test_init_autodetects_tpu_resources(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-8")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    ray_tpu.init(num_cpus=2)
    try:
        total = ray_tpu.cluster_resources()
        assert total.get("TPU") == 8.0
        assert total.get("TPU-v5e-8-head") == 1.0
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("chips", [1, 4])
def test_use_tpu_takes_the_chips_a_host_advertises(monkeypatch, chips):
    """``ScalingConfig(use_tpu=True)`` asks per worker for what one host of
    the cluster has — 1 on a one-chip machine, where the old literal 4
    could never be leased."""
    from ray_tpu.train import ScalingConfig

    monkeypatch.setattr(
        tpu_mod, "_chip_device_files",
        lambda: [f"/dev/vfio/{i}" for i in range(chips)],
    )
    ray_tpu.init(num_cpus=2, num_nodes=2)
    try:
        assert ray_tpu.cluster_resources()["TPU"] == float(chips)  # node 0
        res = ScalingConfig(use_tpu=True).worker_resources()
        assert res == {"CPU": 1.0, "TPU": float(chips)}
        explicit = ScalingConfig(
            use_tpu=True, resources_per_worker={"TPU": 2}
        ).worker_resources()
        assert explicit["TPU"] == 2
    finally:
        ray_tpu.shutdown()
