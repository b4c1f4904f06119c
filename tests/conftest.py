"""Test fixtures (reference analog: ``python/ray/tests/conftest.py`` —
ray_start_regular :611 / ray_start_cluster :694).

JAX tests run on a virtual 8-device CPU mesh: the platform and the device
count are fixed here, before the first backend initialises, and node
processes the tests spawn inherit both through the environment.
"""
import os

_flags = [
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
]
_flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(_flags)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running matrix tests (tier-1 runs -m 'not slow')",
    )


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Expose per-phase reports on the item so fixtures can act on test
    outcome during teardown (the chaos flight-trace dump in
    test_faultpoints.py checks ``item.rep_call.failed``)."""
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    devs = jax.devices()
    assert devs[0].platform == "cpu", f"tests must run on CPU, got {devs[0]}"
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    yield


@pytest.fixture
def rt_start(request):
    """Start a small cluster; params: dict(num_cpus=..., num_nodes=...)."""
    import ray_tpu

    kwargs = getattr(request, "param", None) or {}
    kwargs.setdefault("num_cpus", 4)
    ctx = ray_tpu.init(**kwargs)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def chaos_flight_trace(request, tmp_path):
    """Chaos forensics: record the RPC plane during the test; on assertion
    failure dump the fault-annotated trace as flight_<test>.json into the
    tmp dir. The trace JOINS both observability planes: flight spans
    (faultpoint hits stamp their enclosing spans) AND the task-event
    tracks from the state API, so a matrix failure attributes to a verb
    *and* a task phase out of the box. Prefers a cluster-wide snapshot
    (worker rings + head task events) while the cluster is still up,
    falling back to the local ring."""
    import json as _json

    from ray_tpu._private import flight, taskpath

    flight.enable()
    yield
    rep = getattr(request.node, "rep_call", None)
    try:
        if rep is not None and rep.failed:
            snaps, events = None, []
            try:
                from ray_tpu.util import state as _state

                snaps = _state.flight_snapshot(drain=True)
                events = _state.list_tasks(limit=100_000)
            except Exception as e:
                # Cluster already torn down by the test's finally: the
                # local ring still holds the driver-side story.
                print(f"[chaos] cluster-wide snapshot unavailable ({e}); "
                      f"dumping the local ring only")
            if not snaps:
                snap = flight.drain()
                snap["offset"] = 0.0
                snaps = [snap]
            merged = sorted(
                flight.merge_snapshots(snaps)
                + taskpath.task_events_to_merged(events),
                key=lambda e: e["ts"],
            )
            trace = flight.to_chrome_trace(merged)
            path = tmp_path / f"flight_{request.node.name}.json"
            path.write_text(_json.dumps(trace))
            print(f"\n[chaos] wrote annotated flight trace "
                  f"({len(events)} task events joined) to {path}")
    finally:
        flight.disable()


@pytest.fixture
def rt_cluster(request):
    """Multi-node cluster fixture: yields (module, LocalCluster)."""
    import ray_tpu

    kwargs = getattr(request, "param", None) or {}
    kwargs.setdefault("num_cpus", 2)
    kwargs.setdefault("num_nodes", 2)
    ray_tpu.init(**kwargs)
    yield ray_tpu, ray_tpu._internal_cluster()
    ray_tpu.shutdown()
