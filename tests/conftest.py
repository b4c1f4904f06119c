"""Test fixtures (reference analog: ``python/ray/tests/conftest.py`` —
ray_start_regular :611 / ray_start_cluster :694).

JAX tests run on a virtual 8-device CPU mesh: the platform and the device
count are fixed here, before the first backend initialises, and node
processes the tests spawn inherit both through the environment.
"""
import contextlib
import json
import os
import subprocess
import sys

_flags = [
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
]
_flags.append("--xla_force_host_platform_device_count=8")
# Tier-1 compiles tiny programs for the CPU far longer than it runs them, so
# it asks LLVM for its lowest level (a constant of the harness; the
# environment's own setting of the flag wins). Measured: six compile-heavy
# files 280 -> 191 s (level 1: 243; PR 59), ``test_family_reference.py``
# 161 -> 132 s, and the files that RUN their loops pay for it:
# ``test_rllib_dreamer.py`` with ``test_rllib_offpolicy.py`` 94 -> 111 s
# (PR 60). The HLO a test reads stays, both sides of a comparison share one
# arithmetic, a described TPU's compile gives the same text and sizes.
if not any("xla_backend_optimization_level" in f for f in _flags):
    _flags.append("--xla_backend_optimization_level=0")
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


@contextlib.contextmanager
def _settings_end_here():
    """``init(_system_config=..)`` outlives ``shutdown`` in ``rt_config``
    (``ROADMAP.md`` Queue 3, item 13): under ``--dist loadfile``
    ``test_serve_chaos.py``'s ``rpc_deadline_s`` of 2.0 was the deadline of
    every file its worker ran next, which is how ``test_train.py``'s first
    cases failed on a busy box (PRs 55-59). What is set inside is put back
    at the end: of a file, and of a cluster a fixture started."""
    from ray_tpu._private.config import rt_config

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(rt_config, "_system", rt_config.system_config())
        yield


@pytest.fixture(scope="module", autouse=True)
def _system_config_ends_with_its_file():
    with _settings_end_here():
        yield


@pytest.fixture
def rt_start(request):
    """Start a small cluster; params: dict(num_cpus=..., num_nodes=...)."""
    import ray_tpu

    kwargs = getattr(request, "param", None) or {}
    kwargs.setdefault("num_cpus", 4)
    with _settings_end_here():
        ctx = ray_tpu.init(**kwargs)
        yield ctx
        ray_tpu.shutdown()


@pytest.fixture
def srv(rt_start):
    """``rt_start``'s cluster, with Serve shut down before it."""
    from ray_tpu import serve

    yield rt_start
    serve.shutdown()


@pytest.fixture
def rl_cluster():
    """Six CPUs for an algorithm's runners and learner."""
    import ray_tpu

    with _settings_end_here():
        ray_tpu.init(num_cpus=6)
        yield
        ray_tpu.shutdown()


@pytest.fixture
def faults_cleared():
    """No fault point armed before a test, none left armed after it."""
    from ray_tpu._private import faultpoints

    faultpoints.clear()
    yield
    faultpoints.clear()


def start_head(*args):
    """A head in a process of its own, from this checkout and under this
    process' environment as it stands (``head_main`` with ``args``) -> (the
    process, what its first line says of it)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.head_main", *args],
        stdout=subprocess.PIPE, text=True, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc, json.loads(proc.stdout.readline().strip())


def _leases_settled():
    """All leases returned: every alive node's availability is back to its
    full capacity at the head."""
    import ray_tpu

    return all(
        all(n.available.get(k, 0.0) >= v - 1e-9
            for k, v in n.resources.items())
        for n in ray_tpu._internal_cluster().head.nodes.values() if n.alive
    )


def _no_leaked_objects():
    """Zero leaked objects (the memtrack plane's chaos SLO, joined to the
    zero-leaked-leases one): no directory entry past the grace window
    that no live process owns, stores, or borrows."""
    from ray_tpu.util import state

    return state.memory_summary(grace_s=1.0)["leaks"] == []


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
    with _settings_end_here():
        ray_tpu.init(**kwargs)
        yield ray_tpu, ray_tpu._internal_cluster()
        ray_tpu.shutdown()


@pytest.fixture(scope="module")
def one_compile_a_file():
    """``families.one_compile`` for a file (``pytestmark =
    pytest.mark.usefixtures(..)``) none of whose cases patches what a
    program is traced from."""
    from tests.families import one_compile

    with one_compile():
        yield
