"""Aux subsystems: metrics, runtime envs, chaos killers.

Reference analogs: ``python/ray/tests/test_metrics_agent.py``,
``test_tracing.py``, ``test_runtime_env*``, chaos suites under
``release/nightly_tests``.
"""
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util.metrics import Counter, Gauge, Histogram, render_prometheus


@pytest.fixture(autouse=True)
def _fresh_registry():
    metrics_mod.registry().clear()
    yield
    metrics_mod.registry().clear()


# --------------------------------------------------------------- metrics


def test_metric_primitives():
    c = Counter("rt_test_total", "a counter", ("k",))
    c.inc(2, tags={"k": "a"})
    c.inc(3, tags={"k": "a"})
    c.inc(1, tags={"k": "b"})
    g = Gauge("rt_test_gauge")
    g.set(7.5)
    h = Histogram("rt_test_hist", boundaries=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    snap = {m["name"]: m for m in metrics_mod.registry().snapshot()}
    samples = {tuple(sorted(s["tags"].items())): s["value"]
               for s in snap["rt_test_total"]["samples"]}
    assert samples[(("k", "a"),)] == 5.0
    assert samples[(("k", "b"),)] == 1.0
    assert snap["rt_test_gauge"]["samples"][0]["value"] == 7.5
    hs = snap["rt_test_hist"]["samples"][0]
    assert hs["buckets"] == [1, 1, 1] and hs["count"] == 3


def test_counter_rejects_negative():
    c = Counter("rt_test_neg")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_prometheus_rendering():
    c = Counter("rt_render_total", "help text")
    c.inc(4)
    h = Histogram("rt_render_seconds", boundaries=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = render_prometheus({"w1": metrics_mod.registry().snapshot()})
    assert "# TYPE rt_render_total counter" in text
    assert 'rt_render_total{worker_id="w1"} 4.0' in text
    assert 'le="0.1"' in text and 'le="+Inf"' in text
    assert "rt_render_seconds_count" in text


def test_metrics_flow_to_head_and_scrape():
    """Worker-side metric -> head snapshot (the dashboard /metrics source)."""
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def emit():
            from ray_tpu.util.metrics import Counter

            c = Counter("rt_user_metric_total", "from a task")
            c.inc(9)
            return True

        assert ray_tpu.get(emit.remote())
        from ray_tpu._private.worker import get_global_worker

        w = get_global_worker()
        deadline = time.time() + 15
        found = {}
        while time.time() < deadline:
            found = w.run_sync(w.gcs.call("metrics_snapshot", {}))[0][
                "snapshots"
            ]
            if any(
                m["name"] == "rt_user_metric_total"
                for snap in found.values() for m in snap
            ):
                break
            time.sleep(0.3)
        text = render_prometheus(found)
        assert "rt_user_metric_total" in text
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------------ runtime env


def test_runtime_env_working_dir(tmp_path):
    marker = tmp_path / "marker.txt"
    marker.write_text("found me")
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(runtime_env={"working_dir": str(tmp_path)})
        def read_marker():
            import os

            with open("marker.txt") as f:
                return os.path.basename(os.getcwd()), f.read()

        base, content = ray_tpu.get(read_marker.remote())
        assert content == "found me"
        assert base == tmp_path.name
    finally:
        ray_tpu.shutdown()


def test_runtime_env_unknown_plugin_fails_loudly():
    """Round-2 contract change: unknown plugins raise instead of being
    silently dropped (pip/uv/py_modules are now real — test_runtime_env)."""
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(runtime_env={"container": {"image": "x"}})
        def f():
            return "should not run"

        with pytest.raises(ray_tpu.exceptions.RayTpuError):
            ray_tpu.get(f.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()


# ----------------------------------------------------------------- chaos


def test_tasks_survive_node_killer():
    """Retriable tasks complete while a killer takes out nodes mid-run
    (reference: RayletKiller chaos)."""
    from ray_tpu._private.test_utils import NodeKiller

    ray_tpu.init(num_cpus=2, num_nodes=3)
    try:
        cluster = ray_tpu._internal_cluster()

        @ray_tpu.remote(max_retries=5)
        def work(i):
            import time as _t

            _t.sleep(0.05)
            return i * i

        killer = NodeKiller(cluster, interval_s=0.3, min_alive=1).start()
        try:
            refs = [work.remote(i) for i in range(120)]
            results = ray_tpu.get(refs, timeout=120)
            assert results == [i * i for i in range(120)]
        finally:
            killer.stop()
        assert killer.killed, "chaos killer never fired"
    finally:
        ray_tpu.shutdown()


def test_metric_reregistration_accumulates():
    """Re-constructing a metric with the same name must keep accumulating
    into the same series (task bodies re-run on the same worker)."""
    c1 = Counter("rt_reuse_total")
    c1.inc(2)
    c2 = Counter("rt_reuse_total")
    c2.inc(3)
    snap = {m["name"]: m for m in metrics_mod.registry().snapshot()}
    assert snap["rt_reuse_total"]["samples"][0]["value"] == 5.0
    with pytest.raises(ValueError):
        Gauge("rt_reuse_total")  # type change is an error
    h1 = Histogram("rt_reuse_hist", boundaries=(1.0,))
    h1.observe(0.5)
    with pytest.raises(ValueError):
        Histogram("rt_reuse_hist", boundaries=(2.0,))


# ------------------------------------------------------------ memory/OOM


def test_memory_monitor_reads_usage():
    from ray_tpu._private.memory_monitor import MemoryMonitor, get_memory_usage

    used, total = get_memory_usage()
    assert total > 0 and 0 <= used <= total
    assert not MemoryMonitor(threshold=1.0).is_pressing()
    assert MemoryMonitor(threshold=0.0).is_pressing()


def test_oom_rejection_is_retriable_and_surfaces():
    """A node over its memory threshold rejects tasks; the submitter
    retries and finally surfaces OutOfMemoryError (reference: memory
    monitor + worker-killing policy + task retries)."""
    ray_tpu.init(num_cpus=2, _node_env={"RT_MEMORY_THRESHOLD": "0.0"})
    try:
        @ray_tpu.remote(max_retries=1)
        def f():
            return 1

        with pytest.raises(ray_tpu.exceptions.OutOfMemoryError):
            ray_tpu.get(f.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()


def test_oom_retry_lands_on_healthy_node():
    """With one pressured node and one healthy node, retries land the task
    (slot eviction + fresh lease)."""
    ray_tpu.init(num_cpus=2)
    try:
        cluster = ray_tpu._internal_cluster()
        cluster.add_node({"CPU": 2}, env={"RT_MEMORY_THRESHOLD": "0.0"})
        cluster.wait_for_nodes(2)

        @ray_tpu.remote(max_retries=8)
        def f(i):
            return i + 1

        assert ray_tpu.get([f.remote(i) for i in range(20)], timeout=120) == [
            i + 1 for i in range(20)
        ]
    finally:
        ray_tpu.shutdown()


# --------------------------------------------------- debugging / profiling


def test_cluster_stack_dump():
    """Per-node all-thread stack dumps via the head fan-out (reference:
    ``ray stack`` / reporter-agent py-spy hooks — util/debug.py)."""
    import ray_tpu
    from ray_tpu.util.debug import dump_local_stacks, get_cluster_stacks

    local = dump_local_stacks()
    assert "--- thread MainThread" in local
    assert "test_cluster_stack_dump" in local  # sees this very frame

    ray_tpu.init(num_cpus=2, num_nodes=2)
    try:
        stacks = get_cluster_stacks()
        assert "driver" in stacks
        node_entries = [k for k in stacks if k != "driver"]
        assert len(node_entries) == 2
        for nid in node_entries:
            assert "--- thread" in stacks[nid], stacks[nid][:200]
    finally:
        ray_tpu.shutdown()


def test_node_memory_profile():
    """tracemalloc-backed memory profiling on a remote node (memray
    analog): start -> allocate in a task -> snapshot shows sites."""
    import ray_tpu
    from ray_tpu.util import state
    from ray_tpu.util.debug import node_memory_profile

    ray_tpu.init(num_cpus=2, num_nodes=1)
    try:
        node_id = state.list_nodes()[0]["node_id"]
        out = node_memory_profile(node_id, "start")
        assert out["tracing"] is True

        @ray_tpu.remote
        def alloc():
            keep = [bytearray(64_000) for _ in range(20)]
            return len(keep)

        assert ray_tpu.get(alloc.remote()) == 20
        snap = node_memory_profile(node_id, "snapshot", top=5)
        assert snap["tracing"] is True
        assert len(snap["top"]) >= 1
        assert all("size_bytes" in s for s in snap["top"])
        out = node_memory_profile(node_id, "stop")
        assert out["tracing"] is False
    finally:
        ray_tpu.shutdown()


def test_sampling_cpu_profile_local():
    """Pure-stdlib sampling profiler (py-spy record analog) emits folded
    flamegraph stacks that include a busy thread's frames."""
    import threading
    import time

    from ray_tpu.util.debug import sample_cpu_profile

    stop = threading.Event()

    def spin_with_marker_frame():
        while not stop.is_set():
            sum(i * i for i in range(500))

    t = threading.Thread(target=spin_with_marker_frame, daemon=True)
    t.start()
    try:
        folded = sample_cpu_profile(duration_s=0.8, hz=80)
    finally:
        stop.set()
        t.join(timeout=5)
    assert folded, "no samples collected"
    assert "spin_with_marker_frame" in folded
    # folded format: "a;b;c N" per line
    line = next(ln for ln in folded.splitlines()
                if "spin_with_marker_frame" in ln)
    assert line.rsplit(" ", 1)[1].isdigit()


def test_node_cpu_profile_rpc():
    """The sampler runs on a remote node through the head fan-out and sees
    an executing task's frames."""
    import threading
    import time

    import ray_tpu
    from ray_tpu.util import state
    from ray_tpu.util.debug import node_cpu_profile

    ray_tpu.init(num_cpus=2, num_nodes=1)
    try:
        node_id = state.list_nodes()[0]["node_id"]

        @ray_tpu.remote
        def burn_cpu_marker(sec):
            import time as _t
            end = _t.monotonic() + sec
            while _t.monotonic() < end:
                sum(i * i for i in range(400))
            return "done"

        ref = burn_cpu_marker.remote(4.0)
        time.sleep(0.5)
        folded = node_cpu_profile(node_id, duration_s=1.5)
        assert "burn_cpu_marker" in folded, folded[:400]
        assert ray_tpu.get(ref, timeout=30) == "done"
    finally:
        ray_tpu.shutdown()


def test_xla_profile_capture_smoke():
    """XLA trace capture produces a TensorBoard-readable trace dir (CPU
    backend in CI; the same call captures TPU timelines on hardware)."""
    import os

    import pytest as _pt

    from ray_tpu.util.debug import xla_profile_capture

    res = xla_profile_capture(duration_s=0.3)
    if not res.get("ok"):
        _pt.skip(f"jax profiler unavailable here: {res.get('error')}")
    assert os.path.isdir(res["logdir"])
    # the trace writer lays down plugins/profile/<ts>/ under the logdir
    found = []
    for root, _dirs, files in os.walk(res["logdir"]):
        found.extend(files)
    assert found, "trace dir is empty"


def test_cli_stack_command(capsys):
    import ray_tpu
    from ray_tpu import cli

    ray_tpu.init(num_cpus=2, num_nodes=1)
    try:
        addr = ray_tpu._internal_cluster().gcs_addr
        cli.main(["stack", "--address", f"{addr[0]}:{addr[1]}"])
        out = capsys.readouterr().out
        assert "===== node" in out
        assert "--- thread" in out
    finally:
        ray_tpu.shutdown()


def test_config_registry_resolution(monkeypatch):
    """Declared default < _system_config < env var (reference:
    ray_config_def.h RAY_CONFIG + _system_config override)."""
    from ray_tpu._private.config import ConfigRegistry

    reg = ConfigRegistry()
    reg.declare("probe_knob", int, 7, "test knob")
    assert reg.get("probe_knob") == 7
    reg.apply_system_config({"probe_knob": 11})
    assert reg.get("probe_knob") == 11
    monkeypatch.setenv("RT_PROBE_KNOB", "13")
    assert reg.get("probe_knob") == 13
    assert reg.system_config_env() == {"RT_PROBE_KNOB": "11"}
    import pytest as _pytest

    with _pytest.raises(ValueError, match="unknown _system_config"):
        reg.apply_system_config({"nope": 1})


def test_system_config_propagates_to_workers(tmp_path):
    """init(_system_config=...) reaches spawned worker processes as RT_*
    env (the raylet-cmdline propagation analog)."""
    import ray_tpu

    ray_tpu.init(
        num_cpus=2, num_nodes=1,
        _system_config={"lineage_bytes": 123456789},
    )
    try:
        @ray_tpu.remote
        def probe():
            import os

            from ray_tpu._private.config import rt_config

            return os.environ.get("RT_LINEAGE_BYTES"), rt_config.lineage_bytes

        env_val, resolved = ray_tpu.get(probe.remote(), timeout=30)
        assert env_val == "123456789"
        assert resolved == 123456789
    finally:
        ray_tpu.shutdown()


def test_a_files_system_config_ends_with_the_file(tmp_path):
    """``shutdown`` keeps ``_system_config``'s overrides in the process
    (the program's debt: ``ROADMAP.md`` Queue 3, item 13), and an xdist
    worker runs file after file: ``tests/conftest.py`` puts ``rt_config``
    back as a file found it. Two files under that conftest in one process:
    the second finds nothing of what the first set."""
    head = "import ray_tpu\nfrom ray_tpu._private.config import rt_config\n\n"
    (tmp_path / "test_sets.py").write_text(
        head + "def test_sets():\n"
        "    ray_tpu.init(num_cpus=1, _system_config={'rpc_deadline_s': 2.0})\n"
        "    ray_tpu.shutdown()\n"
        "    assert rt_config.system_config() == {'rpc_deadline_s': 2.0}\n")
    (tmp_path / "test_finds.py").write_text(
        head + "def test_finds():\n"
        "    assert rt_config.system_config() == {}\n")
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "tests.conftest",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly",
         str(tmp_path / "test_sets.py"), str(tmp_path / "test_finds.py")],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=300)
    assert "2 passed" in done.stdout, done.stdout + done.stderr
