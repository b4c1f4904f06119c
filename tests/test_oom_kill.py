"""Pressure-based OOM task killing (reference behavior:
``src/ray/raylet/worker_killing_policy_group_by_owner.h`` + memory
monitor): a leaky retriable task is killed mid-run when its node crosses
the memory threshold, the kill actually frees the leaked memory (the task
runs in a subprocess executor), and the owner's retry lands on a
non-pressured node added later — the fleet survives."""
import time

import pytest

import ray_tpu
from ray_tpu._private.memory_monitor import get_memory_usage
from ray_tpu._private.test_utils import wait_for_condition


def _stage(marker):
    """(the task's result, its attempts, whether the memory reading was seen
    over the leaky node's threshold while the task ran)."""
    used, total = get_memory_usage()
    frac = used / max(total, 1)
    leak_bytes = 3 * 1024**3
    if total - used < 4 * leak_bytes:
        pytest.skip("host too full to stage a controlled leak")
    # The first (leaky) node presses once the leak lands (~+2.4% here);
    # the rescue node's threshold sits far above so it never presses.
    thr_leaky = frac + 0.5 * leak_bytes / total
    thr_rescue = min(frac + 10 * leak_bytes / total, 0.98)

    # Only the leaky node exists at submit time, so attempt 1 must land
    # there; the rescue node joins while the leak is in flight.
    ray_tpu.init(num_cpus=1, num_nodes=1,
                 _node_env={"RT_MEMORY_THRESHOLD": f"{thr_leaky:.5f}"})
    try:
        @ray_tpu.remote(num_cpus=1, max_retries=4, runtime_env={"pip": []})
        def leaker(marker_path, leak):
            import os as _os
            import time as _time

            import numpy as np

            with open(marker_path, "a") as f:
                f.write(f"{_os.getpid()}\n")
            attempts = sum(1 for _ in open(marker_path))
            if attempts == 1:
                # leak then linger: the watchdog must kill us mid-run
                hog = [np.ones(leak // 16, np.float64) for _ in range(2)]
                _time.sleep(60)
                return f"leaked-{len(hog)}"  # unreachable if killed
            return "ok"

        ref = leaker.remote(marker, leak_bytes)

        # Attempt 1 has started leaking on the pressured node: bring up
        # the rescue node the retry should land on.
        import os
        wait_for_condition(
            lambda: os.path.exists(marker), timeout=60,
            message="first attempt never started",
        )
        cluster = ray_tpu._internal_cluster()
        cluster.add_node(
            {"CPU": 1},
            env={"RT_MEMORY_THRESHOLD": f"{thr_rescue:.5f}"},
        )

        pressed, deadline = False, time.monotonic() + 120
        while True:
            used, total = get_memory_usage()
            pressed = pressed or used / max(total, 1) > thr_leaky
            done, _ = ray_tpu.wait([ref], timeout=0.2)
            if done or time.monotonic() > deadline:
                break
        out = ray_tpu.get(ref, timeout=5)
        with open(marker) as f:
            return out, len(f.readlines()), pressed
    finally:
        ray_tpu.shutdown()


def test_leaky_task_killed_and_retried_elsewhere(tmp_path):
    """The threshold is the shared host's reading at the start plus half the
    leak: where other tenants give back more than the leak adds, the node
    never presses. A reading that never passed the threshold is the
    staging's failure and it is staged again; a node that pressed and did
    not kill is the watchdog's, at once."""
    for attempt in range(3):
        out, attempts, pressed = _stage(str(tmp_path / f"attempts{attempt}"))
        if out == "ok" or pressed:
            break
    assert out == "ok", f"expected the retry to succeed, got {out!r}"
    assert attempts >= 2, "task was never killed + retried"
