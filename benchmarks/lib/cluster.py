"""What both runners need from the cluster: its environment, the chip
count without JAX, facts read inside the chip-holding node process.

The harness process never initialises a JAX backend while the cluster is
up: a chip belongs to one process, and that process is the node.
"""
from __future__ import annotations

import os
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
# run-time products, all inside the checkout and listed in .gitignore
WORK_DIR = os.path.join(BENCH_DIR, ".work")


class NoAccelerator(Exception):
    """Fewer TPU chips than the cell asks for: no result is printed."""


def prepare_environment() -> None:
    """Environment the node processes inherit. The compile cache sits at a
    fixed path in the checkout (the path is part of the cache key) unless
    the machine names one; its thresholds are zeroed because the engine's
    programs compile in under the default 1 s and would never be stored."""
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".jax_cache"))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    # node processes are `python -m ray_tpu._private.worker_main` and must
    # import both the program and the benchmark's train-loop wrapper
    paths = [CHECKOUT] + [
        p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p]
    os.environ["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(paths))
    os.makedirs(WORK_DIR, exist_ok=True)


def chips_on_this_machine() -> int:
    """Chips counted from device files, without touching JAX."""
    from ray_tpu._private.accelerators import TPUAcceleratorManager

    return int(TPUAcceleratorManager.get_current_node_num_accelerators())


def start(run_name: str) -> None:
    import ray_tpu

    os.environ["RT_SESSION_DIR"] = os.path.join(WORK_DIR, "logs", run_name)
    ray_tpu.init(num_cpus=8, num_nodes=1)


def capture_on_node(duration_s: float, logdir: str) -> dict:
    """A profiler capture in the node process, as a CPU task (see
    ``node_memory_stats`` for why not a chip task)."""
    import ray_tpu

    from benchmarks.lib.capture import capture

    return ray_tpu.get(
        ray_tpu.remote(capture).remote(duration_s, logdir),
        timeout=duration_s + 300)


def _memory_stats() -> list:
    import jax

    return [dict(d.memory_stats() or {}, id=d.id) for d in jax.devices()]


def node_memory_stats() -> list:
    """``memory_stats()`` of every device, read in the node process (a CPU
    task: tasks of a node run as threads of the process that holds the
    chip, and asking for the chip would wait for its holder)."""
    import ray_tpu

    return ray_tpu.get(ray_tpu.remote(_memory_stats).remote(), timeout=120)


def memory_peak_bytes(stats: list) -> int:
    """Peak on the fullest chip. On this runtime ``peak_bytes_in_use``
    counts the live buffers only (state, cache, batch) and
    ``peak_bytes_reserved`` the space the running program reserved for its
    temporaries (11.24 GB for GPT-2-medium's step, which the compiler's own
    analysis confirms; PERF.md section 5). A program holds both at once,
    so the peak is their sum."""
    return max(
        (int(s.get("peak_bytes_in_use", 0))
         + int(s.get("peak_bytes_reserved", 0)) for s in stats),
        default=0)


def _process_time() -> float:
    return time.process_time()


def node_cpu_seconds() -> float:
    """CPU seconds (user + system, all threads) the node process has used:
    the replica's and the engine's threads are threads of that process. A
    run that reads far off shows by two readings whether a thread span."""
    import ray_tpu

    return ray_tpu.get(ray_tpu.remote(_process_time).remote(), timeout=120)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(what)


def log(record: dict) -> None:
    """An earlier output line: sample counts, unjudged tails, lateness."""
    import json

    print(json.dumps(record), flush=True)
