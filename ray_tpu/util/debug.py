"""Cluster debugging: live stack dumps and memory profiling, no deps.

Reference analog: the dashboard reporter agent's profiling hooks
(``dashboard/modules/reporter/profile_manager.py`` — py-spy stack dumps /
flamegraphs, memray memory tracking) and the ``ray stack`` CLI. TPU-era
redesign: workers are CPython processes we own, so stacks come from
``sys._current_frames`` and allocation profiles from ``tracemalloc`` —
no external profilers to install, and the same RPCs work on any host.
"""
from __future__ import annotations

import sys
import threading
import traceback
from typing import Any, Dict, List, Optional


def dump_local_stacks() -> str:
    """Format every thread's current Python stack (py-spy dump analog)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out: List[str] = []
    for tid, frame in sorted(sys._current_frames().items()):
        name = names.get(tid, "?")
        out.append(f"--- thread {name} (tid={tid}) ---")
        out.extend(
            line.rstrip("\n")
            for line in traceback.format_stack(frame)
        )
    return "\n".join(out)


def memory_profile_local(action: str = "snapshot", top: int = 10):
    """tracemalloc control (memray analog): action in start|stop|snapshot.
    Snapshot returns the top allocation sites since start()."""
    import tracemalloc

    if action == "start":
        if not tracemalloc.is_tracing():
            tracemalloc.start()
        return {"tracing": True}
    if action == "stop":
        if tracemalloc.is_tracing():
            tracemalloc.stop()
        return {"tracing": False}
    if action != "snapshot":
        raise ValueError(f"unknown memory_profile action {action!r}")
    if not tracemalloc.is_tracing():
        return {"tracing": False, "top": []}
    snap = tracemalloc.take_snapshot()
    stats = snap.statistics("lineno")[: max(top, 1)]
    return {
        "tracing": True,
        "top": [
            {
                "site": str(s.traceback[0]) if s.traceback else "?",
                "size_bytes": s.size,
                "count": s.count,
            }
            for s in stats
        ],
    }


def sample_cpu_profile(duration_s: float = 5.0, hz: float = 99.0) -> str:
    """Sampling CPU profiler (py-spy record analog, reference:
    ``dashboard/modules/reporter/profile_manager.py``): samples every
    thread's Python stack at ``hz`` for ``duration_s`` and returns
    COLLAPSED stacks ("mod:fn;mod:fn ... count" lines) — the folded
    format flamegraph.pl / speedscope / inferno consume directly. Pure
    stdlib: the sampler is a thread reading sys._current_frames, so it
    works identically in any worker we own (~1% overhead at 99Hz)."""
    import time as _time
    from collections import Counter

    interval = 1.0 / max(hz, 1.0)
    counts: Counter = Counter()
    deadline = _time.monotonic() + max(duration_s, 0.05)
    me = threading.get_ident()
    while _time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue  # the sampler's own loop is noise
            stack = []
            f = frame
            while f is not None:
                code = f.f_code
                stack.append(
                    f"{code.co_filename.rsplit('/', 1)[-1]}:"
                    f"{code.co_name}"
                )
                f = f.f_back
            counts[";".join(reversed(stack))] += 1
        _time.sleep(interval)
    return "\n".join(f"{k} {v}" for k, v in counts.most_common())


def xla_profile_capture(duration_s: float = 3.0,
                        logdir: Optional[str] = None) -> Dict[str, Any]:
    """Capture an XLA/TPU profiler trace for ``duration_s`` (the TPU-native
    profiling the reference never needed): wraps
    ``jax.profiler.start_trace/stop_trace``, producing a TensorBoard-/
    xprof-readable trace dir with device timelines, HLO op costs and HBM
    usage. Runs in the TPU-owning process — call through the node RPC for
    workers.

    The host plane carries the program's own spans (``engine.tick``,
    ``engine.admit``, ``train.step``, ... — ``jax.profiler.TraceAnnotation``
    in ``llm/engine.py``, ``llm/serving.py`` and ``train/trainer.py``) on
    the device's timeline. The Python tracer is off: it stamps every call
    of every thread, slows exactly the host work the spans weigh, and made
    a 4 s capture of a loaded replica outlive its relay."""
    import time as _time

    try:
        import jax
    except ImportError:
        return {"ok": False, "error": "jax not importable here"}
    if logdir is None:
        import tempfile

        logdir = tempfile.mkdtemp(prefix="rt_xla_trace_")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        _time.sleep(max(duration_s, 0.1))
        jax.profiler.stop_trace()
    except Exception as e:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        return {"ok": False, "error": f"{type(e).__name__}: {e}"}
    return {"ok": True, "logdir": logdir,
            "hint": "xprof / tensorboard --logdir <logdir>, or Perfetto"}


class _NoSpan:
    """What ``span`` hands out where no capture can run."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


_NO_SPAN = _NoSpan()


def span(name: str, **args):
    """A ``jax.profiler.TraceAnnotation`` for the serve path
    (``serve/http_proxy.py``, ``handle.py``, ``replica.py``), inert unless a
    capture runs. A process that has not loaded JAX runs no capture, and a
    handle in a driver, or a deployment of plain Python, does not import it
    (seconds) for a span nobody can record: there this is a context that
    does nothing."""
    annotation = getattr(
        sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    return _NO_SPAN if annotation is None else annotation(name, **args)


_compiles = 0
_compiles_listening = False
_compiles_lock = threading.Lock()


def compile_count() -> int:
    """Executables this process has built since the first call of this
    function (compiled, or loaded from the persistent cache): one
    ``jax.monitoring`` listener, registered then. The engine keeps the
    count in ``stats["compiles"]`` and the train loop reports it, so a
    program built inside a measured window shows as a rise."""
    global _compiles_listening
    with _compiles_lock:
        if not _compiles_listening:
            from jax import monitoring
            from jax._src.dispatch import BACKEND_COMPILE_EVENT

            def on_duration(event, duration, **kwargs):
                global _compiles
                if event == BACKEND_COMPILE_EVENT:
                    _compiles += 1

            monitoring.register_event_duration_secs_listener(on_duration)
            _compiles_listening = True
    return _compiles


# ----------------------------------------------------------- cluster-facing


def get_cluster_stacks(
    address: Optional[str] = None, include_driver: bool = True
) -> Dict[str, str]:
    """Per-node stack dumps for every alive node (reference: ``ray stack``),
    keyed by node id. With ``include_driver`` the calling process's own
    stacks are added under "driver" (off for detached tools like the CLI,
    whose stacks are noise)."""
    from ray_tpu.util.state import _call

    out = dict(_call("cluster_stacks", {}, address).get("nodes", {}))
    if include_driver:
        out["driver"] = dump_local_stacks()
    return out


def node_cpu_profile(
    node_id: str, duration_s: float = 5.0, hz: float = 99.0,
    address: Optional[str] = None,
) -> str:
    """Sample one node's CPU profile; returns collapsed stacks (write to a
    .folded file for flamegraph tooling)."""
    from ray_tpu.util.state import _call

    return _call(
        "node_debug",
        {"node_id": node_id, "method": "cpu_profile",
         "duration_s": duration_s, "hz": hz},
        address,
        timeout=duration_s + 60,  # the capture itself takes duration_s
    ).get("folded", "")


def node_xla_profile(
    node_id: str, duration_s: float = 3.0, logdir: Optional[str] = None,
    address: Optional[str] = None,
) -> Dict[str, Any]:
    """Capture an XLA/TPU trace on the node that owns the chips."""
    from ray_tpu.util.state import _call

    return _call(
        "node_debug",
        {"node_id": node_id, "method": "xla_profile",
         "duration_s": duration_s, "logdir": logdir},
        address,
        # the capture takes duration_s, writing it out on a loaded node
        # much longer; the head's relay waits duration_s + 300
        timeout=duration_s + 330,
    )


def node_memory_profile(
    node_id: str, action: str = "snapshot", top: int = 10,
    address: Optional[str] = None,
) -> Dict[str, Any]:
    """Drive tracemalloc on one node: start -> (workload) -> snapshot."""
    from ray_tpu.util.state import _call

    return _call(
        "node_debug",
        {"node_id": node_id, "method": "memory_profile",
         "action": action, "top": top},
        address,
    )
