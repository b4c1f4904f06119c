"""The profiler capture, run in the process that holds the chip (the train
worker's own thread, or a CPU task on the replica's node).

The Python tracer is off: on, it stamps every call of the engine's and the
proxy's threads, slows exactly the host work the trace is there to weigh,
and made one four-second capture of a loaded replica take more than the 34 s
the program's ``node_xla_profile`` relay allows (my chip run, PR 23). The
program's ``util/debug.py:xla_profile_capture`` offers no options, so the
benchmark makes the two profiler calls itself.
"""
from __future__ import annotations

import time


def capture(duration_s: float, logdir: str) -> dict:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    started = time.monotonic()  # one clock for every process of a machine
    try:
        time.sleep(duration_s)
    finally:
        stopped = time.monotonic()
        jax.profiler.stop_trace()
    return {"ok": True, "logdir": logdir,
            "started": started, "stopped": stopped}
