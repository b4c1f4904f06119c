"""Records the small traces under ``benchmarks/tests/data`` on the chip
(``chiprun -- python3 benchmarks/tests/record_small_trace.py <out.pb>``):
twelve executions of a jitted ``step_fn`` — two matmuls and, on several
chips, an all-reduce of a sharded product — with the host tracers off so
that the file stays small. Kept so the recorded files have a provenance;
no test runs it."""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main(out_path: str) -> None:
    devices = jax.devices()
    assert devices[0].platform == "tpu", devices
    mesh = Mesh(np.asarray(devices), ("fsdp",))
    rows = NamedSharding(mesh, P("fsdp", None))
    whole = NamedSharding(mesh, P())

    @jax.jit
    def step_fn(x, w):
        h = jnp.tanh(x @ w)              # rows sharded: no communication
        g = jax.lax.with_sharding_constraint(h.T @ h, whole)  # all-reduce
        return jax.lax.with_sharding_constraint(h @ g, rows)

    x = jax.device_put(jnp.ones((4096, 2048), jnp.bfloat16), rows)
    w = jax.device_put(jnp.ones((2048, 2048), jnp.bfloat16) * 0.01, whole)
    step_fn(x, w).block_until_ready()
    logdir = tempfile.mkdtemp(prefix="small_trace_")
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    for i in range(12):
        x = step_fn(x, w)
        if i % 4 == 3:
            x.block_until_ready()
            time.sleep(0.002)  # a host gap the reduction must find
    x.block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    shutil.copy(found[0], out_path)
    print(out_path, os.path.getsize(out_path), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
