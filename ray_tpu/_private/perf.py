"""Core microbenchmarks (reference analog: ``python/ray/_private/ray_perf.py``
run by ``release/microbenchmark/run_microbenchmark.py`` — same workload shapes
so numbers are directly comparable to BASELINE.md)."""
from __future__ import annotations

import os
import time
from typing import Dict

import numpy as np

import ray_tpu


def _rate(n, t):
    return n / t if t > 0 else float("inf")


def bench_single_client_tasks_async(n: int = 2000) -> float:
    @ray_tpu.remote
    def noop():
        return None

    ray_tpu.get([noop.remote() for _ in range(50)])  # warm the lease path
    t0 = time.perf_counter()
    refs = [noop.remote() for _ in range(n)]
    ray_tpu.get(refs)
    return _rate(n, time.perf_counter() - t0)


def bench_single_client_tasks_sync(n: int = 300) -> float:
    @ray_tpu.remote
    def noop():
        return None

    ray_tpu.get(noop.remote())
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(noop.remote())
    return _rate(n, time.perf_counter() - t0)


def bench_actor_calls_async(n: int = 2000) -> float:
    @ray_tpu.remote
    class A:
        def m(self):
            return None

    a = A.remote()
    ray_tpu.get(a.m.remote())
    t0 = time.perf_counter()
    ray_tpu.get([a.m.remote() for _ in range(n)])
    rate = _rate(n, time.perf_counter() - t0)
    ray_tpu.kill(a)  # release the actor's CPU for the later benches
    return rate


def bench_actor_calls_sync(n: int = 300) -> float:
    @ray_tpu.remote
    class A:
        def m(self):
            return None

    a = A.remote()
    ray_tpu.get(a.m.remote())
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(a.m.remote())
    rate = _rate(n, time.perf_counter() - t0)
    ray_tpu.kill(a)
    return rate


def bench_actor_calls_1_n(n: int = 2000, n_actors: int = 0) -> float:
    """One caller fanning async calls across N actors (reference:
    1_n_actor_calls_async in ray_perf)."""
    if n_actors <= 0:
        n_actors = max(min((os.cpu_count() or 1), 8), 2)

    @ray_tpu.remote
    class A:
        def m(self):
            return None

    actors = [A.remote() for _ in range(n_actors)]
    ray_tpu.get([a.m.remote() for a in actors])
    t0 = time.perf_counter()
    refs = [actors[i % n_actors].m.remote() for i in range(n)]
    ray_tpu.get(refs)
    rate = _rate(n, time.perf_counter() - t0)
    for a in actors:
        ray_tpu.kill(a)
    return rate


def bench_actor_calls_concurrent(n: int = 1000) -> float:
    """Async calls against one max_concurrency=10 actor (reference:
    1_1_actor_calls_concurrent)."""
    @ray_tpu.remote
    class A:
        def m(self):
            return None

    a = A.options(max_concurrency=10).remote()
    ray_tpu.get(a.m.remote())
    t0 = time.perf_counter()
    ray_tpu.get([a.m.remote() for _ in range(n)])
    rate = _rate(n, time.perf_counter() - t0)
    ray_tpu.kill(a)
    return rate


def bench_async_actor_calls(n: int = 1000) -> float:
    """Async (coroutine-method) actor throughput (reference:
    1_1_async_actor_calls_async)."""
    @ray_tpu.remote
    class A:
        async def m(self):
            return None

    a = A.remote()
    ray_tpu.get(a.m.remote())
    t0 = time.perf_counter()
    ray_tpu.get([a.m.remote() for _ in range(n)])
    rate = _rate(n, time.perf_counter() - t0)
    ray_tpu.kill(a)
    return rate


def _client_actor_burst(addr: str, n: int, q):
    """Subprocess body for n_n actor calls: each client owns one actor."""
    import time as _time

    import ray_tpu as rt

    rt.init(address=addr)

    @rt.remote
    class A:
        def m(self):
            return None

    a = A.remote()
    rt.get([a.m.remote() for _ in range(50)])
    t0 = _time.perf_counter()
    rt.get([a.m.remote() for _ in range(n)])
    q.put((os.getpid(), n / (_time.perf_counter() - t0)))
    rt.kill(a)  # return the actor's CPU before exiting — leaked actors
    rt.shutdown()  # would starve every later bench leg


def bench_actor_calls_n_n(clients: int = 4, n: int = 1000) -> float:
    """Aggregate actor-call throughput across N driver processes, each with
    its own actor (reference: n_n_actor_calls_async). Sum of per-client
    steady-state rates."""
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.get_global_worker()
    addr = f"{w.gcs_addr[0]}:{w.gcs_addr[1]}"
    rates, _ = _run_clients(
        _client_actor_burst, [(addr, n) for _ in range(clients)],
        timeout=900.0,
    )
    return float(sum(rates))


def bench_put_gigabytes(total_gb: float = 2.0) -> float:
    """Large-object put throughput (reference shape: ray_perf puts numpy
    arrays; zero-copy serialization means one memcpy into the arena). Refs
    drop as we go — sustained throughput recycles hot arena pages the way
    a training feed does."""
    chunk = np.random.rand(100 * 1024 * 1024 // 8)  # 100MB float64
    n = max(int(total_gb * 1024 / 100), 1)
    ref = ray_tpu.put(chunk)  # warm: arena creation + page faults
    del ref
    t0 = time.perf_counter()
    for _ in range(n):
        ref = ray_tpu.put(chunk)
        del ref
    dt = time.perf_counter() - t0
    return n * chunk.nbytes / (1024 ** 3) / dt


def bench_put_get_device(total_gb: float = 0.5) -> float:
    """Device-plane put/get throughput: a sharded jax.Array crosses
    put()→get() into ANOTHER process (the pull_device_shards DCN leg —
    the same-process path is a table hit and measures nothing). Recorded
    as ``put_get_device_gb_per_s`` next to ``single_client_put_gb_per_s``
    so the device plane's trajectory rides the same bench JSON. The
    producer is the calling driver, which initialises JAX here: callers
    skip this leg on a cluster whose node holds a TPU."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = jax.devices()
    n_shard = min(len(devs), 4)
    mesh = Mesh(np.array(devs[:n_shard]), ("x",))
    rows = 64 * 1024 * n_shard  # ~64MB float32 at 256 cols
    arr = jax.device_put(
        jnp.ones((rows, 256), jnp.float32),
        NamedSharding(mesh, PartitionSpec("x")),
    )
    nbytes = int(arr.nbytes)

    @ray_tpu.remote(num_cpus=1)
    class Consumer:
        def consume(self, ref):
            import numpy as _np

            # Deliberate: the bench measures exactly this consumer-side
            # resolve; one actor on an elastic pool cannot deadlock it.
            v = ray_tpu.get(ref[0])  # raytpu: ignore[RT102]
            return int(_np.asarray(v).shape[0])

    c = Consumer.remote()
    warm = ray_tpu.put(arr)
    assert ray_tpu.get(c.consume.remote([warm]), timeout=120) == rows
    del warm
    n = max(int(total_gb * (1024 ** 3) / nbytes), 1)
    t0 = time.perf_counter()
    for _ in range(n):
        ref = ray_tpu.put(arr)
        # Consumer caches per-oid, and each put is a fresh oid: every
        # round pays the full shard pull.
        ray_tpu.get(c.consume.remote([ref]), timeout=120)
        del ref
    dt = time.perf_counter() - t0
    ray_tpu.kill(c)
    return n * nbytes / (1024 ** 3) / dt


def bench_get_calls(n: int = 2000) -> float:
    ref = ray_tpu.put(np.zeros(1000, np.float64))  # ~8KB, memory-store path
    ray_tpu.get(ref)
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(ref)
    return _rate(n, time.perf_counter() - t0)


def _client_task_burst(addr: str, n: int, q):
    """Subprocess body for the multi-client benches (spawn-safe)."""
    import time as _time

    import ray_tpu as rt

    rt.init(address=addr)

    @rt.remote
    def noop():
        return None

    rt.get([noop.remote() for _ in range(50)])
    t0 = _time.perf_counter()
    rt.get([noop.remote() for _ in range(n)])
    q.put((os.getpid(), n / (_time.perf_counter() - t0)))
    rt.shutdown()


def _client_put_burst(addr: str, total_mb: int, q):
    import time as _time

    import numpy as _np

    import ray_tpu as rt

    rt.init(address=addr)
    chunk = _np.random.rand(50 * 1024 * 1024 // 8)  # 50MB
    n = max(total_mb // 50, 1)
    r = rt.put(chunk)
    del r
    t0 = _time.perf_counter()
    for _ in range(n):
        r = rt.put(chunk)
        del r
    q.put((os.getpid(), n * chunk.nbytes / (1024 ** 3) / (_time.perf_counter() - t0)))
    rt.shutdown()


def _run_clients(target, args_list, timeout=300.0):
    """Run client subprocesses concurrently; returns (results, wall_s).
    Reports are (pid, value) pairs, so a client that exits without ever
    reporting aborts the wait promptly, while one that reported and then
    exited nonzero (e.g. an error inside rt.shutdown) is still counted."""
    import multiprocessing as mp
    import queue as queue_mod

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=target, args=(*a, q)) for a in args_list
    ]
    t0 = time.perf_counter()
    try:
        for p in procs:
            p.start()
        out = []
        reported = set()
        deadline = time.perf_counter() + timeout
        while len(out) < len(procs):
            try:
                pid, val = q.get(timeout=1.0)
                reported.add(pid)
                out.append(val)
                continue
            except queue_mod.Empty:
                pass
            if time.perf_counter() > deadline:
                raise RuntimeError("bench clients timed out")
            silent_dead = [
                p for p in procs
                if not p.is_alive() and p.pid not in reported
            ]
            if silent_dead and q.empty():
                raise RuntimeError(
                    f"{len(silent_dead)} bench client(s) exited "
                    "before reporting"
                )
        wall = time.perf_counter() - t0
        return out, wall
    finally:
        for p in procs:
            try:
                p.join(timeout=5)
                if p.is_alive():
                    p.kill()
            except (ValueError, AssertionError):
                pass  # never started (start() itself raised)


def bench_multi_client_tasks_async(clients: int = 4, n: int = 1000) -> float:
    """Aggregate async-task throughput across independent driver processes
    (reference: multi_client_tasks_async in ray_perf / release benchmarks).
    Reported as the SUM of per-client steady-state rates: client startup
    (jax import etc.) is excluded, and on hosts too small to overlap all
    clients this is an upper bound on sustained concurrent throughput."""
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.get_global_worker()
    addr = f"{w.gcs_addr[0]}:{w.gcs_addr[1]}"
    rates, _ = _run_clients(
        _client_task_burst, [(addr, n) for _ in range(clients)],
        timeout=900.0,
    )
    # Sum of per-client rates (reference semantics): client process startup
    # (jax import etc.) must not dilute the steady-state number.
    return float(sum(rates))


def bench_multi_client_put(clients: int = 4, total_mb: int = 500) -> float:
    """Aggregate put bandwidth (GB/s) across driver processes."""
    from ray_tpu._private import worker as worker_mod

    w = worker_mod.get_global_worker()
    addr = f"{w.gcs_addr[0]}:{w.gcs_addr[1]}"
    rates, _ = _run_clients(
        _client_put_burst, [(addr, total_mb) for _ in range(clients)],
        timeout=900.0,
    )
    return float(sum(rates))


def bench_put_calls(n: int = 1000) -> float:
    """Small-object put ops/s through the shm store (reference:
    single_client_put_calls_Plasma_Store in ray_perf — per-put fixed cost:
    create/seal/register, not bandwidth). 1MB payloads clear the inline
    threshold so every put exercises the arena."""
    chunk = np.random.rand(1024 * 1024 // 8)  # 1MB > INLINE_OBJECT_MAX
    ref = ray_tpu.put(chunk)
    del ref
    t0 = time.perf_counter()
    for _ in range(n):
        ref = ray_tpu.put(chunk)
        del ref
    return _rate(n, time.perf_counter() - t0)


def bench_get_10k_refs(k: int = 10_000) -> float:
    """ops/s for getting one object that contains 10k nested ObjectRefs
    (reference: single_client_get_object_containing_10k_refs — stresses
    borrow registration and nested-ref resolution)."""
    vals = [ray_tpu.put(i) for i in range(k)]
    container = ray_tpu.put(vals)
    n = 5
    ray_tpu.get(container)  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        inner = ray_tpu.get(container)
    dt = time.perf_counter() - t0
    del inner, vals, container
    return _rate(n, dt)


def bench_wait_1k_refs(k: int = 1000) -> float:
    """ops/s for ray.wait over 1k pending refs (reference:
    single_client_wait_1k_refs)."""
    @ray_tpu.remote
    def quick():
        return None

    refs = [quick.remote() for _ in range(k)]
    ray_tpu.get(refs)  # all ready: wait() measures bookkeeping, not tasks
    n = 5
    ray_tpu.wait(refs, num_returns=len(refs), timeout=10)
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.wait(refs, num_returns=len(refs), timeout=10)
    return _rate(n, time.perf_counter() - t0)


def bench_get_actor_refs(k: int = 1000, actors: int = 2) -> float:
    """refs/s for a multi-ref get whose objects live in OTHER workers'
    memory stores (no shm directory entry): exercises the batched
    directory lookup + owner-coalesced pull path — O(owners) RPCs per
    get, not O(refs)."""
    @ray_tpu.remote
    class Holder:
        def make(self, n, base):
            return [ray_tpu.put(base + i) for i in range(n)]

    hs = [Holder.remote() for _ in range(actors)]
    per = k // actors
    refs = []
    for j, h in enumerate(hs):
        refs.extend(ray_tpu.get(h.make.remote(per, j * per)))
    ray_tpu.get(refs)  # warm (pulled values are not cached; resolve repeats)
    n = 3
    t0 = time.perf_counter()
    for _ in range(n):
        out = ray_tpu.get(refs)
    dt = time.perf_counter() - t0
    assert out[0] == 0 and out[-1] == len(refs) - 1
    for h in hs:
        ray_tpu.kill(h)
    return _rate(n * len(refs), dt)


def bench_pg_churn(n: int = 50) -> float:
    """Placement-group create/ready/remove cycles per second (reference
    baseline: placement_group create/removal rate in BASELINE.md)."""
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )

    t0 = time.perf_counter()
    for _ in range(n):
        pg = placement_group([{"CPU": 0.01}])
        pg.ready(timeout=30)
        remove_placement_group(pg)
    return _rate(n, time.perf_counter() - t0)


def bench_many_nodes_tasks(target_nodes: int = 32, n: int = 500) -> float:
    """LEASE-PATH SMOKE, not a many-node benchmark: registers up to
    cores*4 simulated node processes ON ONE HOST and pushes n tasks
    through the head's lease machinery. The number is NOT comparable to
    the reference's many_nodes release benchmark (250 real nodes over a
    network) — it only guards the head's per-node bookkeeping cost from
    regressing. Node count is capped by host cores; simulated nodes carry
    fractional CPU."""
    import os as _os

    import ray_tpu as rt

    cluster = rt._internal_cluster()
    cores = _os.cpu_count() or 1
    extra = max(min(target_nodes, cores * 4) - len(cluster.nodes), 0)
    added = [cluster.add_node({"CPU": 1}) for _ in range(extra)]
    time.sleep(0.5)

    @rt.remote
    def noop():
        return None

    rt.get([noop.remote() for _ in range(50)])
    t0 = time.perf_counter()
    rt.get([noop.remote() for _ in range(n)])
    rate = _rate(n, time.perf_counter() - t0)
    for nh in added:
        # Graceful drain-then-terminate: a planned teardown must not spray
        # warning-level "node dead: connection lost" lines into the bench
        # tail (they read as failures and break tail parsing).
        cluster.remove_node(nh)
    return rate


def bench_many_actors(n: int = 1000) -> float:
    """Actor creation throughput at scale: create N cheap actors, wait for
    all to answer, kill them (reference:
    ``release/benchmarks/many_actors.json`` — 528.8 actors/s creating 10k
    actors across a cluster). Zero-CPU actors ride the node:slot marker so
    N isn't capped by cores."""
    @ray_tpu.remote(num_cpus=0)
    class A:
        def ping(self):
            return None

    t0 = time.perf_counter()
    actors = [A.remote() for _ in range(n)]
    ray_tpu.get([a.ping.remote() for a in actors])
    rate = _rate(n, time.perf_counter() - t0)
    for a in actors:
        ray_tpu.kill(a)
    return rate


def bench_many_pgs(n: int = 200) -> float:
    """Placement-group creation throughput: burst-create N single-bundle
    PGs, wait all ready, then remove (reference:
    ``release/benchmarks/many_pgs.json`` — 80.95 PGs/s). Rate covers
    create+ready; removal is off the clock like the reference."""
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )

    t0 = time.perf_counter()
    pgs = [placement_group([{"CPU": 0.001}]) for _ in range(n)]
    for pg in pgs:
        pg.ready(timeout=60)
    rate = _rate(n, time.perf_counter() - t0)
    for pg in pgs:
        remove_placement_group(pg)
    return rate


def bench_queued_tasks(n: int = 1_000_000) -> float:
    """Seconds to submit-and-drain N queued noop tasks (reference:
    ``release/perf_metrics/scalability/single_node.json`` — 1M queued tasks
    in 140.07s). Returns elapsed SECONDS (lower is better), reported as
    ``queued_{n}_tasks_s``."""
    @ray_tpu.remote(num_cpus=0)
    def noop():
        return None

    ray_tpu.get([noop.remote() for _ in range(100)])  # warm
    t0 = time.perf_counter()
    refs = [noop.remote() for _ in range(n)]
    # Drain in windows: one get() holding N futures peaks memory; the
    # reference benchmark also consumes results incrementally.
    for i in range(0, n, 10_000):
        ray_tpu.get(refs[i : i + 10_000])
    return time.perf_counter() - t0


def _progress(name: str):
    import sys

    print(f"[bench] {name}...", file=sys.stderr, flush=True)


def run_core_benchmarks(quick: bool = False) -> Dict[str, float]:
    scale = 0.25 if quick else 1.0
    out = {}
    # Label which store the object-plane legs exercised: fallback-store
    # numbers are NOT comparable to the native-arena targets, and a silent
    # native-build failure must be visible in the recorded bench artifact.
    from ray_tpu import native as rt_native
    from ray_tpu._private import worker as worker_mod

    out["native_store_active"] = bool(
        worker_mod.get_global_worker().shm.native_enabled
    )
    store_err = rt_native.build_failure("librt_native.so")
    if not out["native_store_active"] and store_err is not None:
        raise RuntimeError(
            "refusing to bench: native store fell back because the native "
            "build FAILED (compile error):\n" + store_err
        )
    _progress("single_client_tasks_async")
    out["single_client_tasks_async_per_s"] = bench_single_client_tasks_async(
        int(2000 * scale)
    )
    _progress("single_client_tasks_sync")
    out["single_client_tasks_sync_per_s"] = bench_single_client_tasks_sync(
        int(300 * scale)
    )
    _progress("actor_calls_async")
    out["actor_calls_async_per_s"] = bench_actor_calls_async(
        int(2000 * scale)
    )
    _progress("actor_calls_sync")
    out["actor_calls_sync_per_s"] = bench_actor_calls_sync(int(300 * scale))
    _progress("actor_calls_1_n")
    out["actor_calls_1_n_per_s"] = bench_actor_calls_1_n(int(2000 * scale))
    _progress("actor_calls_concurrent")
    out["actor_calls_concurrent_per_s"] = bench_actor_calls_concurrent(
        int(1000 * scale)
    )
    _progress("async_actor_calls")
    out["async_actor_calls_per_s"] = bench_async_actor_calls(
        int(1000 * scale)
    )
    _progress("put_gigabytes")
    out["single_client_put_gb_per_s"] = bench_put_gigabytes(
        0.5 if quick else 2.0
    )
    if ray_tpu.cluster_resources().get("TPU", 0) > 0:
        # The leg's producer is this driver: creating its array would take
        # the chip from the node that holds it (one process per chip).
        out["put_get_device_gb_per_s"] = None
        out["put_get_device_note"] = (
            "not measured: a node holds the TPU and the driver stays off JAX"
        )
    else:
        try:
            _progress("put_get_device")
            out["put_get_device_gb_per_s"] = bench_put_get_device(
                0.125 if quick else 0.5
            )
        except Exception as e:
            # jax-less hosts record the miss, never sink the run
            out["put_get_device_error"] = f"{type(e).__name__}: {e}"
    _progress("get_calls")
    out["single_client_get_calls_per_s"] = bench_get_calls(
        int(2000 * scale)
    )
    _progress("put_calls")
    out["single_client_put_calls_per_s"] = bench_put_calls(
        int(1000 * scale)
    )
    _progress("get_10k_refs")
    out["get_10k_refs_per_s"] = bench_get_10k_refs(
        2000 if quick else 10_000
    )
    _progress("wait_1k_refs")
    out["wait_1k_refs_per_s"] = bench_wait_1k_refs(
        250 if quick else 1000
    )
    _progress("get_actor_refs")
    out["get_actor_refs_per_s"] = bench_get_actor_refs(
        250 if quick else 1000
    )
    # Let the 10k-refs/wait legs' free backlog drain: PG churn should
    # measure placement-group ops, not the previous leg's cleanup fanout
    # (observed 79/s mid-drain vs ~2,000/s steady on the same build).
    time.sleep(2.0)
    _progress("pg_churn")
    out["pg_create_remove_per_s"] = bench_pg_churn(20 if quick else 50)
    import os as _os

    cores = _os.cpu_count() or 1
    # Client count/size scale with the host: each client is a full driver
    # process (jax import and all) — 4 of them on a 1-core box time out
    # without measuring anything.
    clients = 2 if (quick or cores < 8) else 4
    mc_n = int(1000 * scale) if cores >= 4 else min(int(1000 * scale), 250)
    try:
        _progress("multi_client_tasks_async")
        out["multi_client_tasks_async_per_s"] = bench_multi_client_tasks_async(
            clients=clients, n=mc_n
        )
    except Exception as e:  # multi-process benches must not sink the run
        import logging

        logging.getLogger(__name__).warning("multi-client bench failed: %s", e)
    try:
        _progress("multi_client_put")
        out["multi_client_put_gb_per_s"] = bench_multi_client_put(
            clients=clients,
            total_mb=(200 if quick else 500) if cores >= 4 else 100,
        )
    except Exception as e:
        import logging

        logging.getLogger(__name__).warning("multi-client put failed: %s", e)
    try:
        _progress("actor_calls_n_n")
        out["actor_calls_n_n_per_s"] = bench_actor_calls_n_n(
            clients=clients, n=mc_n
        )
    except Exception as e:
        import logging

        logging.getLogger(__name__).warning("n_n actor bench failed: %s", e)
    try:
        _progress("many_nodes_tasks")
        # key says "smoke": one-host simulated nodes, NOT comparable to
        # the reference's 250-real-node many_nodes number (see docstring)
        out["many_nodes_lease_smoke_per_s"] = bench_many_nodes_tasks(
            8 if quick else 32, int(500 * scale)
        )
    except Exception as e:
        import logging

        logging.getLogger(__name__).warning("many-nodes bench failed: %s", e)
    # Scale envelope (reference: release/benchmarks/*.json +
    # scalability/single_node.json). Failures are recorded, not swallowed:
    # a missing number in the bench artifact hides a regression.
    for key, fn in (
        ("many_actors_per_s",
         lambda: bench_many_actors(200 if quick else 1000)),
        ("many_pgs_per_s", lambda: bench_many_pgs(50 if quick else 200)),
        ("queued_5k_tasks_s" if quick else "queued_1m_tasks_s",
         lambda: bench_queued_tasks(5_000 if quick else 1_000_000)),
    ):
        try:
            _progress(key)
            out[key] = fn()
        except Exception as e:
            out[key + "_error"] = f"{type(e).__name__}: {e}"
    return out
