"""Deterministic fault injection plane + RPC hardening tests.

Reference analog: the reference exercises its retry/dedup machinery with
per-RPC injected failures (``RAY_testing_rpc_failure`` hooks consulted in
``src/ray/rpc/grpc_client.h``), not just whole-node kills. Here:

- unit coverage for the spec language, seeded determinism, and kind
  semantics of ``_private/faultpoints.py``;
- cluster tests proving the hardening holds where injection bites —
  dropped lease/create_actor replies are retried and corr-deduped
  (never double-applied), dropped/failed pulls re-arm, a timed-out
  ``run_sync`` cancels its coroutine;
- a ``slow``-marked chaos matrix running core workloads under sustained
  10% faults at the major points, asserting completion and no leaked
  lease accounting;
- head-snapshot-restore under injected faults (corrupt snapshot + a
  dropped first post-restore lease reply must leave the head serving).
"""
import asyncio
import threading
import time
from concurrent.futures import TimeoutError as SyncTimeoutError

import pytest

import ray_tpu
from ray_tpu._private import faultpoints as fp
from ray_tpu._private.test_utils import NodeKiller, wait_for_condition
from tests.conftest import _leases_settled, _no_leaked_objects


pytestmark = pytest.mark.usefixtures("faults_cleared")


# chaos_flight_trace moved to conftest.py (shared with the serve chaos
# matrix): it now joins the task-event tracks into the failure artifact.


@pytest.fixture
def fast_rpc(monkeypatch):
    """Short deadlines so dropped replies retry in test time, plus extra
    retries so sustained-probability faults can't exhaust the budget."""
    monkeypatch.setenv("RT_RPC_DEADLINE_S", "2")
    monkeypatch.setenv("RT_LEASE_REQUEST_TIMEOUT_S", "1")
    monkeypatch.setenv("RT_RPC_RETRIES", "4")


# ------------------------------------------------------------- spec parsing
def test_parse_full_and_partial_specs():
    specs = fp.parse_spec(
        "worker.pull:error:0.5:3:42, gcs.dispatch.lease:drop:0.1"
    )
    assert [(s.point, s.kind, s.prob, s.count, s.seed) for s in specs] == [
        ("worker.pull", "error", 0.5, 3, 42),
        ("gcs.dispatch.lease", "drop", 0.1, 0, 0),
    ]


def test_parse_rejects_unknown_point():
    with pytest.raises(ValueError, match="unknown fault point"):
        fp.parse_spec("no.such.point:error:1.0")


def test_parse_rejects_unknown_and_unsupported_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        fp.parse_spec("worker.pull:explode:1.0")
    # spill.write supports error/delay only
    with pytest.raises(ValueError, match="does not support"):
        fp.parse_spec("spill.write:drop:1.0")


def test_parse_rejects_bad_prob():
    with pytest.raises(ValueError, match="prob"):
        fp.parse_spec("worker.pull:error:1.5")


def test_wildcard_spec_matches_all_verbs():
    fp.configure("gcs.dispatch.*:drop:1.0:0:1")
    assert fp.fire("gcs.dispatch.kv_put") == "drop"
    assert fp.fire("gcs.dispatch.lease") == "drop"
    assert fp.fire("worker.pull") is None


def test_inactive_is_total_noop():
    assert fp.ACTIVE is False
    assert fp.fire("worker.pull") is None
    assert fp.stats() == []


def test_configure_and_clear_toggle_active():
    fp.configure("worker.pull:error:1.0")
    assert fp.ACTIVE is True
    fp.clear()
    assert fp.ACTIVE is False


# ------------------------------------------------------------- determinism
def _collect_indices(spec, n=50):
    fp.configure(spec)
    for _ in range(n):
        try:
            fp.fire("worker.pull")
        except ConnectionError:
            pass
    return fp.stats()[0]["indices"]


def test_same_seed_injects_at_identical_indices():
    a = _collect_indices("worker.pull:error:0.3:0:42")
    b = _collect_indices("worker.pull:error:0.3:0:42")
    assert a == b and len(a) > 0


def test_different_seed_injects_differently():
    a = _collect_indices("worker.pull:error:0.3:0:42")
    b = _collect_indices("worker.pull:error:0.3:0:43")
    assert a != b


def test_count_caps_injections_without_shifting_draws():
    # count=2 must stop injecting after two hits, but the RNG draw stream
    # keeps advancing so the WOULD-HAVE indices match the uncapped run.
    uncapped = _collect_indices("worker.pull:error:0.3:0:7")
    capped = _collect_indices("worker.pull:error:0.3:2:7")
    assert capped == uncapped[:2]
    assert fp.stats()[0]["calls"] == 50


def test_error_kind_carries_unavailable_code():
    fp.configure("worker.pull:error:1.0:0:1")
    with pytest.raises(ConnectionError) as ei:
        fp.fire("worker.pull")
    assert getattr(ei.value, "code", None) == "unavailable"


def test_error_kind_uses_call_site_exception_class():
    from ray_tpu._private import protocol

    fp.configure("worker.pull:error:1.0:0:1")
    with pytest.raises(protocol.ConnectionLost):
        fp.fire("worker.pull", err=protocol.ConnectionLost)


def test_delay_kind_sleeps_then_proceeds():
    fp.configure("worker.pull:delay:1.0:0:1", delay_s=0.1)
    t0 = time.monotonic()
    assert fp.fire("worker.pull") == "delay"
    assert time.monotonic() - t0 >= 0.09


def test_async_fire_matches_sync_semantics():
    fp.configure("worker.pull:drop:1.0:0:1")

    async def go():
        return await fp.async_fire("worker.pull")

    assert asyncio.run(go()) == "drop"


def test_env_spec_format_via_configure_roundtrip():
    # the RT_FAULT_SPEC string format is the configure() format
    fp.configure("spill.write:error:1.0:1:5,spill.restore:delay:0.5")
    assert [s["point"] for s in fp.stats()] == [
        "spill.write", "spill.restore"
    ]


# ----------------------------------------------------- spill chaos (unit)
def test_spill_write_fault_keeps_object_in_arena(tmp_path):
    from ray_tpu._private.spill import SpillManager

    sm = SpillManager(root=str(tmp_path / "spill"))
    fp.configure("spill.write:error:1.0:1:9")
    metas = sm.spill_many([("aa" * 28, [b"x" * 10]), ("bb" * 28, [b"y"])])
    # exactly one write hit the injected storage failure; the batch API
    # reports it as None (object stays in the arena) without raising
    assert metas.count(None) == 1
    ok = [m for m in metas if m is not None]
    assert len(ok) == 1 and sm.stats["spilled_objects"] == 1
    # restore: first read hits the injected failure -> None (callers fall
    # back to pull/reconstruction); the next read succeeds
    fp.configure("spill.restore:error:1.0:1:9")
    assert sm.read(ok[0]) is None
    frames = sm.read(ok[0])
    assert frames is not None and sm.stats["restored_objects"] == 1
    sm.cleanup()


# ------------------------------------------------- test_utils satellites
class _FakeNode:
    def __init__(self, node_id):
        self.node_id = node_id

    def alive(self):
        return True


class _FailingCluster:
    def __init__(self):
        self.nodes = [_FakeNode("aaaa1111"), _FakeNode("bbbb2222")]

    def kill_node(self, handle):
        raise RuntimeError("kill exploded")


def test_node_killer_records_failed_kills():
    cluster = _FailingCluster()
    killer = NodeKiller(cluster, interval_s=0.01, min_alive=1).start()
    try:
        wait_for_condition(
            lambda: killer.kill_errors, timeout=5,
            message="NodeKiller never recorded the failed kill",
        )
    finally:
        killer.stop()
    assert killer.killed == []
    node_id, err = killer.kill_errors[0]
    assert node_id in ("aaaa1111", "bbbb2222") and "kill exploded" in err


def test_wait_for_condition_polls_and_times_out():
    hits = []

    def cond():
        hits.append(1)
        return len(hits) >= 3

    wait_for_condition(cond, timeout=5, interval=0.01)
    assert len(hits) == 3
    with pytest.raises(TimeoutError, match="nope"):
        wait_for_condition(lambda: False, timeout=0.2, interval=0.01,
                           message="nope")


# --------------------------------------------------- cluster: retry/dedup
def test_lease_reply_drop_is_retried_and_deduped(rt_start, fast_rpc):
    # The FIRST lease reply is swallowed after the head applied the grant;
    # the client's deadline fires, the retry carries the same correlation
    # id, and the head replays the original grants — the task completes
    # and no capacity is double-acquired.
    fp.configure("gcs.dispatch.lease:drop:1.0:1:7")

    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(41), timeout=60) == 42
    s = fp.stats()[0]
    assert s["injected"] == 1
    fp.clear()
    wait_for_condition(_leases_settled, timeout=15,
                       message="dropped-then-replayed lease leaked")


def test_lease_error_unavailable_is_retried(rt_start, fast_rpc):
    # Verb fails twice with the transient-unavailability class before it
    # ever grants; the retryable client re-issues until it lands.
    fp.configure("gcs.dispatch.lease:error:1.0:2:3")

    @ray_tpu.remote
    def f():
        return "ok"

    assert ray_tpu.get(f.remote(), timeout=60) == "ok"
    assert fp.stats()[0]["injected"] == 2


def test_pull_reply_drop_rearms_long_poll(rt_start, fast_rpc):
    @ray_tpu.remote
    def make():
        return ray_tpu.put(123)  # inner ref owned by the executing worker

    inner = ray_tpu.get(make.remote(), timeout=60)
    fp.configure("worker.pull:drop:1.0:1:5")
    # the first pull's reply is lost; the attempt deadline re-arms the
    # long-poll instead of hanging the get() forever
    assert ray_tpu.get(inner, timeout=60) == 123
    assert fp.stats()[0]["injected"] == 1


def test_pull_connection_errors_are_retried(rt_start, fast_rpc):
    @ray_tpu.remote
    def make():
        return ray_tpu.put([1, 2, 3])

    inner = ray_tpu.get(make.remote(), timeout=60)
    fp.configure("worker.pull:error:1.0:2:6")
    assert ray_tpu.get(inner, timeout=60) == [1, 2, 3]
    assert fp.stats()[0]["injected"] == 2


def test_create_actor_reply_drop_is_deduped(rt_start, fast_rpc):
    # Reply to create_actor dropped after the actor was placed: the retry
    # must return the ORIGINAL placement, not create a twin. A NAMED
    # actor keeps the synchronous per-actor verb (anonymous creations
    # ride create_actor_batch since round 10 — their dropped-reply replay
    # is pinned in test_submission_plane.py).
    fp.configure("gcs.dispatch.create_actor:drop:1.0:1:1")

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

    a = Counter.options(name="dedup-droptest").remote()
    assert ray_tpu.get(a.incr.remote(), timeout=60) == 1
    assert fp.stats()[0]["injected"] == 1
    head = ray_tpu._internal_cluster().head
    live = [x for x in head.actors.values() if x.state == "ALIVE"]
    assert len(live) == 1, "retry after dropped reply double-created"
    ray_tpu.kill(a)


def test_task_push_failure_retries_elsewhere(rt_start, fast_rpc):
    # An injected connection loss on the push path must surface as a
    # retriable worker failure, and the released slots must not leak the
    # head's capacity accounting.
    fp.configure("worker.task.push:error:1.0:1:4")

    @ray_tpu.remote
    def f(x):
        return x * 2

    assert ray_tpu.get(f.remote(21), timeout=60) == 42
    assert fp.stats()[0]["injected"] == 1
    fp.clear()
    wait_for_condition(_leases_settled, timeout=15,
                       message="push-failure slots leaked at the head")


def test_run_sync_timeout_cancels_coroutine(rt_start):
    from ray_tpu._private.worker import get_global_worker

    w = get_global_worker()
    state = {}
    started = threading.Event()

    async def slow():
        started.set()
        try:
            await asyncio.sleep(60)
            state["done"] = True
        except asyncio.CancelledError:
            state["cancelled"] = True
            raise

    with pytest.raises(SyncTimeoutError):
        w.run_sync(slow(), timeout=0.2)
    assert started.wait(5)
    wait_for_condition(
        lambda: state.get("cancelled"), timeout=5,
        message="timed-out run_sync left its coroutine running",
    )
    assert "done" not in state


# ------------------------------------------- head restore under faults
def test_head_restore_corrupt_snapshot_then_lease_drop(tmp_path):
    """A corrupt/truncated snapshot must not crash-loop the head, and a
    dropped reply on the first post-restore lease RPC must leave it
    serving: the corr-tagged retry replays the original grant."""
    from ray_tpu._private import protocol
    from ray_tpu._private.gcs import HeadService

    state = tmp_path / "head_state.bin"
    state.write_bytes(b"\x80\x04garbage truncated snapshot")

    async def run():
        head = HeadService()
        assert head.load_from_file(str(state)) is False  # fresh, no crash
        addr = await head.start()
        fp.configure("gcs.dispatch.lease:drop:1.0:1:11")
        conn = await protocol.connect(addr)
        await conn.call("register_node", {
            "node_id": "n1", "addr": ["127.0.0.1", 1],
            "resources": {"CPU": 2.0}, "labels": {},
        })
        req = {"resources": {"CPU": 1.0}, "count": 1, "timeout": 5.0,
               "corr": "restore-test-corr"}
        # first attempt: grant applied, reply swallowed -> client deadline
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(conn.call("lease", dict(req)), 1.5)
        # retry with the same corr: the head is still serving and replays
        # the ORIGINAL grant instead of acquiring a second CPU
        h, _ = await asyncio.wait_for(conn.call("lease", dict(req)), 10)
        assert len(h["grants"]) == 1
        assert head.nodes["n1"].available["CPU"] == pytest.approx(1.0)
        await conn.close()
        await head.close()

    asyncio.run(run())


# ------------------------------------------------------- chaos matrix
def _workload_fanout():
    @ray_tpu.remote
    def sq(x):
        return x * x

    refs = [sq.remote(i) for i in range(24)]
    assert ray_tpu.get(refs, timeout=120) == [i * i for i in range(24)]


def _workload_actor_roundtrip():
    @ray_tpu.remote
    class Acc:
        def __init__(self):
            self.total = 0

        def add(self, v):
            self.total += v
            return self.total

    a = Acc.remote()
    for i in range(1, 6):
        last = a.add.remote(i)
    assert ray_tpu.get(last, timeout=120) == 15
    ray_tpu.kill(a)


def _workload_multiref_get_wait():
    @ray_tpu.remote
    def nest(i):
        return ray_tpu.put(i)

    inners = ray_tpu.get([nest.remote(i) for i in range(8)], timeout=120)
    ready, not_ready = ray_tpu.wait(inners, num_returns=len(inners),
                                    timeout=120)
    assert not not_ready
    assert sorted(ray_tpu.get(inners, timeout=120)) == list(range(8))


def _workload_pg():
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )

    pg = placement_group([{"CPU": 1}], strategy="PACK", timeout=60)
    assert pg.ready(timeout=60)
    remove_placement_group(pg)


def _workload_device_objects():
    """Device plane under chaos, both directions: driver-owned sharded
    array consumed by a task (owner-side shard serving), task-owned
    device object pulled by the driver (consumer-side pull + retry)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    arr = jax.device_put(
        jnp.arange(32, dtype=jnp.float32).reshape(4, 8),
        NamedSharding(mesh, P("x")),
    )
    ref = ray_tpu.put(arr)

    @ray_tpu.remote
    def consume(v):
        import numpy as _np

        return float(_np.asarray(v).sum())

    @ray_tpu.remote
    def produce():
        import jax.numpy as _jnp

        return ray_tpu.put(_jnp.ones((8, 8), _jnp.float32))

    expect = float(np.asarray(arr).sum())
    assert ray_tpu.get(consume.remote(ref), timeout=120) == expect
    inner = ray_tpu.get(produce.remote(), timeout=120)
    v = ray_tpu.get(inner, timeout=120)
    assert float(np.asarray(v).sum()) == 64.0


CHAOS_SPECS = [
    "gcs.dispatch.lease:drop:0.1:0:101",
    "gcs.dispatch.lease:error:0.1:0:102",
    "gcs.lease.grant:error:0.1:0:103",
    "worker.pull:drop:0.1:0:104",
    "worker.pull:error:0.1:0:105",
    # Anonymous creations ride the round-10 batched verb: a dropped batch
    # reply must replay the ORIGINAL per-item outcomes via corr dedup (no
    # double-created actors, no leaked placements).
    "gcs.dispatch.create_actor_batch:drop:1.0:1:106",
    "gcs.dispatch.create_pg:drop:1.0:1:107",
    "protocol.rpc.reply:delay:0.2:0:108",
    "worker.actor.push:drop:0.2:0:109",
    # Batch-entry failure fires BEFORE any item registers: retryable-
    # unavailable, the client re-issues, nothing half-created.
    "gcs.create_actor_batch:error:1.0:1:111",
    # Spec-template build failure degrades that submission to the inline
    # full-header path — framing is an optimization, never a correctness
    # dependency.
    "worker.spec.frame:error:0.5:0:110",
    # Device plane: a failed/lost shard pull is retried against the owner
    # as a typed retryable error (never a hang, never a half-materialized
    # array); a lost registration degrades readers to pull-from-owner.
    "devstore.shard_pull:error:0.3:0:112",
    "devstore.shard_pull:drop:1.0:1:113",
    "devstore.register:drop:1.0:1:114",
    # Reply plane (round 15): a dropped coalesced multi-result frame
    # loses EVERY rider's reply at once — each per-task deadline must
    # re-arm and the corr-deduped re-push must replay recorded outcomes
    # (exactly-once application), with zero leaked leases/objects.
    "worker.reply.window:drop:1.0:1:115",
    "worker.reply.window:error:0.1:0:116",
    # Arg interning, both sides: pusher-side error degrades that push to
    # full frames / drop resets peer coverage; executor-side error forces
    # — and drop really performs — an interned-frame eviction, so the
    # typed arg_intern_miss retry re-sends the exact bytes.
    "worker.arg.intern:error:0.2:0:117",
    "worker.arg.intern:drop:0.3:0:118",
    # Transit pacing (round 16): error degrades a chunk to the fixed
    # pre-pacing fan-out, drop cold-resets a slot's window to its floor
    # — pacing is an optimization, so every workload must complete with
    # zero leaked leases/objects either way.
    "worker.push.window:error:0.3:0:119",
    "worker.push.window:drop:0.3:0:120",
    # Round-17 RT403 dividend (the lint catalog now pins the fire-site
    # set; these were live points with no matrix row). Named/synchronous
    # actor creation failing at the head must surface as a retryable
    # error the client re-issues — same contract the batched verb
    # already proves above.
    "gcs.actor.create:error:0.2:0:121",
    # Sender-side RPC delay: every control verb tolerates a slow write
    # leg the same way it tolerates the matrixed slow reply leg.
    "protocol.rpc.send:delay:0.2:0:122",
    # Driver loop scale-out (round 20): a refused settle-plane handoff
    # settles THAT reply batch inline on the event loop; a refused
    # pack-plane handoff packs THAT submission inline on the caller
    # thread. Either way every frame/task completes — the planes are
    # optimizations, never correctness gates — with zero leaked
    # leases/objects.
    "driver.settle.handoff:error:0.3:0:123",
    "driver.settle.handoff:drop:0.3:0:124",
    "driver.settle.handoff:delay:0.2:0:125",
    "driver.submit.pack:error:0.3:0:126",
    "driver.submit.pack:drop:0.3:0:127",
]


@pytest.mark.slow
@pytest.mark.parametrize("spec", CHAOS_SPECS)
def test_chaos_matrix(spec, monkeypatch, chaos_flight_trace):
    """Core workloads complete under sustained injected faults at every
    major point, and the head's lease accounting converges back to full
    capacity (no leaked leases). The spec rides RT_FAULT_SPEC into the
    spawned node processes too (they configure at import), so
    executor-side points — the reply-window flush, the interned-arg
    lookup — inject where they actually live, not just in the driver. A
    failure dumps the fault-annotated flight trace (chaos_flight_trace
    fixture)."""
    monkeypatch.setenv("RT_RPC_DEADLINE_S", "2")
    monkeypatch.setenv("RT_LEASE_REQUEST_TIMEOUT_S", "1")
    monkeypatch.setenv("RT_RPC_RETRIES", "6")
    monkeypatch.setenv("RT_FAULT_SPEC", spec)
    if spec.startswith("driver.settle.handoff"):
        # The settle plane auto-stands-down on single-core hosts; these
        # rows exercise the handoff path itself, so pin it live.
        monkeypatch.setenv("RT_DRIVER_SETTLE_THREAD", "1")
    ray_tpu.init(num_cpus=2)
    try:
        fp.configure(spec)
        _workload_fanout()
        _workload_actor_roundtrip()
        _workload_multiref_get_wait()
        _workload_pg()
        _workload_device_objects()
        calls = sum(s["calls"] for s in fp.stats())
        if not calls:
            # Executor-side-only point: its hits live in the node
            # processes — probe one (any node of this cluster carries
            # the env-configured spec).
            @ray_tpu.remote
            def _node_fp_stats():
                from ray_tpu._private import faultpoints as fpp

                return fpp.stats()

            calls = sum(
                s["calls"]
                for s in ray_tpu.get(_node_fp_stats.remote(), timeout=60)
            )
        assert calls > 0, "chaos spec never matched a fired point"
        fp.clear()
        wait_for_condition(_leases_settled, timeout=20,
                           message=f"leaked leases under {spec}")
        wait_for_condition(_no_leaked_objects, timeout=20,
                           message=f"leaked objects under {spec}")
    finally:
        fp.clear()
        ray_tpu.shutdown()


@pytest.mark.slow
def test_chaos_matrix_worker_crash(monkeypatch, chaos_flight_trace):
    """The ``crash`` fault kind, exercised for real: a worker process
    hard-exits (os._exit, the SIGKILL-equivalent) at its first task
    execution — after the lease was consumed, before any reply. The
    workload must still complete (pushes fail over and retry on the
    surviving node) and the head's lease accounting must converge with
    zero leaked leases; the dead node lands in the tombstone cache."""
    monkeypatch.setenv("RT_RPC_DEADLINE_S", "2")
    monkeypatch.setenv("RT_LEASE_REQUEST_TIMEOUT_S", "1")
    monkeypatch.setenv("RT_RPC_RETRIES", "6")
    ray_tpu.init(num_cpus=2)
    cluster = ray_tpu._internal_cluster()
    try:
        # "doom" pins the bait task to this node: the crash must fire on
        # ITS first dispatch, not depend on how a burst happens to spread.
        doomed = cluster.add_node(
            resources={"CPU": 2, "doom": 2},
            env={"RT_FAULT_SPEC": "worker.task.exec:crash:1.0:1:1"},
        )

        @ray_tpu.remote
        def sq(x):
            return x * x

        # Fire-and-forget bait: its execution attempt kills the process,
        # so its ref can never resolve (no other node has "doom") — we
        # only await the plain workload, which must fail over cleanly.
        sq.options(resources={"doom": 1}).remote(0)
        refs = [sq.remote(i) for i in range(24)]
        assert ray_tpu.get(refs, timeout=120) == [i * i for i in range(24)]
        # the injected crash really killed the process, mid-dispatch
        wait_for_condition(
            lambda: not doomed.alive(), timeout=30,
            message="doomed worker survived its crash faultpoint",
        )
        assert doomed.proc.returncode == 17  # faultpoints' os._exit code
        # the head noticed: the node is no longer alive in its view
        wait_for_condition(
            lambda: doomed.node_id not in cluster.head.nodes
            or not cluster.head.nodes[doomed.node_id].alive,
            timeout=30, message="head never observed the crashed node",
        )
        # and the crash leaked no lease accounting on the survivors —
        # nor any object: whatever the dead node registered must either
        # be borrower-held or gone from the directory
        wait_for_condition(_leases_settled, timeout=20,
                           message="worker crash leaked leases")
        wait_for_condition(_no_leaked_objects, timeout=20,
                           message="worker crash leaked objects")
    finally:
        ray_tpu.shutdown()


def test_chaos_smoke(rt_start, fast_rpc):
    """Fast tier-1 slice of the matrix: one dropped lease reply + one
    failed pull inside a single fan-out workload."""
    fp.configure(
        "gcs.dispatch.lease:drop:1.0:1:7,worker.pull:error:1.0:1:8"
    )
    _workload_multiref_get_wait()
    fp.clear()
    wait_for_condition(_leases_settled, timeout=15,
                       message="chaos smoke leaked leases")
    wait_for_condition(_no_leaked_objects, timeout=15,
                       message="chaos smoke leaked objects")
