"""Driver event-loop scale-out (round 20).

Pins the three driver planes the way ``test_transit_plane.py`` pins the
transit plane:

- the settle plane (``specframe.PlaneQueue`` / ``SettlePlane``) drains
  whole backlogs per worker wakeup and re-enters each owning event loop
  with ONE ``call_soon_threadsafe`` per drain — wakeups are O(drains),
  never O(frames);
- the bounded handoff queue REJECTS when full (producers settle inline,
  frames are never lost) and counts every reject;
- cross-thread settling preserves per-loop FIFO order and routes every
  future to the loop that owns it — the invariant sharded pusher loops
  lean on;
- pusher-shard slot affinity: every slot of one peer address lands on
  ONE shard loop, for the slot's whole life
  (``pusher_shard_affinity_breaks == 0``);
- the ``driver_settle_thread`` / ``submit_pack_thread`` /
  ``pusher_loop_shards`` gates restore the single-loop pre-round-20
  driver byte-identically when off;
- the ``driver.settle.handoff`` / ``driver.submit.pack`` faultpoints
  degrade a handoff to the inline path, never correctness.
"""
import asyncio
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import faultpoints as fp
from ray_tpu._private import specframe
from ray_tpu._private import worker as worker_mod


pytestmark = pytest.mark.usefixtures("faults_cleared")


# ------------------------------------------------------ plane queue units
def test_plane_queue_drains_whole_backlog_per_wakeup():
    """Items that accumulate while the worker is busy ride the NEXT
    drain together: worker calls are O(drains), not O(items)."""
    hold = threading.Event()
    seen = []

    def worker(batch):
        seen.append(list(batch))
        hold.wait(5.0)

    q = specframe.PlaneQueue("t-drain", worker=worker, maxsize=64)
    try:
        assert q.offer("a")  # wakes the thread; worker blocks on hold
        deadline = time.monotonic() + 5.0
        while not seen and time.monotonic() < deadline:
            time.sleep(0.005)
        assert seen == [["a"]]
        # Backlog accumulates behind the blocked worker...
        for item in ("b", "c", "d"):
            assert q.offer(item)
        assert q.depth() == 3
        hold.set()
        deadline = time.monotonic() + 5.0
        while len(seen) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        # ...and drains as ONE batch: 4 items, 2 worker calls.
        assert seen == [["a"], ["b", "c", "d"]]
        snap = q.snapshot()
        assert snap["handoffs"] == 4
        assert snap["items"] == 4
        assert snap["drains"] == 2
        assert snap["max_drain"] == 3
        assert snap["rejects"] == 0
        assert snap["depth"] == 0
    finally:
        hold.set()
        q.close()


def test_plane_queue_bounded_handoff_rejects_when_full():
    """A full queue refuses the offer (the producer must settle inline)
    instead of blocking or dropping; rejects are counted and the items
    that DID hand off all drain."""
    hold = threading.Event()
    drained = []

    def worker(batch):
        hold.wait(5.0)
        drained.extend(batch)

    q = specframe.PlaneQueue("t-full", worker=worker, maxsize=2)
    try:
        assert q.offer(0)  # taken by the worker thread, which blocks
        deadline = time.monotonic() + 5.0
        while q.depth() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert q.offer(1)
        assert q.offer(2)
        assert not q.offer(3)  # bound hit: reject, never block/drop
        assert not q.offer(4)
        snap = q.snapshot()
        assert snap["rejects"] == 2
        assert snap["peak_depth"] == 2
        hold.set()
        deadline = time.monotonic() + 5.0
        while len(drained) < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert drained == [0, 1, 2]  # every accepted item settled
    finally:
        hold.set()
        q.close()


def test_plane_queue_close_rejects_further_offers():
    q = specframe.PlaneQueue("t-close", worker=lambda b: None, maxsize=8)
    assert q.offer("x")
    q.close()
    assert not q.offer("y")


# ------------------------------------------------- settle plane mechanics
class _FakeLoop:
    """Counts call_soon_threadsafe re-entries and runs them inline —
    the wakeup ledger for the O(drains) contract."""

    def __init__(self):
        self.wakeups = 0
        self.applied = []

    def call_soon_threadsafe(self, fn, *args):
        self.wakeups += 1
        fn(*args)


class _FakeOwner:
    """Owner whose _settle_prepare fans its payload items out to the
    loop each item names — the shape Connection/RingConnection return."""

    def __init__(self):
        self.prepared = 0

    def _settle_prepare(self, payload):
        self.prepared += 1
        ops = []
        for loop, record, value in payload:
            ops.append((loop, record.append, value))
        return ops


def test_settle_plane_wakeups_are_o_drains_not_o_frames():
    """N frames offered while the plane worker is busy settle with ONE
    loop re-entry for the whole drain: call_soon_threadsafe counts stay
    O(drains), never O(frames)."""
    loop = _FakeLoop()
    owner = _FakeOwner()
    record = []
    sp = specframe.SettlePlane(maxsize=64)
    try:
        # Stall the plane thread with a gate payload so a burst piles up
        # behind it, then release: the burst must drain as one batch.
        gate = threading.Event()

        class _GateOwner:
            def _settle_prepare(self, payload):
                gate.wait(5.0)
                return []

        assert sp.offer(_GateOwner(), None)
        time.sleep(0.05)  # plane thread is now parked in the gate
        n = 32
        for i in range(n):
            assert sp.offer(owner, [(loop, record, i)])
        gate.set()
        deadline = time.monotonic() + 5.0
        while len(record) < n and time.monotonic() < deadline:
            time.sleep(0.005)
        assert record == list(range(n))  # all frames, in offer order
        assert owner.prepared == n  # every frame prepared off-loop
        # The whole burst re-entered the loop in O(drains) wakeups —
        # with one stalled handoff ahead of it, that is a handful of
        # drains for 32 frames, never one wakeup per frame.
        snap = sp.snapshot()
        assert loop.wakeups == snap["applies"]
        assert loop.wakeups < n / 2, (loop.wakeups, snap)
        assert snap["items"] == n + 1
    finally:
        sp.close()


def test_settle_plane_routes_futures_to_their_owning_loop_in_order():
    """One drain carrying futures homed on TWO loops settles each on
    its own loop, preserving per-loop FIFO — the invariant that lets
    sharded pusher futures ride the same settle plane as driver-loop
    futures."""
    loops, threads = [], []
    for i in range(2):
        ready = threading.Event()
        holder = {}

        def runner(ready=ready, holder=holder):
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            holder["loop"] = loop
            ready.set()
            loop.run_forever()

        t = threading.Thread(target=runner, daemon=True)
        t.start()
        assert ready.wait(5.0)
        loops.append(holder["loop"])
        threads.append(t)

    settled = {0: [], 1: []}

    class _TwoLoopOwner:
        def _settle_prepare(self, payload):
            ops = []
            for which, value in payload:
                ops.append((loops[which], settled[which].append, value))
            return ops

    sp = specframe.SettlePlane(maxsize=64)
    try:
        owner = _TwoLoopOwner()
        # Interleave the two loops' items across several offers.
        for i in range(10):
            assert sp.offer(owner, [(0, f"a{i}"), (1, f"b{i}")])
        deadline = time.monotonic() + 5.0
        while ((len(settled[0]) < 10 or len(settled[1]) < 10)
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert settled[0] == [f"a{i}" for i in range(10)]
        assert settled[1] == [f"b{i}" for i in range(10)]
    finally:
        sp.close()
        for loop in loops:
            loop.call_soon_threadsafe(loop.stop)
        for t in threads:
            t.join(timeout=5)


def test_settle_plane_faultpoint_degrades_offer_to_inline():
    """driver.settle.handoff error/drop = the offer returns False (the
    producer settles inline); nothing reaches the plane queue."""
    sp = specframe.SettlePlane(maxsize=8)
    try:
        fp.configure("driver.settle.handoff:drop:1.0")
        assert not sp.offer(_FakeOwner(), [])
        fp.configure("driver.settle.handoff:error:1.0")
        assert not sp.offer(_FakeOwner(), [])
        fp.clear()
        assert sp.offer(_FakeOwner(), [])
        assert sp.snapshot()["handoffs"] == 1
    finally:
        sp.close()


# --------------------------------------------------- end-to-end behavior
def test_driver_planes_carry_the_workload(monkeypatch):
    """Gates pinned on (RT_DRIVER_SETTLE_THREAD=1 overrides the
    single-core auto stand-down): the settle and pack planes exist,
    every submitted task flows THROUGH the pack plane, TCP reply frames
    flow through the settle plane queue, ring replies settle under the
    same discipline on the pump thread, and loop re-entries stay
    O(drains)."""
    monkeypatch.setenv("RT_DRIVER_SETTLE_THREAD", "1")
    monkeypatch.setenv("RT_SUBMIT_PACK_THREAD", "1")
    ray_tpu.init(num_cpus=4)
    try:
        w = worker_mod.global_worker
        assert w._settle_plane is not None
        assert w._pack_plane is not None
        names = {t.name for t in threading.enumerate()}
        assert "rt-settle" in names and "rt-submit-pack" in names

        @ray_tpu.remote
        def noop(i):
            return i

        n = 300
        assert ray_tpu.get([noop.remote(i) for i in range(n)],
                           timeout=120) == list(range(n))
        ts = w.transit_stats()
        pk = ts["pack_plane"]
        assert pk["items"] >= n and pk["rejects"] == 0
        # Batched handoff: the loop saw far fewer drains than tasks.
        assert pk["drains"] < pk["items"]
        # TCP replies (GCS registration, leases) ride the plane queue;
        # ring task replies settle IN PLACE on the pump thread (already
        # off-loop) under the same per-loop-bucketed discipline.
        st = ts["settle_plane"]
        assert st["items"] > 0 and st["depth"] == 0
        assert ts["settle"]["frames"] >= n
        # O(drains) loop re-entries: one apply per (drain, loop), and
        # with sharding off every future homes on the one driver loop.
        assert st["applies"] <= st["drains"] * max(1, len(w._pusher_loops))
    finally:
        ray_tpu.shutdown()


def test_gates_off_restore_single_loop_driver(monkeypatch):
    """RT_DRIVER_SETTLE_THREAD=0 / RT_SUBMIT_PACK_THREAD=0 /
    RT_PUSHER_LOOP_SHARDS=0: no plane objects, no plane threads, no
    shard loops — and a burst completes identically with no _sq stamp
    ever carved out of pump-queue."""
    monkeypatch.setenv("RT_DRIVER_SETTLE_THREAD", "0")
    monkeypatch.setenv("RT_SUBMIT_PACK_THREAD", "0")
    monkeypatch.setenv("RT_PUSHER_LOOP_SHARDS", "0")
    ray_tpu.init(num_cpus=2)
    try:
        w = worker_mod.global_worker
        assert w._settle_plane is None
        assert w._pack_plane is None
        assert w._pusher_loops == []
        names = {t.name for t in threading.enumerate()}
        assert not any(
            n.startswith(("rt-settle", "rt-submit-pack", "rt-pusher"))
            for n in names
        ), names
        for c in list(w.peers.values()) + [w.gcs]:
            assert getattr(c, "settle_plane", None) is None

        @ray_tpu.remote
        def noop(i):
            return i

        n = 150
        assert ray_tpu.get([noop.remote(i) for i in range(n)],
                           timeout=120) == list(range(n))
        ts = w.transit_stats()
        assert "settle_plane" not in ts
        assert "pack_plane" not in ts
        assert "pusher_shards" not in ts
        assert w._stats["pusher_shard_affinity_breaks"] == 0
    finally:
        ray_tpu.shutdown()


def test_pusher_shards_slot_affinity(monkeypatch):
    """RT_PUSHER_LOOP_SHARDS=2: shard loops exist, every chunk was
    pushed from a shard (the per-shard ledger accounts every task), and
    slot affinity NEVER broke — one peer's slots live on one loop, so
    its push window and rendezvous event stay single-loop."""
    monkeypatch.setenv("RT_PUSHER_LOOP_SHARDS", "2")
    ray_tpu.init(num_cpus=2)
    try:
        w = worker_mod.global_worker
        assert len(w._pusher_loops) == 2
        names = {t.name for t in threading.enumerate()}
        assert {"rt-pusher-0", "rt-pusher-1"} <= names

        @ray_tpu.remote
        def noop(i):
            return i

        n = 300
        assert ray_tpu.get([noop.remote(i) for i in range(n)],
                           timeout=120) == list(range(n))
        shards = w.transit_stats()["pusher_shards"]
        assert len(shards) == 2
        assert sum(s["tasks"] for s in shards) >= n
        assert sum(s["chunks"] for s in shards) > 0
        # Chunk batching survived the move off the driver loop.
        assert sum(s["chunks"] for s in shards) < n
        assert w._stats["pusher_shard_affinity_breaks"] == 0
        # Live slots are pinned to a real shard loop, consistently by
        # peer address.
        by_addr = {}
        for ls in w.leases.values():
            for s in ls.slots:
                if s.shard_loop is None:
                    continue
                assert s.shard_loop in w._pusher_loops
                prev = by_addr.setdefault(s.addr, s.shard_loop)
                assert prev is s.shard_loop
    finally:
        ray_tpu.shutdown()


def test_dependent_tasks_pushed_in_one_chunk_do_not_deadlock(monkeypatch):
    """``z = f(y)`` submitted right behind ``y`` rides the same pushed chunk.
    A shard pusher used to hold ``y``'s reply until the WHOLE chunk had
    replied, while ``z`` sat on its node waiting for ``y`` from its owner:
    on any host with enough cores for the planes to switch on, every task
    that depended on a pending one hung forever (and with it the tier-1
    run, from tests/test_core_api.py on). Replies now reach the driver
    loop before the pusher waits for one that has not arrived."""
    monkeypatch.setenv("RT_PUSHER_LOOP_SHARDS", "2")
    monkeypatch.setenv("RT_SUBMIT_PACK_THREAD", "1")
    ray_tpu.init(num_cpus=4)
    try:
        w = worker_mod.global_worker
        assert len(w._pusher_loops) == 2 and w._pack_plane is not None

        @ray_tpu.remote
        def add(a, b):
            return a + b

        x = ray_tpu.put(10)
        y = add.remote(x, 5)
        z = add.remote(y, y)
        chain = z
        for _ in range(20):
            chain = add.remote(chain, 1)
        assert ray_tpu.get([z, chain], timeout=60) == [30, 50]
        assert w._stats["pusher_shard_affinity_breaks"] == 0
    finally:
        ray_tpu.shutdown()


def test_submit_pack_faultpoint_degrades_inline(rt_start):
    """driver.submit.pack error/drop = THAT submission packs inline on
    the caller thread; every task still completes and none is lost."""
    w = worker_mod.global_worker
    assert w._pack_plane is not None

    @ray_tpu.remote
    def noop(i):
        return i

    ray_tpu.get([noop.remote(i) for i in range(10)], timeout=120)  # warm
    fp.configure("driver.submit.pack:error:0.5:0:11")
    n = 120
    assert ray_tpu.get([noop.remote(i) for i in range(n)],
                       timeout=120) == list(range(n))
    st = fp.stats()
    assert sum(s["injected"] for s in st) > 0, st


def test_settle_handoff_faultpoint_degrades_inline(monkeypatch):
    """driver.settle.handoff drop at 1.0 = EVERY TCP reply frame
    settles inline on the event loop (pre-round-20 path) while the gate
    stays on; no frame is lost, no future hangs."""
    monkeypatch.setenv("RT_DRIVER_SETTLE_THREAD", "1")
    ray_tpu.init(num_cpus=4)
    try:
        w = worker_mod.global_worker
        assert w._settle_plane is not None

        @ray_tpu.remote
        def noop(i):
            return i

        ray_tpu.get([noop.remote(i) for i in range(10)],
                    timeout=120)  # warm
        before = w._settle_plane.snapshot()["handoffs"]
        fp.configure("driver.settle.handoff:drop:1.0")
        n = 120
        assert ray_tpu.get([noop.remote(i) for i in range(n)],
                           timeout=120) == list(range(n))
        fp.clear()
        # Every offer was refused: the plane ledger did not advance.
        assert w._settle_plane.snapshot()["handoffs"] == before
    finally:
        ray_tpu.shutdown()
