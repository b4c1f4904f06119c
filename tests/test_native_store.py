"""Native shm arena store: allocator, pin/delete lifetime, cross-process.

Mirrors the reference's plasma tests
(src/ray/object_manager/plasma/test/object_store_test.cc — create/seal/get/
delete lifecycle) against our arena client.
"""
import multiprocessing as mp
import os
import secrets

import pytest

from ray_tpu import native as rt_native
from ray_tpu.native import load_library
from ray_tpu.native.arena import HybridShmStore, NativeArenaStore

# A compile error with a working toolchain is a repo bug and must FAIL the
# suite (collection error), never skip — see test_native_build.py.
if load_library() is None and rt_native.build_failure() is not None:
    raise RuntimeError(
        "native build FAILED (compile error, toolchain present):\n"
        + rt_native.build_failure()
    )

pytestmark = pytest.mark.skipif(
    load_library() is None, reason="native toolchain unavailable"
)


def _hex() -> str:
    return secrets.token_hex(28)


@pytest.fixture
def arena():
    name = f"/rt_test_{os.getpid()}_{secrets.token_hex(4)}"
    store = NativeArenaStore(name, capacity=1 << 24)
    yield store
    store.close_all()


def test_roundtrip_frames(arena):
    oid = _hex()
    frames = [b"header-bytes", b"x" * 100_000, b""]
    meta = arena.put_frames(oid, frames)
    assert meta["arena"] == arena.name
    got = arena.get_frames(oid, meta)
    assert [bytes(f) for f in got] == frames
    assert arena.contains(oid)


def test_get_is_zero_copy(arena):
    oid = _hex()
    arena.put_frames(oid, [b"a" * 4096])
    v1 = arena.get_frames(oid, {})[0]
    v2 = arena.get_frames(oid, {})[0]
    # Same underlying arena memory, not copies.
    import ctypes
    a1 = ctypes.addressof(ctypes.c_char.from_buffer(v1))
    a2 = ctypes.addressof(ctypes.c_char.from_buffer(v2))
    assert a1 == a2


def test_missing_object(arena):
    assert arena.get_frames(_hex(), {}) is None
    assert not arena.contains(_hex())


def test_delete_reclaims_memory(arena):
    base = arena.stats()["bytes_in_use"]
    oids = []
    for _ in range(16):
        oid = _hex()
        arena.put_frames(oid, [b"y" * 50_000])
        oids.append(oid)
    assert arena.stats()["num_objects"] == 16
    for oid in oids:
        arena.free(oid)
    st = arena.stats()
    assert st["num_objects"] == 0
    assert st["bytes_in_use"] == base


def test_pinned_object_survives_delete(arena):
    import gc

    oid = _hex()
    arena.put_frames(oid, [b"z" * 1000])
    view = arena.get_frames(oid, {})[0]  # pin rides the view's lifetime
    # Creator deletes while the reader view is live: memory must not be
    # reused until the view dies (plasma pin semantics).
    in_use = arena.stats()["bytes_in_use"]
    arena._created.pop(oid, None)  # simulate owner in another process
    arena._lib.rt_obj_delete(arena._h, oid.encode())
    assert arena.stats()["bytes_in_use"] == in_use  # still held by pin
    assert bytes(view) == b"z" * 1000
    del view
    gc.collect()
    assert arena.stats()["bytes_in_use"] < in_use


def test_double_delete_does_not_steal_reader_pin(arena):
    """Owner free AND creator free (object_free pubsub fanout) both call
    rt_obj_delete; the creator pin must drop exactly once, or the second
    delete steals the READER's pin and the block is reclaimed (and reused)
    under a live zero-copy view — observed as streamed values swapping."""
    import gc

    oid = _hex()
    arena.put_frames(oid, [b"A" * 100_000])
    view = arena.get_frames(oid, {})[0]  # reader pin rides the view
    in_use = arena.stats()["bytes_in_use"]
    # owner-side free (borrower process path: delete via meta)
    arena._lib.rt_obj_delete(arena._h, oid.encode())
    # creator-side free (pubsub fanout path) — a second delete
    arena._created.pop(oid, None)
    arena._lib.rt_obj_delete(arena._h, oid.encode())
    assert arena.stats()["bytes_in_use"] == in_use, "reader pin stolen"
    # A new same-size object must NOT overwrite the pinned block.
    oid2 = _hex()
    arena.put_frames(oid2, [b"B" * 100_000])
    assert bytes(view[:10]) == b"A" * 10
    del view
    gc.collect()
    # Pin released: now the block reclaims.
    assert arena.stats()["bytes_in_use"] <= in_use


def test_coalescing_allows_large_realloc(arena):
    # Fill with small objects, free them all, then allocate one block that
    # only fits if neighbors coalesced back into a single free range.
    cap = arena.stats()["capacity"]
    oids = []
    small = (cap // 64) & ~15
    for _ in range(32):
        oid = _hex()
        if arena.put_frames(oid, [b"s" * small]) is None:
            break
        oids.append(oid)
    for oid in oids:
        arena.free(oid)
    big = int(cap * 0.75)
    oid = _hex()
    assert arena.put_frames(oid, [b"B" * big]) is not None
    arena.free(oid)


def test_arena_full_returns_none(arena):
    cap = arena.stats()["capacity"]
    oid = _hex()
    assert arena.put_frames(oid, [b"Q" * (cap * 2)]) is None


def test_duplicate_create_raises(arena):
    oid = _hex()
    arena.put_frames(oid, [b"1"])
    with pytest.raises(RuntimeError):
        arena.put_frames(oid, [b"2"])


def _child_reader(name, oid, payload_len, q):
    try:
        store = NativeArenaStore(name, create=False)
        frames = store.get_frames(oid, {})
        q.put(("ok", bytes(frames[1]) == b"p" * payload_len))
    except Exception as e:  # pragma: no cover - diagnostic path
        q.put(("err", repr(e)))


def test_cross_process_read():
    name = f"/rt_test_xp_{os.getpid()}_{secrets.token_hex(4)}"
    store = NativeArenaStore(name, capacity=1 << 24)
    try:
        oid = _hex()
        store.put_frames(oid, [b"hdr", b"p" * 10_000])
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        p = ctx.Process(target=_child_reader, args=(name, oid, 10_000, q))
        p.start()
        status, ok = q.get(timeout=30)
        p.join(timeout=10)
        assert status == "ok", ok
        assert ok
    finally:
        store.close_all()


def _child_writer(name, oid, q):
    try:
        store = NativeArenaStore(name, create=False)
        store.put_frames(oid, [b"from-child" * 100])
        q.put("ok")
        # Exit WITHOUT delete: creator pin leaks, object must stay readable.
    except Exception as e:  # pragma: no cover
        q.put(repr(e))


def test_cross_process_write_then_parent_read():
    name = f"/rt_test_xw_{os.getpid()}_{secrets.token_hex(4)}"
    store = NativeArenaStore(name, capacity=1 << 24)
    try:
        oid = _hex()
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        p = ctx.Process(target=_child_writer, args=(name, oid, q))
        p.start()
        assert q.get(timeout=30) == "ok"
        p.join(timeout=10)
        frames = store.get_frames(oid, {})
        assert bytes(frames[0]) == b"from-child" * 100
    finally:
        store.close_all()


def test_hybrid_falls_back_when_arena_full(monkeypatch):
    # the smallest arena there is (``_shm_budget``'s floor of 16 MiB): any
    # object larger than the arena proves the fallback, and twice the
    # default 4 GiB was 8 GiB moved several times
    monkeypatch.setenv("RT_ARENA_BYTES", str(1 << 20))
    name = f"/rt_test_hy_{os.getpid()}_{secrets.token_hex(4)}"
    store = HybridShmStore(name)
    try:
        if store.arena is None:
            pytest.skip("no native arena")
        cap = store.arena.stats()["capacity"]
        assert cap <= 1 << 24
        oid = _hex()
        meta = store.put_frames(oid, [b"W" * (cap * 2)])
        assert "seg" in meta  # portable fallback segment
        got = store.get_frames(oid, meta)
        assert bytes(got[0]) == b"W" * (cap * 2)
        store.free(oid, meta)
    finally:
        store.close_all()


def test_many_alloc_free_cycles(arena):
    """Allocator churn: interleaved sizes, no leak at the end."""
    import random

    rng = random.Random(0)
    live = {}
    base = arena.stats()["bytes_in_use"]
    for i in range(400):
        if live and (rng.random() < 0.45 or len(live) > 40):
            oid = rng.choice(list(live))
            n = live.pop(oid)
            got = arena.get_frames(oid, {})
            assert len(got[0]) == n
            arena.free(oid)
        else:
            oid = _hex()
            n = rng.randrange(10, 60_000)
            if arena.put_frames(oid, [bytes([i % 256]) * n]) is not None:
                live[oid] = n
    for oid in list(live):
        arena.free(oid)
    del got
    import gc

    gc.collect()  # drop view pins so deletable blocks reclaim
    assert arena.stats()["bytes_in_use"] == base
    assert arena.stats()["num_objects"] == 0


def test_tombstone_rehash_bounded():
    """Churn far more objects than index slots: tombstones must rehash away
    and lookups keep working."""
    name = f"/rt_test_tb_{os.getpid()}_{secrets.token_hex(4)}"
    store = NativeArenaStore(name, capacity=1 << 24, index_slots=256)
    try:
        for i in range(2000):
            oid = _hex()
            assert store.put_frames(oid, [b"t" * 64]) is not None
            assert store.contains(oid)
            store.free(oid)
        tombs = store._lib.rt_arena_num_tombs(store._h)
        assert tombs <= 64, f"tombstones not rehashed: {tombs}"
        assert not store.contains(_hex())  # miss lookups still terminate
        st = store.stats()
        assert st["num_objects"] == 0
    finally:
        store.close_all()


def _child_crash_in_lock(name, q):
    import time as _time

    try:
        store = NativeArenaStore(name, create=False)
        store.put_frames(secrets.token_hex(28), [b"pre-crash" * 10])
        store._lib.rt_test_hold_lock(store._h)
        q.put("locked")
        # Let the queue feeder thread flush, then die holding the mutex.
        # (The parent blocks on the robust mutex until this process dies,
        # then wakes with EOWNERDEAD.)
        _time.sleep(0.5)
        os._exit(9)
    except Exception as e:  # pragma: no cover
        q.put(repr(e))


def test_crash_recovery_eownerdead():
    """A process dying inside the critical section must not wedge or corrupt
    the arena: the next locker recovers and normal operation continues."""
    name = f"/rt_test_cr_{os.getpid()}_{secrets.token_hex(4)}"
    store = NativeArenaStore(name, capacity=1 << 24)
    try:
        survivor = _hex()
        store.put_frames(survivor, [b"S" * 5000])
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        p = ctx.Process(target=_child_crash_in_lock, args=(name, q))
        p.start()
        assert q.get(timeout=30) == "locked"
        p.join(timeout=10)
        # Next operation takes the robust mutex, recovers, and proceeds.
        assert store.contains(survivor)
        got = store.get_frames(survivor, {})
        assert bytes(got[0]) == b"S" * 5000
        # Allocator still sane after recovery: alloc/free cycles work.
        for _ in range(50):
            oid = _hex()
            assert store.put_frames(oid, [b"x" * 10_000]) is not None
            store.free(oid)
    finally:
        store.close_all()


def _child_pin_and_die(name, oid, q):
    try:
        store = NativeArenaStore(name, create=False)
        frames = store.get_frames(oid, {})
        assert frames is not None
        q.put("pinned")
        import time as _t
        _t.sleep(0.5)  # let the queue flush
        os._exit(9)  # die holding the reader pin (no release)
    except Exception as e:  # pragma: no cover
        q.put(repr(e))


def test_dead_process_pins_are_scrubbed():
    """A reader killed while holding pins must not leak its blocks: the
    scrub (also triggered on allocation pressure) subtracts the dead
    process's pin ledger and reclaims (plasma client-disconnect analog)."""
    name = f"/rt_test_sc_{os.getpid()}_{secrets.token_hex(4)}"
    store = NativeArenaStore(name, capacity=1 << 24)
    try:
        oid = _hex()
        store.put_frames(oid, [b"L" * 100_000])
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        p = ctx.Process(target=_child_pin_and_die, args=(name, oid, q))
        p.start()
        assert q.get(timeout=30) == "pinned"
        p.join(timeout=10)
        base = store.stats()["bytes_in_use"]
        store.free(oid)  # owner delete: dead reader's pin still blocks it
        assert store.stats()["bytes_in_use"] == base
        live = store._lib.rt_arena_scrub(store._h)
        assert live >= 1  # this process
        assert store.stats()["bytes_in_use"] < base
        assert store.stats()["num_objects"] == 0
    finally:
        store.close_all()


def test_scrub_triggers_on_allocation_pressure():
    """When the arena fills, create() scrubs dead clients automatically and
    retries before reporting ENOSPC."""
    name = f"/rt_test_sp_{os.getpid()}_{secrets.token_hex(4)}"
    store = NativeArenaStore(name, capacity=1 << 24)
    try:
        cap = store.stats()["capacity"]
        big = int(cap * 0.6)
        oid = _hex()
        store.put_frames(oid, [b"X" * big])
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        p = ctx.Process(target=_child_pin_and_die, args=(name, oid, q))
        p.start()
        assert q.get(timeout=30) == "pinned"
        p.join(timeout=10)
        store.free(oid)  # deletable, but dead reader pin holds it
        # This put only fits if the dead client's pin got scrubbed inline.
        oid2 = _hex()
        assert store.put_frames(oid2, [b"Y" * big]) is not None
        store.free(oid2)
    finally:
        store.close_all()


def _child_multithread_putter(name, oid, n, q):
    try:
        # RT_COPY_THREADS was set by the parent BEFORE spawn: the budget is
        # cached on first use, so it must be in the env at process start.
        store = NativeArenaStore(name, create=False)
        payload = bytes(range(256)) * (n // 256) + b"Z" * (n % 256)
        store.put_frames(oid, [payload])
        q.put(("ok", len(payload)))
    except Exception as e:  # pragma: no cover
        q.put(("err", repr(e)))


@pytest.mark.parametrize("extra", [1, 63, 65, 4097])
def test_parallel_copy_covers_tail(extra):
    """Multi-threaded payload copies must cover every byte: chunk rounding
    that floors len/nthreads before 64-aligning used to drop the tail when
    the floor was already aligned (silent corruption on multi-core hosts)."""
    name = f"/rt_test_tail_{os.getpid()}_{secrets.token_hex(4)}"
    store = NativeArenaStore(name, capacity=1 << 25)
    n = (8 << 20) + extra  # >= 2 x 4MB per-thread chunks, never divisible
    try:
        oid = _hex()
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        env_backup = os.environ.get("RT_COPY_THREADS")
        os.environ["RT_COPY_THREADS"] = "4"
        try:
            p = ctx.Process(
                target=_child_multithread_putter, args=(name, oid, n, q)
            )
            p.start()
            status, detail = q.get(timeout=60)
            p.join(timeout=10)
        finally:
            if env_backup is None:
                os.environ.pop("RT_COPY_THREADS", None)
            else:
                os.environ["RT_COPY_THREADS"] = env_backup
        assert status == "ok", detail
        got = store.get_frames(oid, {})[0]
        expect = bytes(range(256)) * (n // 256) + b"Z" * (n % 256)
        assert len(got) == n
        assert bytes(got[-4096:]) == expect[-4096:]  # the dropped region
        assert bytes(got) == expect
    finally:
        store.close_all()


def _mapped(name: str) -> bool:
    with open("/proc/self/maps") as f:
        return any(name.strip("/") in line for line in f)


def test_closed_arena_is_unmapped_when_its_last_view_dies():
    """A process that runs many init()/shutdown() cycles (a test worker)
    must not keep every session's prefaulted arena mapped until it exits —
    six such workers once pinned 116 GB of tmpfs and the kernel killed two.
    A view that outlives close_all() stays readable; the mapping goes with
    the last one."""
    import gc

    name = f"/rt_test_{os.getpid()}_{secrets.token_hex(4)}"
    store = NativeArenaStore(name, capacity=1 << 24)
    oid = _hex()
    store.put_frames(oid, [b"z" * 8192])
    view = store.get_frames(oid, {})[0]
    assert _mapped(name)
    store.close_all()
    assert _mapped(name) and bytes(view[:4]) == b"zzzz"  # still readable
    assert store.put_frames(_hex(), [b"late"]) is not None  # and writable
    del view
    gc.collect()
    assert not _mapped(name)
    with pytest.raises(RuntimeError, match="errno 9"):  # EBADF, not a crash
        store.put_frames(_hex(), [b"after detach"])

    idle = NativeArenaStore(name + "b", capacity=1 << 24)
    idle.close_all()  # no view outstanding: unmapped at once
    assert not _mapped(name + "b")
