"""Reply-plane batching & arg-interning economics (round 15).

Pins the RPC shape of the coalesced reply plane the way
``test_submission_plane.py`` pins the request side:

- a queued single-peer burst settles with O(bursts) coalesced reply
  frames (the executor's ReplyWindow self-clocks on the driver's acks),
  never one reply message per task;
- a repeated small argument frame ships its bytes ONCE per peer
  (digest-only afterwards), and the bytes reaching the executor are
  byte-identical to what the submitter framed — including across
  receiver-LRU eviction, where the typed ``arg_intern_miss`` makes the
  pusher re-send the blob;
- a dropped coalesced reply frame re-arms the per-task deadlines and the
  corr-deduped re-push REPLAYS recorded outcomes (each task executes
  exactly once, no future settles twice);
- ``worker.shutdown()`` flushes results still riding an open window
  (the PR 7 tail-event flush discipline, applied to the reply plane);
- the ``reply_batching`` / ``arg_interning`` gates restore the per-task /
  per-arg wire byte-identically when off.
"""
import time

import pytest

import ray_tpu
from ray_tpu._private import protocol, specframe
from ray_tpu._private import worker as worker_mod


pytestmark = pytest.mark.usefixtures("faults_cleared")


# ------------------------------------------------------ window mechanics
def test_reply_window_self_clocks_on_acks():
    """First result of an idle window flushes immediately; results
    completing before the ack ride the NEXT frame; an ack over an empty
    buffer returns the window to idle (= the create_actor_batch
    discipline, mirrored onto replies)."""
    sent = []
    w = specframe.ReplyWindow(sent.append, max_items=100, horizon_s=60.0)
    w.add({"i": 1}, [b"x"])
    assert [len(b) for b in sent] == [1]  # opener: a frame of one, NOW
    for i in range(2, 12):
        w.add({"i": i}, [b"x"])
    assert len(sent) == 1  # all ten ride the in-flight ack
    w.on_ack()
    assert len(sent) == 2 and len(sent[1]) == 10  # ONE frame for all ten
    assert [s["i"] for s, _f, _t in sent[1]] == list(range(2, 12))
    w.on_ack()  # nothing buffered: back to idle
    w.add({"i": 99}, [b"y"])
    assert len(sent) == 3 and len(sent[2]) == 1  # idle again => immediate


def test_reply_window_caps_and_horizon():
    """Item/byte caps force a mid-ack flush (bounded memory, frames stay
    under the ring limit); a lapsed ack horizon re-arms the window so a
    lost ack can never buffer results forever."""
    sent = []
    w = specframe.ReplyWindow(sent.append, max_items=4, horizon_s=60.0)
    w.add({"i": 0}, [b"x"])
    for i in range(1, 5):
        w.add({"i": i}, [b"x"])
    assert len(sent) == 2 and len(sent[1]) == 4  # item cap flushed
    sent.clear()
    w = specframe.ReplyWindow(sent.append, max_bytes=100, horizon_s=60.0)
    w.add({"i": 0}, [b"x"])
    w.add({"i": 1}, [b"y" * 200])  # byte cap exceeded while in flight
    assert len(sent) == 2
    sent.clear()
    w = specframe.ReplyWindow(sent.append, horizon_s=0.0)
    for i in range(3):
        w.add({"i": i}, [b"x"])
    assert len(sent) == 3  # horizon 0 = every add re-arms (degenerate)


def test_reply_window_timer_mode_gap_paces_and_tail_flushes():
    """Ring-mode window (gap + defer): a quiet window flushes the first
    result immediately; results inside the gap buffer and go out via the
    deferred tail flush — which re-arms itself while traffic flows and
    quiesces on an empty tick. No acks are involved (on_ack is a no-op:
    ring flushes carry no ``wa``, so there is no mrack traffic to
    contend with the pusher on the ring send lock)."""
    sent = []
    timers = []
    w = specframe.ReplyWindow(
        sent.append, max_items=100, gap_s=60.0,
        defer=lambda delay, cb: timers.append((delay, cb)),
    )
    w.add({"i": 1}, [b"x"])
    assert [len(b) for b in sent] == [1]  # quiet window: immediate
    for i in range(2, 6):
        w.add({"i": i}, [b"x"])
    assert len(sent) == 1  # inside the gap: buffered
    assert len(timers) == 1  # ONE armed tail timer for the whole buffer
    w.on_ack()  # acks are not this mode's clock
    assert len(sent) == 1
    timers.pop()[1]()  # gap elapses
    assert len(sent) == 2 and len(sent[1]) == 4
    assert [s["i"] for s, _f, _t in sent[1]] == [2, 3, 4, 5]
    assert len(timers) == 1  # flushed => re-armed (traffic may continue)
    timers.pop()[1]()  # empty tick: quiesce, no flush, no re-arm
    assert len(sent) == 2 and not timers
    # A batch landing inside the gap of the LAST flush still buffers —
    # quiescing stops the ticker, not the gap clock — and arms a fresh
    # tail timer that delivers it as one frame.
    w.add_many([({"i": 9}, [b"y"], None), ({"i": 10}, [b"y"], None)])
    assert len(sent) == 2 and len(timers) == 1
    timers.pop()[1]()
    assert len(sent) == 3 and len(sent[2]) == 2


def test_reply_window_add_many_matches_add_semantics():
    """The drain loop's batch hand-off obeys the same caps and clock as
    per-result adds (ack mode here): a batch landing on a quiet window
    emits once; batches riding an in-flight frame buffer until the ack,
    with the item cap forcing a mid-ack flush."""
    sent = []
    w = specframe.ReplyWindow(sent.append, max_items=5, horizon_s=60.0)
    w.add_many([({"i": 0}, [b"x"], None)])
    assert len(sent) == 1
    w.add_many([({"i": i}, [b"x"], None) for i in (1, 2)])
    assert len(sent) == 1  # rides the in-flight ack
    w.add_many([({"i": i}, [b"x"], None) for i in (3, 4, 5)])
    assert len(sent) == 2 and len(sent[1]) == 5  # item cap crossed
    w.on_ack()
    assert len(sent) == 2  # nothing left behind the cap flush


def test_shutdown_flushes_open_reply_windows(rt_start):
    """Results buffered behind a lost ack must not die with the process:
    the shutdown step drains every open window (regression for the
    graceful-drain / short-lived-executor path, beside the PR 7
    tail-event flush)."""
    sent = []
    win = specframe.ReplyWindow(sent.append, horizon_s=60.0)
    win.add({"i": 1}, [b"a"])
    win.add({"i": 2}, [b"b"])
    win.add({"i": 3}, [b"c"])
    assert len(sent) == 1  # two results parked behind the unacked opener

    class _Conn:
        _closed = False

    conn = _Conn()
    conn._rt_reply_window = win
    w = worker_mod.global_worker
    w._reply_windows.append(conn)
    try:
        w._flush_reply_windows()
    finally:
        w._reply_windows.remove(conn)
    assert len(sent) == 2
    assert [s["i"] for s, _f, _t in sent[1]] == [2, 3]


# ------------------------------------------------- arg interning mechanics
def test_arg_intern_wire_roundtrip_is_byte_exact(rt_start):
    """Wire-build + executing-side expansion round-trip on real worker
    state: first push ships blobs and asks the peer to intern (``aib``),
    the second carries digests only (``ai``) and reconstructs the EXACT
    bytes; a purged digest raises the typed miss, never garbage."""
    w = worker_mod.global_worker
    peer = ("test-peer", 1)
    header = {"tid": "ab" * 12, "fkey": "f" * 40, "i": 7, "nret": 1}
    frames = [b"meta", b"y" * 500, b"z" * 300]  # meta below min: inline
    h1, w1 = w._arg_intern_wire(peer, header, frames)
    assert "aib" in h1 and "ai" not in h1
    assert w1 == frames  # first push: full bytes still on the wire
    eh1, ef1 = w._expand_task_header(h1, w1)
    assert ef1 == frames and "aib" not in eh1

    h2, w2 = w._arg_intern_wire(peer, header, frames)
    assert "ai" in h2 and "aib" not in h2
    assert w2 == [b"meta"]  # repeated frames stayed home
    eh2, ef2 = w._expand_task_header(h2, w2)
    assert ef2 == frames  # byte-exact reconstruction from the LRU

    # Evict and retry the digest-only wire: typed miss, pusher re-sends.
    w._arg_intern.purge([d for _p, d in h2["ai"]])
    with pytest.raises(protocol.RpcError) as ei:
        w._expand_task_header(h2, w2)
    assert ei.value.code == "arg_intern_miss"
    w._arg_ledger.forget_peer(peer)


def test_gates_off_keep_wire_and_paths_byte_identical(monkeypatch):
    """RT_REPLY_BATCHING=0 / RT_ARG_INTERNING=0 restore the pre-round-15
    behavior exactly: _task_wire is the identity composition (same
    objects, no ai/aib/corr), no window ever opens, no reply frame ever
    coalesces."""
    monkeypatch.setenv("RT_REPLY_BATCHING", "0")
    monkeypatch.setenv("RT_ARG_INTERNING", "0")
    ray_tpu.init(num_cpus=2)
    try:
        w = worker_mod.global_worker
        assert not w._reply_batching and not w._arg_interning
        header = {"tid": "cd" * 12, "fkey": "g" * 40, "nret": 1}
        frames = [b"meta", b"y" * 500]
        h2, f2 = w._arg_intern_wire(("p", 1), header, frames)
        assert h2 is header and f2 is frames  # identity, not a copy

        @ray_tpu.remote
        def f(cfg, i):
            return (cfg["v"], i)

        cfg = {"pad": "x" * 4096, "v": 5}
        n = 60
        assert ray_tpu.get([f.remote(cfg, i) for i in range(n)],
                           timeout=120) == [(5, i) for i in range(n)]
        assert w._stats["arg_frames_interned"] == 0
        assert w._stats["arg_blobs_pushed"] == 0

        @ray_tpu.remote
        def stats():
            return dict(worker_mod.global_worker._stats)

        s = ray_tpu.get(stats.remote(), timeout=60)
        assert s["reply_windows_flushed"] == 0
        assert s["reply_results_coalesced"] == 0
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------- RPC economics
@pytest.mark.parametrize("rt_start", [dict(num_cpus=2)], indirect=True)
def test_queued_burst_reply_frames_are_o_bursts(rt_start):
    """A queued single-peer noop burst settles in far fewer coalesced
    reply frames than tasks: the opener flushes immediately, everything
    completing behind it rides the in-flight ack. (The exact count is
    load-dependent; the invariant is frames << tasks, average batch >= 2
    even on a box where acks return instantly.)"""

    @ray_tpu.remote
    def stats():
        return dict(worker_mod.global_worker._stats)

    @ray_tpu.remote
    def noop(i):
        return i

    ray_tpu.get([noop.remote(i) for i in range(20)], timeout=120)  # warm
    before = ray_tpu.get(stats.remote(), timeout=60)
    n = 400
    assert ray_tpu.get([noop.remote(i) for i in range(n)],
                       timeout=120) == list(range(n))
    after = ray_tpu.get(stats.remote(), timeout=60)
    coalesced = (after["reply_results_coalesced"]
                 - before["reply_results_coalesced"])
    flushed = (after["reply_windows_flushed"]
               - before["reply_windows_flushed"])
    assert coalesced >= n  # every small result rode a window
    assert flushed <= coalesced // 2, (flushed, coalesced)


def test_arg_blob_ships_once_per_peer(rt_start):
    """The classic "same config dict to N tasks" shape: the serialized
    arg frame crosses the wire ONCE (aib), every later push carries the
    16-byte digest — O(unique args) arg bytes per peer — and the values
    the tasks observe round-trip exactly."""
    w = worker_mod.global_worker
    cfg = {"pad": "x" * 8192, "v": 11}

    @ray_tpu.remote
    def use(c, i):
        return (c, i)

    base_interned = w._stats["arg_frames_interned"]
    base_saved = w._stats["arg_intern_bytes_saved"]
    n = 50
    out = ray_tpu.get([use.remote(cfg, i) for i in range(n)], timeout=120)
    assert out == [(cfg, i) for i in range(n)]  # byte-exact round trip
    interned = w._stats["arg_frames_interned"] - base_interned
    saved = w._stats["arg_intern_bytes_saved"] - base_saved
    assert interned >= n - 2, interned  # the blob shipped at most twice
    assert saved >= (n - 2) * 8000, saved


def test_intern_eviction_miss_resends_byte_exact(monkeypatch):
    """A receiver LRU small enough to thrash forces real evictions: the
    digest-only push surfaces the typed miss, the pusher resets coverage
    and re-sends the blob, and every task still sees exact bytes."""
    monkeypatch.setenv("RT_ARG_INTERN_CACHE_BYTES", "20000")
    ray_tpu.init(num_cpus=2)
    try:
        w = worker_mod.global_worker

        @ray_tpu.remote
        def use(c):
            return c

        cfgs = [{"k": i, "pad": chr(ord("a") + i) * 9000} for i in range(3)]
        # Cover all three (third insert evicts the first), then re-use
        # the first: its digest-only push MUST miss and recover.
        for cfg in cfgs:
            assert ray_tpu.get(use.remote(cfg), timeout=120) == cfg
        for cfg in cfgs:
            assert ray_tpu.get(use.remote(cfg), timeout=120) == cfg
        assert w._stats["arg_intern_miss_retries"] >= 1
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------- drop / replay semantics
def test_dropped_window_frame_replays_without_reexecution(monkeypatch):
    """The first coalesced reply frame is dropped in transit AFTER the
    tasks ran: every rider's per-task deadline re-arms, the re-push hits
    the executor's corr-dedup cache and REPLAYS the recorded outcomes —
    results arrive correct, each task executed exactly once, and no
    future is ever settled twice (a double settle would raise in
    asyncio; a re-execution shows in the executor-side counter)."""
    monkeypatch.setenv("RT_RPC_DEADLINE_S", "1")
    ray_tpu.init(num_cpus=2)
    cluster = ray_tpu._internal_cluster()
    try:
        cluster.add_node(
            resources={"CPU": 2, "doom": 100},
            env={"RT_FAULT_SPEC": "worker.reply.window:drop:1.0:1:42"},
        )

        @ray_tpu.remote(num_cpus=0)
        def bump(i):
            st = worker_mod.global_worker._stats
            st["_test_execs"] = st.get("_test_execs", 0) + 1
            return i * 3

        n = 24
        refs = [bump.options(resources={"doom": 1}).remote(i)
                for i in range(n)]
        assert ray_tpu.get(refs, timeout=120) == [i * 3 for i in range(n)]

        @ray_tpu.remote(num_cpus=0)
        def probe():
            from ray_tpu._private import faultpoints as fpp

            return (dict(worker_mod.global_worker._stats), fpp.stats())

        s, fstats = ray_tpu.get(
            probe.options(resources={"doom": 1}).remote(), timeout=60
        )
        assert sum(x["injected"] for x in fstats) == 1, fstats  # it fired
        assert s.get("_test_execs") == n  # replay, never re-execution
    finally:
        ray_tpu.shutdown()


# ----------------------------------------------------------- TCP parity
def test_reply_batching_over_tcp(monkeypatch):
    """With the shm ring disabled the slow path serves every push over
    TCP — results must still coalesce (Connection.send_reply_batch, the
    batched-reply unpack, and the mrack ack all exercised) and the wire
    stays correct."""
    monkeypatch.setenv("RT_NATIVE_RING", "0")
    ray_tpu.init(num_cpus=2)
    try:

        @ray_tpu.remote
        def noop(i):
            return i

        n = 100
        assert ray_tpu.get([noop.remote(i) for i in range(n)],
                           timeout=120) == list(range(n))

        @ray_tpu.remote
        def stats():
            return dict(worker_mod.global_worker._stats)

        s = ray_tpu.get(stats.remote(), timeout=60)
        assert s["reply_windows_flushed"] > 0
        assert s["reply_results_coalesced"] >= n
        assert s["reply_windows_flushed"] < s["reply_results_coalesced"]
    finally:
        ray_tpu.shutdown()
