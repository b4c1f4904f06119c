"""Transit-plane pacing economics (round 16).

Pins the three self-clocking transit mechanisms the way
``test_reply_plane.py`` pins the reply plane:

- the per-slot adaptive push window (``specframe.PushWindow``) grows
  additively on clean drains, shrinks multiplicatively when settle
  latency inflates, and never leaves its floor/ceiling box — a
  saturated executor stops accumulating parked chunks, an idle one
  ramps immediately;
- the ring pump hands a WHOLE drain to the executor-side batch dispatch
  in one pass: executor-pool wakeups are O(drains), never O(messages);
- the driver's TCP recv loop settles every already-buffered reply frame
  in one wakeup (multi-frame settling), and the ``pump-queue`` phase
  the analyzer carves out of reply-ack keeps named + residual == wall;
- the ``push_window`` / ``pump_batch_drain`` / ``settle_batching``
  gates restore the fixed pre-round-16 fan-out and per-message loops
  byte-identically when off;
- the ``worker.push.window`` faultpoint degrades pacing, never
  correctness.
"""
import asyncio

import pytest

import ray_tpu
from ray_tpu._private import faultpoints as fp
from ray_tpu._private import protocol, specframe, taskpath
from ray_tpu._private import worker as worker_mod


pytestmark = pytest.mark.usefixtures("faults_cleared")


# ------------------------------------------------------ window mechanics
def test_push_window_grows_additively_on_clean_drains():
    """Settles at steady low latency grow the window one task per chunk
    up to the ceiling — an idle executor's fast acks ramp it straight
    from initial toward the pipe's real depth."""
    w = specframe.PushWindow(initial=8, floor=2, ceiling=16)
    assert w.window == 8
    for _ in range(40):
        n = w.grant(4)
        assert n > 0
        w.on_settled(n, 0.005)
    assert w.window == 16  # ceiling, never beyond
    assert w.peak == 16
    assert w.shrinks == 0


def test_push_window_shrinks_on_settle_latency_inflation():
    """An inflated settle (> latency_factor x the clean baseline) halves
    the window; sustained inflation walks it to the floor and no
    further. Recovery after the congestion clears regrows additively."""
    w = specframe.PushWindow(initial=16, floor=2, ceiling=32,
                             latency_factor=3.0)
    w.on_settled(w.grant(4), 0.010)  # baseline ~10ms
    w.on_settled(w.grant(4), 0.010)
    assert w.window == 17  # second clean settle grew it
    assert not w.on_settled(w.grant(4), 0.100)  # 10x: congestion
    assert w.window == 8  # multiplicative: 17 -> 8
    for _ in range(10):
        w.on_settled(w.grant(4), 0.100)
    assert w.window == 2  # floored, never below
    for _ in range(8):
        w.on_settled(w.grant(2), 0.010)
    assert w.window > 2  # clean settles regrow
    assert w.shrinks >= 3


def test_push_window_grant_release_accounting():
    """grant() never exceeds window - inflight; release()/on_settled()
    free capacity; reset() re-ramps the pacing state but keeps flight
    accounting (in-flight chunks still settle correctly)."""
    w = specframe.PushWindow(initial=8, floor=2, ceiling=16)
    assert w.grant(6) == 6
    assert w.grant(6) == 2  # clipped to remaining room
    assert w.grant(6) == 0  # full
    w.release(2)
    assert w.inflight == 6
    assert w.grant(6) == 2
    w.reset()
    assert w.window == 2  # cold re-ramp from the floor
    assert w.inflight == 8  # accounting survived the reset
    w.on_settled(8, 0.005)
    assert w.inflight == 0


def test_push_window_min_base_guards_noise():
    """Micro-latency jitter on a quiet box (base well under min_base_s)
    must not read as 3x inflation: 0.1ms -> 0.5ms is noise, not
    congestion."""
    w = specframe.PushWindow(initial=8, floor=2, ceiling=16,
                             latency_factor=3.0, min_base_s=0.002)
    w.on_settled(w.grant(4), 0.0001)
    assert w.on_settled(w.grant(4), 0.0005)  # clean despite 5x base
    assert w.shrinks == 0


# ------------------------------------------------- pump drain economics
@pytest.mark.parametrize("rt_start", [dict(num_cpus=2)], indirect=True)
def test_pump_wakeups_are_o_drains_not_o_tasks(rt_start):
    """A queued single-peer burst reaches the executor pool through
    O(drains) batch handoffs and executor wakeups — never one wakeup per
    task or per wire message. (Drain counts are load-dependent; the
    invariant is wakeups << tasks and one claim pass per drain.)"""

    @ray_tpu.remote
    def noop(i):
        return i

    @ray_tpu.remote
    def probe():
        w = worker_mod.global_worker
        return (
            {k: v for k, v in w._stats.items() if k.startswith("pump_")},
            w.transit_stats()["pump"],
        )

    ray_tpu.get([noop.remote(i) for i in range(20)], timeout=120)  # warm
    before, _ = ray_tpu.get(probe.remote(), timeout=60)
    n = 400
    assert ray_tpu.get([noop.remote(i) for i in range(n)],
                       timeout=120) == list(range(n))
    after, pump = ray_tpu.get(probe.remote(), timeout=60)
    calls = after["pump_batch_calls"] - before["pump_batch_calls"]
    items = after["pump_batch_items"] - before["pump_batch_items"]
    wakeups = after["pump_exec_wakeups"] - before["pump_exec_wakeups"]
    assert items >= n  # every task rode a batch handoff
    assert calls <= items // 4, (calls, items)  # one pass per DRAIN
    assert wakeups <= n // 4, (wakeups, n)  # pool wakeups O(drains)
    assert pump["drains"] <= pump["msgs"]  # drains coalesce messages


def test_push_window_paces_live_burst(rt_start):
    """On a real cluster the driver's slots carry live windows: a burst
    settles them (settled ~ tasks), the window stays inside its
    floor/ceiling box, and the rt_push_window gauge sees the peer."""
    from ray_tpu._private.config import rt_config

    @ray_tpu.remote
    def noop(i):
        return i

    n = 300
    assert ray_tpu.get([noop.remote(i) for i in range(n)],
                       timeout=120) == list(range(n))
    w = worker_mod.global_worker
    push = w.transit_stats()["push_window"]
    assert push, "no push-window stats recorded"
    floor = int(rt_config.push_window_floor)
    ceiling = int(rt_config.push_window_ceiling)
    total_settled = 0
    for peer, s in push.items():
        assert floor <= s["window"] <= ceiling, (peer, s)
        assert s["peak"] <= ceiling
        total_settled += s["settled"]
    assert total_settled >= n


# -------------------------------------------------- multi-frame settling
def test_multi_frame_settle_one_wakeup(monkeypatch):
    """N coalesced reply frames already buffered on the driver's stream
    settle in ONE recv-loop wakeup: the drain parses them straight from
    the reader buffer (no per-frame coroutine hop), every future
    resolves, and the settle stats pin the economics."""

    async def run():
        reader = asyncio.StreamReader()
        writer_sink = []

        class _W:  # minimal writer stand-in (never used by the drain)
            def write(self, d):
                writer_sink.append(d)

            def close(self):
                pass

            async def drain(self):
                pass

        conn = protocol.Connection(reader, _W(), name="test")
        conn._settle_batching = True
        conn.start()
        futs = {}
        for cid in range(1, 7):
            conn._next_id = cid
            fut = asyncio.get_running_loop().create_future()
            conn._pending[cid] = fut
            futs[cid] = fut
        # Six single-reply frames land in the buffer as one TCP segment.
        blob = b"".join(
            protocol.encode_message({"i": cid, "r": 1, "rets": [cid]}, [])
            for cid in range(1, 7)
        )
        reader.feed_data(blob)
        await asyncio.wait_for(
            asyncio.gather(*futs.values()), timeout=5
        )
        for cid, fut in futs.items():
            h, frames = fut.result()
            assert h["rets"] == [cid]
        st = conn.settle_stats
        assert st["frames"] == 6
        assert st["wakeups"] == 1, st  # ONE loop wakeup settled all six
        assert st["drained"] == 5
        assert st["max_batch"] == 6
        await conn.close()

    asyncio.run(run())


def test_settle_batching_off_one_frame_per_wakeup():
    """Gate off: the recv loop settles exactly one frame per wakeup —
    the pre-round-16 loop, byte-identically (drained stays 0)."""

    async def run():
        reader = asyncio.StreamReader()

        class _W:
            def write(self, d):
                pass

            def close(self):
                pass

            async def drain(self):
                pass

        conn = protocol.Connection(reader, _W(), name="test")
        conn._settle_batching = False
        conn.start()
        futs = {}
        for cid in range(1, 5):
            fut = asyncio.get_running_loop().create_future()
            conn._pending[cid] = fut
            futs[cid] = fut
        reader.feed_data(b"".join(
            protocol.encode_message({"i": cid, "r": 1}, [])
            for cid in range(1, 5)
        ))
        await asyncio.wait_for(asyncio.gather(*futs.values()), timeout=5)
        st = conn.settle_stats
        assert st["frames"] == 4
        assert st["drained"] == 0, st
        await conn.close()

    asyncio.run(run())


def test_parse_buffered_partial_and_exact():
    """The buffer parser consumes exactly one complete message and
    reports None for any partial prefix — byte-boundary safety for the
    in-place drain."""
    msg = protocol.encode_message({"i": 9, "r": 1}, [b"abc", b"defg"])
    for cut in range(len(msg)):
        assert protocol._parse_buffered(bytearray(msg[:cut])) is None
    buf = bytearray(msg + b"tail")
    header, frames, consumed = protocol._parse_buffered(buf)
    assert header["i"] == 9 and frames == [b"abc", b"defg"]
    assert consumed == len(msg)


# --------------------------------------------------- pump-queue analysis
def test_pump_queue_phase_keeps_attribution_exhaustive():
    """The new pump-queue phase is carved OUT of reply-ack (their sum is
    the old reply-ack), pump-queue renders in PHASES, and
    named + residual == wall still holds exactly."""
    assert "pump-queue" in taskpath.PHASES
    tid = "ab" * 12
    t0 = 1000.0
    spans = [
        {"kind": "task", "cid": tid, "verb": "task.submit",
         "ts": t0, "dur": 0.001},
        {"kind": "task", "cid": tid, "verb": "task.queued",
         "ts": t0 + 0.001, "dur": 0.002, "outcome": "submit-queue"},
        {"kind": "task", "cid": tid, "verb": "task.serve",
         "ts": t0 + 0.004, "dur": 0.010},
        {"kind": "task", "cid": tid, "verb": "task.exec",
         "ts": t0 + 0.006, "dur": 0.004},
        {"kind": "task", "cid": tid, "verb": "task.pump_queue",
         "ts": t0 + 0.020, "dur": 0.015},
        {"kind": "task", "cid": tid, "verb": "task.push",
         "ts": t0 + 0.003, "dur": 0.040},
    ]
    for e in spans:
        e.setdefault("outcome", "ok")
    b = taskpath.task_breakdown(spans, tid)
    ph = b["phases"]
    assert ph["pump-queue"] == pytest.approx(0.015)
    # reply-ack = push - serve - reply-window - pump-queue
    assert ph["reply-ack"] == pytest.approx(0.040 - 0.010 - 0.015)
    named = sum(v for p, v in ph.items())
    assert named == pytest.approx(b["wall_s"])  # residual explicit
    # Rendering: the fixed-width table names the phase.
    assert "pump-queue" in taskpath.format_task_timeline(b)


# ------------------------------------------------------- gates-off parity
def test_gates_off_restore_fixed_fanout(monkeypatch):
    """RT_PUSH_WINDOW=0 / RT_PUMP_BATCH_DRAIN=0 / RT_SETTLE_BATCHING=0:
    no window objects ever attach to slots, the TCP recv loop never
    drains past one frame, and a burst completes identically."""
    monkeypatch.setenv("RT_PUSH_WINDOW", "0")
    monkeypatch.setenv("RT_PUMP_BATCH_DRAIN", "0")
    monkeypatch.setenv("RT_SETTLE_BATCHING", "0")
    ray_tpu.init(num_cpus=2)
    try:
        w = worker_mod.global_worker
        assert not w._push_window

        @ray_tpu.remote
        def noop(i):
            return i

        n = 150
        assert ray_tpu.get([noop.remote(i) for i in range(n)],
                           timeout=120) == list(range(n))
        assert w.transit_stats()["push_window"] == {}
        assert all(
            s.pwin is None
            for ls in w.leases.values() for s in ls.slots
        )
        for c in list(w.peers.values()) + [w.gcs]:
            st = getattr(c, "settle_stats", None)
            assert st is None or st["drained"] == 0, (c.name, st)
        assert w._stats["push_window_waits"] == 0
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------ faultpoint chaos
def test_push_window_faultpoint_degrades_not_breaks(rt_start):
    """worker.push.window error = that chunk pushes with the fixed
    fan-out (pacing is an optimization); drop = the slot's window
    cold-resets to its floor and re-ramps. Either way every task
    completes and no future is lost."""
    w = worker_mod.global_worker

    @ray_tpu.remote
    def noop(i):
        return i

    ray_tpu.get([noop.remote(i) for i in range(10)], timeout=120)  # warm
    fp.configure("worker.push.window:error:0.5:0:7")
    n = 120
    assert ray_tpu.get([noop.remote(i) for i in range(n)],
                       timeout=120) == list(range(n))
    st = fp.stats()
    assert sum(s["injected"] for s in st) > 0, st
    fp.configure("worker.push.window:drop:1.0:2:9")
    assert ray_tpu.get([noop.remote(i) for i in range(n)],
                       timeout=120) == list(range(n))
    assert sum(s["injected"] for s in fp.stats()) > 0
    # Windows (where still attached) came back inside the box.
    from ray_tpu._private.config import rt_config

    for ls in w.leases.values():
        for s in ls.slots:
            if s.pwin is not None:
                assert s.pwin.window >= int(rt_config.push_window_floor)
    fp.clear()
