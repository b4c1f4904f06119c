"""Serve-plane request-lifecycle fault tolerance (reference test model:
``python/ray/serve/tests/test_replica_*``, ``test_proxy*``).

The contract under test (ISSUE 6 tentpole):

- a request that fails BEFORE reaching user code fails over transparently
  to another replica (bounded, jittered);
- a replica dying mid-execution / mid-stream surfaces a TYPED retryable
  error (``serve.ReplicaDiedError``) — never a hang, never a bare
  transport exception;
- graceful drain: scale-down lets in-flight requests finish (zero
  dropped);
- proxy admission control: global in-flight cap -> 503 + Retry-After,
  request deadline -> 504 + Retry-After, app errors stay 500;
- router accounting: no stranded in-flight counts after replicas die or
  the set refreshes (power-of-2 routing stays honest).
"""
import json
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import faultpoints as fp
from ray_tpu._private.test_utils import wait_for_condition
from tests.conftest import _leases_settled, _no_leaked_objects


pytestmark = pytest.mark.usefixtures("faults_cleared")


def _replica_handles(name):
    from ray_tpu.serve.controller import CONTROLLER_NAME

    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return ray_tpu.get(controller.get_handles.remote(name), timeout=30)


def _zero_stranded(router):
    snap = router.inflight_snapshot()
    return sum(snap.values()) == 0, snap


# ------------------------------------------------- pre-dispatch failover
@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_failover_before_user_code_is_transparent(srv):
    """An injected transport failure at handle->replica dispatch (request
    never reached user code) retries on another replica invisibly."""
    @serve.deployment(num_replicas=2)
    def echo(x):
        return x * 2

    handle = serve.run(echo.bind(), name="fo_app")
    assert handle.remote(1).result(timeout=30) == 2  # replicas warm
    fp.configure("serve.replica.call:error:1.0:2:21")
    assert handle.remote(21).result(timeout=30) == 42
    assert fp.stats()[0]["injected"] == 2
    fp.clear()
    ok, snap = _zero_stranded(handle._router)
    assert ok, f"stranded in-flight counts after failover: {snap}"


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_failover_budget_exhausted_raises_typed_retryable(srv):
    @serve.deployment(num_replicas=2)
    def echo(x):
        return x

    handle = serve.run(echo.bind(), name="fo_exhaust")
    assert handle.remote(0).result(timeout=30) == 0
    fp.configure("serve.replica.call:error:1.0:0:22")  # every dispatch
    with pytest.raises(serve.ReplicaDiedError) as ei:
        handle.remote(1)
    assert isinstance(ei.value, serve.ServeRetryableError)
    assert ei.value.retryable
    fp.clear()
    ok, snap = _zero_stranded(handle._router)
    assert ok, f"stranded in-flight counts after exhausted failover: {snap}"


# --------------------------------------------- mid-execution replica death
@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_mid_execution_death_surfaces_typed_error_and_evicts(
        srv, monkeypatch):
    """A replica killed while executing must fail the request with the
    typed retryable class (not a raw ActorDiedError), evict the dead
    replica, and strand no router counts."""
    # Short reply deadline: the caller notices the kill at the next
    # re-arm probe instead of 30s later.
    monkeypatch.setenv("RT_RPC_DEADLINE_S", "2")

    @serve.deployment(num_replicas=1)
    class Slow:
        def __call__(self, x):
            time.sleep(float(x))
            return x

    handle = serve.run(Slow.bind(), name="mid_death")
    victims = _replica_handles("Slow")
    assert len(victims) == 1
    resp = handle.remote(30)  # parks inside user code
    time.sleep(0.5)
    ray_tpu.kill(victims[0])
    with pytest.raises(serve.ReplicaDiedError) as ei:
        resp.result(timeout=60)
    assert ei.value.retryable
    assert ei.value.__cause__ is not None  # original infra error chained
    ok, snap = _zero_stranded(handle._router)
    assert ok, f"replica death stranded router counts: {snap}"
    # the reconcile loop replaces the dead replica; new requests succeed
    deadline = time.time() + 45
    while time.time() < deadline:
        try:
            assert handle.remote(0).result(timeout=30) == 0
            break
        except serve.ServeRetryableError:
            time.sleep(0.2)
    else:
        pytest.fail("deployment never recovered after replica death")


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_stream_replica_crash_mid_stream_terminal_typed_error(
        srv, monkeypatch):
    """A replica dying with an OPEN stream: the consumer sees a typed
    terminal error promptly (no hang until the 300s chunk deadline), and
    no router count is stranded."""
    monkeypatch.setenv("RT_RPC_DEADLINE_S", "2")

    @serve.deployment(num_replicas=1)
    class Gen:
        def __call__(self, req):
            for i in range(1000):
                time.sleep(0.01)
                yield f"c{i}"

    handle = serve.run(Gen.bind(), name="stream_crash")
    victims = _replica_handles("Gen")
    it = iter(handle.options(stream=True).remote({}))
    got = [next(it) for _ in range(20)]  # at least one pull round-trip
    assert got[0] == "c0"
    ray_tpu.kill(victims[0])
    t0 = time.monotonic()
    with pytest.raises(serve.ReplicaDiedError):
        for _ in range(2000):
            next(it)
    assert time.monotonic() - t0 < 90, "mid-stream death hung the consumer"
    ok, snap = _zero_stranded(handle._router)
    assert ok, f"mid-stream death stranded router counts: {snap}"


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_injected_stream_fault_is_typed(srv):
    """serve.replica.stream faultpoint: an injected mid-stream transport
    error surfaces as the typed retryable class."""
    @serve.deployment(num_replicas=1)
    class Gen:
        def __call__(self, req):
            for i in range(64):
                yield i

    handle = serve.run(Gen.bind(), name="stream_fault")
    it = iter(handle.options(stream=True).remote({}))
    assert next(it) == 0
    fp.configure("serve.replica.stream:error:1.0:0:23")
    # buffered chunks drain first; the next PULL hits the fault
    with pytest.raises(serve.ReplicaDiedError):
        while True:
            next(it)
    fp.clear()


# ------------------------------------------------------- router accounting
@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_inflight_counts_survive_refresh_and_reach_zero(srv):
    """Regression: router keys must be stable replica identities, not
    id(handle) — a refresh used to zero every count (handles are new
    objects per fetch), blinding power-of-2 routing; a dead replica used
    to strand its counts forever."""
    @serve.deployment(num_replicas=2, max_ongoing_requests=4)
    class Slow:
        def __call__(self, x):
            time.sleep(1.5)
            return x

    handle = serve.run(Slow.bind(), name="acct")
    resps = [handle.remote(i) for i in range(4)]
    router = handle._router
    assert sum(router.inflight_snapshot().values()) == 4
    router._refresh(force=True)
    assert sum(router.inflight_snapshot().values()) == 4, (
        "refresh wiped live in-flight counts (unstable router keys)"
    )
    assert [r.result(timeout=30) for r in resps] == list(range(4))
    ok, snap = _zero_stranded(router)
    assert ok, f"counts failed to settle: {snap}"


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_abandoned_response_settles_router_slot(srv):
    """A fire-and-forget handle call (response dropped without result())
    must not strand its in-flight slot once the response is GC'd."""
    @serve.deployment(num_replicas=1)
    def f(x):
        return x

    handle = serve.run(f.bind(), name="abandon")
    resp = handle.remote(1)
    router = handle._router
    assert sum(router.inflight_snapshot().values()) == 1
    del resp
    import gc

    gc.collect()
    ok, snap = _zero_stranded(router)
    assert ok, f"abandoned response stranded a slot: {snap}"


# ----------------------------------------------------------- graceful drain
@pytest.mark.parametrize(
    "rt_start", [{"num_cpus": 8}], indirect=True)
def test_graceful_drain_zero_dropped_on_scale_down(srv):
    """Scale 3 -> 1 with a burst in flight: every request completes
    (drained replicas finish their work before stopping), and the
    deployment converges to the new target with nothing draining."""
    @serve.deployment(num_replicas=3, max_ongoing_requests=4)
    class Work:
        def __call__(self, x):
            time.sleep(0.3)
            return x

    handle = serve.run(Work.bind(), name="drain_app")
    results = {}
    errors = []

    def one(i):
        try:
            results[i] = handle.remote(i).result(timeout=60)
        except Exception as e:  # noqa: BLE001 - recorded for the assert
            errors.append((i, e))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(24)]
    for t in threads[:12]:
        t.start()
    time.sleep(0.15)  # burst mid-flight on all 3 replicas
    serve.run(Work.options(num_replicas=1).bind(), name="drain_app")
    for t in threads[12:]:
        t.start()
    for t in threads:
        t.join(timeout=90)
    assert not errors, f"scale-down dropped requests: {errors[:3]}"
    assert results == {i: i for i in range(24)}

    def converged():
        st = serve.status()["Work"]
        return st["running"] == 1 and st["draining"] == 0

    wait_for_condition(converged, timeout=60,
                       message=f"drain never converged: {serve.status()}")
    ok, snap = _zero_stranded(handle._router)
    assert ok, f"drain stranded router counts: {snap}"


@pytest.mark.parametrize(
    "rt_start",
    [{"num_cpus": 8, "_system_config": {"serve_drain_deadline_s": 1.0}}],
    indirect=True)
def test_drain_deadline_cuts_wedged_replica(srv):
    """A replica that can't finish by the drain deadline is cut: teardown
    never waits forever on a wedged request."""
    @serve.deployment(num_replicas=1)
    class Stuck:
        def __call__(self, x):
            time.sleep(120)
            return x

    handle = serve.run(Stuck.bind(), name="stuck_app")
    resp = handle.remote(1)  # occupies the replica forever
    time.sleep(0.3)
    serve.delete("stuck_app")
    wait_for_condition(
        lambda: "Stuck" not in serve.status(), timeout=30,
        message=f"drain deadline did not cut the replica: {serve.status()}",
    )
    with pytest.raises((serve.ServeRetryableError, ray_tpu.exceptions.RayTpuError)):
        resp.result(timeout=30)


# -------------------------------------------------- proxy admission control
@pytest.mark.parametrize(
    "rt_start",
    [{"num_cpus": 8, "_system_config": {"serve_max_inflight": 1}}],
    indirect=True)
def test_proxy_inflight_cap_sheds_with_503_retry_after(srv):
    import urllib.error
    import urllib.request

    @serve.deployment(num_replicas=1, max_ongoing_requests=4)
    class Slow:
        def __call__(self, req):
            time.sleep(2.0)
            return {"ok": True}

    serve.run(Slow.bind(), name="cap_app", route_prefix="/cap")
    port = serve.start_http_proxy(port=0)
    codes = []

    def hit():
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/cap", timeout=30
            ) as r:
                codes.append((r.status, dict(r.headers)))
        except urllib.error.HTTPError as e:
            codes.append((e.code, dict(e.headers)))

    threads = [threading.Thread(target=hit) for _ in range(3)]
    for t in threads:
        t.start()
        time.sleep(0.15)  # first request is parked in user code
    for t in threads:
        t.join(timeout=60)
    by_code = {}
    for code, headers in codes:
        by_code.setdefault(code, []).append(headers)
    assert 200 in by_code, f"no request succeeded: {by_code}"
    assert 503 in by_code, f"cap=1 never shed load: {by_code}"
    assert all("Retry-After" in h for h in by_code[503]), (
        f"shed without Retry-After: {by_code[503]}"
    )


@pytest.mark.parametrize(
    "rt_start",
    [{"num_cpus": 8, "_system_config": {"serve_request_timeout_s": 0.5}}],
    indirect=True)
def test_proxy_deadline_maps_to_504_and_app_error_to_500(srv):
    import urllib.error
    import urllib.request

    @serve.deployment(num_replicas=1)
    class Api:
        def __call__(self, req):
            if req["query"].get("boom"):
                raise ValueError("app exploded")
            time.sleep(3)
            return {"ok": True}

    serve.run(Api.bind(), name="dl_app", route_prefix="/dl")
    port = serve.start_http_proxy(port=0)
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"http://127.0.0.1:{port}/dl", timeout=30)
    assert ei.value.code == 504, "deadline must be 504, not 500"
    assert ei.value.headers.get("Retry-After"), "504 without Retry-After"
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/dl?boom=1", timeout=30
        )
    assert ei.value.code == 500, "application errors stay 500"


# --------------------------------------------- SSE client-disconnect cleanup
@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_sse_client_disconnect_cancels_replica_generator(srv):
    """A client dropping an open SSE stream must release the replica-side
    generator and its slot promptly (cancel_stream), not leak it until
    the 10-minute idle sweep."""
    import http.client

    @serve.deployment(num_replicas=1)
    class Stream:
        def __call__(self, req):
            for i in range(2000):
                time.sleep(0.02)
                yield f"data: {i}\n\n"

    serve.run(Stream.bind(), name="sse_app", route_prefix="/sse")
    port = serve.start_http_proxy(port=0)
    replica = _replica_handles("Stream")[0]
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/sse", body=json.dumps({"stream": True}))
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.read(16)  # stream is live on the replica
    assert ray_tpu.get(replica.stats.remote(), timeout=10)["streams"] == 1
    # client vanishes mid-stream: SHUT_RDWR forces the FIN out even while
    # the response file object still references the socket, so the
    # proxy's next writes get RST instead of landing in a zombie buffer
    import socket as socketmod

    conn.sock.shutdown(socketmod.SHUT_RDWR)
    conn.sock.close()
    wait_for_condition(
        lambda: ray_tpu.get(
            replica.stats.remote(), timeout=10)["streams"] == 0,
        timeout=30,
        message="client disconnect leaked the replica-side stream slot",
    )


@pytest.mark.parametrize(
    "rt_start",
    [{"num_cpus": 8, "_system_config": {"rpc_deadline_s": 2.0}}],
    indirect=True)
def test_sse_mid_stream_replica_crash_emits_terminal_error_event(srv):
    """HTTP SSE + replica crash mid-stream: the client receives a typed
    terminal ``event: error`` frame marked retryable — not a silent
    truncation, not a hang. (_system_config shortens the PROXY process's
    reply deadline so it notices the kill promptly.)"""
    import http.client

    @serve.deployment(num_replicas=1)
    class Stream:
        def __call__(self, req):
            for i in range(2000):
                time.sleep(0.02)
                yield f"data: {i}\n\n"

    serve.run(Stream.bind(), name="sse_crash", route_prefix="/ssec")
    port = serve.start_http_proxy(port=0)
    replica = _replica_handles("Stream")[0]
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/ssec", body=json.dumps({"stream": True}))
    resp = conn.getresponse()
    assert resp.read(16)
    ray_tpu.kill(replica)
    rest = resp.read()  # proxy must terminate the stream promptly
    conn.close()
    assert b"event: error" in rest, (
        f"no terminal error event after replica crash: ...{rest[-200:]!r}"
    )
    frame = json.loads(
        rest.split(b"event: error\ndata: ", 1)[1].split(b"\n", 1)[0]
    )
    assert frame["retryable"] is True
    assert frame["error"] == "ReplicaDiedError"


# ------------------------------------------------------------ chaos matrix
@pytest.mark.slow
def test_serve_chaos_matrix_mixed_faults_and_crash(monkeypatch,
                                                   chaos_flight_trace):
    """The serve request lifecycle under sustained 10% faults at the new
    serve.* points PLUS a replica crash mid-stream: every request ends in
    success or a typed retryable error (no hangs, no raw transport
    errors), zero leaked leases, zero stranded router counts. A failure
    dumps the joined flight + task-track trace (chaos_flight_trace)."""
    monkeypatch.setenv("RT_RPC_DEADLINE_S", "2")
    ray_tpu.init(num_cpus=4)
    try:
        @serve.deployment(num_replicas=3, max_ongoing_requests=8)
        class App:
            def __call__(self, x):
                time.sleep(0.05)
                return x * 2

            def gen(self, n):
                for i in range(int(n)):
                    time.sleep(0.02)
                    yield i

        handle = serve.run(App.bind(), name="chaos_app")
        assert handle.remote(1).result(timeout=60) == 2
        fp.configure(
            "serve.replica.call:error:0.1:0:201,"
            "serve.replica.stream:error:0.1:0:202"
        )
        outcomes = []

        def unary(i):
            try:
                outcomes.append(("ok", handle.remote(i).result(timeout=60)))
            except serve.ServeRetryableError as e:
                outcomes.append(("retryable", e))
            except Exception as e:  # noqa: BLE001 - the assert below flags it
                outcomes.append(("BAD", e))

        def stream(i):
            try:
                got = list(handle.options(stream=True).gen.remote(40))
                outcomes.append(("ok", len(got)))
            except serve.ServeRetryableError as e:
                outcomes.append(("retryable", e))
            except Exception as e:  # noqa: BLE001
                outcomes.append(("BAD", e))

        threads = (
            [threading.Thread(target=unary, args=(i,)) for i in range(30)]
            + [threading.Thread(target=stream, args=(i,)) for i in range(6)]
        )
        for t in threads:
            t.start()
        time.sleep(0.4)  # streams + unary in flight everywhere
        ray_tpu.kill(_replica_handles("App")[0])  # crash mid-stream
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), (
            "a request hung under chaos"
        )
        bad = [o for o in outcomes if o[0] == "BAD"]
        assert not bad, (
            f"untyped failures under chaos: "
            f"{[(type(e).__name__, str(e)[:120]) for _, e in bad[:4]]}"
        )
        assert sum(s["calls"] for s in fp.stats()) > 0
        fp.clear()
        ok, snap = _zero_stranded(handle._router)
        assert ok, f"chaos stranded router counts: {snap}"
        serve.shutdown()  # releases replica leases
        wait_for_condition(_leases_settled, timeout=30,
                           message="serve chaos leaked leases")
        wait_for_condition(_no_leaked_objects, timeout=20,
                           message="serve chaos leaked objects")
    finally:
        fp.clear()
        ray_tpu.shutdown()


def test_serve_chaos_smoke(srv):
    """Fast tier-1 slice: one injected dispatch fault (transparent
    failover) + one injected stream fault (typed terminal error) in a
    single app."""
    @serve.deployment(num_replicas=2)
    class App:
        def __call__(self, x):
            return x + 1

        def gen(self, n):
            for i in range(int(n)):
                yield i

    handle = serve.run(App.bind(), name="chaos_smoke")
    assert handle.remote(1).result(timeout=30) == 2
    fp.configure("serve.replica.call:error:1.0:1:31")
    assert handle.remote(2).result(timeout=30) == 3  # failed over
    assert fp.stats()[0]["injected"] == 1
    fp.clear()
    it = iter(handle.options(stream=True).gen.remote(64))
    assert next(it) == 0
    fp.configure("serve.replica.stream:error:1.0:0:32")
    with pytest.raises(serve.ReplicaDiedError):
        while True:
            next(it)
    fp.clear()
    ok, snap = _zero_stranded(handle._router)
    assert ok, f"smoke stranded router counts: {snap}"
