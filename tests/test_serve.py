"""Serve layer tests (reference test model: ``python/ray/serve/tests``)."""
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_deploy_and_call(srv):
    @serve.deployment(num_replicas=2)
    class Echo:
        def __call__(self, x):
            return {"echo": x}

        def shout(self, x):
            return str(x).upper()

    handle = serve.run(Echo.bind(), name="echo_app")
    assert handle.remote(41).result(timeout=30) == {"echo": 41}
    assert handle.shout.remote("hi").result(timeout=30) == "HI"
    st = serve.status()
    assert st["Echo"]["running"] == 2


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_function_deployment_and_requests_spread(srv):
    import os

    @serve.deployment(num_replicas=2)
    def pid_of(x):
        import threading

        return f"{os.getpid()}:{id(threading.current_thread())}"

    handle = serve.run(pid_of.bind(), name="fn_app")
    outs = {handle.remote(i).result(timeout=30) for i in range(8)}
    assert len(outs) >= 1  # routed successfully (spread depends on timing)


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_composition_handles(srv):
    @serve.deployment
    class Adder:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Chain:
        def __init__(self, adder):
            self.adder = adder

        def __call__(self, x):
            return self.adder.remote(x).result(timeout=30) * 10

    handle = serve.run(Chain.bind(Adder.bind()), name="chain")
    assert handle.remote(4).result(timeout=30) == 50


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_batching(srv):
    @serve.deployment
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        async def handle_batch(self, items):
            self.batch_sizes.append(len(items))
            return [i * 2 for i in items]

        async def __call__(self, x):
            return await self.handle_batch(x)

        def sizes(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind(), name="batched")
    resps = [handle.remote(i) for i in range(8)]
    assert [r.result(timeout=30) for r in resps] == [i * 2 for i in range(8)]
    sizes = handle.sizes.remote().result(timeout=30)
    assert max(sizes) > 1, f"no dynamic batching happened: {sizes}"


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_autoscaling_scales_up(srv):
    @serve.deployment(
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=1, max_replicas=3, target_ongoing_requests=1.0,
            upscale_delay_s=0.1,
        ),
        num_replicas=1,
    )
    class Slow:
        async def __call__(self, x):
            import asyncio

            await asyncio.sleep(0.5)
            return x

    handle = serve.run(Slow.bind(), name="slow")
    resps = [handle.remote(i) for i in range(8)]  # queue depth >> target
    deadline = time.time() + 20
    scaled = False
    while time.time() < deadline:
        if serve.status()["Slow"]["running"] > 1:
            scaled = True
            break
        time.sleep(0.2)
    for r in resps:
        r.result(timeout=60)
    assert scaled, f"autoscaler never scaled up: {serve.status()}"


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_replica_death_recovers(srv):
    @serve.deployment(num_replicas=2)
    class Fragile:
        def __call__(self, x):
            return x

        def die(self):
            import os

            os._exit(1)  # kills the hosting worker process

    handle = serve.run(Fragile.bind(), name="fragile")
    assert handle.remote(1).result(timeout=30) == 1
    st = serve.status()
    assert st["Fragile"]["running"] == 2
    # controller reconcile loop should restore the target count
    deadline = time.time() + 30
    while time.time() < deadline:
        if serve.status()["Fragile"]["running"] >= 2:
            break
        time.sleep(0.2)
    assert serve.status()["Fragile"]["running"] >= 1


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_http_proxy(srv):
    import json
    import urllib.request

    @serve.deployment
    class Api:
        def __call__(self, request):
            q = request["query"]
            return {"path": request["path"], "x": int(q.get("x", 0)) * 2}

    serve.run(Api.bind(), name="api", route_prefix="/api")
    port = serve.start_http_proxy(port=0)
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/api/predict?x=21", timeout=30
    ) as resp:
        out = json.loads(resp.read())
    assert out == {"path": "/api/predict", "x": 42}
    # unknown route → 404
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/nope", timeout=30
        )
    assert ei.value.code == 404


def test_gang_scheduled_deployment(srv):
    """gang_size>1: one replica = a placement-group gang of actors; rank 0
    serves, every member gets a GangContext (reference: serve/gang.py)."""
    import ray_tpu
    from ray_tpu import serve

    @serve.deployment(num_replicas=1, gang_size=2,
                      ray_actor_options={"num_cpus": 1})
    class GangModel:
        def __init__(self):
            from ray_tpu.serve import get_gang_context

            self.ctx = get_gang_context()

        def __call__(self, x):
            return {
                "rank": self.ctx.rank,
                "world_size": self.ctx.world_size,
                "value": x * 2,
            }

    h = serve.run(GangModel.bind(), name="gang_app")
    try:
        out = h.remote(21).result(timeout=60)
        assert out == {"rank": 0, "world_size": 2, "value": 42}
        # both gang members exist as replica actors under one pg
        from ray_tpu._private.worker import get_global_worker

        w = get_global_worker()
        pgs = w.run_sync(w.gcs.call("list_pgs", {}))[0]["pgs"]
        created = [p for p in pgs if p["state"] == "CREATED"]
        assert any(len(p["bundles"]) == 2 for p in created)
    finally:
        serve.shutdown()


def test_gang_member_death_recycles_whole_gang(srv):
    """Death of ANY gang member must tear down and replace the whole gang
    (scale-as-a-unit; reference: gang autoscaling semantics)."""
    import time

    import ray_tpu
    from ray_tpu import serve

    @serve.deployment(num_replicas=1, gang_size=2,
                      ray_actor_options={"num_cpus": 1})
    class G:
        def __call__(self, x):
            return x

    h = serve.run(G.bind(), name="gang_ft")
    try:
        assert h.remote(1).result(timeout=60) == 1
        from ray_tpu.serve.controller import CONTROLLER_NAME

        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        st = ray_tpu.get(controller.status.remote(), timeout=30)
        assert st["G"]["running"] == 1
        handles = ray_tpu.get(controller.get_handles.remote("G"), timeout=30)
        # kill the rank-1 member behind the controller's back: fetch the
        # full member list via replica state
        reps = ray_tpu.get(controller.get_replicas.remote("G"), timeout=30)
        assert len(reps) == 1
        # rank-0 handle is what get_handles returns; kill it to simulate
        # member death (any member death must recycle the gang)
        ray_tpu.kill(handles[0])
        deadline = time.time() + 60
        while time.time() < deadline:
            st = ray_tpu.get(controller.status.remote(), timeout=30)
            if st.get("G", {}).get("running", 0) >= 1:
                try:
                    if h.remote(2).result(timeout=10) == 2:
                        break
                except Exception:
                    pass
            time.sleep(0.3)
        assert h.remote(3).result(timeout=30) == 3
    finally:
        serve.shutdown()


def test_local_testing_mode_no_cluster():
    """serve.run(..., local_testing_mode=True) runs the graph in-process —
    no init(), no actors (reference: local_testing_mode.py)."""
    from ray_tpu import serve

    @serve.deployment(user_config={"suffix": "!"})
    class Shouter:
        def __init__(self, downstream=None):
            self.suffix = ""
            self.downstream = downstream

        def reconfigure(self, cfg):
            self.suffix = cfg["suffix"]

        def __call__(self, text):
            if self.downstream is not None:
                text = self.downstream.remote(text).result()
            return text.upper() + self.suffix

        def whisper(self, text):
            return text.lower()

    @serve.deployment(name="inner")
    class Inner:
        def __call__(self, text):
            return f"<{text}>"

    h = serve.run(Shouter.bind(Inner.bind()), local_testing_mode=True)
    assert h.remote("hey").result() == "<HEY>!"
    assert h.whisper.remote("LOUD").result() == "loud"


def test_grpc_proxy_unary(srv):
    """gRPC ingress shares the router with HTTP (reference: dual-protocol
    ProxyActor, serve/_private/proxy.py:11). Unary Predict + status codes."""
    import grpc
    import msgpack

    @serve.deployment
    class Api:
        def __call__(self, data):
            return {"doubled": data["x"] * 2}

        def extra(self, data):
            return {"method": "extra", "x": data["x"]}

    serve.run(Api.bind(), name="gapi", route_prefix="/gapi")
    port = serve.start_grpc_proxy(port=0)

    chan = grpc.insecure_channel(f"127.0.0.1:{port}")
    predict = chan.unary_unary(
        "/rayserve.v1.RayServe/Predict",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b,
    )
    out = msgpack.unpackb(predict(
        msgpack.packb({"route": "/gapi", "data": {"x": 21}},
                      use_bin_type=True), timeout=60,
    ), raw=False)
    assert out == {"doubled": 42}

    # named-method dispatch
    out = msgpack.unpackb(predict(
        msgpack.packb({"route": "/gapi", "method": "extra",
                       "data": {"x": 7}}, use_bin_type=True), timeout=60,
    ), raw=False)
    assert out == {"method": "extra", "x": 7}

    # unknown route -> NOT_FOUND
    with pytest.raises(grpc.RpcError) as ei:
        predict(msgpack.packb({"route": "/nope", "data": None},
                              use_bin_type=True), timeout=60)
    assert ei.value.code() == grpc.StatusCode.NOT_FOUND

    # user error -> INTERNAL
    with pytest.raises(grpc.RpcError) as ei:
        predict(msgpack.packb({"route": "/gapi", "data": {}},
                              use_bin_type=True), timeout=60)
    assert ei.value.code() == grpc.StatusCode.INTERNAL
    chan.close()


def test_grpc_proxy_streaming(srv):
    """Server-streaming over a generator deployment."""
    import grpc
    import msgpack

    @serve.deployment
    class Gen:
        def __call__(self, data):
            for i in range(int(data["n"])):
                yield {"i": i}

    serve.run(Gen.bind(), name="ggen", route_prefix="/ggen")
    port = serve.start_grpc_proxy(port=0)

    chan = grpc.insecure_channel(f"127.0.0.1:{port}")
    stream = chan.unary_stream(
        "/rayserve.v1.RayServe/PredictStream",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b,
    )
    chunks = [
        msgpack.unpackb(c, raw=False)
        for c in stream(
            msgpack.packb({"route": "/ggen", "data": {"n": 4}},
                          use_bin_type=True), timeout=60,
        )
    ]
    assert chunks == [{"i": 0}, {"i": 1}, {"i": 2}, {"i": 3}]
    chan.close()


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_max_queued_requests_sheds_load(srv):
    """Handle-side load shedding (reference: Serve max_queued_requests ->
    BackPressureError / HTTP 503): once the in-flight cap is reached,
    further submissions fail fast instead of queueing unboundedly."""
    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      max_queued_requests=2)
    class Slow:
        def __call__(self, x):
            time.sleep(3)
            return x

    handle = serve.run(Slow.bind(), name="slow_app")
    admitted = [handle.remote(i) for i in range(2)]
    with pytest.raises(serve.BackPressureError, match="max_queued"):
        for i in range(10):  # cap must trip within the window
            admitted.append(handle.remote(100 + i))
    # The admitted requests still complete: shedding, not failure.
    assert admitted[0].result(timeout=30) == 0


@pytest.mark.parametrize("rt_start", [{"num_cpus": 8}], indirect=True)
def test_replica_change_push_invalidates_handles(srv):
    """Scaling a deployment pushes a replica-change message (long-poll
    fan-out analog); handles re-fetch on the NEXT call instead of waiting
    out the slow poll interval."""
    @serve.deployment(num_replicas=1)
    def f(x):
        return x

    handle = serve.run(f.bind(), name="scale_app")
    assert handle.remote(1).result(timeout=30) == 1
    router = handle._router
    assert len(router._replicas) == 1
    # Scale 1 -> 3; the push must invalidate well before the 5s poll.
    serve.run(f.options(num_replicas=3).bind(), name="scale_app")
    deadline = time.monotonic() + 4.0
    while time.monotonic() < deadline and len(router._replicas) < 3:
        handle.remote(2).result(timeout=30)  # pick() applies invalidation
        time.sleep(0.1)
    assert len(router._replicas) == 3, "push invalidation never landed"
