"""Serve model multiplexing + response streaming.

Reference analogs: ``python/ray/serve/multiplex.py`` (per-replica model LRU,
model-aware routing, ``get_multiplexed_model_id``) and streaming
DeploymentResponses over generator deployments.
"""
import os
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster():
    ray_tpu.init(num_cpus=6)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@serve.deployment(num_replicas=1)
class MultiModel:
    def __init__(self):
        self.loads = []

    @serve.multiplexed(max_num_models_per_replica=2)
    async def get_model(self, model_id: str):
        self.loads.append(model_id)
        return {"name": model_id, "pid": os.getpid()}

    async def __call__(self, x):
        model_id = serve.get_multiplexed_model_id()
        model = await self.get_model(model_id)
        return {
            "model": model["name"],
            "ctx_model_id": model_id,
            "loads": list(self.loads),
            "pid": os.getpid(),
            "x": x,
        }


def test_multiplexed_lru_and_context(serve_cluster):
    handle = serve.run(MultiModel.bind(), name="mux")
    out = handle.options(multiplexed_model_id="m1").remote(1).result()
    assert out["model"] == "m1"
    assert out["ctx_model_id"] == "m1"
    assert out["loads"] == ["m1"]
    # same model again: served from cache, no reload
    out = handle.options(multiplexed_model_id="m1").remote(2).result()
    assert out["loads"] == ["m1"]
    # second model fits (max 2)
    out = handle.options(multiplexed_model_id="m2").remote(3).result()
    assert out["loads"] == ["m1", "m2"]
    # third model evicts the LRU (m1); re-requesting m1 reloads it
    handle.options(multiplexed_model_id="m3").remote(4).result()
    out = handle.options(multiplexed_model_id="m1").remote(5).result()
    assert out["loads"] == ["m1", "m2", "m3", "m1"]
    serve.delete("mux")


def test_router_prefers_model_holder(serve_cluster):
    handle = serve.run(
        MultiModel.options(num_replicas=2).bind(), name="mux2"
    )
    # Warm one replica with m7, then let the router learn the mapping.
    first = handle.options(multiplexed_model_id="m7").remote(0).result()
    time.sleep(1.3)  # > router refresh interval
    pids = set()
    for i in range(8):
        out = handle.options(multiplexed_model_id="m7").remote(i).result()
        pids.add(out["pid"])
        assert out["loads"].count("m7") == 1  # never reloaded anywhere
    assert pids == {first["pid"]}, "requests did not stick to the holder"
    serve.delete("mux2")


@serve.deployment(num_replicas=1)
class MuxStreamer:
    """Multiplexing + streaming combined: the generator body must still see
    the request's model id (it runs under next_chunks, not handle_request)."""

    @serve.multiplexed(max_num_models_per_replica=2)
    async def get_model(self, model_id: str):
        return model_id.upper()

    async def tokens(self, n: int):
        model = await self.get_model(serve.get_multiplexed_model_id())
        for i in range(n):
            yield f"{model}:{i}"


def test_streaming_sees_multiplexed_model_id(serve_cluster):
    handle = serve.run(MuxStreamer.bind(), name="muxstream")
    it = (
        handle.options(multiplexed_model_id="mA", stream=True)
        .tokens.remote(3)
        .result()
    )
    assert list(it) == ["MA:0", "MA:1", "MA:2"]
    serve.delete("muxstream")


@serve.deployment
class Streamer:
    def stream_sync(self, n: int):
        for i in range(n):
            yield {"i": i}

    async def stream_async(self, n: int):
        for i in range(n):
            yield i * 10


def test_streaming_sync_generator(serve_cluster):
    handle = serve.run(Streamer.bind(), name="streamer")
    it = handle.options(stream=True).stream_sync.remote(40).result()
    assert [c["i"] for c in it] == list(range(40))
    serve.delete("streamer")


def test_streaming_async_generator(serve_cluster):
    handle = serve.run(Streamer.bind(), name="streamer2")
    # async generators stream implicitly (no other way to return)
    out = handle.stream_async.remote(5).result()
    assert list(out) == [0, 10, 20, 30, 40]
    serve.delete("streamer2")


@serve.deployment(max_ongoing_requests=48)
class Rendezvous:
    """Every stream's first chunk waits until all of them have started: a
    replica that runs fewer generator bodies at once than it was given
    streams never gets there."""

    def __init__(self):
        import threading

        self.barrier = threading.Barrier(40)

    def tokens(self, i: int):
        self.barrier.wait(timeout=30)
        for j in range(3):
            yield (i, j)


def test_as_many_sync_streams_run_at_once_as_the_replica_was_given(
        serve_cluster):
    """40 streams on one replica (``max_ongoing_requests`` 48): a sync
    generator's body runs in a thread while a pull waits for it, and the
    event loop's default executor has only cpu_count + 4 of them."""
    from concurrent.futures import ThreadPoolExecutor

    handle = serve.run(Rendezvous.bind(), name="rendezvous")

    def one(i):
        it = handle.options(stream=True).tokens.remote(i).result()
        return list(it)

    with ThreadPoolExecutor(40) as pool:
        got = list(pool.map(one, range(40)))
    assert got == [[(i, j) for j in range(3)] for i in range(40)]
    serve.delete("rendezvous")
