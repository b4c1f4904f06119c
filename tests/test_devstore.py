"""Device-plane object store + XLA collective backend (round 14).

Pins the device plane's contract the way ``test_submission_plane.py``
pins the submission plane's — by counting, not by vibes:

- metadata round-trip: a sharded ``jax.Array`` put() registers the
  PINNED directory schema ``{dtype, shape, nbytes, platform, sharding,
  placement}``; get() on the owner is a table hit (same object back);
- cross-process get materializes the consumer's value bit-equal to the
  ``np.asarray`` ground truth, with the CONSUMER's requested sharding
  applied via ``devstore.get_array``/``reshard``;
- call-counting economics: ZERO cloudpickle calls on the device put
  path, O(owners)=1 ``pull_device_shards`` RPC per consumer (repeat
  gets are cache hits), zero ``pull_object`` fallbacks on the happy
  path;
- ``device_objects=False`` restores the host cloudpickle path (and the
  host-staging ledger records device payloads that cross it);
- faultpoints: a failed/lost shard pull retries against the owner and
  completes; a lost registration degrades readers to pull-from-owner;
- memtrack: ``kind="device"`` rows/totals flow through memory_summary
  and the freed object leaves zero leak candidates;
- the registered ``"xla"`` collective backend matches the host backend
  bit-for-bit (float32) on allreduce/allgather/reduce_scatter/broadcast,
  lowering through jitted ``shard_map`` (stats pinned).

The single-node owner-side tests share ONE class-scoped cluster (the
device plane leaves no cross-test state: faultpoints are cleared by the
autouse fixture, env gates are read per call, freed objects leave the
directory) — a per-test cluster would multiply tier-1 wall time for no
isolation gain.
"""
import gc

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import devstore
from ray_tpu._private import faultpoints as fp
from ray_tpu._private import worker as worker_mod
from ray_tpu._private.test_utils import wait_for_condition


pytestmark = pytest.mark.usefixtures("faults_cleared")


@pytest.fixture
def fast_rpc(monkeypatch):
    monkeypatch.setenv("RT_RPC_DEADLINE_S", "1")
    monkeypatch.setenv("RT_RPC_RETRIES", "4")


def _sharded(n_shards=2, shape=(8, 8)):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("x",))
    size = int(np.prod(shape))
    return jax.device_put(
        jnp.arange(size, dtype=jnp.float32).reshape(shape),
        NamedSharding(mesh, P("x")),
    )


class TestDevicePlane:
    """Single-node device-plane contract on one shared cluster."""

    @pytest.fixture(scope="class", autouse=True)
    def _cluster(self):
        ctx = ray_tpu.init(num_cpus=4)
        yield ctx
        ray_tpu.shutdown()

    # -------------------------------------------------- metadata roundtrip
    def test_put_registers_device_metadata_and_local_get_is_table_hit(self):
        w = worker_mod.global_worker
        arr = _sharded(n_shards=2)
        ref = ray_tpu.put(arr)
        hex_ = ref.id().hex()
        assert w.memory_store[hex_][0] == "dev"
        # Owner-side get: the very same array object, zero copies.
        assert ray_tpu.get(ref) is arr

        head = ray_tpu._internal_cluster().head
        wait_for_condition(
            lambda: hex_ in head.object_dir, timeout=10,
            message="device registration never reached the head",
        )
        meta = head.object_dir[hex_]
        assert meta["size"] == arr.nbytes
        assert list(meta["owner"]) == list(w.addr)
        spec = meta["device"]
        # The PINNED device-metadata schema (PARITY.md Round-14).
        assert set(spec) >= {"dtype", "shape", "nbytes", "platform",
                             "sharding", "placement"}
        assert spec["dtype"] == "float32"
        assert spec["shape"] == [8, 8]
        assert spec["platform"] == "cpu"
        assert spec["sharding"]["type"] == "named"
        assert spec["sharding"]["axes"] == [["x", 2]]
        assert len(spec["placement"]) == 2  # one entry per shard
        for shard in spec["placement"]:
            assert set(shard) >= {"shard", "device", "node", "index"}
        # Shard indices tile the global shape along axis 0.
        assert sorted(p["index"][0] for p in spec["placement"]) == [
            [0, 4], [4, 8]
        ]

    def test_consumer_requested_resharding(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        @ray_tpu.remote
        def produce():
            import jax as j
            import jax.numpy as jnp
            from jax.sharding import Mesh as M, NamedSharding as NS
            from jax.sharding import PartitionSpec as PS

            mesh = M(np.array(j.devices()[:2]), ("x",))
            return ray_tpu.put(j.device_put(
                jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                NS(mesh, PS("x")),
            ))

        ref = ray_tpu.get(produce.remote(), timeout=120)
        want = np.arange(64, dtype=np.float32).reshape(8, 8)
        # The consumer asks for a DIFFERENT layout: column-sharded over
        # its own pick of devices.
        target = NamedSharding(
            Mesh(np.array(jax.devices()[2:4]), ("y",)), P(None, "y")
        )
        out = devstore.get_array(ref, sharding=target)
        np.testing.assert_array_equal(np.asarray(out), want)
        assert out.sharding.is_equivalent_to(target, out.ndim)
        # Each of the 2 shards holds an (8, 4) column block.
        assert sorted(s.data.shape for s in out.addressable_shards) == [
            (8, 4), (8, 4)
        ]

    # ---------------------------------------------------- call economics
    def test_zero_cloudpickle_and_o_owners_pull_rpcs(self, monkeypatch):
        """The payload NEVER passes through cloudpickle on the device
        path, and a consumer pays exactly ONE pull_device_shards RPC
        (repeat gets are table hits; zero pull_object fallbacks)."""
        import ray_tpu._private.serialization as ser

        w = worker_mod.global_worker
        arr = _sharded(n_shards=2)
        want_sum = float(np.asarray(arr).sum())

        pickled = []
        orig_dumps = ser.cloudpickle.dumps

        def counting_dumps(obj, *a, **k):
            pickled.append(type(obj).__name__)
            return orig_dumps(obj, *a, **k)

        monkeypatch.setattr(ser.cloudpickle, "dumps", counting_dumps)
        ref = ray_tpu.put(arr)
        monkeypatch.setattr(ser.cloudpickle, "dumps", orig_dumps)
        assert pickled == [], f"device put cloudpickled: {pickled}"

        calls = {"dev_pull": 0, "obj_pull": 0}
        orig_dev = w.rpc_pull_device_shards
        orig_obj = w.rpc_pull_object

        async def counted_dev(h, frames, conn):
            calls["dev_pull"] += 1
            return await orig_dev(h, frames, conn)

        async def counted_obj(h, frames, conn):
            calls["obj_pull"] += 1
            return await orig_obj(h, frames, conn)

        # Instance-attr shadow (dispatch getattrs per call); restored
        # below — the cluster is shared.
        w.rpc_pull_device_shards = counted_dev
        w.rpc_pull_object = counted_obj

        @ray_tpu.remote
        class Consumer:
            def consume(self, refs):
                import numpy as _np

                return float(_np.asarray(ray_tpu.get(refs[0])).sum())

        try:
            c = Consumer.remote()
            assert ray_tpu.get(c.consume.remote([ref]),
                               timeout=120) == want_sum
            assert ray_tpu.get(c.consume.remote([ref]),
                               timeout=120) == want_sum
        finally:
            del w.rpc_pull_device_shards
            del w.rpc_pull_object
        assert calls["dev_pull"] == 1, calls  # O(owners); cached repeat
        assert calls["obj_pull"] == 0, calls  # directory hit, no fallback
        ray_tpu.kill(c)

    # -------------------------------------------------------- disabled mode
    def test_disabled_mode_falls_back_to_host_path(self, monkeypatch):
        """device_objects=False: byte-identical host cloudpickle behavior
        — the store entry is a host kind and the staging ledger records
        the device payload that crossed it."""
        monkeypatch.setenv("RT_DEVICE_OBJECTS", "0")
        w = worker_mod.global_worker
        arr = _sharded(n_shards=2)
        staged_before = devstore.host_staged_stats()
        ref = ray_tpu.put(arr)
        assert w.memory_store[ref.id().hex()][0] in ("mem", "shm")
        out = ray_tpu.get(ref)
        assert out is not arr  # host round-trip, not a table hit
        np.testing.assert_array_equal(np.asarray(out), np.asarray(arr))
        assert np.asarray(out).dtype == np.asarray(arr).dtype
        staged = devstore.host_staged_stats()
        assert staged["count"] == staged_before["count"] + 1
        assert staged["bytes"] == staged_before["bytes"] + arr.nbytes

    def test_nested_device_arrays_keep_host_semantics(self):
        """Only TOP-LEVEL device arrays route to the devstore (the pinned
        interception boundary): one nested in a container rides
        cloudpickle exactly as before the plane existed, byte-correct."""
        w = worker_mod.global_worker
        arr = _sharded(n_shards=2, shape=(4, 4))
        ref = ray_tpu.put({"weights": arr, "step": 3})
        assert w.memory_store[ref.id().hex()][0] in ("mem", "shm")
        out = ray_tpu.get(ref)
        assert out["step"] == 3
        np.testing.assert_array_equal(np.asarray(out["weights"]),
                                      np.asarray(arr))

    # ----------------------------------------------------------- faultpoints
    def test_shard_pull_error_is_retried_against_owner(self, fast_rpc):
        @ray_tpu.remote
        def produce():
            import jax.numpy as jnp

            return ray_tpu.put(jnp.ones((16, 4), jnp.float32))

        inner = ray_tpu.get(produce.remote(), timeout=120)
        # Two consumer-side failures, then success — retried against the
        # owner, never surfaced to the caller.
        fp.configure("devstore.shard_pull:error:1.0:2:7")
        out = ray_tpu.get(inner, timeout=120)
        assert float(np.asarray(out).sum()) == 64.0
        assert fp.stats()[0]["injected"] == 2

    def test_shard_pull_drop_rearms_instead_of_hanging(self, fast_rpc):
        @ray_tpu.remote
        def produce():
            import jax.numpy as jnp

            return ray_tpu.put(jnp.full((8, 8), 2.0, jnp.float32))

        inner = ray_tpu.get(produce.remote(), timeout=120)
        fp.configure("devstore.shard_pull:drop:1.0:1:5")
        out = ray_tpu.get(inner, timeout=120)
        assert float(np.asarray(out).sum()) == 128.0
        assert fp.stats()[0]["injected"] == 1

    def test_register_drop_degrades_to_owner_pull(self, fast_rpc):
        """A lost directory registration must not lose the object:
        readers miss the directory and pull from the owner (pull_object
        answers with the device spec, then the shard pull proceeds)."""
        fp.configure("devstore.register:drop:1.0:1:3")
        arr = _sharded(n_shards=2, shape=(4, 4))
        ref = ray_tpu.put(arr)
        hex_ = ref.id().hex()
        assert fp.stats()[0]["injected"] == 1
        fp.clear()
        head = ray_tpu._internal_cluster().head
        assert hex_ not in head.object_dir  # registration really dropped

        @ray_tpu.remote
        def consume(refs):
            import numpy as _np

            return float(_np.asarray(ray_tpu.get(refs[0])).sum())

        want = float(np.asarray(arr).sum())
        assert ray_tpu.get(consume.remote([ref]), timeout=120) == want

    # ---------------------------------------------------------- memtrack
    def test_device_rows_flow_through_memory_summary(self):
        from ray_tpu._private import memtrack
        from ray_tpu.util import state

        arr = _sharded(n_shards=2)
        ref = ray_tpu.put(arr)
        hex_ = ref.id().hex()
        head = ray_tpu._internal_cluster().head
        wait_for_condition(lambda: hex_ in head.object_dir, timeout=10)

        s = state.memory_summary()
        rows = {r["oid"]: r for r in s["rows"]}
        assert hex_ in rows
        assert rows[hex_]["kind"] == "device"
        assert rows[hex_]["bytes"] == arr.nbytes
        assert s["totals"]["device_bytes"] >= arr.nbytes
        w = worker_mod.global_worker
        node = str(w.node_id)[:12]
        assert s["reconcile"][node]["owner_device_bytes"] >= arr.nbytes
        assert s["reconcile"][node]["directory_device_bytes"] >= arr.nbytes

        # Gauge-tick coverage on the same cluster: device bytes aggregate
        # per (kind, node) and push_gauges handles the new kind.
        snap = memtrack.local_snapshot(w)
        agg = {(k, n): v for k, n, v in snap["bytes_by_kind_node"]}
        assert agg.get(("device", node), 0) >= arr.nbytes
        assert "device_host_staged" in snap
        memtrack.push_gauges(w)  # must not break the 2s tick

        # Freeing the last ref reclaims the device table entry, the
        # directory entry, and leaves ZERO leak candidates — the chaos
        # SLO for kind="device" matches every other kind.
        del ref
        gc.collect()
        wait_for_condition(
            lambda: hex_ not in head.object_dir, timeout=10,
            message="freed device object stuck in directory",
        )
        assert hex_ not in w._device_objects
        assert state.memory_summary(grace_s=0.5)["leaks"] == []


@pytest.mark.parametrize("rt_start", [dict(num_cpus=2, num_nodes=2)],
                         indirect=True)
def test_cross_process_get_matches_ground_truth(rt_start):
    arr = _sharded(n_shards=4, shape=(8, 8))
    want = np.asarray(arr)
    ref = ray_tpu.put(arr)

    @ray_tpu.remote
    def consume_arg(v):
        import jax as j
        import numpy as _np

        return (
            type(v).__name__,
            _np.asarray(v).tolist(),
            isinstance(v, j.Array) and len(v.sharding.device_set),
        )

    @ray_tpu.remote
    def consume_get(refs):
        import numpy as _np

        return _np.asarray(ray_tpu.get(refs[0])).tolist()

    name, got, n_dev = ray_tpu.get(consume_arg.remote(ref), timeout=120)
    assert name == "ArrayImpl"
    assert got == want.tolist()
    assert n_dev == 4  # producer-equivalent sharding rebuilt at consumer
    assert ray_tpu.get(consume_get.remote([ref]),
                       timeout=120) == want.tolist()


# ------------------------------------------------------------ xla backend
@ray_tpu.remote
class _ColMember:
    def __init__(self, world, rank, backend, name):
        from ray_tpu.util import collective as col

        self.col = col
        self.rank, self.world = rank, world
        col.init_collective_group(world, rank, backend=backend,
                                  group_name=name)
        self.g = name

    def allreduce(self):
        return self.col.allreduce(
            np.full((4,), float(self.rank + 1), np.float32), self.g
        )

    def allgather(self):
        return self.col.allgather(
            np.array([self.rank + 1], np.float32), self.g
        )

    def reducescatter(self):
        return self.col.reducescatter(
            np.arange(self.world * 3, dtype=np.float32) * (self.rank + 1),
            self.g,
        )

    def rs_max(self):
        from ray_tpu.util.collective.types import ReduceOp

        return self.col.reducescatter(
            np.arange(4, dtype=np.float32), self.g, op=ReduceOp.MAX
        )

    def broadcast(self):
        x = (np.arange(3, dtype=np.float32)
             if self.rank == 0 else np.zeros(3, np.float32))
        return self.col.broadcast(x, src_rank=0, group_name=self.g)

    def stats(self):
        from ray_tpu.util.collective.collective import _group_mgr

        return dict(_group_mgr.get_group(self.g).stats)


@pytest.mark.parametrize("rt_start", [dict(num_cpus=8)], indirect=True)
def test_xla_backend_bitwise_parity_with_host(rt_start):
    """backend="xla" on a CPU mesh: every collective matches the host
    backend bit-for-bit for exact float32 inputs through the lowered
    (shard_map) path — and a non-SUM reduce-scatter (psum_scatter cannot
    express it) falls back to the host path with identical results,
    explicitly counted."""
    world = 2
    xla = [_ColMember.remote(world, r, "xla", "par-x")
           for r in range(world)]
    host = [_ColMember.remote(world, r, "host", "par-h")
            for r in range(world)]
    for method in ("allreduce", "allgather", "reducescatter", "broadcast",
                   "rs_max"):
        got_x = ray_tpu.get(
            [getattr(m, method).remote() for m in xla], timeout=180
        )
        got_h = ray_tpu.get(
            [getattr(m, method).remote() for m in host], timeout=180
        )
        for a, b in zip(got_x, got_h):
            if isinstance(a, list):
                assert len(a) == len(b)
                for x, y in zip(a, b):
                    assert np.array_equal(np.asarray(x), np.asarray(y)), \
                        method
            else:
                assert np.array_equal(np.asarray(a), np.asarray(b)), method
    stats = ray_tpu.get(xla[0].stats.remote(), timeout=60)
    # 4 lowered collectives; rs_max is the explicit host fallback.
    assert stats["shard_map_calls"] == 4
    assert stats["host_fallbacks"] == 1
    for m in xla + host:
        ray_tpu.kill(m)
