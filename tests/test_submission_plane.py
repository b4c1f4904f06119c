"""Submission-plane batching & caching economics (round 10).

Pins the RPC shape of the batched/cached submission plane by counting
verb executions at the head and template builds on the submitting worker
(style of ``test_batched_refs.py``):

- a K-task burst of one (function, options) pair serializes the spec
  template ONCE (everything else is per-call deltas spliced into the
  wire buffer);
- function-table traffic is O(unique functions), not O(fresh slots):
  push-through piggybacks the blob on the first push to each peer
  (zero head ``kv_get``s for pushed functions), and concurrent
  ``_load_function`` misses coalesce into one ``kv_get_batch``;
- an N-actor anonymous burst issues O(bursts) ``create_actor_batch``
  head RPCs (zero per-actor ``create_actor`` calls), and a dropped batch
  reply is replayed from the corr-dedup cache without double-creating a
  single actor;
- the warm worker pool turns add_node / demand growth into standby
  activation instead of a cold process spawn;
- the ``worker.spec.frame`` faultpoint degrades framing to the inline
  header path without losing a task.
"""
import time

import cloudpickle
import pytest

import ray_tpu
from ray_tpu._private import faultpoints as fp
from ray_tpu._private import worker as worker_mod
from ray_tpu._private.test_utils import wait_for_condition
from ray_tpu._private.worker import FN_NS


pytestmark = pytest.mark.usefixtures("faults_cleared")


class _HeadVerbCounter:
    """Counts head verb EXECUTIONS by shadowing ``rpc_<verb>`` on the
    in-process HeadService (dispatch resolves the handler per call, so an
    instance attribute wins). Corr-dedup replays answer from the reply
    cache without re-entering the handler — exactly the distinction the
    no-double-create assertions need. ``ns`` restricts counting to one
    KV namespace."""

    def __init__(self, head, verbs, ns=None):
        self.counts = {}
        for v in verbs:
            fn = getattr(head, "rpc_" + v)

            async def counted(h, frames, conn, _v=v, _fn=fn):
                if ns is None or h.get("ns") == ns:
                    self.counts[_v] = self.counts.get(_v, 0) + 1
                return await _fn(h, frames, conn)

            setattr(head, "rpc_" + v, counted)


# ------------------------------------------------------- spec templates
def test_spec_template_serialized_once_per_burst(rt_start):
    """K tasks of one cached function build exactly ONE spec template;
    a distinct options combination builds its own, then also caches."""
    w = worker_mod.global_worker

    @ray_tpu.remote
    def f(i):
        return i

    before = w._stats["spec_templates_built"]
    assert ray_tpu.get([f.remote(i) for i in range(200)],
                       timeout=120) == list(range(200))
    assert w._stats["spec_templates_built"] - before == 1
    # second burst of the same function: template cache hit, zero builds
    assert ray_tpu.get([f.remote(i) for i in range(50)],
                       timeout=120) == list(range(50))
    assert w._stats["spec_templates_built"] - before == 1


def test_function_push_through_zero_head_kv_gets(rt_start):
    """The function blob rides the first push to each worker (wire flag
    ``fb``): a burst on fresh workers costs ZERO function-table fetches
    at the head — O(unique functions) coverage comes from the pushes
    themselves, not kv_get round trips."""
    head = ray_tpu._internal_cluster().head
    counter = _HeadVerbCounter(head, ["kv_get", "kv_get_batch"], ns=FN_NS)

    @ray_tpu.remote
    def g(i):
        return i * 2

    assert ray_tpu.get([g.remote(i) for i in range(100)],
                       timeout=120) == [i * 2 for i in range(100)]
    fn_fetches = (counter.counts.get("kv_get", 0)
                  + counter.counts.get("kv_get_batch", 0))
    assert fn_fetches == 0, counter.counts


def test_load_function_misses_coalesce_into_one_batch(rt_start):
    """Concurrent function-table misses for K distinct keys issue ONE
    kv_get_batch (not K kv_gets): the fallback path a piggyback-less
    worker takes is itself batched."""
    w = worker_mod.global_worker
    head = ray_tpu._internal_cluster().head
    keys = []
    for i in range(8):
        key = f"subplane-test-fn-{i}"
        blob = cloudpickle.dumps(i)  # _load_function just unpickles
        w.run_sync(w.gcs.call("kv_put", {"ns": FN_NS, "key": key}, [blob]))
        keys.append(key)
    counter = _HeadVerbCounter(head, ["kv_get", "kv_get_batch"], ns=FN_NS)

    async def load_all():
        import asyncio

        return await asyncio.gather(*(w._load_function(k) for k in keys))

    assert w.run_sync(load_all(), timeout=30) == list(range(8))
    assert counter.counts.get("kv_get_batch", 0) == 1
    assert counter.counts.get("kv_get", 0) == 0
    for k in keys:
        w.fn_cache.pop(k, None)


# ------------------------------------------------------- batched actors
def test_actor_burst_is_o_bursts_head_rpcs(rt_start):
    """An N-actor anonymous burst costs O(bursts) create_actor_batch
    executions at the head — never a per-actor create_actor RPC. The
    first batch is gated at the head until the whole burst is enqueued,
    so the self-clocking flush is deterministic: exactly 2 batch RPCs
    (the 1-item opener, then everything that accumulated behind it)."""
    import asyncio

    w = worker_mod.global_worker
    head = ray_tpu._internal_cluster().head
    counter = _HeadVerbCounter(head, ["create_actor"])
    gate = w.run_sync(_make_event(), timeout=10)
    executions = []
    orig = head.rpc_create_actor_batch

    async def gated(h, frames, conn):
        executions.append(len(h.get("items", ())))
        await gate.wait()
        return await orig(h, frames, conn)

    head.rpc_create_actor_batch = gated

    @ray_tpu.remote(num_cpus=0)
    class A:
        def ping(self):
            return 1

    n = 100
    # Enqueue the opener and WAIT for its 1-item batch to reach the
    # (gated) head before bursting the rest: whether the opener's drain
    # callback wins the race against a tight enqueue loop is GIL
    # preemption luck, and this test pins the batching invariant, not
    # that race.
    actors = [A.remote()]
    wait_for_condition(lambda: len(executions) == 1, timeout=10)
    actors += [A.remote() for _ in range(n - 1)]
    w.loop.call_soon_threadsafe(gate.set)
    assert ray_tpu.get([a.ping.remote() for a in actors],
                       timeout=120) == [1] * n
    assert counter.counts.get("create_actor", 0) == 0
    assert len(executions) == 2, executions
    assert sum(executions) == n
    for a in actors:
        ray_tpu.kill(a)


async def _make_event():
    import asyncio

    return asyncio.Event()


def test_dropped_batch_reply_replays_without_double_create(
        rt_start, monkeypatch):
    """The FIRST create_actor_batch reply is dropped after the head
    applied every item; the client's deadline re-issues under the same
    corr id and the dedup cache replays the original outcomes — the
    handler runs once per batch, each actor exists exactly once, and the
    placements it reserved all come back after the kill."""
    monkeypatch.setenv("RT_RPC_DEADLINE_S", "1")
    head = ray_tpu._internal_cluster().head
    counter = _HeadVerbCounter(head, ["create_actor_batch"])
    before_ids = set(head.actors)
    fp.configure("gcs.dispatch.create_actor_batch:drop:1.0:1:42")

    @ray_tpu.remote(num_cpus=0.01)
    class B:
        def ping(self):
            return 2

    n = 16
    actors = [B.remote() for _ in range(n)]
    assert ray_tpu.get([a.ping.remote() for a in actors],
                       timeout=120) == [2] * n
    s = fp.stats()[0]
    assert s["injected"] == 1, s  # the drop really happened
    fp.clear()
    new_ids = set(head.actors) - before_ids
    assert len(new_ids) == n  # every actor exactly once, none doubled
    # dedup replay answered the retry: executions == distinct batches,
    # strictly fewer than client attempts (which include the retry)
    assert counter.counts.get("create_actor_batch", 0) <= n
    for a in actors:
        ray_tpu.kill(a)

    def _placements_returned():
        return all(
            all(node.available.get(k, 0.0) >= v - 1e-9
                for k, v in node.resources.items())
            for node in head.nodes.values() if node.alive
        )

    wait_for_condition(_placements_returned, timeout=20,
                       message="replayed batch leaked actor placements")


# ---------------------------------------------------- faultpoint degrade
def test_spec_frame_fault_degrades_to_inline_path(rt_start):
    """Template-build failure must cost nothing but the optimization:
    every submission still completes via the inline full-header path."""
    fp.configure("worker.spec.frame:error:1.0:0:7")

    @ray_tpu.remote
    def h(i):
        return i + 10

    assert ray_tpu.get([h.remote(i) for i in range(20)],
                       timeout=120) == [i + 10 for i in range(20)]
    s = fp.stats()[0]
    assert s["injected"] >= 1, s


# --------------------------------------------------------- warm pool
@pytest.mark.parametrize(
    "rt_start", [dict(num_cpus=1, num_nodes=1)], indirect=True)
def test_warm_pool_add_node_consumes_standby(rt_start):
    """add_node with the pool's resource spec activates a preforked
    standby (same node id) instead of cold-spawning a process, and the
    head flips it schedulable."""
    cluster = ray_tpu._internal_cluster()
    cluster.start_warm_pool(1)
    assert len(cluster.warm) == 1
    warm_id = cluster.warm[0].node_id
    nh = cluster.add_node({"CPU": 1})
    assert nh.node_id == warm_id
    assert not cluster.warm
    info = cluster.head.nodes.get(warm_id)
    assert info is not None and info.alive and not info.standby


@pytest.mark.parametrize(
    "rt_start", [dict(num_cpus=1, num_nodes=1)], indirect=True)
def test_warm_pool_auto_activates_on_demand(rt_start):
    """When demand outgrows schedulable capacity the head activates a
    standby on its own: two 1-CPU actors on a 1-CPU cluster means the
    second creation lands on the (activated) warm node."""
    cluster = ray_tpu._internal_cluster()
    cluster.start_warm_pool(1)

    @ray_tpu.remote(num_cpus=1)
    class C:
        def ping(self):
            return 3

    a, b = C.remote(), C.remote()
    assert ray_tpu.get([a.ping.remote(), b.ping.remote()],
                       timeout=120) == [3, 3]
    active = [n for n in cluster.head.nodes.values()
              if n.alive and not n.standby]
    assert len(active) == 2  # the standby joined the schedulable set
    for x in (a, b):
        ray_tpu.kill(x)


def test_standby_nodes_invisible_until_activated(rt_start):
    """A registered standby neither counts toward wait_for_nodes nor
    receives work while capacity suffices elsewhere (sequential
    submissions: demand never outgrows the active node, so the head has
    no reason to burn the reserve)."""
    cluster = ray_tpu._internal_cluster()
    cluster.start_warm_pool(1)

    def _standby_registered():
        return any(n.standby and n.alive
                   for n in cluster.head.nodes.values())

    wait_for_condition(_standby_registered, timeout=60,
                       message="warm standby never registered")
    standby_ids = {n.node_id for n in cluster.head.nodes.values()
                   if n.standby}
    # wait_for_nodes counts only schedulable nodes: satisfied at 1 even
    # though two processes are registered
    assert len(cluster._head_active_nodes()) == 1

    @ray_tpu.remote
    def where():
        return worker_mod.global_worker.node_id

    spots = {ray_tpu.get(where.remote(), timeout=60) for _ in range(8)}
    assert not (spots & standby_ids)
    assert any(n.standby for n in cluster.head.nodes.values())
