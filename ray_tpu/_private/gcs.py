"""Head service: cluster metadata, scheduling, actor management, pubsub, KV.

TPU-native analog of the reference GCS (``src/ray/gcs/gcs_server.h:97`` and its
managers: GcsNodeManager, GcsResourceManager, GcsActorManager,
GcsPlacementGroupManager, internal KV, function manager, pubsub). Design
differences, deliberate (SURVEY.md §7):

- **Process-per-host model**: a "node" here is one worker process (on a TPU pod
  each host runs exactly one multi-chip worker process), so the reference's
  raylet/worker split collapses into a single per-node service. The head
  schedules leases directly onto nodes — there is no per-node secondary
  scheduler in round 1.
- **Typed TPU resources**: nodes advertise {"CPU": n, "TPU": m, ...} plus
  labels (topology, slice name). Slice-aware gang placement lives in
  ``placement_group`` with STRICT_PACK ≈ one ICI slice.
- Transport is the framed-msgpack RPC in ``protocol.py`` (not gRPC); workers
  keep one bidirectional connection to the head, over which the head also
  pushes actor-creation requests and pubsub messages (reference's
  long-poll pubsub ``src/ray/pubsub/publisher.h`` becomes a plain push).
"""
from __future__ import annotations

import asyncio
import itertools
import logging
import os
import time
from collections import OrderedDict, defaultdict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu._private import faultpoints, flight, protocol
from ray_tpu._private.asyncio_util import spawn_logged
from ray_tpu._private.ids import ActorID, NodeID, PlacementGroupID

logger = logging.getLogger(__name__)


@dataclass
class NodeInfo:
    node_id: str
    addr: Tuple[str, int]          # worker-service address for task push
    resources: Dict[str, float]    # total
    available: Dict[str, float]    # currently available
    labels: Dict[str, str] = field(default_factory=dict)
    conn: Optional[protocol.Connection] = None  # head<->node control conn
    alive: bool = True
    start_time: float = field(default_factory=time.time)
    # Registration epoch: a stale close event from a connection this node
    # already replaced (re-register after a blip) must not kill the node.
    epoch: int = 0
    # Warm worker pool: a standby node is fully registered (process up,
    # connected, rings attachable) but invisible to the scheduler until
    # activated — the instant-capacity reserve rt_config.warm_workers
    # preforks (reference: prestarted idle workers in worker_pool.cc).
    standby: bool = False

    def to_public(self) -> dict:
        return {
            "node_id": self.node_id,
            "addr": list(self.addr),
            "resources": dict(self.resources),
            "available": dict(self.available),
            "labels": dict(self.labels),
            "alive": self.alive,
            "standby": self.standby,
        }


@dataclass
class ActorInfo:
    actor_id: str
    name: Optional[str]
    namespace: str
    state: str                     # PENDING | ALIVE | RESTARTING | DEAD
    node_id: Optional[str]
    addr: Optional[Tuple[str, int]]
    resources: Dict[str, float]
    max_restarts: int
    restarts_used: int = 0
    creation_frames: Optional[List[bytes]] = None  # replayed on restart
    death_reason: str = ""
    class_name: str = ""
    pg_id: Optional[str] = None
    bundle_index: int = -1
    detached: bool = False  # lifetime="detached": survives its owner
    # method name -> declared num_returns (@method(num_returns=N)); rides
    # the actor table so get_actor() handles honor declarations too.
    method_meta: Dict[str, int] = field(default_factory=dict)

    def to_public(self) -> dict:
        return {
            "actor_id": self.actor_id,
            "name": self.name,
            "namespace": self.namespace,
            "state": self.state,
            "node_id": self.node_id,
            "addr": list(self.addr) if self.addr else None,
            "class_name": self.class_name,
            "restarts_used": self.restarts_used,
            "death_reason": self.death_reason,
            "method_meta": dict(self.method_meta),
        }


@dataclass
class PlacementGroupInfo:
    pg_id: str
    bundles: List[Dict[str, float]]
    strategy: str
    state: str                     # PENDING | CREATED | REMOVED
    bundle_nodes: List[Optional[str]] = field(default_factory=list)
    name: str = ""

    def to_public(self) -> dict:
        return {
            "placement_group_id": self.pg_id,
            "name": self.name,
            "bundles": self.bundles,
            "strategy": self.strategy,
            "state": self.state,
            "bundle_nodes": self.bundle_nodes,
        }


def _fits(avail: Dict[str, float], need: Dict[str, float]) -> bool:
    return all(avail.get(k, 0.0) + 1e-9 >= v for k, v in need.items())


def _acquire(avail: Dict[str, float], need: Dict[str, float]):
    for k, v in need.items():
        avail[k] = avail.get(k, 0.0) - v


def _release(avail: Dict[str, float], need: Dict[str, float]):
    for k, v in need.items():
        avail[k] = avail.get(k, 0.0) + v


class HeadService:
    """The cluster head. Runs inside the driver process's core event loop in
    round 1 (single head service; reference runs it as a separate gcs_server
    process — the RPC surface is identical so it can be split out later)."""

    def __init__(self):
        self.kv: Dict[str, Dict[str, bytes]] = defaultdict(dict)  # ns -> key -> val
        self.nodes: Dict[str, NodeInfo] = {}
        # Bounded tombstones for the state API: node ids are fresh per
        # registration, so without pruning both this dict and the native
        # scheduler's node vector grow forever under autoscaler churn
        # (reference: GcsNodeManager keeps a capped dead-node cache).
        self.dead_nodes: Dict[str, NodeInfo] = {}
        self._DEAD_NODE_CACHE = 256
        self.actors: Dict[str, ActorInfo] = {}
        self.named_actors: Dict[Tuple[str, str], str] = {}  # (ns, name) -> actor_id
        self.pgs: Dict[str, PlacementGroupInfo] = {}
        # pg_id -> bundle_index -> remaining reserved resources on that node
        self.pg_reserved: Dict[str, List[Dict[str, float]]] = {}
        self.subscribers: Dict[str, List[protocol.Connection]] = defaultdict(list)
        self.object_dir: Dict[str, dict] = {}  # object hex -> shm layout metadata
        self.server: Optional[protocol.RpcServer] = None
        self.addr: Optional[Tuple[str, int]] = None
        self._pending_waiters: List[asyncio.Future] = []  # resource-wait futures
        self._last_reclaim = 0.0  # lease_reclaim publish rate limit
        # Monotonic serial per client connection — NOT id(conn): a closed
        # connection's id() can be reused by a new one before the scheduled
        # cleanup task runs, which would tear down the new owner's state.
        self._conn_serial = itertools.count(1)
        # conn-serial -> actor ids whose owner is that connection
        # (non-detached actors are destroyed when their owner disconnects)
        self._conn_actors: Dict[int, set] = {}
        # conn-serial -> outstanding lease grants [(node_id, resources,
        # strategy)]: a client killed mid-burst (SIGKILL, OOM) can never
        # send release_lease, so its grants are replayed on disconnect —
        # otherwise the head's view of node capacity leaks permanently
        # (reference: raylet returns a dead worker's leased resources via
        # the worker-failure path, ``cluster_lease_manager.cc``).
        self._conn_leases: Dict[int, list] = {}
        # Task-event ring for the state API: a bounded deque, consistent
        # with the flight recorder's ring semantics — append is O(1),
        # overflow drops the OLDEST event (a plain list trimmed with del
        # slicing memmoved the whole buffer on every overflow), and the
        # drop count is reported, never silent.
        self.task_events: deque = deque(maxlen=10_000)
        self._task_events_total = 0
        # Log plane: recent worker log lines per node (bounded ring), fed
        # by worker_logs notifies, served to `rt logs` + the dashboard.
        self.log_buffer: Dict[str, deque] = {}
        self._LOG_BUFFER_LINES = 10_000
        self.jobs: Dict[str, dict] = {}
        self._schedule_rr = 0  # round-robin cursor
        self._shutting_down = False
        self._death_tasks: set = set()  # in-flight _on_node_dead tasks
        # Unsatisfied lease demands, keyed by waiter id — the autoscaler's
        # scale-up signal (reference: GcsAutoscalerStateManager feeding
        # autoscaler v2 with pending resource demands).
        self.pending_demands: Dict[int, dict] = {}
        self.job_procs: Dict[str, object] = {}  # submission_id -> Popen
        self.worker_metrics: Dict[str, list] = {}  # worker -> metric snapshot
        # Correlation-id dedup for retried non-idempotent verbs (lease,
        # create_actor, create_pg): a retry after a DROPPED REPLY must
        # return the original outcome, not apply the verb twice — the
        # reference's reply-path failures are absorbed the same way by
        # server-side request dedup. Entries are (conn serial, reply) —
        # connection-scoped, since a disconnect rolls the outcome back —
        # in a bounded LRU; only successful replies are cached (a failed
        # attempt may legitimately succeed on retry).
        self._corr_replies: "OrderedDict[str, tuple]" = OrderedDict()
        self._CORR_CACHE = 1024
        self._task_state_counts: Dict[str, int] = {}  # FINISHED/FAILED/...
        # Native C++ scheduler (reference: the C++ ClusterResourceScheduler,
        # ``raylet/scheduling/cluster_resource_scheduler.cc:155``): fixed-point
        # resource accounting + best-node policies in ray_tpu/native/src/sched.cc.
        # The NodeInfo.available dicts stay as a mirror for the state API and
        # autoscaler; scheduling decisions come from the native side when the
        # library is buildable (RT_NATIVE_SCHED=0 forces the Python fallback).
        self._nsched = None
        from ray_tpu._private.config import rt_config

        if rt_config.native_sched:
            try:
                from ray_tpu.native import sched as _native_sched

                self._nsched = _native_sched.create()
            except Exception:
                logger.exception("native scheduler unavailable; Python fallback")

    # ------------------------------------------------------------------ setup

    async def start(self, host: str = "127.0.0.1", port: int = 0):
        self.server = protocol.RpcServer(self._handle, host, port)
        self.addr = await self.server.start()
        logger.info("head service listening on %s", self.addr)
        # Structured export-event pipeline (reference: RayEventRecorder →
        # aggregator agent): lifecycle transitions below emit typed events
        # persisted as JSON-lines in the session dir.
        try:
            from ray_tpu.util.events import EventRecorder

            session_dir = os.environ.get(
                "RT_SESSION_DIR", f"/tmp/ray_tpu/session_p{self.addr[1]}"
            )
            self.events = EventRecorder(
                path=os.path.join(session_dir, "events", "events.jsonl")
            )
        except Exception:
            logger.exception("export-event recorder unavailable")
            self.events = None
        return self.addr

    def _emit_event(self, source_type: str, event_type: str,
                    entity_id: str, message: str = "", **attrs):
        if getattr(self, "events", None) is None:
            return
        try:
            self.events.emit(
                source_type, event_type, entity_id, message, **attrs
            )
        except Exception as e:
            # Observability must never take down the control plane, but a
            # persistently failing exporter should be visible in debug logs.
            logger.debug("export-event emit (%s/%s) failed: %s",
                         source_type, event_type, e)

    # WAL: durable-table mutations (KV, jobs) append a record BEFORE the
    # RPC reply, closing the between-snapshots loss window (reference:
    # redis_store_client.cc — per-mutation durability, not timer-based).
    def attach_wal(self, path_prefix: str):
        from ray_tpu._private.wal import WalWriter

        self.wal = WalWriter(path_prefix)
        return self.wal

    def _wal_append(self, op: dict):
        wal = getattr(self, "wal", None)
        if wal is None:
            return
        try:
            wal.append(op)
            wal.schedule_fsync(asyncio.get_running_loop())
        except Exception:
            logger.exception("WAL append failed (durability degraded)")

    def replay_wal(self, path_prefix: str) -> int:
        """Apply surviving WAL records over restored snapshot state.
        Idempotent: puts overwrite, deletes are best-effort, job records
        merge like restore() (running work is terminal after a restart)."""
        from ray_tpu._private.wal import replay_all

        n = 0
        for op in replay_all(path_prefix):
            kind = op.get("op")
            if kind == "kv_put":
                self.kv[op["ns"]][op["key"]] = op["val"]
            elif kind == "kv_del":
                self.kv[op["ns"]].pop(op["key"], None)
            elif kind == "kv_del_prefix":
                ns = self.kv[op["ns"]]
                for k in [k for k in ns if k.startswith(op["prefix"])]:
                    ns.pop(k, None)
            elif kind == "job":
                info = dict(op["job"])
                if info.get("status") in ("RUNNING", "STOPPING", "PENDING"):
                    info["status"] = "FAILED"
                    info.setdefault("end_time", time.time())
                if info.get("state") == "RUNNING":
                    info["state"] = "DEAD"
                    info.setdefault("end_time", time.time())
                self.jobs[info["job_id"]] = {
                    **self.jobs.get(info["job_id"], {}), **info
                }
            n += 1
        return n

    async def close(self):
        self._shutting_down = True
        if self.server:
            await self.server.close()
        # Settle in-flight node-death handlers so none outlive the loop.
        if self._death_tasks:
            await asyncio.gather(
                *list(self._death_tasks), return_exceptions=True
            )
        if getattr(self, "events", None) is not None:
            try:
                self.events.close()
            except Exception:
                pass

    # -------------------------------------------------------- persistence
    # Reference analog: GCS fault tolerance via Redis-backed store +
    # GcsInitData replay (``gcs/store_client/redis_store_client.cc``,
    # ``gcs_init_data.cc``): durable metadata survives a head restart.
    # Round-1 scope: the durable tables are the KV (function table, train
    # rendezvous, user data) and job records; live process state (nodes,
    # actors) re-registers on reconnect.

    def snapshot(self) -> bytes:
        import pickle

        jobs = {
            jid: {k: v for k, v in info.items()}
            for jid, info in self.jobs.items()
        }
        return pickle.dumps({
            "version": 1,
            # The listen address rides the snapshot so a restarted head can
            # REBIND the same port — live nodes/drivers reconnect to the
            # address they already hold (reference: GCS restarts behind a
            # stable address and raylets reconnect, gcs_init_data replay).
            "addr": list(self.addr) if self.addr else None,
            "kv": {ns: dict(kvs) for ns, kvs in self.kv.items()},
            "jobs": jobs,
        })

    def restore(self, blob: bytes):
        import pickle

        state = pickle.loads(blob)
        # Surfaced for head_main: rebind this port so live clients rejoin.
        self.restored_addr = (
            tuple(state["addr"]) if state.get("addr") else None
        )
        for ns, kvs in state.get("kv", {}).items():
            self.kv[ns].update(kvs)
        for jid, info in state.get("jobs", {}).items():
            info = dict(info)
            # processes did not survive the head: running work is terminal.
            # Submission jobs track "status"; driver-registered jobs "state".
            if info.get("status") in ("RUNNING", "STOPPING", "PENDING"):
                info["status"] = "FAILED"
                info.setdefault("end_time", time.time())
            if info.get("state") == "RUNNING":
                info["state"] = "DEAD"
                info.setdefault("end_time", time.time())
            self.jobs.setdefault(jid, info)

    @staticmethod
    def write_snapshot(path: str, blob: bytes):
        """Atomic fsync'd write; safe to run off the event loop (the blob
        was produced on-loop, so no handler races the tables)."""
        import os
        import tempfile

        d = os.path.dirname(os.path.abspath(path))
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".head_state_")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())  # replace() must publish complete bytes
            os.replace(tmp, path)
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise

    def save_to_file(self, path: str):
        self.write_snapshot(path, self.snapshot())

    def load_from_file(self, path: str) -> bool:
        try:
            with open(path, "rb") as f:
                self.restore(f.read())
            return True
        except FileNotFoundError:
            return False
        except Exception:
            # A corrupt/truncated snapshot must not crash-loop the head —
            # starting empty beats never starting.
            logger.exception("head state %s unreadable; starting fresh", path)
            return False

    # ------------------------------------------------------------- dispatcher

    async def _handle(self, method, header, frames, conn):
        if not flight.ENABLED:
            return await self._handle_inner(method, header, frames, conn)
        # Per-verb dispatch span with queue wait (message arrival → handler
        # start, i.e. head event-loop backlog) recorded separately from
        # handler time — the breakdown the two ROADMAP perf items need.
        t0 = time.monotonic()
        arr = header.get("_fr") or t0
        try:
            out = await self._handle_inner(method, header, frames, conn)
        except faultpoints.DropReply:
            flight.record_dispatch(f"gcs.{method}", "head", header, arr,
                                   t0, 0, "drop_reply")
            raise
        except BaseException as e:
            flight.record_dispatch(f"gcs.{method}", "head", header, arr,
                                   t0, 0, f"error:{type(e).__name__}")
            raise
        flight.record_dispatch(f"gcs.{method}", "head", header, arr, t0)
        return out

    async def _handle_inner(self, method, header, frames, conn):
        fn = getattr(self, "rpc_" + method, None)
        if fn is None:
            raise protocol.RpcError(f"unknown head rpc {method}")
        corr = header.get("corr")
        fut = None
        if corr is not None:
            # Dedup entries are CONNECTION-scoped: a disconnect replays the
            # ledger (leases returned, owned actors reaped), so a retry
            # arriving on a NEW connection must re-execute the verb — the
            # cached outcome describes state the disconnect already rolled
            # back, and replaying e.g. grants would hand out capacity the
            # head no longer tracks.
            serial = self._conn_key(conn)
            cached = self._corr_replies.get(corr)
            if cached is not None and cached[0] != serial:
                self._corr_replies.pop(corr, None)
                cached = None
            if cached is not None:
                payload = cached[1]
                if isinstance(payload, asyncio.Future):
                    # Retry of a request the head is STILL executing (the
                    # client's deadline beat a slow verb): attach to the
                    # in-flight execution instead of double-applying it.
                    return await asyncio.shield(payload)
                # Retry of a request whose reply we already produced (it
                # was dropped in flight): replay the original outcome.
                return payload
            fut = asyncio.get_running_loop().create_future()
            # A failed attempt is retried for real, but its exception must
            # count as retrieved for any attached retry (and the default
            # handler's never-retrieved warning).
            fut.add_done_callback(
                lambda f: f.exception() if not f.cancelled() else None
            )
            self._corr_replies[corr] = (serial, fut)
        act = None
        if faultpoints.ACTIVE:
            # error fails the verb BEFORE it runs (code="unavailable" so
            # retryable clients re-issue); drop is remembered and applied
            # AFTER — the applied-but-unacknowledged partial failure.
            try:
                act = await faultpoints.async_fire(f"gcs.dispatch.{method}")
            except BaseException as e:
                if fut is not None:
                    self._corr_replies.pop(corr, None)
                    fut.set_exception(e)
                raise
        try:
            out = await fn(header, frames, conn)
        except BaseException as e:
            if fut is not None:
                # Real failure: drop the entry so a retry re-executes.
                self._corr_replies.pop(corr, None)
                fut.set_exception(e)
            raise
        if fut is not None:
            self._corr_replies[corr] = (serial, out)
            fut.set_result(out)
            # Evict oldest COMPLETED entries only: popping an in-flight
            # future would let that request's retry double-execute — the
            # overshoot is bounded by the number of concurrent corr verbs.
            while len(self._corr_replies) > self._CORR_CACHE:
                k, v = next(iter(self._corr_replies.items()))
                if isinstance(v[1], asyncio.Future):
                    break
                self._corr_replies.pop(k, None)
        if act == "drop":
            raise faultpoints.DropReply()
        return out

    # ------------------------------------------------------------------- kv

    async def rpc_kv_put(self, h, frames, conn):
        ns = h.get("ns", "")
        val = frames[0] if frames else b""
        self.kv[ns][h["key"]] = val
        self._wal_append({"op": "kv_put", "ns": ns, "key": h["key"],
                          "val": val})
        return {}, []

    async def rpc_kv_get(self, h, frames, conn):
        val = self.kv[h.get("ns", "")].get(h["key"])
        return {"found": val is not None}, ([val] if val is not None else [])

    async def rpc_kv_get_batch(self, h, frames, conn):
        """Multi-key kv_get in one round trip. Workers coalesce concurrent
        function-table misses into one of these, so a burst that lands on
        a fresh worker costs O(unique functions) head RPCs, not O(tasks)
        (reference shape: MGET batching in the GCS table client). Reply
        frames carry only the found values, in key order."""
        ns = self.kv[h.get("ns", "")]
        found = []
        vals = []
        for k in h.get("keys", ()):
            v = ns.get(k)
            found.append(v is not None)
            if v is not None:
                vals.append(v)
        return {"found": found}, vals

    async def rpc_kv_del(self, h, frames, conn):
        existed = self.kv[h.get("ns", "")].pop(h["key"], None) is not None
        if existed:
            self._wal_append({"op": "kv_del", "ns": h.get("ns", ""),
                              "key": h["key"]})
        return {"deleted": existed}, []

    async def rpc_kv_del_prefix(self, h, frames, conn):
        ns = self.kv[h.get("ns", "")]
        doomed = [k for k in ns if k.startswith(h.get("prefix", ""))]
        for k in doomed:
            ns.pop(k, None)
        if not ns:
            self.kv.pop(h.get("ns", ""), None)
        if doomed:
            self._wal_append({"op": "kv_del_prefix", "ns": h.get("ns", ""),
                              "prefix": h.get("prefix", "")})
        return {"deleted": len(doomed)}, []

    async def rpc_kv_keys(self, h, frames, conn):
        prefix = h.get("prefix", "")
        keys = [k for k in self.kv[h.get("ns", "")] if k.startswith(prefix)]
        return {"keys": keys}, []

    async def rpc_kv_exists(self, h, frames, conn):
        return {"exists": h["key"] in self.kv[h.get("ns", "")]}, []

    # ------------------------------------------------------------------ nodes

    async def rpc_register_node(self, h, frames, conn):
        info = NodeInfo(
            node_id=h["node_id"],
            addr=tuple(h["addr"]),
            resources=dict(h["resources"]),
            available=dict(h["resources"]),
            # Label values are strings (as in the reference's label
            # selectors); stringify so the Python and native comparison
            # paths agree for non-string inputs.
            labels={k: str(v) for k, v in h.get("labels", {}).items()},
            conn=conn,
            standby=bool(h.get("standby")),
        )
        # Activation is sticky across re-registration: a blip + reconnect
        # of a node the head already activated (it may hold leases and
        # running tasks, which don't show up in hosted_actors) must not
        # fall back into the invisible standby set.
        prior = self.nodes.get(info.node_id)
        if info.standby and prior is not None and not prior.standby:
            info.standby = False
        self.nodes[info.node_id] = info
        # A fixed-id node (worker_main --node-id) may re-register after a
        # death: drop its tombstone or it would be listed both alive and
        # dead — and the autoscaler's dead_ids check would terminate the
        # healthy instance on every reconcile.
        self.dead_nodes.pop(info.node_id, None)
        # Standby (warm pool) nodes stay OUT of the scheduler until
        # activated; the native scheduler learns about them at activation.
        if self._nsched is not None and not info.standby:
            self._nsched.add_node(info.node_id, info.resources, info.labels)
        # Epoch guards the close handler: the OLD connection of a node that
        # just re-registered (blip + reconnect) must not tear down the NEW
        # registration when its queued close event finally runs.
        info.epoch = next(self._conn_serial)
        self._emit_event("NODE", "NODE_ALIVE", info.node_id,
                         addr=list(info.addr), resources=info.resources)
        conn.peer_info["node_id"] = info.node_id
        conn.on_close = self._make_node_close_handler(info.node_id, info.epoch)
        # Live rejoin after a head restart: the node re-reports the actors
        # it is still hosting; adopt them as ALIVE so handles (and names)
        # keep resolving. Owner tracking died with the old head — adopted
        # actors behave as detached until explicitly killed (reference:
        # GcsInitData replay rebuilding the actor table).
        for a in h.get("hosted_actors", ()):
            existing = self.actors.get(a["actor_id"])
            if existing is not None and existing.state != "DEAD":
                # Same-head re-register (connection blip): the fresh
                # NodeInfo reset availability, so re-deduct what this
                # still-ALIVE actor occupies (PG-backed actors draw from
                # their bundle reservation instead).
                if existing.node_id == info.node_id and not existing.pg_id \
                        and existing.resources:
                    self._node_acquire(info, existing.resources)
                continue
            ainfo = ActorInfo(
                actor_id=a["actor_id"],
                name=a.get("name"),
                namespace=a.get("namespace", "default"),
                state="ALIVE",
                node_id=info.node_id,
                addr=tuple(h["addr"]),
                resources={
                    k: float(v) for k, v in (a.get("resources") or {}).items()
                },
                max_restarts=0,
                creation_frames=[],
                class_name=a.get("class_name", ""),
                detached=True,
                method_meta=dict(a.get("method_meta") or {}),
            )
            self.actors[a["actor_id"]] = ainfo
            if ainfo.name:
                self.named_actors[(ainfo.namespace, ainfo.name)] = (
                    ainfo.actor_id
                )
            # The adopted actor still occupies its slot on the node.
            if ainfo.resources:
                self._node_acquire(info, ainfo.resources)
        # PG bundles reserved on this node also still occupy capacity —
        # re-deduct them from the fresh NodeInfo (same-head re-register;
        # a restarted head has no pgs and this is a no-op).
        for pg_id, pg in self.pgs.items():
            if pg.state != "CREATED":
                continue
            for i, nid in enumerate(pg.bundle_nodes):
                if nid == info.node_id:
                    self._node_acquire(info, pg.bundles[i])
        # Likewise plain leases other (still-connected) clients hold here.
        for ledger in self._conn_leases.values():
            for nid, need, strategy in ledger:
                if nid == info.node_id and not (strategy or {}).get("pg_id"):
                    self._node_acquire(info, need)
        self._wake_waiters()
        self.publish("nodes", {"event": "node_added", "node": info.to_public()})
        return {"ok": True}, []

    def _make_node_close_handler(self, node_id, epoch: int = 0):
        loop = asyncio.get_running_loop()

        def _spawn():
            # During shutdown every node connection closes at once; spawning
            # death handlers then races loop.stop (tasks created but never
            # run → "coroutine was never awaited" warnings) and does no
            # useful work — the cluster is going away.
            if loop.is_closed() or self._shutting_down:
                return
            coro = self._on_node_dead(node_id, epoch=epoch)
            try:
                t = loop.create_task(coro)
            except RuntimeError:
                coro.close()  # loop torn down between check and create
            else:
                self._death_tasks.add(t)
                t.add_done_callback(self._death_tasks.discard)

        def _on_close(conn):
            if not loop.is_closed() and not self._shutting_down:
                try:
                    loop.call_soon_threadsafe(_spawn)
                except RuntimeError:
                    pass  # loop torn down concurrently
        return _on_close

    async def _on_node_dead(self, node_id: str, reason: str = "connection lost",
                            epoch: int = 0):
        info = self.nodes.get(node_id)
        if info is None or not info.alive:
            return
        if epoch and getattr(info, "epoch", 0) != epoch:
            # Stale close event from a connection the node already replaced
            # by re-registering: the live registration stays up.
            return
        info.alive = False
        if self._nsched is not None:
            self._nsched.set_alive(node_id, False)
        # Planned departures (drain_node before a deliberate teardown,
        # cluster shutdown) are expected: warning-level "node dead" lines
        # for them read as failures in bench/CI tails and mask real ones.
        log = (
            logger.debug
            if getattr(self, "_shutting_down", False) or reason == "drained"
            else logger.warning
        )
        log("node %s dead: %s", node_id[:8], reason)
        self._emit_event("NODE", "NODE_DEAD", node_id, message=reason)
        self.publish("nodes", {"event": "node_dead", "node_id": node_id})
        # Log plane: keep a post-mortem tail for the dead node but shrink
        # its ring (a full 10k-line deque per dead node would grow the head
        # without bound under autoscaler churn), and cap how many dead-node
        # tails are retained at all.
        buf = self.log_buffer.get(node_id)
        if buf is not None and len(buf) > 500:
            self.log_buffer[node_id] = deque(
                itertools.islice(buf, len(buf) - 500, None), maxlen=500
            )
        dead_with_logs = [
            nid for nid in self.log_buffer
            if nid not in self.nodes or not self.nodes[nid].alive
        ]
        for nid in dead_with_logs[: max(len(dead_with_logs) - 32, 0)]:
            self.log_buffer.pop(nid, None)
        # Fail/restart actors that lived there.
        for actor in list(self.actors.values()):
            if actor.node_id == node_id and actor.state in ("ALIVE", "PENDING"):
                await self._on_actor_dead(actor, f"node {node_id[:8]} died")
        # Release PG reservations on that node.
        for pg in self.pgs.values():
            for i, nid in enumerate(pg.bundle_nodes):
                if nid == node_id:
                    pg.bundle_nodes[i] = None
        # Drop the dead node's metric series.
        self.worker_metrics = {
            wid: rec for wid, rec in self.worker_metrics.items()
            if rec.get("node_id") != node_id
        }
        # Actors/PG reservations are drained above and lease releases tolerate
        # a missing node, so retire the node now: scheduler state goes away
        # entirely (best_node scans linearly), the public record moves to the
        # bounded tombstone cache.
        if self._nsched is not None:
            self._nsched.remove_node(node_id)
        info = self.nodes.pop(node_id, None)
        if info is not None:
            info.conn = None
            self.dead_nodes[node_id] = info
            while len(self.dead_nodes) > self._DEAD_NODE_CACHE:
                self.dead_nodes.pop(next(iter(self.dead_nodes)))

    async def rpc_cluster_stacks(self, h, frames, conn):
        """Fan out all-thread stack dumps to every alive node (reference:
        ``ray stack`` + the reporter agent's py-spy hooks; workers answer
        natively from sys._current_frames — util/debug.py)."""
        alive = [
            n for n in self.nodes.values() if n.alive and n.conn is not None
        ]

        async def one(node):
            try:
                hh, _ = await asyncio.wait_for(
                    node.conn.call("dump_stacks", {}), timeout=10
                )
                return node.node_id, hh.get("stacks", "")
            except Exception as e:
                return (
                    node.node_id,
                    f"<unavailable: {type(e).__name__}: {e}>",
                )

        # concurrent fan-out: a partially-hung cluster (the very case a
        # stack tool exists for) costs one timeout, not one per dead node
        results = await asyncio.gather(*(one(n) for n in alive))
        return {"nodes": dict(results)}, []

    async def rpc_flight_snapshot(self, h, frames, conn):
        """Fan ``flight_drain`` out to every alive node and return the
        clock-annotated per-process snapshots (this process's ring first).

        Each node snapshot gets an ``offset``: seconds to add to its wall
        times to land on the head's clock, estimated Cristian-style from
        the drain RPC midpoint vs. the node's reported wall clock — so the
        merged trace (flight.merge_snapshots) is head-clock aligned."""
        drain = bool(h.get("drain", True))
        local = flight.drain() if drain else flight.snapshot()
        local["offset"] = 0.0
        # Drain every connected PROCESS, not just registered nodes:
        # remote drivers (init(address=...)) hold the submission-side
        # spans — exactly the costs this instrument measures. Every peer
        # with a CoreWorker answers flight_drain; tool clients (sync CLI,
        # dashboard) reply without a "flight" payload and are skipped.
        targets = {}
        for n in self.nodes.values():
            if n.alive and n.conn is not None:
                targets[id(n.conn)] = (n.conn, n.node_id[:8])
        for conn in (self.server.connections if self.server else ()):
            targets.setdefault(id(conn), (conn, None))

        async def one(conn, label):
            t_send = time.time()
            try:
                hh, _ = await asyncio.wait_for(
                    conn.call("flight_drain", {"drain": drain}),
                    timeout=10,
                )
            except (asyncio.TimeoutError, protocol.RpcError,
                    protocol.ConnectionLost, OSError) as e:
                logger.debug("flight_drain from %s failed: %s",
                             label or conn.name, e)
                return None
            t_recv = time.time()
            s = hh.get("flight")
            if not s:
                return None
            s["offset"] = (t_send + t_recv) / 2.0 - float(
                s.get("now") or t_recv
            )
            if label:
                s.setdefault("proc", label)
            elif s.get("proc") == "driver":
                # Remote drivers: keep their track groups distinct.
                s["proc"] = f"driver-{s.get('pid')}"
            return s

        results = await asyncio.gather(
            *(one(conn, label) for conn, label in targets.values())
        )
        # One snapshot per PROCESS: a peer reachable over two connections
        # answers the drain once with events, once empty — keep the
        # fuller reply (and never this process twice). Keyed by the
        # recorder's process token, not the OS pid: pids collide across
        # hosts.
        def skey(s):
            return s.get("token") or ("pid", s.get("pid"))

        by_proc = {skey(local): local}
        for s in results:
            if not s:
                continue
            prev = by_proc.get(skey(s))
            if prev is None or len(s.get("events") or ()) > len(
                prev.get("events") or ()
            ):
                by_proc[skey(s)] = s
        return {"snapshots": list(by_proc.values()),
                "enabled": flight.ENABLED}, []

    async def rpc_node_debug(self, h, frames, conn):
        """Relay a debug RPC (memory_profile, dump_stacks) to one node."""
        node = self.nodes.get(h.get("node_id") or "")
        if node is None or not node.alive or node.conn is None:
            raise protocol.RpcError(f"node {h.get('node_id')!r} unavailable")
        method = h.get("method")
        if method not in ("memory_profile", "dump_stacks", "cpu_profile",
                          "xla_profile"):
            raise protocol.RpcError(f"node_debug: unsupported {method!r}")
        fwd = {
            k: h[k]
            for k in ("action", "top", "duration_s", "hz", "logdir")
            if k in h
        }
        # a profiler capture is written out after its duration_s, which on
        # a loaded chip host takes far longer than the capture itself
        slack = 300 if method == "xla_profile" else 30
        hh, _ = await asyncio.wait_for(
            node.conn.call(method, fwd),
            timeout=float(h.get("duration_s") or 0) + slack,
        )
        # strip the forwarded reply's RPC envelope fields
        return {k: v for k, v in hh.items() if k not in ("i", "r")}, []

    async def rpc_drain_node(self, h, frames, conn):
        await self._on_node_dead(h["node_id"], "drained")
        return {}, []

    def _public_nodes(self) -> list:
        """Alive nodes plus dead tombstones — the state API and the
        autoscaler (phantom-instance reclaim) both need the dead ones."""
        return [
            n.to_public()
            for n in (*self.nodes.values(), *self.dead_nodes.values())
        ]

    async def rpc_get_nodes(self, h, frames, conn):
        return {"nodes": self._public_nodes()}, []

    # -------------------------------------------------------------- scheduler

    def _node_acquire(self, node: NodeInfo, need: Dict[str, float]):
        """Node-level resource acquisition: Python mirror + native scheduler."""
        _acquire(node.available, need)
        if self._nsched is not None:
            self._nsched.acquire(node.node_id, need)

    def _node_release(self, node: NodeInfo, need: Dict[str, float]):
        _release(node.available, need)
        # Invariant clamp: a release the (possibly restarted) head never
        # granted — e.g. a worker finishing a pre-restart busy lease — must
        # not inflate availability past the node's physical total.
        for k, total in node.resources.items():
            if node.available.get(k, 0.0) > total:
                node.available[k] = total
        if self._nsched is not None:
            self._nsched.release(node.node_id, need)

    def _schedulable_nodes(self, need, labels=None, node_id=None):
        out = []
        for n in self.nodes.values():
            if not n.alive or n.standby:
                continue
            if node_id is not None and n.node_id != node_id:
                continue
            if labels and any(
                n.labels.get(k) != str(v) for k, v in labels.items()
            ):
                continue
            out.append(n)
        return out

    def _pick_node(self, need: Dict[str, float], strategy: dict,
                   avoid=None) -> Optional[NodeInfo]:
        """Hybrid policy (reference: ``scheduling/policy/hybrid_scheduling_policy.cc``):
        pack onto earliest nodes with room, spread when strategy requests it.
        ``avoid``: soft blocklist (e.g. memory-pressured nodes) — used only
        when an alternative fits."""
        pg_id = strategy.get("pg_id")
        if pg_id:
            return self._pick_pg_node(need, pg_id, strategy.get("bundle_index", -1))
        if self._nsched is not None:
            node_id = self._nsched.best_node(
                need,
                spread=bool(strategy.get("spread")),
                affinity_node=strategy.get("node_id"),
                labels=strategy.get("labels"),
                avoid=avoid or (),
            )
            if node_id:
                return self.nodes.get(node_id)
            return self._activate_standby(need, strategy)
        cands = self._schedulable_nodes(
            need, strategy.get("labels"), strategy.get("node_id")
        )
        fitting = [n for n in cands if _fits(n.available, need)]
        if avoid:
            preferred = [n for n in fitting if n.node_id not in avoid]
            if preferred:
                fitting = preferred
        if not fitting:
            return self._activate_standby(need, strategy)
        if strategy.get("spread"):
            self._schedule_rr += 1
            return fitting[self._schedule_rr % len(fitting)]
        # pack: most-utilized first for binpacking; stable by id
        fitting.sort(key=lambda n: (sum(n.available.values()), n.node_id))
        return fitting[0]

    def _pick_pg_node(self, need, pg_id, bundle_index) -> Optional[NodeInfo]:
        pg = self.pgs.get(pg_id)
        if pg is None or pg.state != "CREATED":
            return None
        indices = [bundle_index] if bundle_index >= 0 else range(len(pg.bundles))
        for i in indices:
            node_id = pg.bundle_nodes[i]
            if node_id is None:
                continue
            node = self.nodes.get(node_id)
            reserved = self.pg_reserved[pg_id][i]
            if node and node.alive and _fits(reserved, need):
                _acquire(reserved, need)
                return node
        return None

    def _activate_standby(self, need, strategy) -> Optional["NodeInfo"]:
        """Warm worker pool: when demand outgrows schedulable capacity,
        flip a fitting STANDBY node into the active set and hand it
        straight to the caller — the first task/actor push lands on an
        already-initialized process instead of waiting out a cold node
        spawn. No-op (None) when the pool is empty."""
        labels = (strategy or {}).get("labels")
        want_id = (strategy or {}).get("node_id")
        for n in self.nodes.values():
            if not n.standby or not n.alive:
                continue
            if want_id is not None and n.node_id != want_id:
                continue
            if labels and any(
                n.labels.get(k) != str(v) for k, v in labels.items()
            ):
                continue
            if not _fits(n.available, need):
                continue
            self._activate_node(n)
            # Taskpath plane: the grant built from this pick is tagged
            # "warm" so the driver can name a queued task's wait
            # warm-pool-hit instead of lease-wait (popped by rpc_lease).
            n.__dict__["_rt_warm_grant"] = True
            return n
        return None

    def _activate_node(self, n: "NodeInfo"):
        """Standby -> schedulable: register with the native scheduler,
        announce the capacity, and wake anyone blocked on placement."""
        n.standby = False
        if self._nsched is not None:
            self._nsched.add_node(n.node_id, n.resources, n.labels)
        self._emit_event("NODE", "NODE_ACTIVATED", n.node_id,
                         resources=n.resources)
        self.publish("nodes", {"event": "node_added", "node": n.to_public()})
        self._wake_waiters()

    async def rpc_activate_node(self, h, frames, conn):
        """Explicitly activate a standby node (LocalCluster.add_node's
        warm fast path). Idempotent: activating an active node is ok."""
        n = self.nodes.get(h.get("node_id") or "")
        if n is None or not n.alive:
            return {"found": False}, []
        if n.standby:
            self._activate_node(n)
        return {"found": True, "node_id": n.node_id}, []

    async def rpc_lease(self, h, frames, conn):
        """Grant up to ``count`` leases for ``resources`` (one task slot each).

        Reference shape: NormalTaskSubmitter's RequestWorkerLease
        (``task_submission/normal_task_submitter.h:271``) against the raylet's
        ClusterLeaseManager; here the head is the single lease authority.
        """
        if faultpoints.ACTIVE:
            # Before ANY acquisition: an injected grant failure must leave
            # the availability ledger untouched.
            await faultpoints.async_fire("gcs.lease.grant")
        need = {k: float(v) for k, v in h.get("resources", {}).items()}
        strategy = h.get("strategy", {})
        count = h.get("count", 1)
        timeout = h.get("timeout", 30.0)
        avoid = set(h.get("avoid") or ())
        grants = []
        deadline = time.monotonic() + timeout
        while len(grants) < count:
            if getattr(conn, "_rt_conn_dead", False):
                break  # requester died while waiting; don't grant to a ghost
            node = self._pick_node(need, strategy, avoid)
            if node is not None:
                if not strategy.get("pg_id"):
                    self._node_acquire(node, need)
                grant = {"node_id": node.node_id, "addr": list(node.addr)}
                if node.__dict__.pop("_rt_warm_grant", False):
                    grant["warm"] = 1
                grants.append(grant)
                self._track_conn_lease(conn, node.node_id, need, strategy)
                continue
            if grants:
                break  # return partial grants rather than blocking
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            # Ask workers to return cached idle leases before blocking:
            # a recent task burst can leave every CPU pinned by slots that
            # are idle but inside their reaper window.
            self._maybe_reclaim_leases([need])
            fut = asyncio.get_running_loop().create_future()
            self._pending_waiters.append(fut)
            self.pending_demands[id(fut)] = {
                "resources": dict(need),
                "count": count - len(grants),  # bundles still unsatisfied
                "since": time.time(),
            }
            try:
                await asyncio.wait_for(fut, timeout=min(remaining, 1.0))
            except asyncio.TimeoutError:
                pass
            finally:
                self.pending_demands.pop(id(fut), None)
        return {"grants": grants, "resources": need}, []

    async def rpc_release_lease(self, h, frames, conn):
        need = {k: float(v) for k, v in h.get("resources", {}).items()}
        strategy = h.get("strategy", {})
        self._untrack_conn_lease(conn, h.get("node_id"), need, strategy)
        pg_id = strategy.get("pg_id")
        if pg_id:
            pg = self.pgs.get(pg_id)
            reserved = self.pg_reserved.get(pg_id)
            if pg is not None and reserved is not None:
                # return to the bundle's reservation
                idx = strategy.get("bundle_index", -1)
                node_id = h.get("node_id")
                indices = [idx] if idx >= 0 else range(len(pg.bundles))
                for i in indices:
                    if pg.bundle_nodes[i] == node_id:
                        _release(reserved[i], need)
                        break
            elif pg is not None:
                # PG was removed while this lease was outstanding: the bundle
                # reservation is gone, so the loaned resources go straight
                # back to the node (remove_pg only returned the unloaned
                # remainder).
                node = self.nodes.get(h.get("node_id") or "")
                if node is not None and node.alive:
                    self._node_release(node, need)
        else:
            node = self.nodes.get(h["node_id"])
            if node is not None:
                self._node_release(node, need)
        self._wake_waiters()
        return {}, []

    def _maybe_reclaim_leases(self, needs: List[Dict[str, float]]):
        """Publish lease_reclaim only when it could actually help and at
        most ~4x/s: an infeasible request (bundle bigger than any node's
        TOTAL capacity) must not flush every worker's lease cache once per
        wait iteration for its whole timeout — that would disable the
        cache cluster-wide for concurrent workloads."""
        now = time.monotonic()
        if now - self._last_reclaim < 0.25:
            return
        alive = [n for n in self.nodes.values() if n.alive]
        for need in needs:
            if not any(
                all(n.resources.get(k, 0.0) >= v for k, v in need.items())
                for n in alive
            ):
                return  # can't fit even on an empty node: reclaim won't help
        self._last_reclaim = now
        self.publish("lease_reclaim", {})

    def _wake_waiters(self):
        waiters, self._pending_waiters = self._pending_waiters, []
        for fut in waiters:
            if not fut.done():
                fut.set_result(None)
        # Freed resources may satisfy a placement group whose creation RPC
        # already returned PENDING; without this retry it would pend
        # forever even on an empty cluster.
        self._schedule_pending_pgs()

    # ----------------------------------------------------------------- actors

    async def rpc_create_actor(self, h, frames, conn):
        """Register + schedule an actor (reference: GcsActorManager
        ``HandleRegisterActor``/``HandleCreateActor``
        ``gcs/actor/gcs_actor_manager.cc:310/:429`` + GcsActorScheduler)."""
        if faultpoints.ACTIVE:
            # Fires before registration: an injected failure leaves no
            # half-created actor behind for the retry to collide with.
            await faultpoints.async_fire("gcs.actor.create")
        return await self._create_one_actor(h, frames, conn)

    async def rpc_create_actor_batch(self, h, frames, conn):
        """Batched actor creation: one head RPC covers a whole submission
        burst (reference: the async registration queue in GcsActorManager —
        N registrations amortize one RPC envelope each here). Items
        schedule concurrently; each reports {"ok", "addr", "node_id"} or
        {"err"} so one unschedulable actor never fails its batchmates.
        The caller's correlation id covers the WHOLE batch: a retry after
        a dropped reply replays every item's original outcome via the
        dispatch-level dedup cache — no double-created actors."""
        if faultpoints.ACTIVE:
            # Before ANY item registers: an injected batch failure is
            # retryable-unavailable with nothing half-applied.
            await faultpoints.async_fire("gcs.create_actor_batch")
        per_item = protocol.unpack_multi_frames(
            h.get("fcounts", []), frames
        )

        async def one(item, item_frames):
            try:
                if faultpoints.ACTIVE:
                    await faultpoints.async_fire("gcs.actor.create")
                extras, _ = await self._create_one_actor(
                    item, item_frames, conn
                )
                return {"ok": True, **extras}
            except asyncio.CancelledError:
                raise
            except protocol.RpcError as e:
                return {"err": str(e)}
            except Exception as e:
                return {"err": f"{type(e).__name__}: {e}"}

        results = await asyncio.gather(
            *(one(i, f) for i, f in zip(h.get("items", ()), per_item))
        )
        return {"results": list(results)}, []

    async def _create_one_actor(self, h, frames, conn):
        actor_id = h["actor_id"]
        name = h.get("name") or None
        ns = h.get("namespace", "default")
        if name:
            key = (ns, name)
            if key in self.named_actors:
                existing = self.actors.get(self.named_actors[key])
                if existing is not None and existing.state != "DEAD":
                    if h.get("get_if_exists"):
                        return {"existing": existing.to_public()}, []
                    raise protocol.RpcError(
                        f"actor name '{name}' already taken in namespace '{ns}'"
                    )
        info = ActorInfo(
            actor_id=actor_id,
            name=name,
            namespace=ns,
            state="PENDING",
            node_id=None,
            addr=None,
            resources={k: float(v) for k, v in h.get("resources", {}).items()},
            max_restarts=h.get("max_restarts", 0),
            creation_frames=list(frames),
            class_name=h.get("class_name", ""),
            pg_id=(h.get("strategy") or {}).get("pg_id"),
            bundle_index=(h.get("strategy") or {}).get("bundle_index", -1),
            detached=h.get("lifetime") == "detached",
            method_meta=dict(h.get("method_meta") or {}),
        )
        self.actors[actor_id] = info
        if name:
            self.named_actors[(ns, name)] = actor_id
        if not info.detached:
            # Non-detached actors die with their owner (reference:
            # GcsActorManager destroys an actor when its owner worker/job
            # exits — ``gcs_actor_manager.cc OnWorkerDead/OnJobFinished``).
            # The owner is whoever issued create_actor on this connection.
            self._track_actor_owner(conn, actor_id)
        ok = await self._schedule_actor(info, h.get("strategy") or {})
        if not ok:
            info.state = "DEAD"
            info.death_reason = "unschedulable: insufficient resources"
            raise protocol.RpcError(info.death_reason)
        return {"addr": list(info.addr), "node_id": info.node_id}, []

    async def _schedule_actor(self, info: ActorInfo, strategy: dict) -> bool:
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if info.state == "DEAD":
                # Killed while pending (e.g. owner disconnected mid-wait):
                # placing it now would orphan an ALIVE actor whose cleanup
                # already ran and permanently leak its node resources.
                return False
            node = self._pick_node(info.resources, strategy)
            if node is None:
                fut = asyncio.get_running_loop().create_future()
                self._pending_waiters.append(fut)
                # actors are the third demand source next to leases and PGs
                self.pending_demands[id(fut)] = {
                    "resources": dict(info.resources), "count": 1,
                    "since": time.time(),
                }
                try:
                    await asyncio.wait_for(fut, timeout=1.0)
                except asyncio.TimeoutError:
                    pass
                finally:
                    self.pending_demands.pop(id(fut), None)
                continue
            if not strategy.get("pg_id"):
                self._node_acquire(node, info.resources)
            try:
                await node.conn.call(
                    "create_actor",
                    {
                        "actor_id": info.actor_id,
                        # Public metadata the hosting worker re-reports if
                        # the head restarts and it re-registers (live
                        # rejoin; reference: gcs_init_data replay).
                        "meta": {
                            "name": info.name,
                            "namespace": info.namespace,
                            "class_name": info.class_name,
                            "resources": info.resources,
                            "detached": info.detached,
                            "method_meta": info.method_meta,
                        },
                    },
                    info.creation_frames,
                )
            except protocol.RpcError as e:
                # Actor __init__ raised: actor is born dead; surface the error.
                if not strategy.get("pg_id"):
                    self._node_release(node, info.resources)
                info.state = "DEAD"
                info.death_reason = str(e)
                self.publish(f"actor:{info.actor_id}", info.to_public())
                raise
            except protocol.ConnectionLost:
                continue  # node died mid-create; try another
            if info.state == "DEAD":
                # Owner disconnected during the create RPC: its cleanup saw
                # PENDING (nothing to kill yet), so undo the placement here.
                try:
                    await node.conn.call(
                        "kill_actor", {"actor_id": info.actor_id}
                    )
                except (protocol.RpcError, protocol.ConnectionLost) as e:
                    logger.debug(
                        "kill_actor %s during create-undo failed: %s",
                        info.actor_id, e,
                    )
                if not strategy.get("pg_id"):
                    self._node_release(node, info.resources)
                    self._wake_waiters()
                return False
            info.node_id = node.node_id
            info.addr = node.addr
            info.state = "ALIVE"
            self._emit_event("ACTOR", "ACTOR_ALIVE", info.actor_id,
                             class_name=info.class_name,
                             node_id=node.node_id,
                             restarts_used=info.restarts_used)
            self.publish(f"actor:{info.actor_id}", info.to_public())
            return True
        return False

    def _release_actor_placement(self, actor: ActorInfo):
        """Return the actor's reserved resources to its (still-alive) node or
        PG bundle. No-op when the node is dead: its whole availability died
        with it."""
        if actor.node_id is None:
            return
        node = self.nodes.get(actor.node_id)
        if node is None or not node.alive:
            return
        if actor.pg_id:
            reserved = self.pg_reserved.get(actor.pg_id)
            pg = self.pgs.get(actor.pg_id)
            if reserved is None or pg is None:
                # PG removed while the actor was alive: its loaned bundle
                # resources return straight to the node.
                self._node_release(node, actor.resources)
                self._wake_waiters()
                return
            indices = (
                [actor.bundle_index]
                if actor.bundle_index >= 0
                else [
                    i for i, nid in enumerate(pg.bundle_nodes)
                    if nid == actor.node_id
                ]
            )
            if indices:
                _release(reserved[indices[0]], actor.resources)
        else:
            self._node_release(node, actor.resources)
        self._wake_waiters()

    async def _on_actor_dead(self, actor: ActorInfo, reason: str):
        if actor.state == "DEAD":
            return
        restartable = actor.restarts_used < actor.max_restarts or actor.max_restarts == -1
        if restartable:
            self._release_actor_placement(actor)
            actor.restarts_used += 1
            actor.state = "RESTARTING"
            self._emit_event("ACTOR", "ACTOR_RESTARTING", actor.actor_id,
                             message=reason,
                             restarts_used=actor.restarts_used)
            actor.death_reason = reason
            self.publish(f"actor:{actor.actor_id}", actor.to_public())
            strategy = {}
            if actor.pg_id:
                strategy = {"pg_id": actor.pg_id, "bundle_index": actor.bundle_index}
            try:
                ok = await self._schedule_actor(actor, strategy)
            except protocol.RpcError:
                ok = False
            if not ok:
                actor.state = "DEAD"
                self.publish(f"actor:{actor.actor_id}", actor.to_public())
        else:
            actor.state = "DEAD"
            actor.death_reason = reason
            self._emit_event("ACTOR", "ACTOR_DEAD", actor.actor_id,
                             message=reason,
                             class_name=actor.class_name)
            if actor.name:
                self.named_actors.pop((actor.namespace, actor.name), None)
            self._release_actor_placement(actor)
            self.publish(f"actor:{actor.actor_id}", actor.to_public())

    async def rpc_actor_exited(self, h, frames, conn):
        """A node reports that an actor exited (clean exit or crash)."""
        actor = self.actors.get(h["actor_id"])
        if actor is None:
            return {}, []
        if h.get("clean"):
            actor.max_restarts = 0  # intentional exit is never restarted
        await self._on_actor_dead(actor, h.get("reason", "actor exited"))
        return {}, []

    def _conn_key(self, conn) -> int:
        """Stable per-connection key + one close hook that tears down ALL
        connection-scoped state (owned actors, outstanding leases)."""
        key = getattr(conn, "_rt_serial", None)
        if key is not None:
            return key
        key = conn._rt_serial = next(self._conn_serial)
        prev = conn.on_close
        loop = asyncio.get_event_loop()

        def _on_close(c):
            # Set BEFORE the async cleanup runs: an rpc_lease that was
            # still waiting for resources when the client died completes
            # later on this loop — it must see the flag and return its
            # grant instead of recording a zombie ledger entry after the
            # ledger was already drained.
            c._rt_conn_dead = True
            if prev is not None:
                try:
                    prev(c)
                except Exception:
                    logger.exception("chained on_close failed")
            if self._shutting_down or loop.is_closed():
                self._conn_actors.pop(key, None)
                self._conn_leases.pop(key, None)
                return
            try:
                loop.call_soon_threadsafe(
                    lambda: spawn_logged(loop, self._on_conn_closed(key),
                                         "gcs.on_conn_closed")
                )
            except RuntimeError:
                pass

        conn.on_close = _on_close
        return key

    async def _on_conn_closed(self, key: int):
        self._release_conn_leases(key)
        await self._on_actor_owner_closed(key)

    def _track_actor_owner(self, conn, actor_id: str):
        self._conn_actors.setdefault(self._conn_key(conn), set()).add(actor_id)

    def _track_conn_lease(self, conn, node_id: str, resources: dict,
                          strategy: dict):
        key = self._conn_key(conn)
        if getattr(conn, "_rt_conn_dead", False):
            # Granted after (or while) the client's disconnect cleanup
            # drains its ledger: hand the resources straight back without
            # touching the ledger (it may hold other not-yet-drained
            # entries).
            self._release_lease_entry(node_id, resources, strategy)
            self._wake_waiters()
            return
        self._conn_leases.setdefault(key, []).append(
            (node_id, resources, strategy)
        )

    def _untrack_conn_lease(self, conn, node_id: str, resources: dict,
                            strategy: dict):
        ledger = self._conn_leases.get(getattr(conn, "_rt_serial", -1))
        if not ledger:
            return
        pg = (strategy or {}).get("pg_id")
        for i, (nid, res, strat) in enumerate(ledger):
            if nid == node_id and res == resources \
                    and (strat or {}).get("pg_id") == pg:
                del ledger[i]
                return

    def _release_lease_entry(self, node_id: str, need: dict, strategy: dict):
        """Return one lease's resources: PG leases to their bundle
        reservation (or the node if the PG is already gone — mirrors
        rpc_release_lease), plain leases to the node."""
        pg_id = (strategy or {}).get("pg_id")
        if pg_id:
            pg = self.pgs.get(pg_id)
            reserved = self.pg_reserved.get(pg_id)
            if pg is not None and reserved is not None:
                idx = (strategy or {}).get("bundle_index", -1)
                indices = [idx] if idx >= 0 else range(len(pg.bundles))
                for i in indices:
                    if pg.bundle_nodes[i] == node_id:
                        _release(reserved[i], need)
                        break
            elif pg is not None:
                node = self.nodes.get(node_id)
                if node is not None and node.alive:
                    self._node_release(node, need)
            return
        node = self.nodes.get(node_id)
        if node is not None and node.alive:
            self._node_release(node, need)

    def _release_conn_leases(self, key: int):
        """Client connection gone: return every lease it still held."""
        for node_id, need, strategy in self._conn_leases.pop(key, ()):
            self._release_lease_entry(node_id, need, strategy)
        self._wake_waiters()

    async def _on_actor_owner_closed(self, key: int):
        """Owner connection gone: kill its non-detached actors (they may be
        ALIVE on some node, or PENDING). Named entries are dropped so the
        name becomes reusable."""
        for actor_id in self._conn_actors.pop(key, set()):
            actor = self.actors.get(actor_id)
            if actor is None or actor.state == "DEAD":
                continue
            actor.max_restarts = 0
            node = self.nodes.get(actor.node_id) if actor.node_id else None
            if node is not None and node.conn is not None and actor.state == "ALIVE":
                try:
                    await node.conn.call(
                        "kill_actor", {"actor_id": actor.actor_id}
                    )
                except (protocol.RpcError, protocol.ConnectionLost) as e:
                    logger.debug(
                        "kill_actor %s on owner disconnect failed "
                        "(node death will reap it): %s", actor.actor_id, e,
                    )
            await self._on_actor_dead(actor, "owner disconnected")

    async def rpc_kill_actor(self, h, frames, conn):
        actor = self.actors.get(h["actor_id"])
        if actor is None:
            return {"found": False}, []
        if h.get("no_restart", True):
            actor.max_restarts = 0
        node = self.nodes.get(actor.node_id) if actor.node_id else None
        if node is not None and node.conn is not None and actor.state == "ALIVE":
            try:
                await node.conn.call("kill_actor", {"actor_id": actor.actor_id})
            except (protocol.RpcError, protocol.ConnectionLost) as e:
                logger.debug(
                    "kill_actor RPC to node %s failed (actor %s marked "
                    "dead regardless): %s", actor.node_id, actor.actor_id, e,
                )
        await self._on_actor_dead(actor, "killed via kill_actor")
        return {"found": True}, []

    async def rpc_get_actor(self, h, frames, conn):
        if "name" in h:
            aid = self.named_actors.get((h.get("namespace", "default"), h["name"]))
            if aid is None:
                return {"found": False}, []
            actor = self.actors.get(aid)
        else:
            actor = self.actors.get(h["actor_id"])
        if actor is None:
            return {"found": False}, []
        return {"found": True, "actor": actor.to_public()}, []

    async def rpc_list_actors(self, h, frames, conn):
        return {"actors": [a.to_public() for a in self.actors.values()]}, []

    # ------------------------------------------------------- placement groups

    async def rpc_create_pg(self, h, frames, conn):
        """Two-phase bundle reservation (reference: GcsPlacementGroupScheduler
        prepare/commit ``gcs_placement_group_scheduler.h:115-117``). On a
        single head the phases collapse, but bundles are still all-or-nothing."""
        pg_id = h["pg_id"]
        bundles = [
            {k: float(v) for k, v in b.items()} for b in h["bundles"]
        ]
        strategy = h.get("pg_strategy", "PACK")
        pg = PlacementGroupInfo(
            pg_id=pg_id, bundles=bundles, strategy=strategy, state="PENDING",
            bundle_nodes=[None] * len(bundles), name=h.get("name", ""),
        )
        self.pgs[pg_id] = pg
        deadline = time.monotonic() + h.get("timeout", 30.0)
        while time.monotonic() < deadline:
            if pg.state == "REMOVED":  # removed while we waited
                return {"state": "REMOVED"}, []
            if self._commit_pg(pg):
                return {"state": "CREATED", "bundle_nodes": pg.bundle_nodes}, []
            # Same demand-driven reclaim as rpc_lease: idle cached slots on
            # workers are the usual reason an otherwise-free cluster can't
            # place a bundle.
            self._maybe_reclaim_leases(bundles)
            fut = asyncio.get_running_loop().create_future()
            self._pending_waiters.append(fut)
            try:
                await asyncio.wait_for(fut, timeout=1.0)
            except asyncio.TimeoutError:
                pass
        # The group STAYS registered as PENDING: whenever resources free
        # (_wake_waiters), the head retries it — the reference reschedules
        # pending placement groups the same way
        # (gcs_placement_group_manager SchedulePendingPlacementGroups);
        # clients poll get_pg and observe the late CREATED.
        return {"state": "PENDING"}, []

    def _commit_pg(self, pg) -> bool:
        """All-or-nothing bundle commit; publishes + flips state on
        success. Shared by the creation RPC and the pending-PG retry."""
        if pg.state == "CREATED":
            return True
        placement = self._try_place_bundles(pg)
        if placement is None:
            return False
        for i, node in enumerate(placement):
            self._node_acquire(node, pg.bundles[i])
            pg.bundle_nodes[i] = node.node_id
        self.pg_reserved[pg.pg_id] = [dict(b) for b in pg.bundles]
        pg.state = "CREATED"
        self._emit_event("PLACEMENT_GROUP", "PG_CREATED", pg.pg_id,
                         strategy=pg.strategy, bundles=len(pg.bundles))
        self.publish(f"pg:{pg.pg_id}", pg.to_public())
        return True

    def _schedule_pending_pgs(self):
        for pg in list(self.pgs.values()):
            if pg.state == "PENDING":
                self._commit_pg(pg)

    def _try_place_bundles(self, pg) -> Optional[List[NodeInfo]]:
        # Work on a scratch copy of availability so it's all-or-nothing.
        # Standby (warm pool) nodes are excluded: bundles reserve capacity
        # long-term, which would silently consume the instant-activation
        # reserve (lease/actor demand activates standbys via _pick_node).
        scratch = {
            n.node_id: dict(n.available)
            for n in self.nodes.values() if n.alive and not n.standby
        }
        chosen: List[str] = []
        nodes_sorted = sorted(
            (n for n in self.nodes.values() if n.alive and not n.standby),
            key=lambda n: n.node_id,
        )
        for i, bundle in enumerate(pg.bundles):
            placed = None
            if pg.strategy in ("STRICT_PACK",):
                cands = [chosen[0]] if chosen else [n.node_id for n in nodes_sorted]
            elif pg.strategy == "STRICT_SPREAD":
                cands = [n.node_id for n in nodes_sorted if n.node_id not in chosen]
            elif pg.strategy == "SPREAD":
                cands = sorted(
                    (n.node_id for n in nodes_sorted),
                    key=lambda nid: chosen.count(nid),
                )
            else:  # PACK: prefer reusing nodes already chosen
                cands = sorted(
                    (n.node_id for n in nodes_sorted),
                    key=lambda nid: (0 if nid in chosen else 1, nid),
                )
            for nid in cands:
                if nid in scratch and _fits(scratch[nid], bundle):
                    _acquire(scratch[nid], bundle)
                    placed = nid
                    break
            if placed is None:
                return None
            chosen.append(placed)
        return [self.nodes[nid] for nid in chosen]

    async def rpc_remove_pg(self, h, frames, conn):
        pg = self.pgs.get(h["pg_id"])
        if pg is None or pg.state == "REMOVED":
            return {}, []
        self._emit_event("PLACEMENT_GROUP", "PG_REMOVED", pg.pg_id)
        if pg.state == "CREATED":
            for i, nid in enumerate(pg.bundle_nodes):
                node = self.nodes.get(nid) if nid else None
                if node is not None and node.alive:
                    # Return whatever of the bundle is not currently loaned out;
                    # loaned resources return via release_lease.
                    remainder = self.pg_reserved.get(pg.pg_id)
                    self._node_release(
                        node,
                        remainder[i] if remainder is not None else pg.bundles[i],
                    )
        pg.state = "REMOVED"
        self.pg_reserved.pop(pg.pg_id, None)
        self._wake_waiters()
        self.publish(f"pg:{pg.pg_id}", pg.to_public())
        return {}, []

    async def rpc_get_pg(self, h, frames, conn):
        pg = self.pgs.get(h["pg_id"])
        if pg is None:
            return {"found": False}, []
        return {"found": True, "pg": pg.to_public()}, []

    async def rpc_list_pgs(self, h, frames, conn):
        return {"pgs": [p.to_public() for p in self.pgs.values()]}, []

    # ----------------------------------------------------------------- pubsub

    async def rpc_subscribe(self, h, frames, conn):
        self.subscribers[h["channel"]].append(conn)
        return {}, []

    async def rpc_publish(self, h, frames, conn):
        self.publish(h["channel"], h.get("data"), frames)
        return {}, []

    # ---------------------------------------------------------- log plane

    async def rpc_worker_logs(self, h, frames, conn):
        """A worker's log monitor pushed new lines: buffer a bounded ring
        per node for rt logs/dashboard, fan out live to subscribed
        drivers (reference behavior: log_monitor publish + driver echo)."""
        buf = self.log_buffer.get(h["node_id"])
        if buf is None:
            buf = self.log_buffer[h["node_id"]] = deque(
                maxlen=self._LOG_BUFFER_LINES
            )
        pid, stream = h.get("pid"), h.get("stream", "stdout")
        for line in h.get("lines", ()):
            buf.append((stream, pid, line))
        # "shared": the worker's spawn job is not any registered driver job
        # (rt start / autoscaler workers get a random JobID) — such lines
        # belong to no one driver, so every driver may echo them. Without
        # this, shared-cluster topologies would never see remote prints.
        job = h.get("job_id", "")
        self.publish("worker_logs", {
            "node_id": h["node_id"], "pid": pid, "stream": stream,
            "job_id": job, "shared": job not in self.jobs,
            "lines": h.get("lines", []),
        })
        return {}, []

    async def rpc_get_logs(self, h, frames, conn):
        """Read back buffered worker logs: optional node filter + tail
        count (rt logs / dashboard logs view)."""
        node = h.get("node_id")
        try:
            tail = int(h["tail"]) if h.get("tail") is not None else 1000
            tail = max(tail, 0)
        except (TypeError, ValueError):
            tail = 1000
        out = []
        items = (
            [(node, self.log_buffer.get(node))] if node
            else list(self.log_buffer.items())
        )
        items = [(nid, buf) for nid, buf in items if buf]
        # The budget is split ACROSS nodes (lines carry no global order, so
        # a concat-then-truncate would silently drop whole earlier nodes).
        # Fair allocation, quiet nodes' unused share flowing to busy ones:
        # walk ascending by buffer size, each node taking at most an even
        # split of what remains.
        remaining = tail
        left = len(items)
        for nid, buf in sorted(items, key=lambda x: len(x[1])):
            take = min(len(buf), remaining // left) if left else 0
            left -= 1
            remaining -= take
            if take <= 0:
                continue
            # islice, not list(buf)[-n:]: the dashboard polls this every
            # 2s and a full 10k-entry copy per node per poll is pure churn.
            for stream, pid, line in itertools.islice(
                buf, len(buf) - take, None
            ):
                out.append({"node_id": nid, "pid": pid, "stream": stream,
                            "line": line})
        return {"lines": out}, []

    def publish(self, channel: str, data, frames: List[bytes] = ()):
        if faultpoints.ACTIVE:
            try:
                # error and drop both lose the publish for every
                # subscriber (pubsub is fire-and-forget by contract).
                if faultpoints.fire("gcs.pubsub.publish") == "drop":
                    return
            except ConnectionError as e:
                logger.debug("injected publish loss on %s: %s", channel, e)
                return
        for conn in list(self.subscribers.get(channel, [])):
            try:
                conn.notify("pubsub", {"channel": channel, "data": data}, frames)
            except protocol.ConnectionLost:
                self.subscribers[channel].remove(conn)

    # --------------------------------------------------------- object dir

    async def rpc_object_register(self, h, frames, conn):
        # Owners flush registrations in batches ("items") — one notify per
        # put-burst, not per object; single oid/meta kept for compat.
        # Each entry is stamped with the head's wall clock ("_t"): the
        # leak detector's grace window measures age on ONE clock instead
        # of trusting N workers' clocks (a re-registration — e.g. a spill
        # transition — refreshes the stamp, which is correct: the entry
        # was just proven live).
        now = time.time()
        if "items" in h:
            items = h["items"]
            # Both batch shapes are live: dict from rpc-level callers,
            # pair list from the worker's ordered ref-op drain.
            pairs = items.items() if isinstance(items, dict) else items
            for oid, meta in pairs:
                if isinstance(meta, dict):
                    meta["_t"] = now
                self.object_dir[oid] = meta
        else:
            meta = h["meta"]
            if isinstance(meta, dict):
                meta["_t"] = now
            self.object_dir[h["oid"]] = meta
        return {}, []

    async def rpc_object_lookup(self, h, frames, conn):
        meta = self.object_dir.get(h["oid"])
        return {"found": meta is not None, "meta": meta}, []

    async def rpc_object_lookup_batch(self, h, frames, conn):
        """Multi-oid directory lookup: one round-trip resolves a whole
        get()/wait() batch (reference: the owner-resolved directory serves
        location batches, ``ownership_object_directory.h``). ``metas[i]``
        is None for oids without a directory entry (inline objects live
        only in their owner's memory store and are pulled from the owner)."""
        d = self.object_dir
        return {"metas": [d.get(oid) for oid in h["oids"]]}, []

    async def rpc_object_free(self, h, frames, conn):
        metas = [self.object_dir.pop(oid, None) for oid in h["oids"]]
        # Fan out so borrower processes evict cached copies/pins.
        self.publish("object_free", {"oids": h["oids"]})
        return {"metas": [m for m in metas if m]}, []

    # ------------------------------------------------------------- jobs/state

    async def rpc_export_events(self, h, frames, conn):
        """Recent structured export events (reference: the aggregator's
        event query surface); filterable by source/event type."""
        if getattr(self, "events", None) is None:
            return {"events": []}, []
        return {"events": self.events.recent(
            limit=h.get("limit", 100),
            source_type=h.get("source_type"),
            event_type=h.get("event_type"),
        )}, []

    async def rpc_register_job(self, h, frames, conn):
        self.jobs[h["job_id"]] = {
            "job_id": h["job_id"], "start_time": time.time(), "state": "RUNNING",
        }
        self._wal_append({"op": "job", "job": self.jobs[h["job_id"]]})
        self._emit_event("JOB", "JOB_STARTED", h["job_id"])
        return {}, []

    async def rpc_list_jobs(self, h, frames, conn):
        return {"jobs": list(self.jobs.values())}, []

    async def rpc_list_objects(self, h, frames, conn):
        """Directory listing with server-side filters and honest
        truncation: filters ([(key, op, value)], op in =/!=) run over the
        flattened row BEFORE the limit slice, and the reply reports
        {recorded, dropped} like ``list_task_events`` does — a truncated
        listing is visible, never a silent slice."""
        limit = h.get("limit", 1000)
        filters = h.get("filters") or ()
        rows = []
        for oid, meta in list(self.object_dir.items()):
            meta = meta if isinstance(meta, dict) else {}
            row = {
                "object_id": oid,
                "bytes": int(meta.get("size") or 0),
                "node": meta.get("node"),
                "owner": meta.get("owner"),
                "spilled": bool(meta.get("spill")),
                "task": oid[:48],
                "meta": meta,
            }
            keep = True
            for key, op, value in filters:
                have = str(row.get(key))
                if op == "=":
                    keep = have == str(value)
                elif op == "!=":
                    keep = have != str(value)
                else:
                    raise protocol.RpcError(
                        f"unsupported filter op {op!r} (want = or !=)"
                    )
                if not keep:
                    break
            if keep:
                rows.append(row)
        recorded = len(rows)
        if limit:
            rows = rows[:limit]
        return {"objects": rows, "recorded": recorded,
                "dropped": max(recorded - len(rows), 0)}, []

    async def rpc_cluster_load(self, h, frames, conn):
        """Autoscaler feed: unsatisfied demands + pending PG bundles + the
        per-node resource view (reference: gcs_autoscaler_state_manager.cc)."""
        pending_pgs = [
            {"pg_id": pg.pg_id, "bundles": pg.bundles, "strategy": pg.strategy}
            for pg in self.pgs.values() if pg.state == "PENDING"
        ]
        return {
            "pending": list(self.pending_demands.values()),
            "pending_pgs": pending_pgs,
            "nodes": self._public_nodes(),
        }, []

    async def rpc_metrics_push(self, h, frames, conn):
        """Latest metric snapshot per worker (reference: per-node metrics
        agent collecting for the Prometheus scrape). node_id rides along so
        node death can drop the worker's series (stale gauges poison
        Prometheus aggregates)."""
        self.worker_metrics[h["worker_id"]] = {
            "node_id": h.get("node_id"), "metrics": h["metrics"],
        }
        return {}, []

    async def rpc_metrics_snapshot(self, h, frames, conn):
        return {
            "snapshots": {
                wid: rec["metrics"] for wid, rec in self.worker_metrics.items()
            },
            # worker -> node map: the /metrics rollup aggregates series
            # per NODE (one scrape endpoint covering the whole cluster).
            "nodes": {
                wid: rec.get("node_id")
                for wid, rec in self.worker_metrics.items()
            },
        }, []

    async def rpc_memory_summary(self, h, frames, conn):
        """Object-plane cluster snapshot: fan ``memstat_drain`` out to
        every connected process (the ``flight_snapshot`` pattern — remote
        drivers own objects too; tool clients answer without a payload
        and are skipped), and return the raw parts the memtrack join
        needs: per-process accounting snapshots, the head's directory
        (bounded, with honest truncation counts), the task-id → name map
        for creating-task attribution, and the alive-node set."""
        targets = {}
        for n in self.nodes.values():
            if n.alive and n.conn is not None:
                targets[id(n.conn)] = (n.conn, n.node_id)
        for c in (self.server.connections if self.server else ()):
            targets.setdefault(id(c), (c, None))

        async def one(c, label):
            try:
                hh, _ = await asyncio.wait_for(
                    c.call("memstat_drain", {}), timeout=10,
                )
            except (asyncio.TimeoutError, protocol.RpcError,
                    protocol.ConnectionLost, OSError) as e:
                logger.debug("memstat_drain from %s failed: %s",
                             label or c.name, e)
                return None
            s = hh.get("memstat")
            if s and label:
                s.setdefault("node", label)
            return s

        results = await asyncio.gather(
            *(one(c, label) for c, label in targets.values())
        )
        # One snapshot per PROCESS (a peer reachable over two connections
        # answers twice): keyed by worker id, keep the first.
        by_worker = {}
        for s in results:
            if s:
                by_worker.setdefault(s.get("worker") or id(s), s)
        limit = h.get("limit", 10000)
        directory = [
            {"oid": oid, "meta": meta}
            for oid, meta in itertools.islice(
                self.object_dir.items(), limit or None
            )
        ]
        names = {}
        for e in self.task_events:
            tid = e.get("task_id")
            if tid:
                names[tid] = e.get("name")
        recorded = len(self.object_dir)
        return {
            "snapshots": list(by_worker.values()),
            "directory": directory,
            "recorded": recorded,
            "dropped": max(recorded - len(directory), 0),
            "tasks": names,
            "nodes": [n.node_id for n in self.nodes.values() if n.alive],
            "now": time.time(),
            "enabled": bool(by_worker),
        }, []

    async def rpc_task_event(self, h, frames, conn):
        return await self.rpc_task_events(
            {"events": [h["event"]]}, frames, conn
        )

    def builtin_metrics(self) -> Dict[str, float]:
        """Head-derived cluster series for /metrics (reference: the GCS-side
        series the reference dashboard's Grafana panels graph)."""
        counters = self._task_state_counts
        return {
            "rt_nodes_alive": float(
                sum(1 for n in self.nodes.values() if n.alive)
            ),
            "rt_nodes_dead": float(len(self.dead_nodes)),
            "rt_actors_alive": float(
                sum(1 for a in self.actors.values() if a.state == "ALIVE")
            ),
            "rt_placement_groups": float(len(self.pgs)),
            "rt_pending_demands": float(len(self.pending_demands)),
            "rt_object_dir_entries": float(len(self.object_dir)),
            "rt_tasks_finished_total": float(counters.get("FINISHED", 0)),
            "rt_tasks_failed_total": float(counters.get("FAILED", 0)),
        }

    async def rpc_task_events(self, h, frames, conn):
        """Task-event sink (reference: GcsTaskManager fed by the per-worker
        ``task_event_buffer.h`` in 4Hz batches); bounded ring for the state
        API. Oversized string fields are clamped so one hostile event
        cannot dominate the ring's memory."""
        events = h.get("events", [])
        ring = self.task_events
        for e in events:
            s = e.get("state")
            if s:
                self._task_state_counts[s] = (
                    self._task_state_counts.get(s, 0) + 1
                )
            name = e.get("name")
            if isinstance(name, str) and len(name) > 256:
                e["name"] = name[:256]
            ring.append(e)
        self._task_events_total += len(events)
        return {}, []

    async def rpc_list_task_events(self, h, frames, conn):
        limit = h.get("limit", 1000)
        events = list(self.task_events)
        return {
            "events": events[-limit:] if limit else events,
            "recorded": self._task_events_total,
            "dropped": max(self._task_events_total - len(events), 0),
        }, []

    # ------------------------------------------------------ job submission
    # Reference analog: dashboard/modules/job/job_manager.py:58 — submitted
    # entrypoints run as supervised subprocesses with captured logs and a
    # PENDING→RUNNING→SUCCEEDED/FAILED/STOPPED lifecycle. The head owns them
    # here (round-1 single head process).

    def _job_log_path(self, sub_id: str) -> str:
        import os
        import tempfile

        d = os.path.join(tempfile.gettempdir(), "ray_tpu", "jobs")
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, f"{sub_id}.log")

    async def rpc_submit_job(self, h, frames, conn):
        import os
        import subprocess
        import uuid

        sub_id = h.get("submission_id") or f"raysubmit_{uuid.uuid4().hex[:16]}"
        if sub_id in self.job_procs:
            raise protocol.RpcError(f"job {sub_id} already exists")
        env = dict(os.environ)
        runtime_env = h.get("runtime_env") or {}
        env.update(runtime_env.get("env_vars") or {})
        env["RAY_TPU_ADDRESS"] = f"{self.addr[0]}:{self.addr[1]}"
        # The entrypoint must be able to import the framework regardless of
        # its cwd (python puts the script dir, not cwd, on sys.path).
        import ray_tpu

        pkg_parent = os.path.dirname(os.path.dirname(ray_tpu.__file__))
        existing = env.get("PYTHONPATH")
        env["PYTHONPATH"] = (
            pkg_parent + os.pathsep + existing if existing else pkg_parent
        )
        log_path = self._job_log_path(sub_id)
        logf = open(log_path, "wb")
        try:
            proc = subprocess.Popen(
                h["entrypoint"], shell=True, stdout=logf,
                stderr=subprocess.STDOUT, env=env,
                cwd=runtime_env.get("working_dir") or None,
            )
        except OSError as e:
            logf.close()
            raise protocol.RpcError(f"spawn failed: {e}")
        logf.close()
        self.job_procs[sub_id] = proc
        self.jobs[sub_id] = {
            "job_id": sub_id, "submission_id": sub_id, "type": "SUBMISSION",
            "entrypoint": h["entrypoint"], "status": "RUNNING",
            "start_time": time.time(), "end_time": None, "log_path": log_path,
            "metadata": h.get("metadata") or {},
        }
        self._wal_append({"op": "job", "job": dict(self.jobs[sub_id])})
        spawn_logged(None, self._watch_job(sub_id, proc), "gcs.watch_job")
        return {"submission_id": sub_id}, []

    async def _watch_job(self, sub_id: str, proc):
        while proc.poll() is None:
            await asyncio.sleep(0.1)
        info = self.jobs.get(sub_id)
        if info is not None and info["status"] in ("RUNNING", "STOPPING"):
            if info.get("stop_requested"):
                info["status"] = "STOPPED"
            else:
                info["status"] = (
                    "SUCCEEDED" if proc.returncode == 0 else "FAILED"
                )
            info["end_time"] = time.time()
            self._wal_append({"op": "job", "job": dict(info)})

    async def rpc_job_status(self, h, frames, conn):
        info = self.jobs.get(h["submission_id"])
        if info is None:
            return {"found": False}, []
        return {"found": True, "job": info}, []

    async def rpc_job_logs(self, h, frames, conn):
        info = self.jobs.get(h["submission_id"])
        if info is None or "log_path" not in info:
            return {"found": False}, []
        try:
            with open(info["log_path"], "rb") as f:
                data = f.read()
        except OSError:
            data = b""
        return {"found": True}, [data]

    async def rpc_stop_job(self, h, frames, conn):
        proc = self.job_procs.get(h["submission_id"])
        info = self.jobs.get(h["submission_id"])
        if proc is None or info is None:
            return {"stopped": False}, []
        if proc.poll() is None:
            # SIGTERM with SIGKILL escalation; STOPPED is reported only once
            # the process actually exits (_watch_job), so a trap-and-ignore
            # entrypoint can't look terminal while holding resources.
            info["stop_requested"] = True
            info["status"] = "STOPPING"
            proc.terminate()
            spawn_logged(None, self._escalate_stop(proc),
                         "gcs.escalate_stop")
        return {"stopped": True}, []

    async def _escalate_stop(self, proc, grace_s: float = 3.0):
        deadline = time.monotonic() + grace_s
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                return
            await asyncio.sleep(0.1)
        try:
            proc.kill()
        except ProcessLookupError:
            pass

    async def rpc_ping(self, h, frames, conn):
        return {"t": time.time()}, []
