"""Cluster bootstrap: start the head service and spawn node processes.

Reference analog: ``python/ray/_private/node.py`` (``Node.start_head_processes``
:1344, ``start_raylet`` :1144) + ``services.py``. Round-1 shape: the head
service runs on the driver's core event loop (same RPC surface as an external
head, so it can be moved out-of-process later); nodes are subprocesses.
"""
from __future__ import annotations

import atexit
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ray_tpu._private.backoff import Backoff
from ray_tpu._private.ids import JobID, NodeID

import logging

logger = logging.getLogger(__name__)


class NodeHandle:
    def __init__(self, proc: subprocess.Popen, node_id: str, resources: dict,
                 cgroup=None, cgroup_driver=None, standby: bool = False):
        self.proc = proc
        self.node_id = node_id
        self.resources = resources
        self.cgroup = cgroup
        self._cgroup_driver = cgroup_driver
        # True for warm-pool members spawned with --standby: until the
        # head confirms activation they will register scheduler-invisible,
        # so cluster-size accounting must not expect them to turn active.
        self.standby_spawn = standby

    def _drop_cgroup(self):
        if self.cgroup and self._cgroup_driver is not None:
            self._cgroup_driver.remove(self.cgroup)
            self.cgroup = None

    def kill(self, sig=None):
        try:
            self.proc.kill()
        except ProcessLookupError:
            pass
        self._drop_cgroup()

    def terminate(self):
        try:
            self.proc.terminate()
        except ProcessLookupError:
            pass
        self._drop_cgroup()

    def alive(self) -> bool:
        if self.proc.poll() is None:
            return True
        # the process died on its own: its cgroup must not outlive it
        self._drop_cgroup()
        return False


def spawn_node(
    gcs_addr,
    job_id: JobID,
    resources: Dict[str, float],
    labels: Optional[Dict[str, str]] = None,
    env: Optional[Dict[str, str]] = None,
    log_level: str = "WARNING",
    standby: bool = False,
) -> NodeHandle:
    node_id = NodeID.from_random().hex()
    cmd = [
        sys.executable,
        "-m",
        "ray_tpu._private.worker_main",
        "--gcs-host", gcs_addr[0],
        "--gcs-port", str(gcs_addr[1]),
        "--resources", json.dumps(resources),
        "--labels", json.dumps(labels or {}),
        "--job-id", job_id.hex(),
        "--node-id", node_id,
        "--log-level", log_level,
    ]
    if standby:
        # Warm worker pool member: registers with the head but stays out
        # of the scheduler until activated (gcs._activate_standby).
        cmd.append("--standby")
    child_env = dict(os.environ)
    if env:
        child_env.update(env)
    # Log plane: every spawned worker gets a session dir to redirect its
    # stdio into (worker_main + log_monitor). init() passes a timestamped
    # one; standalone spawns (rt start, autoscaler local provider) default
    # to a per-head dir so all of a cluster's workers share one place.
    child_env.setdefault(
        "RT_SESSION_DIR", f"/tmp/ray_tpu/session_p{gcs_addr[1]}"
    )
    # Node processes must not inherit a driver-held TPU.
    proc = subprocess.Popen(cmd, env=child_env)
    cgroup = driver = None
    from ray_tpu._private import cgroups

    if cgroups.enabled():
        # Resource isolation (reference: cgroup2/cgroup_manager.h, gated
        # like enable_resource_isolation): CPU weight from the node's CPU
        # resource; memory capped at the node's memory resource when the
        # operator declared one. Unavailable/unwritable -> disabled.
        driver = cgroups.CgroupDriver()
        mem = resources.get("memory")
        cgroup = driver.create(
            node_id[:12],
            cpu_shares=resources.get("CPU"),
            memory_limit_bytes=int(mem) if mem else None,
        )
        if cgroup and not driver.add_pid(cgroup, proc.pid):
            driver.remove(cgroup)
            cgroup = None
        if cgroup is None and driver.available:
            logger.warning("cgroup isolation requested but not applied "
                           "for node %s", node_id[:8])
    return NodeHandle(proc, node_id, resources, cgroup, driver,
                      standby=standby)


class LocalCluster:
    """In-process test/single-machine cluster (reference analog:
    ``python/ray/cluster_utils.py:137 Cluster`` — multi-node simulated by
    multiple node processes on one machine)."""

    def __init__(self, head_service, gcs_addr, job_id: JobID, driver_worker,
                 session_dir: Optional[str] = None):
        self.head = head_service
        self.gcs_addr = gcs_addr
        self.job_id = job_id
        self.driver = driver_worker
        self.session_dir = session_dir
        self.nodes: List[NodeHandle] = []
        # Warm worker pool (rt_config.warm_workers): preforked STANDBY
        # node processes — registered, initialized, unschedulable until
        # activated. add_node() consumes one instead of a cold spawn; the
        # head auto-activates them when demand outgrows capacity.
        self.warm: List[NodeHandle] = []
        self.warm_resources: Dict[str, float] = {"CPU": 1}
        atexit.register(self.shutdown)

    def start_warm_pool(self, count: int,
                        resources: Optional[Dict[str, float]] = None,
                        env: Optional[Dict[str, str]] = None):
        """Prefork ``count`` standby node processes (non-blocking): they
        boot and register in the background, forming the instant-capacity
        reserve add_node() and the head's auto-activation draw from."""
        if resources:
            self.warm_resources = dict(resources)
        if self.session_dir:
            env = dict(env or {})
            env.setdefault("RT_SESSION_DIR", self.session_dir)
        for _ in range(max(count - len(self.warm), 0)):
            self.warm.append(spawn_node(
                self.gcs_addr, self.job_id, dict(self.warm_resources),
                env=env, standby=True,
            ))

    def _activate_warm(self, handle: NodeHandle,
                       timeout: float = 30.0) -> bool:
        """Ask the head to flip a standby node active; waits out the
        standby's registration if it is still booting."""
        deadline = time.monotonic() + timeout
        poll = Backoff(base=0.02, cap=0.25)
        while time.monotonic() < deadline and handle.alive():
            try:
                h = self.driver.run_sync(
                    self.driver._head_call(
                        "activate_node", {"node_id": handle.node_id}
                    ),
                    timeout=10,
                )[0]
            except Exception as e:
                logger.debug("warm activate %s failed: %s",
                             handle.node_id[:8], e)
                return False
            if h.get("found"):
                return True
            poll.sleep()  # not registered yet: still booting
        return False

    def add_node(
        self,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        env: Optional[Dict[str, str]] = None,
        wait: bool = True,
    ) -> NodeHandle:
        resources = dict(resources or {"CPU": 1})
        resources.setdefault("CPU", 1)
        # Warm fast path: an add matching a standby's OWN spawn spec (and
        # no custom labels/env) activates it — milliseconds instead of a
        # 2-4s cold process spawn. Matching per handle, not against
        # warm_resources: the pool can hold members preforked under an
        # earlier start_warm_pool spec.
        if not labels and not env:
            self.warm = [w for w in self.warm if w.alive()]
            wh = next(
                (w for w in self.warm if w.resources == resources), None
            )
            if wh is not None:
                self.warm.remove(wh)
                # Track it either way (shutdown must reap the process);
                # on activation failure it stays standby at the head, so
                # alive_node_ids_expected() won't count it and the cold
                # spawn below still satisfies wait_for_nodes.
                self.nodes.append(wh)
                if self._activate_warm(wh):
                    wh.standby_spawn = False
                    return wh
        # Added nodes log into the SAME session dir as init-spawned ones —
        # a cluster's log files must not split across two dirs.
        if self.session_dir:
            env = dict(env or {})
            env.setdefault("RT_SESSION_DIR", self.session_dir)
        handle = spawn_node(self.gcs_addr, self.job_id, resources, labels, env)
        self.nodes.append(handle)
        if wait:
            self.wait_for_nodes(len(self.alive_node_ids_expected()))
        return handle

    def alive_node_ids_expected(self):
        out = []
        for n in self.nodes:
            if not n.alive():
                continue
            # A tracked node the head still holds in the standby set (a
            # failed warm activation) is alive but by design invisible to
            # _head_active_nodes — counting it would make wait_for_nodes'
            # target unreachable. Same for a standby spawn that hasn't
            # registered yet (activation timed out pre-registration): it
            # will register AS STANDBY, never active. Unregistered cold
            # spawns count: they're booting toward active.
            info = self.head.nodes.get(n.node_id)
            if info is not None:
                if getattr(info, "standby", False):
                    continue
            elif getattr(n, "standby_spawn", False):
                continue
            out.append(n.node_id)
        return out

    def _head_active_nodes(self):
        """Registered, schedulable nodes in the head's view (standby pool
        members don't count toward expected cluster size)."""
        return [
            n for n in self.head.nodes.values()
            if n.alive and not getattr(n, "standby", False)
        ]

    def wait_for_nodes(self, count: int, timeout: float = 30.0):
        deadline = time.monotonic() + timeout
        poll = Backoff(base=0.02, cap=0.1)
        while time.monotonic() < deadline:
            if len(self._head_active_nodes()) >= count:
                return
            poll.sleep()
        raise TimeoutError(
            f"cluster: only {len(self._head_active_nodes())}"
            f"/{count} nodes registered"
        )

    def kill_node(self, handle: NodeHandle):
        handle.kill()
        deadline = time.monotonic() + 10
        poll = Backoff(base=0.02, cap=0.1)
        while time.monotonic() < deadline:
            info = self.head.nodes.get(handle.node_id)
            if info is None or not info.alive:
                return
            poll.sleep()

    def remove_node(self, handle: NodeHandle, timeout: float = 10.0):
        """Graceful (planned) node teardown: drain at the head FIRST —
        the head logs the departure at debug, reschedules nothing onto
        the node, and the subsequent connection close is a no-op — then
        terminate the process. ``kill_node`` stays the crash-test path
        (unannounced death, warning-level 'node dead')."""
        try:
            self.driver.run_sync(
                self.driver._head_call(
                    "drain_node", {"node_id": handle.node_id}
                ),
                timeout=10,
            )
        except Exception as e:
            logger.debug("drain_node %s failed: %s", handle.node_id[:8], e)
        handle.terminate()
        deadline = time.monotonic() + timeout
        poll = Backoff(base=0.02, cap=0.1)
        while handle.alive() and time.monotonic() < deadline:
            poll.sleep()
        if handle.alive():
            handle.kill()
        if handle in self.nodes:
            self.nodes.remove(handle)

    def shutdown(self):
        atexit.unregister(self.shutdown)
        # Planned teardown: node-death events that follow are expected and
        # must not emit failure-looking warnings (they mask real failures
        # in bench/CI logs).
        if self.head is not None:
            self.head._shutting_down = True
        doomed = self.nodes + self.warm
        for n in doomed:
            n.terminate()
        deadline = time.monotonic() + 3
        for n in doomed:
            poll = Backoff(base=0.02, cap=0.1)
            while n.alive() and time.monotonic() < deadline:
                poll.sleep()
            if n.alive():
                n.kill()
                # Reap before returning: a node that held the chip keeps
                # the TPU runtime's lock until the process is gone, and the
                # caller's next init() starts a node that needs it.
                n.proc.wait()
        self.nodes.clear()
        self.warm.clear()
