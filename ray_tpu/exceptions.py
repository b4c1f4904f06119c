"""User-facing exceptions (reference: ``python/ray/exceptions.py``)."""
from __future__ import annotations


class RayTpuError(Exception):
    """Base class for all framework errors."""


class TaskError(RayTpuError):
    """A task raised an exception during execution.

    Re-raised at ``get()`` on the caller, wrapping the remote traceback
    (reference: ``RayTaskError``).
    """

    def __init__(self, cause_repr: str, traceback_str: str = "", cause=None):
        self.cause_repr = cause_repr
        self.traceback_str = traceback_str
        self.cause = cause
        super().__init__(f"Task failed: {cause_repr}\n{traceback_str}")


class ActorError(RayTpuError):
    """The actor died before or during this method call (reference: RayActorError)."""


class ActorDiedError(ActorError):
    def __init__(self, actor_id=None, reason: str = "actor died"):
        self.actor_id = actor_id
        self.reason = reason
        super().__init__(f"Actor {actor_id} died: {reason}")


class ActorUnavailableError(ActorError):
    """Actor temporarily unreachable; the call may be retried."""


class ObjectLostError(RayTpuError):
    """Object could not be found or reconstructed (reference: ObjectLostError)."""

    def __init__(self, object_id=None, reason: str = "object lost"):
        self.object_id = object_id
        super().__init__(f"Object {object_id} lost: {reason}")


class ObjectStoreFullError(RayTpuError):
    """The shared-memory object store is out of capacity."""


class GetTimeoutError(RayTpuError, TimeoutError):
    """``get(..., timeout=)`` expired before the object was ready."""


class WorkerCrashedError(RayTpuError):
    """The worker process executing the task died unexpectedly."""


class NodeDiedError(RayTpuError):
    """A node was marked dead by the head's health checker."""


class OutOfMemoryError(WorkerCrashedError):
    """A node rejected/killed the task under memory pressure (reference:
    memory-monitor-driven worker killing; subclasses WorkerCrashedError so
    the submitter's retry path treats it as retriable)."""


class RuntimeEnvSetupError(RayTpuError):
    """Failed to materialize the runtime environment for a task/actor."""


class AcceleratorMismatchError(RayTpuError):
    """A process whose node was granted ``TPU`` found JAX running on
    another platform."""


class PlacementGroupUnavailableError(RayTpuError):
    """Placement group cannot be scheduled (e.g. infeasible slice topology)."""


class PendingCallsLimitExceededError(RayTpuError):
    """Actor's max_pending_calls budget exhausted (backpressure signal)."""


class LintError(RayTpuError):
    """Static-analysis check failed at ``@remote`` decoration time.

    Raised when ``RAY_TPU_LINT=1`` and ``ray_tpu.lint`` finds a
    distributed-correctness hazard (non-picklable closure capture,
    blocking get() in a task, unplaceable resources, ...) in the
    decorated function/class — before the bad task ever ships.
    ``findings`` holds the :class:`ray_tpu.lint.Finding` objects.
    """

    def __init__(self, findings):
        self.findings = list(findings)
        lines = [f.format() if hasattr(f, "format") else str(f)
                 for f in self.findings]
        super().__init__(
            "lint failed (%d finding%s):\n%s\nSuppress a line with "
            "'# raytpu: ignore[RULE]' or unset RAY_TPU_LINT."
            % (len(lines), "s" if len(lines) != 1 else "", "\n".join(lines))
        )
