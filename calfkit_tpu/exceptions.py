"""Public exception types (reference: calfkit/exceptions.py:1-233) and the
authoritative ``x-mesh-error-type`` ↔ exception-class table.

The table (ISSUE 5 satellite) is the single place the wire fault vocabulary
and the Python exception surface meet: the fault publisher in
:mod:`calfkit_tpu.nodes.base` uses :func:`error_type_for` to give a typed
exception a typed fault code (instead of harvesting it as a generic
``mesh.node_error``), and the caller-side classifier uses
:data:`RETRIABLE_FAULT_TYPES` / :func:`exception_for` to decide whether a
fault is worth a backoff-retry and which local type represents it.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

from calfkit_tpu.models.error_report import FaultTypes

if TYPE_CHECKING:
    from calfkit_tpu.models.error_report import ErrorReport
    from calfkit_tpu.models.session_context import Envelope

__all__ = [
    "CalfkitError",
    "NodeFaultError",
    "ClientTimeoutError",
    "ClientClosedError",
    "DeserializationError",
    "MeshUnavailableError",
    "RegistryConfigError",
    "SeamContractError",
    "LifecycleConfigError",
    "ProvisioningError",
    "InferenceError",
    "EngineOverloadedError",
    "EngineWedgedError",
    "DeadlineExceededError",
    "RunCancelledError",
    "RunOrphanedError",
    "TenantRateLimitedError",
    "FAULT_TYPE_BY_EXCEPTION",
    "RETRIABLE_FAULT_TYPES",
    "error_type_for",
    "exception_for",
]


class CalfkitError(Exception):
    """Base for all framework exceptions."""


class NodeFaultError(CalfkitError):
    """The typed-fault mint gesture.

    User code raises this (or the kernel mints it) to produce a typed
    ``FaultMessage``; catching it at the client surfaces the ErrorReport.
    """

    def __init__(
        self, report: "ErrorReport", envelope: "Envelope | None" = None
    ):
        self.report = report
        # the terminal fault envelope when available (client side): exposes
        # degradation facts like state_elided to callers
        self.envelope = envelope
        super().__init__(report.describe())


class ClientTimeoutError(CalfkitError, TimeoutError):
    pass


class ClientClosedError(CalfkitError):
    pass


class DeserializationError(CalfkitError):
    pass


class MeshUnavailableError(CalfkitError):
    def __init__(self, message: str, *, reason: str = "unavailable"):
        self.reason = reason
        super().__init__(message)


class RegistryConfigError(CalfkitError):
    """Bad handler registration (duplicate route, invalid pattern, ...)."""


class SeamContractError(CalfkitError):
    """A policy seam had the wrong arity or returned an illegal value."""


class LifecycleConfigError(CalfkitError):
    """Worker lifecycle hook/resource misconfiguration."""


class ProvisioningError(CalfkitError):
    pass


class InferenceError(CalfkitError):
    """Local inference backend failure."""


class EngineOverloadedError(CalfkitError):
    """Bounded admission shed this request (ISSUE 5).

    Raised AT SUBMIT when an engine lane's queue is at
    ``RuntimeConfig.max_pending`` (or when a stalled consumer tripped the
    ``max_out_blocks`` delivery bound, or a draining worker refused the
    call).  Typed and retriable by contract: the caller may back off and
    retry the same call against the same or another engine — nothing was
    partially executed.
    """

    def __init__(
        self,
        message: str,
        *,
        lane: str = "short",
        pending: int = 0,
        limit: int = 0,
    ):
        self.lane = lane
        self.pending = pending
        self.limit = limit
        super().__init__(message)


class EngineWedgedError(CalfkitError):
    """The engine's dispatch-progress watchdog tripped (ISSUE 9): work was
    pending but no dispatch landed for ``RuntimeConfig.watchdog_stall_s``
    — the "wedged device grant" state.  Requests caught
    in (or queued behind) the wedge are faulted with this instead of
    silently burning their deadlines.  Typed and RETRIABLE by contract:
    the caller observed no tokens from this engine, so the same call may
    run whole on another replica — the fleet gateway's failover path
    treats it exactly like a shed.
    """

    def __init__(self, message: str, *, stalled_s: float = 0.0):
        self.stalled_s = stalled_s
        super().__init__(message)


class DeadlineExceededError(CalfkitError, TimeoutError):
    """The request's absolute deadline (``x-mesh-deadline``) passed.

    Minted wherever the expiry is first observed — engine admission, the
    queued-request reaper, or a mesh hop receiving an already-expired
    call.  NOT retriable: the caller's budget is spent, retrying would
    burn capacity for an answer nobody is waiting for.
    """


class RunCancelledError(CalfkitError):
    """The run's caller published a mesh ``cancel`` before this call
    started executing — the admission gate hit the correlation id's
    tombstone (see :func:`calfkit_tpu.cancellation.was_cancelled`) and
    refused to execute for a caller that already left.  NOT retriable:
    the cancel was deliberate.
    """


class RunOrphanedError(CalfkitError):
    """The run's CALLER liveness lease lapsed (ISSUE 10): heartbeats on
    ``mesh.caller_liveness`` stopped for longer than the lease TTL (hard
    caller death), or the caller released the lease on clean close — and
    the engine's orphan reaper abandoned the run, freeing its slot,
    pages, and prefix refs for callers that are still alive.  NOT
    retriable: there is nobody left to answer.  This is what makes
    fire-and-forget ``send()`` safe — the client-side failover
    supervisor (ISSUE 9) cannot cover a run nobody awaits.
    """

    def __init__(self, message: str, *, lease_id: str = "", lapsed_s: float = 0.0):
        self.lease_id = lease_id
        self.lapsed_s = lapsed_s
        super().__init__(message)


class TenantRateLimitedError(CalfkitError):
    """The node kernel's per-tenant token bucket refused this call
    (ISSUE 20): the tenant (lease id where present, else caller client
    id) spent its admission budget.  Refused BEFORE the engine's queues
    — nothing was admitted, no slot or page was held.  Typed and
    RETRIABLE by contract: the bucket refills on the deadline clock's
    schedule, so ``retry_after_s`` is an honest backoff hint (unlike a
    deadline fault, where the budget is gone forever).
    """

    def __init__(
        self,
        message: str,
        *,
        tenant_id: str = "",
        retry_after_s: float = 0.0,
    ):
        self.tenant_id = tenant_id
        self.retry_after_s = retry_after_s
        super().__init__(message)


# --------------------------------------------------------------------------- #
# the authoritative x-mesh-error-type ↔ exception-class table
# --------------------------------------------------------------------------- #
# One direction is a plain dict; subclass lookups go through
# error_type_for's MRO walk so e.g. a subclass of EngineOverloadedError
# still classifies as mesh.overloaded.  NodeFaultError is deliberately
# absent: it CARRIES a report with its own error_type rather than mapping
# to one.

FAULT_TYPE_BY_EXCEPTION: dict[type[BaseException], str] = {
    EngineOverloadedError: FaultTypes.OVERLOADED,
    EngineWedgedError: FaultTypes.WEDGED,
    DeadlineExceededError: FaultTypes.DEADLINE_EXCEEDED,
    RunCancelledError: FaultTypes.CANCELLED,
    RunOrphanedError: FaultTypes.ORPHANED,
    TenantRateLimitedError: FaultTypes.RATE_LIMITED,
    ClientTimeoutError: FaultTypes.TIMEOUT,
    DeserializationError: FaultTypes.DESERIALIZATION_ERROR,
    InferenceError: FaultTypes.MODEL_ERROR,
    MeshUnavailableError: FaultTypes.CAPABILITY_UNAVAILABLE,
    ProvisioningError: FaultTypes.LIFECYCLE_ERROR,
    LifecycleConfigError: FaultTypes.LIFECYCLE_ERROR,
}

# faults a caller may retry with backoff: the work was refused whole
# (shed, drain, transport hiccup), never half-done.  Deadline faults are
# deliberately NOT here — the budget is gone; timeouts are here because a
# TRANSIENT downstream timeout (not the caller's own deadline) can succeed
# on a less-loaded instance.
RETRIABLE_FAULT_TYPES: frozenset[str] = frozenset(
    {
        FaultTypes.OVERLOADED,
        FaultTypes.TIMEOUT,
        FaultTypes.CAPABILITY_UNAVAILABLE,
        # a wedge fault means NOTHING reached the caller from this engine
        # (the watchdog faults before any terminal): the call is whole and
        # another replica can serve it — failover territory (ISSUE 9)
        FaultTypes.WEDGED,
        # a rate-limit refusal (ISSUE 20) happens at the node kernel's
        # gate, before any queue or slot — the token bucket refills on a
        # known schedule, so backoff-and-retry is exactly right
        FaultTypes.RATE_LIMITED,
    }
)

# reverse direction, first-writer-wins where two exceptions share a code
# (the dict above lists the canonical class first per code)
_EXCEPTION_BY_FAULT_TYPE: dict[str, type[BaseException]] = {}
for _exc_type, _code in FAULT_TYPE_BY_EXCEPTION.items():
    _EXCEPTION_BY_FAULT_TYPE.setdefault(_code, _exc_type)


def error_type_for(exc: BaseException) -> "str | None":
    """The ``x-mesh-error-type`` code for an exception, honoring subclass
    relationships; ``None`` when the exception has no typed code (the
    fault publisher then falls back to its own generic code)."""
    for klass in type(exc).__mro__:
        code = FAULT_TYPE_BY_EXCEPTION.get(klass)
        if code is not None:
            return code
    return None


def exception_for(error_type: "str | None") -> "type[BaseException] | None":
    """The canonical local exception class for a wire fault code;
    ``None`` for unknown/untyped codes."""
    if not error_type:
        return None
    return _EXCEPTION_BY_FAULT_TYPE.get(error_type)
