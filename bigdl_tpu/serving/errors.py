"""Typed failure taxonomy for the online-serving runtime.

Every way a request can fail to produce a prediction has its own
exception type carrying a machine-readable ``reason`` string — the same
string used for the ``serve.shed`` / ``serve.request`` ledger records
and the per-reason Prometheus counters, so a client, the run ledger and
the metrics all agree on *why* a request died.  The split mirrors the
admission pipeline:

* :class:`ShedError` subtypes — rejected synchronously at ``submit()``
  before any work was queued (admission control); the caller gets the
  exception directly, never a future.
* post-admission failures (:class:`DeadlineExceededError`,
  :class:`ForwardFailedError`, :class:`PackFailedError`) — delivered
  through the request's future; the batch around them is unaffected.

``InvalidRequestError`` subclasses ``ValueError`` too, so callers that
only know numpy ("this row is the wrong shape") catch it naturally.
"""

from __future__ import annotations


class ServingError(RuntimeError):
    """Base of every serving-runtime failure; ``reason`` is the
    machine-readable tag shared with ledger records and metrics."""

    reason = "error"


class ShedError(ServingError):
    """Admission rejected the request synchronously (load shedding):
    no future was created and no queued work exists for it."""

    reason = "shed"


class QueueFullError(ShedError):
    """The bounded request queue is at capacity — backpressure, not a
    server fault; retry after a backoff or scale out."""

    reason = "queue_full"


class DeadlineUnmeetableError(ShedError):
    """The request's deadline is provably unmeetable: even dispatched
    immediately, the best-case observed service time would overrun it.
    Rejecting now is strictly better than queueing doomed work."""

    reason = "deadline_unmeetable"


class BreakerOpenError(ShedError):
    """The circuit breaker around the device worker is open: the
    forward path is known-broken, so the request fails fast instead of
    queueing behind a failure."""

    reason = "breaker_open"


class DrainingError(ShedError):
    """The server is draining (or closed): admission has stopped, only
    already-accepted requests are being flushed."""

    reason = "draining"


class SlotCapacityError(ShedError):
    """A generation request can never fit the KV-cache capacity:
    ``prompt_len + max_new`` exceeds the cache length (or the prompt
    exceeds the largest prefill bucket).  Shed eagerly at ``submit()``
    — admitting it would force the decode loop past the cache end,
    where ``dynamic_update_slice`` CLAMPS into the last slot and
    silently corrupts a neighbor's cache (``TransformerLM.decode``'s
    documented overrun hazard)."""

    reason = "over_capacity"


class MemoryBudgetError(ShedError):
    """The tenant's device-memory budget cannot cover the request's
    byte footprint (KV pages for ``prompt + max_new`` plus what the
    tenant already holds resident), even after the degradation ladder
    — rung-executable eviction, prefix-cache reclaim, idle-session
    parking — has run.  Byte starvation sheds TYPED at admission
    instead of surfacing later as a device OOM crash: the neighbor
    tenants' budgets are untouched and the client gets an attributable
    reason, not a dead server."""

    reason = "byte_starved"


class UnknownTenantError(ShedError):
    """The fleet admission plane has no tenant by that name — it was
    never registered, or was deregistered while the client still held
    the handle.  Shed synchronously and attributably: a request for a
    rolled-out model must not land in some other tenant's queue."""

    reason = "unknown_tenant"


class RecurrentStateError(ShedError, ValueError):
    """The model keeps a fixed-size recurrent state per slot (a linear-
    attention or state-space layer) that pages do not carry, and the
    feature asked for moves or shares PAGES only: a prefix-cache hit, a
    speculative verify, a session kept between turns or parked to the
    host would each resume from a state nobody saved.  Refused typed —
    at construction for a draft model, at ``submit(session=...)`` and at
    ``park()`` — instead of answering from the wrong state; snapshots of
    the state at page boundaries would lift it (ROADMAP.md B-I 5)."""

    reason = "recurrent_state"


class InvalidRequestError(ServingError, ValueError):
    """The request's feature payload cannot be served (wrong shape /
    size for the compiled executable) — a client bug, rejected at
    ``submit()`` so it can never poison a batch."""

    reason = "invalid"


class DeadlineExceededError(ServingError):
    """The request was accepted but its deadline expired while queued —
    cancelled before device dispatch rather than wasting a device slot
    on an answer nobody is waiting for."""

    reason = "expired"


class PackFailedError(ServingError):
    """Host-side batch packing failed.  Packing is host work, so this
    does NOT count against the device circuit breaker."""

    reason = "pack_failed"


class ForwardFailedError(ServingError):
    """The device forward for this request's batch failed (after any
    configured retries); counts toward opening the circuit breaker."""

    reason = "forward_failed"
