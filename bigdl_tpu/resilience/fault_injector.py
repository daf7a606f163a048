"""Deterministic fault injection — the proof harness for every recovery
path.

Spark let the reference *test* recovery by killing executors; an SPMD
process has no such seam, so the code plants explicit, normally-inert
injection sites and this module arms them.  A site is a dotted string
checked at the moment the real fault would strike:

===================   =====================================================
site                  where it fires
===================   =====================================================
``train.step``        driver loop, AFTER step N's update + snapshot logic
                      (a preemption between steps)
``grad.nan``          query site: step N's batch is poisoned to NaN so the
                      in-step non-finite guard must skip it
``checkpoint.save``   ``save_sharded`` — raises after creating a torn
                      (uncommitted, partial) snapshot directory
``prefetch.producer`` ``PrefetchToDevice``'s background producer thread
``prefetch.put``      the H2D ``device_put`` inside the producer (raises
                      a *retryable* ``OSError`` — exercises the retry
                      wrapper, transparent to the consumer)
``io.read``           record-file open in ``dataset/seqfile``
``serve.forward``     every serving worker's device forward
                      (``serving/scheduler/pool.py``; ``@N`` = batch
                      sequence N, retries re-check the site)
``serve.worker<i>.forward``  worker ``i``'s device forward ONLY — the
                      pool drill's seam: kill one worker's forwards,
                      prove its breaker opens while the fleet serves
``serve.pack``        the serving worker's host-side batch packing
                      (fails only that batch; never trips the breaker)
``ingest.worker``     the sharded-ingest decode/augment worker PROCESS,
                      before it touches its chunk (raises — propagates
                      as itself through the pool, ``dataset/ingest_pool``)
``ingest.worker.kill``  query site in the same worker: hard ``os._exit``
                      mid-chunk — the real death; the consumer gets a
                      typed ``IngestWorkerDied``, never a hang
``ingest.stage``      the staging ring's stager thread, before copying a
                      batch into a pinned slot (``dataset/staging``)
===================   =====================================================

Worker processes spawned by the ingest pool inherit ``BIGDL_TPU_FAULTS``
through the environment and re-arm themselves on their first check, so
the ingest drills work without any parent-side plumbing (each worker
arms its own counts).

Arming is programmatic (``FaultInjector.install(...)``) or by environment
for relaunched processes::

    BIGDL_TPU_FAULTS="train.step@5;io.read*2;grad.nan@3"

``site@N`` fires at step N (sites checked without a step treat ``@N`` as
"the Nth check"), ``site*K`` fires the first K times (default 1).  Every
match is deterministic — no randomness — because the tests assert exact
recovery, not probabilistic survival.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import List, Optional

from bigdl_tpu.observability import ledger as run_ledger

logger = logging.getLogger("bigdl_tpu.resilience")


class InjectedFault(RuntimeError):
    """Raised by an armed injection site (default exception type)."""


# spellable exception types for env-armed faults: transient (retryable)
# vs hard faults select different recovery paths
_EXC_TYPES = {"InjectedFault": InjectedFault, "OSError": OSError,
              "TimeoutError": TimeoutError, "RuntimeError": RuntimeError}


class Fault:
    """One armed fault: fire at ``site`` (at ``step``, or the first
    ``count`` checks), raising ``exc``."""

    def __init__(self, site: str, step: Optional[int] = None,
                 count: int = 1, exc: type = InjectedFault):
        self.site = site
        self.step = step
        self.count = count
        self.exc = exc
        self._seen = 0          # checks observed (for step-less sites)

    def matches(self, site: str, step: Optional[int]) -> bool:
        if site != self.site or self.count <= 0:
            return False
        if self.step is None:
            return True
        if step is None:
            # step-less call site against a @N fault: fire on the Nth check
            self._seen += 1
            return self._seen == self.step
        return step == self.step

    @classmethod
    def parse(cls, spec: str) -> "Fault":
        """``site[@step][*count][=ExcName]`` (see module docstring)."""
        exc = InjectedFault
        if "=" in spec:
            spec, name = spec.split("=", 1)
            try:
                exc = _EXC_TYPES[name]
            except KeyError:
                raise ValueError(
                    f"unknown fault exception {name!r}; choose from "
                    f"{sorted(_EXC_TYPES)}") from None
        count = 1
        if "*" in spec:
            spec, c = spec.split("*", 1)
            count = int(c)
        step = None
        if "@" in spec:
            spec, s = spec.split("@", 1)
            step = int(s)
        if not spec:
            raise ValueError("fault spec has an empty site")
        return cls(spec, step=step, count=count, exc=exc)


class FaultInjector:
    """Process-wide registry of armed faults.

    All check sites go through the classmethods so production code pays
    one ``is None`` test when nothing is armed.  ``install`` replaces the
    active injector; ``clear`` disarms.  A fresh process re-arms itself
    from ``BIGDL_TPU_FAULTS`` on the first check — that is what lets a
    kill-and-relaunch test inject into the *relaunched* run.
    """

    _active: Optional["FaultInjector"] = None
    _env_loaded = False
    _lock = threading.Lock()

    def __init__(self, faults: Optional[List[Fault]] = None):
        self.faults = list(faults or [])
        self.fired: List[str] = []      # audit trail for tests/diagnostics

    def add(self, site: str, step: Optional[int] = None, count: int = 1,
            exc: type = InjectedFault) -> "FaultInjector":
        self.faults.append(Fault(site, step=step, count=count, exc=exc))
        return self

    # -- arming ------------------------------------------------------------

    @classmethod
    def install(cls, injector: Optional["FaultInjector"]) -> None:
        with cls._lock:
            cls._active = injector
            cls._env_loaded = True      # explicit install wins over env

    @classmethod
    def clear(cls) -> None:
        with cls._lock:
            cls._active = None
            cls._env_loaded = True

    @classmethod
    def from_env(cls, spec: str) -> "FaultInjector":
        return cls([Fault.parse(s) for s in spec.split(";") if s.strip()])

    @classmethod
    def active(cls) -> Optional["FaultInjector"]:
        if not cls._env_loaded:
            with cls._lock:
                if not cls._env_loaded:     # double-checked under the lock
                    spec = os.environ.get("BIGDL_TPU_FAULTS", "")
                    if spec:
                        cls._active = cls.from_env(spec)
                        logger.warning(
                            "FaultInjector armed from BIGDL_TPU_FAULTS=%r",
                            spec)
                    cls._env_loaded = True
        return cls._active

    # -- check sites -------------------------------------------------------

    @classmethod
    def fire(cls, site: str, step: Optional[int] = None) -> None:
        """Raise if a fault is armed for ``site`` (at ``step``)."""
        inj = cls.active()
        if inj is None:
            return
        with cls._lock:
            for f in inj.faults:
                if f.matches(site, step):
                    f.count -= 1
                    inj.fired.append(site)
                    logger.warning("injecting fault at %s (step %s): %s",
                                   site, step, f.exc.__name__)
                    _ledger_event(site, step, f.exc.__name__)
                    raise f.exc(f"injected fault at {site}"
                                + (f" step {step}" if step is not None
                                   else ""))

    @classmethod
    def armed(cls, site: str, step: int) -> bool:
        """Whether ``fire(site, step)`` would raise, without consuming the
        fault: a loop that must settle its state before the fault strikes
        asks first.  (A check with a step never moves a fault's count of
        step-less checks.)"""
        inj = cls.active()
        if inj is None:
            return False
        with cls._lock:
            return any(f.matches(site, step) for f in inj.faults)

    @classmethod
    def should(cls, site: str, step: Optional[int] = None) -> bool:
        """Non-raising query form (e.g. ``grad.nan``: the caller poisons
        data instead of raising)."""
        inj = cls.active()
        if inj is None:
            return False
        with cls._lock:
            for f in inj.faults:
                if f.matches(site, step):
                    f.count -= 1
                    inj.fired.append(site)
                    logger.warning("injecting fault at %s (step %s)",
                                   site, step)
                    _ledger_event(site, step, None)
                    return True
        return False


def _ledger_event(site: str, step: Optional[int], exc: Optional[str]) -> None:
    """Record an injected fault in the run ledger (flushed: the fault
    frequently kills the process it was injected into)."""
    fields = {"site": site}
    if step is not None:
        fields["step"] = step
    if exc is not None:
        fields["exc"] = exc
    run_ledger.emit_critical("event", kind="fault.injected", **fields)
