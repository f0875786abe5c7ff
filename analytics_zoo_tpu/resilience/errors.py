"""Failure classification for the resilience layer.

Every error class here is dependency-free on purpose: the data layer
(``data/prefetch.py``, ``data/records.py``), the checkpoint layer
(``parallel/checkpoint.py``) and the supervisor (``parallel/elastic.py``)
all import from this module, so it must sit at the bottom of the import
graph.

The split that matters operationally is *retryable* vs *fatal*:

- retryable — the program was correct but the world failed under it
  (device lost, host preempted, a step hung, a worker thread died).  The
  :func:`~analytics_zoo_tpu.parallel.elastic.run_resilient` supervisor
  rebuilds and resumes from the newest intact checkpoint.
- fatal — a programming or configuration error (``TypeError``,
  ``ValueError``, shape mismatches).  Restarting cannot fix these; they
  propagate on the first attempt so the bug surfaces immediately.

``retryable_errors()`` assembles the canonical retryable tuple, including
``jax.errors.JaxRuntimeError`` (transient XLA/device errors — the
TPU-native analogue of a lost Spark executor).
"""

from __future__ import annotations

from typing import Tuple, Type


class Preempted(RuntimeError):
    """The host received SIGTERM/SIGINT mid-training; a graceful final
    checkpoint was taken at the step boundary before raising.  Retryable:
    a supervisor (or the next scheduled incarnation of this job) resumes
    from that checkpoint."""


class StallError(RuntimeError):
    """A train step or data fetch made no progress past the
    :class:`~analytics_zoo_tpu.resilience.watchdog.StallWatchdog`
    deadline.  Raised *instead of hanging forever* — a hung device call
    or dead input pipeline otherwise blocks the host loop silently."""


class PrefetchWorkerDied(RuntimeError):
    """An input-pipeline worker died and could not be replaced.

    Raised by (a) the prefetch thread (``data.prefetch``) when the
    worker thread dies without enqueueing its stop sentinel — the
    consumer would previously block on ``q.get()`` forever — and (b)
    the multiprocess loader (``data.parallel.ParallelLoader``) when a
    worker PROCESS dies and the bounded respawn budget
    (``max_respawns`` per epoch; deterministic seeding lets a respawn
    recompute exactly the groups still owed) is exhausted.  Retryable:
    a fresh attempt rebuilds the whole input pipeline."""


class CheckpointCorrupt(RuntimeError):
    """A snapshot failed manifest verification (missing manifest, missing
    file, size or checksum mismatch) and no older intact snapshot could
    be restored in its place."""


class ShardReadError(IOError):
    """A data-shard read kept failing after the bounded retry/backoff
    budget was exhausted.  Persistent (not transient) by definition —
    NOT retryable via restart; use ``skip_errors=True`` in the record
    reader to skip-and-count the shard instead."""


class InjectedFault(RuntimeError):
    """Default exception for chaos/fault injection — stands in for a
    lost device or killed task, so it counts as retryable."""


class TrainingDiverged(RuntimeError):
    """Numerical recovery is exhausted: the anomaly ladder (skip the
    step → roll back to the last-known-good snapshot → re-seek past the
    bad region) was climbed to its top and the run STILL produces
    non-finite losses/grads/params — or no last-known-good snapshot
    exists to roll back to.  Fatal by design: a blind restart would
    resume from the same checkpoint into the same divergence, so the
    supervisor must NOT retry; a human (armed with the forensics bundle
    ``anomaly_<step>.json`` and ``tools/replay_batch.py``) decides what
    changes.  Also raised by the legacy
    :class:`~analytics_zoo_tpu.parallel.elastic.DivergenceDetector`
    after a non-finite loss streak."""


class ServerOverloaded(RuntimeError):
    """The serving admission queue is full — the request was SHED at
    submit time, before consuming any device time (``serving.request.
    AdmissionQueue``).  Retryable WITH BACKOFF: the queue being bounded
    is the load-shedding contract, so an immediate blind retry from
    every rejected client would just re-create the overload; clients
    should back off (exponentially) or hedge to another serving cell."""


class RequestTimeout(RuntimeError):
    """A serving request's deadline passed while it was still queued, so
    it was shed before device dispatch (a late answer costs the same
    device time as a useful one).  Retryable: the client may resubmit
    with a fresh deadline — by then the burst that starved this request
    has usually drained (or the degradation ladder has stepped down)."""


class ReplicaWedged(RuntimeError):
    """A serving replica's forward wedged past its StallWatchdog
    deadline or crashed mid-batch.  Dual semantics by design:

    - for the REPLICA this is fatal — the runtime fences it (no further
      dispatches) and restarts it in the background;
    - for the REQUESTS of the in-flight batch it is retryable — the
      runtime re-dispatches that batch to a healthy replica exactly
      once, and only if THAT dispatch also fails do the requests fail
      with this error (at which point the client may retry elsewhere).

    Registered retryable because the error object only
    ever escapes to request/supervisor scope — replica fencing is
    handled internally by ``serving.replica.ReplicaPool``."""


class DeviceQuarantine(RuntimeError):
    """The device-health sentinel (``resilience/health.py``) confirmed a
    specific device as unhealthy — a parity-audit minority vote, a
    shadow-recompute mismatch with a tiebreak, or a persistent straggler
    past the hysteresis ladder — and quarantined it.  ``device`` names
    the flat mesh index (or replica id) being evicted.  Retryable: the
    culprit is ATTRIBUTED, so the supervisor rebuilds on the surviving
    devices (``health.evict_device`` + ``SpecSet.replace_mesh`` + LKG
    tier + ``elastic_resume_coordinates``) and the smaller-width restart
    does not re-create the fault."""

    def __init__(self, message: str, device=None):
        super().__init__(message)
        self.device = device


class SdcDetected(RuntimeError):
    """Silent data corruption was PROVEN (replica fingerprints diverged,
    or a shadow recompute disagreed with the primary) but could not be
    attributed to a single device — a two-way split, multiple divergers,
    or no tiebreak vote.  Fatal by design: with no named culprit there
    is nothing to evict, and a blind restart lands on the same silicon
    with corrupted trust in every copy of the params; an operator must
    triage the hardware (the sentinel's event log carries the
    per-replica fingerprints)."""


class ElasticPlacementError(ValueError):
    """An elastic re-placement asked for a mesh that cannot carry the
    declared sharding: the new mesh's axis names do not cover every axis
    the :class:`~analytics_zoo_tpu.parallel.specs.SpecSet` declaration
    references (rules, batch overrides, or the data axis).  Raised at
    the substrate boundary — ``SpecSet.replace_mesh`` / ``place_state``
    / ``place_batch`` — with the missing axes listed, instead of the
    opaque NamedSharding failure jax raises deep inside ``device_put``.
    Fatal: a declaration/mesh mismatch is a configuration error; a
    restart onto the same mesh re-creates it."""


#: Explicit classification registries.  EVERY exception class defined in
#: this module must appear in exactly one of the two tuples below — the
#: classification completeness test (tests/test_anomaly.py) enforces it, so a
#: future error class cannot silently fall through ``run_resilient``'s
#: retry filter with unconsidered semantics.
_RETRYABLE_CLASSES: Tuple[Type[BaseException], ...] = (
    Preempted,
    StallError,
    PrefetchWorkerDied,
    InjectedFault,
    ServerOverloaded,
    RequestTimeout,
    ReplicaWedged,
    DeviceQuarantine,
)

#: Fatal: restarting cannot fix these (no intact snapshot left; a shard
#: that stays unreadable; a run whose numerics keep diverging).
FATAL_ERRORS: Tuple[Type[BaseException], ...] = (
    CheckpointCorrupt,
    ShardReadError,
    TrainingDiverged,
    ElasticPlacementError,
    SdcDetected,
)


def retryable_errors() -> Tuple[Type[BaseException], ...]:
    """The canonical tuple of transient, restart-recoverable failures."""
    # imported here, not at module scope: this module sits at the bottom
    # of the import graph and stays importable without touching jax
    from jax.errors import JaxRuntimeError

    # transient device/runtime errors (lost TPU, HBM OOM)
    return _RETRYABLE_CLASSES + (JaxRuntimeError,)


def is_retryable(exc: BaseException) -> bool:
    """Classify one failure instance as retryable or fatal.  Fatal classes
    win over retryable bases (``TrainingDiverged`` is a ``RuntimeError``
    subclass, but divergence must never be restart-masked)."""
    if isinstance(exc, FATAL_ERRORS):
        return False
    return isinstance(exc, retryable_errors())
