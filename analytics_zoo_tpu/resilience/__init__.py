"""Resilience layer: failure classification, stall detection, graceful
preemption, and chaos fault injection.

The reference delegated its whole failure story to Spark task retry and
lineage (``ssd/example/Train.scala:153``); a TPU-native system owns it
itself.  The pieces (see docs/RESILIENCE.md):

- :mod:`errors` — retryable vs fatal classification (:func:`retryable_errors`,
  :data:`FATAL_ERRORS`, :func:`is_retryable`)
- :mod:`watchdog` — :class:`StallWatchdog` (hung step → StallError)
- :mod:`preempt` — :class:`PreemptionHandler` (SIGTERM → checkpoint →
  Preempted)
- :mod:`anomaly` — the numerical-anomaly sentinel: in-graph health word,
  skip → rollback-to-last-known-good → ``TrainingDiverged`` ladder,
  deterministic bad-batch forensics (``tools/replay_batch.py``)
- :mod:`chaos` — :class:`ChaosMonkey` fault matrix + ``tools/chaos_drill``
- :mod:`health` — the device-health sentinel: cross-replica parity
  audit, shadow recompute spot-check, straggler EWMA ladder, and the
  quarantine/eviction actuators (``tools/sdc_drill``)
- atomic/verified snapshots live in :mod:`analytics_zoo_tpu.parallel.
  checkpoint`; the restart supervisor in :mod:`analytics_zoo_tpu.
  parallel.elastic`.
"""

from analytics_zoo_tpu.resilience.errors import (
    FATAL_ERRORS,
    CheckpointCorrupt,
    DeviceQuarantine,
    ElasticPlacementError,
    InjectedFault,
    Preempted,
    PrefetchWorkerDied,
    SdcDetected,
    ShardReadError,
    StallError,
    TrainingDiverged,
    is_retryable,
    retryable_errors,
)
from analytics_zoo_tpu.resilience.watchdog import StallWatchdog
from analytics_zoo_tpu.resilience.preempt import PreemptionHandler
from analytics_zoo_tpu.resilience.anomaly import (
    AnomalyPolicy,
    AnomalySentinel,
    batch_fingerprint,
    decode_health,
    health_sections,
)
from analytics_zoo_tpu.resilience.chaos import (
    ChaosMonkey,
    FaultSpec,
    corrupt_snapshot,
    transient_xla_error,
)
from analytics_zoo_tpu.resilience.health import (
    AuditVerdict,
    HealthPolicy,
    HealthSentinel,
    evict_device,
    make_audit_fn,
    tree_fingerprint,
)

__all__ = [k for k in dir() if not k.startswith("_")]
