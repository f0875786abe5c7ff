"""Failure detection + elastic restart supervision for the training loop.

The reference's recovery story is Spark task retry (it actually sets
``spark.task.maxFailures=1`` to fail fast, ``ssd/example/Train.scala:153``)
plus data-level tolerance (corrupt images flow through as invalid
features; MultiBoxLoss skips backward when loss > 50 — both ported, see
``FeatureTransformer`` and ``make_train_step(skip_loss_above=...)``).
What Spark provides for free — a supervisor that reruns lost work — has
no JAX equivalent, so this module supplies it TPU-natively:

- :class:`DivergenceDetector` — periodic host-side health check on the
  training loss (a non-finite streak means the run is dead even though
  the device happily keeps stepping; the skip-update guard makes such a
  run *stall* silently rather than crash).
- :func:`run_resilient` — a restart supervisor around the
  :class:`~analytics_zoo_tpu.parallel.train.Optimizer`: on a retryable
  failure (device/runtime error, stall, preemption) it rebuilds the
  whole program via the caller's factory and resumes from the latest
  orbax checkpoint, up to ``max_restarts`` times.  Rebuilding matters on
  TPU: after a device reset the old compiled executables and live
  buffers are garbage; a fresh ``Optimizer`` re-traces and
  re-replicates from the restored host-side state.

Fault injection for tests: :class:`FaultInjector` wraps a dataset and
raises a chosen exception at a chosen global batch index, once.  The
full chaos matrix (SIGTERM, mid-save kill, snapshot corruption, stalls,
transient XLA errors on a schedule) lives in
:mod:`analytics_zoo_tpu.resilience.chaos`.
"""

from __future__ import annotations

import logging
import math
from typing import Callable, Optional, Sequence, Tuple, Type

from analytics_zoo_tpu.resilience.errors import (
    InjectedFault,
    TrainingDiverged,
    retryable_errors,
)

logger = logging.getLogger("analytics_zoo_tpu")


#: Failures worth restarting for: preemption, stalls, dead input
#: pipelines, injected chaos, and jaxlib device/runtime errors.
#: Deliberately NOT ``RuntimeError`` — a bare RuntimeError is usually a
#: programming error and must propagate on attempt 1.  ``TrainingDiverged``
#: moved OUT of this tuple (resilience/errors.py classifies it fatal):
#: restarting resumes from the same checkpoint into the same divergence,
#: and the in-loop anomaly ladder (``resilience.anomaly``) already owns
#: the recoverable part of that failure class.
RETRYABLE_ERRORS: Tuple[Type[BaseException], ...] = retryable_errors()


class DivergenceDetector:
    """Checks the host-synced loss every ``check_every`` iterations; a run
    of ``max_bad_checks`` consecutive non-finite readings raises
    :class:`TrainingDiverged`.  Checking is periodic, not per-step, so the
    device pipeline is only forced to sync ~1/``check_every`` of the time."""

    def __init__(self, check_every: int = 50, max_bad_checks: int = 3):
        if check_every < 1:
            raise ValueError("check_every must be >= 1")
        self.check_every = check_every
        self.max_bad_checks = max_bad_checks
        self._bad = 0

    def should_check(self, iteration: int) -> bool:
        return iteration % self.check_every == 0

    def check(self, loss: float, iteration: int) -> None:
        if math.isfinite(loss):
            self._bad = 0
            return
        self._bad += 1
        logger.warning("non-finite loss %s at iteration %d (%d/%d strikes)",
                       loss, iteration, self._bad, self.max_bad_checks)
        if self._bad >= self.max_bad_checks:
            raise TrainingDiverged(
                f"loss non-finite for {self._bad} consecutive checks "
                f"(every {self.check_every} iterations)")

    def reset(self) -> None:
        self._bad = 0


def run_resilient(
    build_optimizer: Callable[[], "object"],
    checkpoint_path: str,
    max_restarts: int = 3,
    retry_on: Optional[Tuple[Type[BaseException], ...]] = None,
    on_restart: Optional[Callable[[int, BaseException], None]] = None,
):
    """Supervised training: ``build_optimizer()`` must return a fresh,
    fully-configured :class:`Optimizer` each attempt.  The supervisor
    forces checkpointing to ``checkpoint_path`` (every epoch with
    ``keep_last=3`` step snapshots, unless the optimizer already
    configured one) and resume-from-latest, so each restart continues
    where the last checkpoint left off rather than from scratch.
    Returns the trained model.

    ``retry_on`` filters which failures are retryable; it defaults to
    :data:`RETRYABLE_ERRORS` (preemption, stalls, device/runtime
    errors).  Programming errors — ``TypeError``, ``ValueError``, and
    notably *bare* ``RuntimeError`` — propagate on attempt 1 so real
    bugs are never masked by restart churn; ``TrainingDiverged`` is
    likewise fatal (the in-loop anomaly ladder owns numerical recovery —
    restarting into the same divergence cannot help).
    """
    from analytics_zoo_tpu.parallel.optim import Trigger

    if retry_on is None:
        retry_on = RETRYABLE_ERRORS
    attempt = 0
    while True:
        opt = build_optimizer()
        if opt.checkpoint_trigger is None:
            # step-tagged snapshots (not the single overwrite slot): a
            # corrupted newest snapshot can then fall back to an older
            # intact one instead of losing the run
            opt.set_checkpoint(checkpoint_path, Trigger.every_epoch(),
                               overwrite=False, keep_last=3)
        # resume from wherever checkpoints actually land — the optimizer
        # may have configured its own path different from the supervisor's
        opt.set_resume(opt.checkpoint_path)
        try:
            return opt.optimize()
        except retry_on as e:  # type: ignore[misc]
            attempt += 1
            if attempt > max_restarts:
                logger.error("giving up after %d restarts: %s", max_restarts, e)
                raise
            logger.warning("training attempt %d failed (%s: %s); restarting "
                           "from latest checkpoint (%d/%d)",
                           attempt, type(e).__name__, e, attempt, max_restarts)
            if on_restart is not None:
                on_restart(attempt, e)


class FaultInjector:
    """Dataset wrapper that raises ``exc`` just before yielding global
    batch index ``fail_at`` (counted across epochs), exactly once —
    simulating a mid-training device loss / preemption for tests.  The
    default exception is :class:`InjectedFault` (retryable); pass a bare
    ``ValueError``/``RuntimeError`` to simulate a genuine bug instead.
    For multi-fault schedules use ``resilience.chaos.ChaosMonkey``."""

    def __init__(self, dataset, fail_at: int,
                 exc: Optional[BaseException] = None):
        self.dataset = dataset
        self.fail_at = fail_at
        self.exc = exc or InjectedFault("injected fault")
        self._count = 0
        self._fired = False

    def __iter__(self):
        for batch in self.dataset:
            if not self._fired and self._count == self.fail_at:
                self._fired = True
                raise self.exc
            self._count += 1
            yield batch
