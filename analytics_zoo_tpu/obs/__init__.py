"""Unified telemetry spine: spans, metrics, flight recorder, exporters.

Before PR 7 the repo had five generations of ad-hoc telemetry —
``utils/profiling.StepTimer``, ``serving/metrics.ServingMetrics``,
``data/records.ReadStats``, the PR-3 health-word decodes, and per-drill
JSON dumps — with no shared substrate.  This package is that substrate
(Clockwork's bottom-up action logs and Clipper's per-decision
instrumentation are the pattern sources):

- :mod:`span` — :class:`Span`/:class:`Tracer`: trace-ids threaded
  end-to-end (loader epoch/batch → train step → checkpoint; serving
  submit → queue → batch → dispatch → response) plus the
  :func:`span_conservation` structural check; and :func:`stage` /
  :func:`stages`: always-on host stages of a batch, step or epoch, in
  the profiler's trace and in one bounded ring (the input-wait /
  dispatch / fence split of a train step, the stages of a serve batch);
- :mod:`registry` — :class:`MetricRegistry`: counters, gauges,
  bounded-reservoir histograms, one snapshot schema;
- :mod:`recorder` — :class:`FlightRecorder`: bounded ring buffer,
  deterministic JSONL black-box dump on terminal conditions;
- :mod:`exporters` — JSONL dump, Prometheus text rendering,
  :class:`SummaryBridge` into the TensorBoard writers;
- :mod:`runmeta` — :func:`run_metadata`: the artifact-stamping block
  ``tools/check_artifacts.py`` lints for;
- :mod:`trace` — :class:`TraceStore`: indexed span trees over a flight
  recording, critical-path extraction, p99-vs-p50 tail attribution
  (``tools/az_trace.py`` is the CLI);
- :mod:`slo` — :class:`SLO`/:class:`SloEvaluator`: declarative
  objectives over registry snapshots with multi-window burn-rate
  alerting; drives the serving DegradationLadder and the ROADMAP
  item-1 autoscaler hook;
- :mod:`names` — :data:`CATALOG`: every registry metric name declared
  once (the ``registered-metric-names`` az-analyze rule pins usage
  against it); :data:`STAGES`: every stage name, likewise;
  :data:`SCOPES`: every ``jax.named_scope`` of a hot-path device
  program, likewise;
- :mod:`device_scopes` — :func:`register_program` /
  :func:`program_scopes` / :func:`dump_program_scopes` /
  :func:`registered`: the program hands over which instructions of its
  compiled step and serve programs stand under which named scope, so a
  device trace (which names an operation by its HLO line alone) can be
  read by scope.

Everything but the stages runs on the injected clock
(``utils.clock``), so drills on a ``VirtualClock`` produce
byte-identical traces from a seed (``OBS_r01.json`` pins the sha256).
What a stage costs on the chip: PERF.md (PR 26).  Docs:
``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

from typing import Optional

from analytics_zoo_tpu.obs.exporters import (SummaryBridge,
                                             dump_flight_jsonl,
                                             render_prometheus)
from analytics_zoo_tpu.obs.recorder import DEFAULT_CAPACITY, FlightRecorder
from analytics_zoo_tpu.obs.registry import (Counter, Gauge, MetricRegistry,
                                            ReservoirHistogram)
from analytics_zoo_tpu.obs.device_scopes import (dump_program_scopes,
                                                 program_scopes,
                                                 register_program,
                                                 registered)
from analytics_zoo_tpu.obs.names import CATALOG, SCOPES, STAGES
from analytics_zoo_tpu.obs.runmeta import run_metadata
from analytics_zoo_tpu.obs.slo import (SLO, SloDecision, SloEvaluator,
                                       deadline_miss_slo,
                                       default_serving_slos,
                                       model_deadline_miss_slo,
                                       model_shed_rate_slo, model_slos,
                                       p99_latency_slo, shed_rate_slo)
from analytics_zoo_tpu.obs.span import (Span, StageRecord, Tracer,
                                        span_conservation, stage, stages)
from analytics_zoo_tpu.obs.trace import (SEGMENTS, TraceStore,
                                         attribution_rows,
                                         format_critical_path)
from analytics_zoo_tpu.utils.clock import TimeSource


class Observability:
    """The convenience bundle most call sites take: one clock, one
    registry, one flight recorder, one tracer, wired together.

    ``dump_path`` arms the black box: terminal conditions
    (``TrainingDiverged``, replica fences, drill completion) call
    :meth:`dump` and the ring lands there as JSONL.  Subsystems that
    own a clock (the serving runtime) call :meth:`adopt_clock` so the
    whole bundle follows their time source unless one was injected
    explicitly."""

    def __init__(self, clock: TimeSource = None,
                 capacity: int = DEFAULT_CAPACITY,
                 registry: Optional[MetricRegistry] = None,
                 dump_path: Optional[str] = None,
                 seed: int = 0):
        self._clock_pinned = clock is not None
        self.registry = registry if registry is not None \
            else MetricRegistry(seed=seed)
        self.recorder = FlightRecorder(capacity=capacity, clock=clock,
                                       dump_path=dump_path)
        self.tracer = Tracer(clock=clock, recorder=self.recorder)

    @property
    def dump_path(self) -> Optional[str]:
        return self.recorder.dump_path

    def adopt_clock(self, clock: TimeSource) -> None:
        """Follow ``clock`` unless one was injected at construction."""
        if self._clock_pinned or clock is None:
            return
        from analytics_zoo_tpu.utils.clock import as_now_fn

        now = as_now_fn(clock)
        self.recorder.now = now
        self.tracer.now = now

    def dump(self, reason: str, path: Optional[str] = None) -> str:
        return self.recorder.dump(reason, path=path)


__all__ = [
    "CATALOG",
    "Counter",
    "DEFAULT_CAPACITY",
    "FlightRecorder",
    "Gauge",
    "MetricRegistry",
    "Observability",
    "ReservoirHistogram",
    "SEGMENTS",
    "SCOPES",
    "SLO",
    "SloDecision",
    "SloEvaluator",
    "Span",
    "STAGES",
    "StageRecord",
    "SummaryBridge",
    "TraceStore",
    "Tracer",
    "attribution_rows",
    "deadline_miss_slo",
    "default_serving_slos",
    "dump_program_scopes",
    "model_deadline_miss_slo",
    "model_shed_rate_slo",
    "model_slos",
    "dump_flight_jsonl",
    "format_critical_path",
    "p99_latency_slo",
    "program_scopes",
    "register_program",
    "registered",
    "render_prometheus",
    "run_metadata",
    "shed_rate_slo",
    "span_conservation",
    "stage",
    "stages",
]
