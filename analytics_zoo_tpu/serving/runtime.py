"""The online serving runtime: request-level API over the predictors.

Glues the pieces into one synchronous, clock-driven scheduler:

- :class:`~analytics_zoo_tpu.serving.request.AdmissionQueue` — bounded,
  EDF, shed-before-dispatch;
- :class:`~analytics_zoo_tpu.serving.batcher.DeadlineBatcher` — flush on
  full-or-urgent over pre-compiled geometries only;
- :class:`~analytics_zoo_tpu.serving.replica.ReplicaPool` — StallWatchdog
  supervision, fence, exactly-once failover, background restart;
- :class:`~analytics_zoo_tpu.serving.ladder.DegradationLadder` — tier
  step-down under sustained overload, hysteresis step-up;
- :class:`~analytics_zoo_tpu.serving.metrics.ServingMetrics` — the
  snapshot dict the drill banks.

Single-threaded on purpose: every scheduling decision happens inside
:meth:`ServingRuntime.pump`, reading time ONLY through the injected
clock.  ``pump()`` assembles, dispatches and answers every due batch and
returns only when each is answered.  Against a real accelerator the same
loop runs on a :class:`~analytics_zoo_tpu.serving.clock.MonotonicClock`
with jax's async dispatch providing the device overlap: a tier may hand
back its answer as a device array whose program is still running (the
SSD tiers do), and between that return and the fetch of the answer the
runtime assembles the NEXT due batch and starts its transfer
(:meth:`ServingRuntime._assemble_ahead` — one batch of look-ahead, on
this thread, inside the batch's ``az/serve/forward``; the pump's loop
dispatches that batch next).  A tier whose answer is on the host when it
returns sees none of this.  Under a
:class:`~analytics_zoo_tpu.serving.clock.VirtualClock` plus a
``service_time`` model the whole overload/failover story replays
deterministically — that is what ``tests/test_serving.py`` and
``tools/serve_drill.py`` pin.

**Fleet mode** (ISSUE 14 — the Clipper model-multiplexing frontend +
Clockwork predictability discipline): pass ``models=[ModelConfig(...),
...]`` instead of ``tiers`` and ONE runtime schedules several model
families on the SHARED replica pool — per-model batching geometry
(models never share a batch), per-model degradation ladders, per-model
SLOs whose burn rates weight the EDF dispatch order (a burning model's
slack counts for more), and per-model service-time EWMAs (a new model
never inherits another's estimate).  Streaming session models
(``ModelConfig(streaming=True)``) get session-affine scheduling:
:meth:`open_session` pins a session to one replica (where its carry
state lives), every :meth:`submit_chunk` carries an incremental
per-chunk deadline, and chunk order is preserved because chunk
deadlines are monotone under EDF.  A closed-loop
:class:`~analytics_zoo_tpu.serving.autoscale.Autoscaler` (``autoscaler=``)
turns the PR-11 ``SloDecision.scale_hint`` into actual
:meth:`~analytics_zoo_tpu.serving.replica.ReplicaPool.resize` calls —
growth pre-warms compiled geometries before the replica joins dispatch,
shrink drains-then-retires.

Usage::

    tiers = ssd_serving_tiers(model, param)       # pipelines.ssd hook
    rt = ServingRuntime(tiers, n_replicas=2, max_batch=8,
                        queue_capacity=64, default_deadline_s=0.2)
    req = rt.submit({"input": img})               # may raise ServerOverloaded
    rt.pump()                                     # run due scheduling work
    ...
    rt.drain()                                    # flush everything queued
    print(rt.metrics.snapshot())
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import os
from typing import (Any, Callable, Dict, List, Optional, Sequence, Set,
                    Tuple)

import numpy as np

from analytics_zoo_tpu.obs import device_scopes
from analytics_zoo_tpu.obs.span import stage
from analytics_zoo_tpu.resilience.errors import (ReplicaWedged,
                                                 ServerOverloaded)
from analytics_zoo_tpu.serving.batcher import (AssembledBatch,
                                               DeadlineBatcher, FIXED,
                                               ModelPlan)
from analytics_zoo_tpu.serving.clock import Clock, MonotonicClock
from analytics_zoo_tpu.serving.ladder import (DegradationLadder,
                                              LadderPolicy, ServingTier)
from analytics_zoo_tpu.serving.metrics import ServingMetrics
from analytics_zoo_tpu.serving.autoscale import OCCUPANCY_KNEE, Reshape
from analytics_zoo_tpu.serving.replica import (Replica, ReplicaPool,
                                               ReplicaSlice)
from analytics_zoo_tpu.serving.request import (DEFAULT_MODEL,
                                               AdmissionQueue, Request)

#: span trace-id for one request's life (submit → terminal) — the
#: obs.span_conservation check keys on this prefix
REQ_TRACE = "req-{rid}"

logger = logging.getLogger("analytics_zoo_tpu")


@dataclasses.dataclass
class ModelConfig:
    """One multiplexed model family on the shared pool (ISSUE 14).

    ``tiers``: the degradation rungs (cheapest last — the same
    descriptors the single-model runtime takes).  ``tier_factory``
    (optional): ``replica_rid -> [ServingTier]`` building PER-REPLICA
    tier instances — how streaming models give every replica its own
    session-state store, so session affinity is physically meaningful;
    ``tiers`` stays the template (names/speeds/audit hooks).

    ``bucket_edges``/``pad_key``/``length_key``/``max_batch``: the
    model's batching plan (see :class:`~analytics_zoo_tpu.serving.
    batcher.ModelPlan`).  ``default_deadline_s``: per-model deadline
    when ``submit`` doesn't pass one (``None`` = the runtime default).
    ``slos``: this model's objectives (:mod:`analytics_zoo_tpu.obs.slo`
    — e.g. ``model_slos(name)``); their burn rates drive the model's
    ladder and its weighted-EDF dispatch weight.  ``streaming`` marks a
    session-type model (``open_session``/``submit_chunk``) with
    ``chunk_deadline_s`` as the per-chunk incremental deadline.
    ``serial_chunks`` (streaming models): a session may have ONE chunk
    in flight — ``submit_chunk`` refuses the next until the last is
    answered, as a caller that needs the answer to go on (a decoder LM's
    next token) behaves anyway.  With it chunk order needs no help from
    the buckets, so the plan may declare several ``bucket_edges`` (a
    prefill chunk and a decoded token are different geometries).

    ``weights_to_tiers``: ``(placed_variables, replica_rid) ->
    [ServingTier]`` — how :meth:`ServingRuntime.hot_swap` turns a
    checkpoint's (SpecSet-placed) variables into this model's tier
    stack.  ``rid == -1`` builds the canary mirror (not bound to any
    replica).  Without it the model cannot live-swap.
    """

    name: str
    tiers: Sequence[ServingTier]
    tier_factory: Optional[Callable[[int], Sequence[ServingTier]]] = None
    weights_to_tiers: Optional[Callable[[Any, int],
                                        Sequence[ServingTier]]] = None
    bucket_edges: Optional[Sequence[int]] = None
    pad_key: str = "input"
    length_key: Optional[str] = "n_frames"
    max_batch: Optional[int] = None
    default_deadline_s: Optional[float] = None
    slos: Sequence[Any] = ()
    streaming: bool = False
    chunk_deadline_s: float = 0.5
    ladder_policy: Optional[LadderPolicy] = None
    serial_chunks: bool = False

    def __post_init__(self):
        if not self.tiers:
            raise ValueError(f"model {self.name!r} needs at least one tier")
        if self.streaming and self.tier_factory is None:
            raise ValueError(
                f"streaming model {self.name!r} needs a tier_factory — "
                f"session carry state must live per replica for session "
                f"affinity to mean anything")
        if self.streaming and not self.serial_chunks \
                and self.bucket_edges and len(self.bucket_edges) > 1:
            # chunk order relies on EDF within ONE (model, affinity,
            # edge) group: with several edges a session's later chunk
            # could land in a bucket that flushes first and decode out
            # of order.  Session chunks are fixed-size blocks anyway
            # (StreamingDS2 compiles exactly three shapes).
            raise ValueError(
                f"streaming model {self.name!r} may declare at most one "
                f"bucket edge — multiple edges would let a later chunk's "
                f"bucket flush before an earlier chunk's, breaking "
                f"in-order decode (serial_chunks=True lifts this: one "
                f"chunk of a session in flight)")

    def plan(self) -> ModelPlan:
        return ModelPlan(bucket_edges=self.bucket_edges,
                         pad_key=self.pad_key, length_key=self.length_key,
                         max_batch=self.max_batch,
                         streaming=self.streaming)


class ServingRuntime:
    """Deadline-aware serving over N supervised replicas.

    ``tiers``: degradation rungs, cheapest last (see
    ``pipelines.ssd.ssd_serving_tiers`` / ``pipelines.deepspeech2.
    ds2_serving_tiers``) — the single-model path.  ``models``: a list of
    :class:`ModelConfig` instead, for the multiplexed fleet path
    (``tiers`` must then be ``None``).  ``service_time(edge, n, tier)``
    (single-model) / ``service_time(model, edge, n, tier)``
    (multiplexed): estimated service seconds — REQUIRED with a virtual
    clock (it also advances it); with the default monotonic clock it
    may be ``None`` (the batcher then learns a per-(model, edge, tier)
    EWMA from observed forwards).

    ``chaos``: an armed :class:`~analytics_zoo_tpu.resilience.chaos.
    ChaosMonkey` whose serving-kind windows (``slow_forward``,
    ``replica_crash``) are applied per dispatch index.

    ``slo``: an :class:`~analytics_zoo_tpu.obs.slo.SloEvaluator` —
    when armed, every decision window feeds the metric registry's
    snapshot through the multi-window burn-rate evaluation and the
    degradation ladder steps on ``SloDecision.overloaded`` (SLO burn)
    instead of the raw shed/queue-depth flag; each decision is noted
    into the flight recorder (``slo_decision`` events) when ``obs`` is
    armed, and ``snapshot()`` carries the SLO report.  In fleet mode
    the runtime BUILDS the evaluator from the models' declared SLOs
    when none is passed (``slo_params`` forwards evaluator kwargs like
    ``time_scale``), maps each burning SLO back to its model for the
    per-model ladders, and refreshes the weighted-EDF weights from the
    fast-window burns every decision window.

    ``autoscaler``: an armed :class:`~analytics_zoo_tpu.serving.
    autoscale.Autoscaler` — the decision window's ``scale_hint`` feeds
    its policy loop and a due actuation calls ``pool.resize`` (growth
    pre-warmed per ``compile_s``/the models' geometry plan, shrink
    drain-then-retire, session-pinned replicas protected).

    ``fence_budget_s``: bounds wedge detection (see
    :mod:`analytics_zoo_tpu.serving.replica`) — ``None`` keeps the
    PR-5 return-then-check behavior.  ``compile_s``: per-geometry
    compile cost for the pre-warm / cold-compile modeling (0 disables).
    ``retain_requests=False`` drops per-request objects once terminal
    (accounting stays exact via incremental counters) — the
    million-request drill's memory bound.  Either way an answer is handed
    to its ``Request``, which the caller holds and the runtime then does
    not.

    ``specs``: the pipeline's declared
    :class:`~analytics_zoo_tpu.parallel.specs.SpecSet` — pass the SAME
    object the tiers were built with (``ssd_serving_tiers(specs=...)``
    / ``ds2_serving_tiers(specs=...)``), so train and serve share ONE
    sharding declaration.  The runtime itself never places arrays (the
    tiers' annotated forwards do); it records the mesh topology in
    ``snapshot()`` so a banked drill names the serving geometry.
    """

    def __init__(self, tiers: Optional[Sequence[ServingTier]] = None,
                 n_replicas: int = 2,
                 clock: Optional[Clock] = None,
                 queue_capacity: int = 64, max_batch: int = 8,
                 bucket_edges: Optional[Sequence[int]] = None,
                 pad_key: str = "input",
                 length_key: Optional[str] = "n_frames",
                 default_deadline_s: float = 1.0,
                 wedge_timeout_s: float = 10.0,
                 restart_s: float = 5.0,
                 service_time: Optional[Callable[..., float]] = None,
                 slack_margin_s: float = 0.0,
                 ladder_policy: Optional[LadderPolicy] = None,
                 decision_every: int = 8,
                 shed_expired: bool = True,
                 chaos=None, obs=None, specs=None, slo=None,
                 models: Optional[Sequence[ModelConfig]] = None,
                 autoscaler=None,
                 fence_budget_s: Optional[float] = None,
                 compile_s: float = 0.0,
                 slo_params: Optional[Dict[str, Any]] = None,
                 weight_cap: float = 4.0,
                 retain_requests: bool = True,
                 parallel_replicas: bool = False,
                 slice_width: int = 1,
                 device_budget: Optional[int] = None,
                 health=None):
        if models is not None:
            if tiers is not None:
                raise ValueError("pass tiers= OR models=, not both")
            if not models:
                raise ValueError("models= must name at least one model")
            self.models: Dict[str, ModelConfig] = {}
            for cfg in models:
                if cfg.name in self.models:
                    raise ValueError(f"duplicate model name {cfg.name!r}")
                self.models[cfg.name] = cfg
            self._multi = True
            self.tiers = None
        else:
            if not tiers:
                raise ValueError("need at least one ServingTier")
            self.tiers = list(tiers)
            self.models = {DEFAULT_MODEL: ModelConfig(
                name=DEFAULT_MODEL, tiers=self.tiers,
                bucket_edges=bucket_edges, pad_key=pad_key,
                length_key=length_key)}
            self._multi = False
        self.specs = specs
        self.clock = clock or MonotonicClock()
        self.default_deadline_s = float(default_deadline_s)
        self.max_batch = int(max_batch)
        self.decision_every = int(decision_every)
        self.wedge_timeout_s = float(wedge_timeout_s)
        self.chaos = chaos
        # device-health sentinel (resilience.health.HealthSentinel):
        # parallel-mode completions feed per-replica service times into
        # its straggler EWMA ladder; a flagged replica is quarantined
        # through the pool's drain-then-retire path with device_budget
        # decremented.  None (default) = zero behavior change.
        self.health = health
        self.weight_cap = float(weight_cap)
        self.retain_requests = bool(retain_requests)
        # parallel-service mode (the fleet capacity model): dispatch
        # assigns a batch to a FREE replica whose completion lands at
        # start + cold_tax + service on ITS busy horizon — replicas
        # serve concurrently and pool size IS capacity.  The legacy
        # serial mode (every dispatch sleeps the shared clock) stays
        # the default: the PR-5/PR-11 drills replay byte-identically,
        # and chaos wedge/crash injection lives there.
        self.parallel = bool(parallel_replicas)
        if self.parallel and service_time is None:
            raise ValueError("parallel_replicas needs a service_time "
                             "model (it is a virtual-time mode)")
        # telemetry spine (obs.Observability): request-lifecycle spans
        # into the flight recorder, metrics into the shared registry; a
        # replica fence dumps the black box when a dump_path is armed
        self.obs = obs
        if obs is not None:
            obs.adopt_clock(self.clock)
        self.metrics = ServingMetrics(
            registry=obs.registry if obs is not None else None)
        # SLO engine (obs.slo.SloEvaluator): when armed, each decision
        # window feeds a registry snapshot through the multi-window
        # burn-rate evaluation and the ladder steps on SLO burn instead
        # of the raw shed/depth flag (see _decide_window).  Fleet mode
        # builds it from the models' declared SLOs when none is passed.
        self._slo_model: Dict[str, str] = {}
        for cfg in self.models.values():
            for s in cfg.slos:
                self._slo_model[s.name] = cfg.name
        if slo is None and self._slo_model:
            from analytics_zoo_tpu.obs.slo import SloEvaluator

            all_slos = [s for cfg in self.models.values()
                        for s in cfg.slos]
            slo = SloEvaluator(slos=all_slos,
                               registry=self.metrics.registry,
                               **(slo_params or {}))
        self.slo = slo
        self._slo_params = dict(slo_params or {})
        # live-weight hot-swap control (ISSUE 18): one rollout at a
        # time — canary stage, then the pool's one-replica-at-a-time
        # machine; _swap_ctl is None between rollouts, _swap_log keeps
        # the banked history, _lkg the pending serve-LKG hysteresis
        self._swap_ctl: Optional[Dict[str, Any]] = None
        self._swap_counter = 0
        self._swap_log: List[Dict[str, Any]] = []
        self._swap_stats = {"completed": 0, "rollbacks": 0, "trips": 0,
                            "lkg_promotions": 0}
        self._lkg: Optional[Dict[str, Any]] = None
        self.autoscaler = autoscaler
        if autoscaler is not None and autoscaler.registry is None:
            autoscaler.registry = self.metrics.registry
        # replicas-as-mesh-slices (ISSUE 19): every pool entry occupies
        # ``slice_width`` devices; ``device_budget`` is the pool's hard
        # device ceiling.  ``_model_width`` tracks each model's CURRENT
        # slice width (a reshape moves one model wider); the service
        # model divides by the occupancy-limited width speedup, so
        # width only pays off past the ≈B/128 knee (docs/MFU_CEILING.md)
        if slice_width < 1:
            raise ValueError(f"slice_width must be >= 1, got {slice_width}")
        self.slice_width = int(slice_width)
        self._model_width: Dict[str, int] = {
            name: self.slice_width for name in self.models}
        #: per-model batch-fill EWMA — the autoscaler's width-vs-count
        #: saturation signal (0..1 of the model's batch budget)
        self._fill_ewma: Dict[str, float] = {}
        self._reshape_log: List[Dict[str, Any]] = []
        self.requests: List[Request] = []      # every request ever submitted
        self._rid = itertools.count()
        self._spans: Dict[int, Dict[str, Any]] = {}   # rid -> open spans
        self._window_shed = 0
        self._window_shed_by: Dict[str, int] = {}
        self._dispatch_idx = 0                 # chaos serving-fault index
        self._since_decision = 0
        # incremental accounting (exact at any retention mode): every
        # terminal transition flows through the runtime, so the counters
        # stay correct when retain_requests=False drops the objects
        self._submitted = 0
        self._by_state: Dict[str, int] = {}
        # streaming sessions: sid -> {model, replica, open, chunks} for
        # LIVE sessions only — entries are released when the final
        # chunk reaches a terminal state (or the session is killed), so
        # session bookkeeping stays O(active sessions), not O(ever
        # opened); aggregate history lives in the int counters below
        self._sessions: Dict[int, Dict[str, Any]] = {}
        self._next_sid = 0
        self._sessions_opened = 0
        self._sessions_failed = 0
        self._open_sessions = 0
        #: open/in-flight session count per replica rid — the
        #: open_session placement input and the shrink-protection set
        self._session_load: Dict[int, int] = {}
        #: the batch assembled while another's program ran, which the
        #: pump's loop dispatches next (_assemble_ahead), or what its
        #: assembly raised, which the loop raises there; ``_force`` is
        #: the running pump's, for that assembly
        self._held: Optional[AssembledBatch] = None
        self._held_error: Optional[BaseException] = None
        self._force = False

        self.queue = AdmissionQueue(queue_capacity, self.clock,
                                    on_shed=self._on_shed,
                                    shed_expired=shed_expired)
        if self._multi:
            plans = {name: cfg.plan() for name, cfg in self.models.items()}
            self.batcher = DeadlineBatcher(
                self.queue, max_batch, service_time=service_time,
                slack_margin_s=slack_margin_s, plans=plans)
        else:
            self.batcher = DeadlineBatcher(
                self.queue, max_batch, bucket_edges=bucket_edges,
                pad_key=pad_key, length_key=length_key,
                service_time=service_time, slack_margin_s=slack_margin_s)
        self._service_time = service_time
        virtual = service_time is not None

        def service_hook(batch: AssembledBatch, rid: int) -> float:
            if self._multi:
                s = service_time(batch.model, batch.edge,
                                 batch.n_valid, batch.tier)
            else:
                s = service_time(batch.edge, batch.n_valid, batch.tier)
            w = self._model_width.get(batch.model, 1)
            if w > 1:
                # a width-w slice serves the batch w-way sharded, but
                # only as fast as per-device occupancy allows — below
                # the knee the shards starve and width buys nothing
                s = s / self._width_speedup(batch.n_valid, w)
            return s

        self._service_hook = service_hook if virtual else None
        self.pool = ReplicaPool(
            [self._make_replica(r) for r in range(n_replicas)],
            self.clock, restart_s=restart_s,
            observer=self._on_pool_event,
            fence_budget_s=fence_budget_s,
            replica_factory=self._make_replica,
            prewarm_keys=self._geometry_plan(),
            compile_s=compile_s,
            device_budget=device_budget)
        self.ladders: Dict[str, DegradationLadder] = {
            name: DegradationLadder(
                len(cfg.tiers), cfg.ladder_policy or ladder_policy)
            for name, cfg in self.models.items()}
        #: single-model alias — the PR-5 API surface
        self.ladder = (self.ladders[DEFAULT_MODEL]
                       if not self._multi else None)
        self._register_programs()

    # -- construction helpers ------------------------------------------------
    def _geometry_plan(self) -> List[Tuple[str, Any, int]]:
        """Every (model, edge, tier) program a replica must hold warm —
        what pre-warm compiles before a growth replica joins dispatch."""
        keys: List[Tuple[str, Any, int]] = []
        for name, cfg in self.models.items():
            edges = cfg.bucket_edges or [FIXED]
            for edge in edges:
                for tier in range(len(cfg.tiers)):
                    keys.append((name, edge, tier))
        return keys

    def _register_programs(self) -> None:
        """Note, for every geometry of the plan whose tier exposes its
        device program, how to compile it again from shapes
        (``obs.device_scopes``: ``serve/<model>/<tier>/<edge>``) — what a
        traced run maps the device's operations to named scopes with.  A
        dict store a geometry: nothing is traced or compiled here, and
        the runtime is held weakly."""
        for name, edge, tier in self._geometry_plan():
            template = self.models[name].tiers[tier]
            if template.device_program is None:
                continue
            device_scopes.register_program(
                f"serve/{name}/{template.name}/{edge}",
                device_scopes.weak_thunk(
                    self, lambda rt, key=(name, edge, tier):
                    rt._geometry_program(*key)))

    def _geometry_program(self, model: str, edge: Any, tier: int) -> tuple:
        """``(jitted, args, static_argnums)`` of one geometry of the
        plan as the first replica dispatches it: the batcher's rows for
        the model, padded to ``edge``."""
        t = self.pool.replicas[0].tier_objs[model][tier]
        if t.device_program_for is None:
            return t.device_program()
        return t.device_program_for(
            edge, self.batcher.model_batch(model))()

    def _make_replica(self, rid: int) -> Replica:
        """Build one replica (also the pool's growth factory): the
        per-model tier table, with per-replica tier INSTANCES when a
        model declares a ``tier_factory`` (streaming session stores
        live per replica).  Warmth is the POOL's business: replicas
        built here are fully warm (PR 5 compiles serving programs at
        startup) and ``resize`` re-marks growth replicas warming/cold."""
        fwd: Dict[str, List[Callable]] = {}
        tier_objs: Dict[str, List[ServingTier]] = {}
        for name, cfg in self.models.items():
            t = cfg.tier_factory(rid) if cfg.tier_factory else cfg.tiers
            if len(t) != len(cfg.tiers):
                raise ValueError(
                    f"model {name!r}: tier_factory built {len(t)} tiers, "
                    f"template declares {len(cfg.tiers)}")
            fwd[name] = [tier.forward for tier in t]
            tier_objs[name] = list(t)
        if self.slice_width > 1:
            # the replica IS a mesh slice (ISSUE 19): its programs are
            # jitted against the tier SpecSet's width-w sub-mesh — the
            # same declaration the elastic trainer re-places — and the
            # pool accounts it as ``width`` devices
            slice_specs = self.specs
            if slice_specs is not None \
                    and slice_specs.data_axis_size != self.slice_width:
                from analytics_zoo_tpu.parallel import mesh as mesh_lib

                devs = list(
                    slice_specs.mesh.devices.reshape(-1)
                    [: self.slice_width])
                sub = mesh_lib.create_mesh(
                    (self.slice_width,),
                    (mesh_lib.data_axis(slice_specs.mesh),),
                    devices=devs)
                slice_specs = slice_specs.replace_mesh(sub)
            replica = ReplicaSlice(
                rid, fwd, self.clock, self.wedge_timeout_s,
                width=self.slice_width, specs=slice_specs,
                service_hook=self._service_hook)
        else:
            replica = Replica(rid, fwd, self.clock, self.wedge_timeout_s,
                              service_hook=self._service_hook)
        replica.tier_objs = tier_objs
        return replica

    @staticmethod
    def _width_speedup(n_valid: int, width: int) -> float:
        """Occupancy-limited service speedup of a width-``width`` slice
        on a batch of ``n_valid``: each of the ``width`` shards serves
        ``n_valid/width`` at ``min(1, (n/w)/knee)`` occupancy, so the
        slice delivers ``w`` × that against the width-1 baseline's
        ``min(1, n/knee)``.  Saturated (n ≥ w·knee) → exactly
        ``width``; below the knee (n ≤ knee) → exactly 1.0 — width
        buys NOTHING until the model is batch-saturated, which is the
        whole width-vs-count policy (docs/MFU_CEILING.md)."""
        n = max(float(n_valid), 1.0)
        base = min(1.0, n / OCCUPANCY_KNEE)
        wide = min(1.0, (n / width) / OCCUPANCY_KNEE) * width
        return wide / base

    # -- telemetry -----------------------------------------------------------
    def _on_pool_event(self, ev: Dict[str, Any]) -> None:
        """Every pool event (fence / failover / restart / resize /
        cold compile) lands in the flight recorder; a FENCE is a
        terminal condition — it trips the black-box dump when one is
        armed.  Cold compiles also count into the registry (the
        pre-warm drill's tax counter)."""
        if ev["kind"] == "cold_compile":
            self.metrics.registry.counter("serve/cold_compiles").inc()
        if self.obs is None:
            return
        self.obs.recorder.record(ev)
        if ev["kind"] == "replica_fenced" and self.obs.dump_path:
            self.obs.dump("replica_fenced")

    def _end_request_spans(self, req: Request, status: str,
                           at: Optional[float] = None,
                           **attrs: Any) -> None:
        if self.obs is None:
            return
        spans = self._spans.pop(req.rid, None)
        if spans is None:
            return
        d = spans.get("dispatch")
        if d is not None:
            d.end(status=status, at=at, **attrs)
        spans["root"].end(status=status, at=at)

    # -- shed observer -------------------------------------------------------
    def _on_shed(self, req: Request, cause: str) -> None:
        self.metrics.on_shed(cause, model=req.model if self._multi
                             else None)
        self._window_shed += 1
        self._window_shed_by[req.model] = \
            self._window_shed_by.get(req.model, 0) + 1
        self._account_terminal(req)
        if req.session is not None:
            # a gap in the chunk stream silently corrupts the session's
            # carry — a shed chunk fails the WHOLE session honestly
            self._kill_session(req, f"chunk shed ({cause})")
        if self.obs is not None:
            spans = self._spans.pop(req.rid, None)
            if spans is not None:
                q = spans.get("queue")
                if q is not None:
                    q.end(status=cause)
                spans["root"].end(status=req.state, cause=cause)

    def _account_terminal(self, req: Request) -> None:
        self._by_state[req.state] = self._by_state.get(req.state, 0) + 1

    # -- client API ----------------------------------------------------------
    def _resolve_model(self, model: Optional[str]) -> ModelConfig:
        if model is None:
            if self._multi and len(self.models) > 1:
                raise ValueError(
                    f"multiplexed runtime serves "
                    f"{sorted(self.models)} — submit(model=...) is "
                    f"required")
            return next(iter(self.models.values()))
        try:
            return self.models[model]
        except KeyError:
            raise KeyError(f"unknown model {model!r} (registered: "
                           f"{sorted(self.models)})") from None

    def submit(self, payload: Any, deadline_s: Optional[float] = None,
               length: Optional[int] = None,
               model: Optional[str] = None) -> Request:
        """Admit one request; raises
        :class:`~analytics_zoo_tpu.resilience.errors.ServerOverloaded`
        on a full queue (the request is still accounted, state
        ``shed``).  ``length``: variable-axis length for bucket
        assignment.  ``model``: which multiplexed model (required when
        the runtime serves more than one)."""
        cfg = self._resolve_model(model)
        if cfg.streaming:
            raise ValueError(
                f"model {cfg.name!r} is a streaming session model — use "
                f"open_session()/submit_chunk()")
        if deadline_s is None:
            deadline_s = (cfg.default_deadline_s
                          if cfg.default_deadline_s is not None
                          else self.default_deadline_s)
        return self._submit(payload, deadline_s, length, cfg.name)

    def _submit(self, payload: Any, deadline_s: float,
                length: Optional[int], model: str,
                session: Optional[int] = None,
                affinity: Optional[int] = None,
                final: bool = False) -> Request:
        now = self.clock.now()
        req = Request(rid=next(self._rid), payload=payload, arrival_t=now,
                      deadline_t=now + deadline_s, length=length,
                      model=model, session=session, affinity=affinity,
                      final=final)
        self._submitted += 1
        if self.retain_requests:
            self.requests.append(req)
        self.metrics.on_submit(model=model if self._multi else None)
        if self.obs is not None:
            # root span of this request's trace: opened here, closed at
            # whatever terminal state the request reaches
            root = self.obs.tracer.start(
                "request", REQ_TRACE.format(rid=req.rid), rid=req.rid,
                deadline_s=round(req.deadline_t - now, 6))
            self._spans[req.rid] = {"root": root}
        self.queue.submit(req)   # may raise; _on_shed closes the spans
        if self.obs is not None and req.rid in self._spans:
            spans = self._spans[req.rid]
            spans["queue"] = self.obs.tracer.start(
                "queue", spans["root"].trace_id, parent=spans["root"])
        return req

    # -- streaming sessions --------------------------------------------------
    def open_session(self, model: Optional[str] = None) -> int:
        """Open a streaming session on its least-loaded healthy replica
        (session-affine: every chunk of this session dispatches THERE —
        the model's carry state lives on that replica).  Raises
        :class:`ServerOverloaded` when no replica is dispatchable."""
        cfg = self._resolve_model(model)
        if not cfg.streaming:
            raise ValueError(f"model {cfg.name!r} is not a streaming "
                             f"session model")
        healthy = self.pool.healthy()
        if not healthy:
            raise ServerOverloaded("no healthy replica to pin a "
                                   "session to; retry with backoff")
        rid = min((r.rid for r in healthy),
                  key=lambda r: (self._session_load.get(r, 0), r))
        sid = self._next_sid
        self._next_sid += 1
        self._sessions[sid] = {"model": cfg.name, "replica": rid,
                               "open": True, "chunks": 0}
        self._sessions_opened += 1
        self._open_sessions += 1
        self._session_load[rid] = self._session_load.get(rid, 0) + 1
        self.metrics.registry.counter("serve/sessions/opened").inc()
        self.metrics.registry.gauge("serve/sessions_open").set(
            float(self._open_sessions))
        if self.obs is not None:
            self.obs.recorder.note("session_opened", session=sid,
                                   model=cfg.name, replica=rid,
                                   t=round(self.clock.now(), 6))
        return sid

    def submit_chunk(self, sid: int, payload: Any,
                     length: Optional[int] = None,
                     deadline_s: Optional[float] = None,
                     final: bool = False) -> Request:
        """Feed one chunk of an open session.  The chunk's deadline is
        INCREMENTAL — anchored at this submit instant (``deadline_s`` or
        the model's ``chunk_deadline_s``), so a long-lived stream never
        accumulates slack debt and chunk deadlines stay monotone — EDF
        therefore preserves chunk order within the session's single
        (model, affinity, edge) group (``ModelConfig`` rejects
        multi-edge streaming plans for exactly this reason).
        ``final=True`` flushes the
        session (the stateful forward emits the tail) and closes it on
        successful admission — a final chunk shed at the door kills the
        session instead (the flush tail is unrecoverable)."""
        sess = self._sessions.get(sid)
        if sess is None:
            if 0 <= sid < self._next_sid:
                raise RuntimeError(f"session {sid} is closed")
            raise KeyError(f"unknown session {sid}")
        if not sess["open"]:
            raise RuntimeError(f"session {sid} is closed")
        cfg = self.models[sess["model"]]
        if cfg.serial_chunks and sess.get("last") is not None \
                and not sess["last"].finished:
            raise RuntimeError(
                f"session {sid}: chunk {sess['chunks']} is not answered "
                f"yet and model {cfg.name!r} takes one chunk of a session "
                f"at a time (serial_chunks)")
        if deadline_s is None:
            deadline_s = cfg.chunk_deadline_s
        # chunk deadlines must stay MONOTONE within the session — EDF
        # order IS chunk order, so a custom deadline_s earlier than a
        # previous chunk's would reorder the decode; clamp up to the
        # session's deadline high-water mark
        now = self.clock.now()
        deadline_s = max(deadline_s,
                         sess.get("last_deadline_t", 0.0) - now)
        # submit FIRST: a queue-full shed routes through _on_shed which
        # kills the session (a gap in the chunk stream would silently
        # corrupt the carry); only a successfully admitted final chunk
        # marks the session closed
        req = self._submit(
            payload, deadline_s, length, cfg.name, session=sid,
            affinity=sess["replica"], final=final)
        sess["chunks"] += 1
        sess["last_deadline_t"] = req.deadline_t
        if cfg.serial_chunks:
            sess["last"] = req
        if final:
            self._close_session_books(sess)
        return req

    def close_session(self, sid: int) -> None:
        """Client-initiated abort of an open session WITHOUT a flush
        chunk (the stream was abandoned): books close, the live entry
        and its replica pin release, and the pinned replica's store
        entry is evicted — so an abandoned session doesn't hold its
        replica hostage against autoscaler shrink or leak carry state.
        (An idle-session TTL that does this automatically is ROADMAP
        item-1 follow-up work; until then abandonment is the caller's
        contract.)  No-op if the session is already closed/released."""
        sess = self._sessions.get(sid)
        if sess is None:
            return
        self._close_session_books(sess)
        replica = self.pool.replica_by_rid(sess["replica"])
        self._release_session(sid)
        if replica is not None:
            for tier in replica.tier_objs.get(sess["model"], []):
                if tier.evict_session is not None:
                    tier.evict_session(sid)
        if self.obs is not None:
            self.obs.recorder.note("session_closed", session=sid,
                                   t=round(self.clock.now(), 6))

    def _close_session_books(self, sess: Dict[str, Any]) -> None:
        if not sess["open"]:
            return
        sess["open"] = False
        self._open_sessions -= 1
        self.metrics.registry.counter("serve/sessions/closed").inc()
        self.metrics.registry.gauge("serve/sessions_open").set(
            float(self._open_sessions))

    def _session_rids(self) -> Set[int]:
        """Replicas pinned by sessions with work outstanding (open, or
        closed with the final chunk still in flight) — protected from
        the autoscaler's drain-then-retire."""
        return {rid for rid, n in self._session_load.items() if n > 0}

    def _release_session(self, sid: int) -> None:
        """The session's last outcome landed (final chunk terminal, or
        killed): drop the live entry and its replica pin."""
        sess = self._sessions.pop(sid, None)
        if sess is None:
            return
        rid = sess["replica"]
        n = self._session_load.get(rid, 0) - 1
        if n > 0:
            self._session_load[rid] = n
        else:
            self._session_load.pop(rid, None)

    def _kill_session(self, req: Request, reason: str) -> None:
        """A chunk died without being served (shed, dispatch failure,
        replica loss): the session's carry now has a gap, so the whole
        session fails honestly — books closed, live entry released, and
        the pinned replica's store entry evicted
        (``ServingTier.evict_session``) so dead sessions don't leak
        state.  Chunks of this session still queued are failed before
        their dispatch (``_scrub_dead_session_rows``) — they never
        serve from recreated-empty state."""
        sid = req.session
        sess = self._sessions.get(sid)
        if sess is not None:
            self._close_session_books(sess)
            self._release_session(sid)
            self._sessions_failed += 1
            replica = self.pool.replica_by_rid(req.affinity) \
                if req.affinity is not None else None
            if replica is not None:
                for tier in replica.tier_objs.get(req.model, []):
                    if tier.evict_session is not None:
                        tier.evict_session(sid)
            if self.obs is not None:
                self.obs.recorder.note("session_failed", session=sid,
                                       reason=reason[:160],
                                       t=round(self.clock.now(), 6))

    def _scrub_dead_session_rows(self, batch: AssembledBatch) -> None:
        """A killed session's chunks may still be queued (admitted
        before the kill): fail them BEFORE the forward and mask their
        rows (session −1, final 0), so they neither return garbage
        marked ``done`` nor recreate the evicted store entry on the
        replica."""
        if batch.affinity is None:
            return
        for i, req in enumerate(batch.requests):
            if req.session is None or req.session in self._sessions:
                continue
            req.finish("failed", self.clock.now(), error=ReplicaWedged(
                f"session {req.session} already failed"))
            self._account_terminal(req)
            self.metrics.on_fail(model=batch.model if self._multi
                                 else None)
            self._end_request_spans(req, "failed", attempts=req.attempts)
            batch.batch["session"][i] = -1
            batch.batch["final"][i] = 0

    # -- warm-up -------------------------------------------------------------
    def warm(self, payload: Any, model: Optional[str] = None
             ) -> Dict[Tuple[str, Any, int], float]:
        """Compile every (edge, tier) geometry of ``model`` on every
        replica before traffic arrives, by running each once on a batch
        built from ``payload`` (an example request payload; a bucketed
        model's variable axis is padded/cut to each edge) —
        :meth:`Replica.warm`, off the dispatch path.  On a real clock
        the first dispatch of a cold geometry would otherwise compile
        for seconds under the wedge watchdog and the request deadlines.
        Compile errors propagate.  Returns ``{(model, edge, tier):
        slowest replica's seconds}``.

        A streaming session model has a dry run only if every tier of it
        says so (``ServingTier.pads_session_rows``): the warm-up batch's
        rows all carry session −1, which such a tier takes for padding."""
        cfg = self._resolve_model(model)
        if cfg.streaming and not all(t.pads_session_rows
                                     for t in cfg.tiers):
            raise ValueError(
                f"model {cfg.name!r} is a streaming session model — its "
                f"forward mutates session state, there is no dry run "
                f"(a tier that takes rows of session -1 for padding says "
                f"so: ServingTier.pads_session_rows)")
        now = self.clock.now()
        took: Dict[Tuple[str, Any, int], float] = {}
        for key in self._geometry_plan():
            name, edge, tier = key
            if name != cfg.name:
                continue
            req = Request(rid=-1, payload=payload, arrival_t=now,
                          deadline_t=float("inf"),
                          length=None if edge is FIXED else int(edge),
                          model=name)
            batch = self.batcher._collate([req], edge, tier, model=name)
            self._count_staging(batch)
            took[key] = max(r.warm(batch) for r in self.pool.replicas)
        return took

    # -- scheduler -----------------------------------------------------------
    def _tier_arg(self):
        if self._multi:
            return {name: ladder.tier
                    for name, ladder in self.ladders.items()}
        return self.ladder.tier

    def pump(self, force: bool = False) -> int:
        """Run all currently due scheduling work: shed expired requests,
        assemble and dispatch every flush-ready batch.  Returns the
        number of batches dispatched.  Call after submits and after
        advancing the clock."""
        with stage("az/serve/pump"):
            self._swap_tick()
            self._force = force
            dispatched = 0
            while True:
                if self.parallel and not force \
                        and not self.pool.any_free(self.clock.now()):
                    # every replica is serving concurrently — assembling
                    # a batch now would only burn its members' slack;
                    # expiry still ran on the previous iteration's
                    # next_batch
                    self.queue.expire()
                    break
                batch = self._take_held()
                if batch is None:
                    batch = self.batcher.next_batch(self._tier_arg(),
                                                    force=force)
                if batch is None:
                    # no batch is flush-ready; expiry may still have
                    # shed — that counts toward the current decision
                    # window
                    break
                self._dispatch(batch)
                dispatched += 1
            return dispatched

    def _take_held(self) -> Optional[AssembledBatch]:
        """The batch assembled ahead, if there is one — or the error its
        assembly met, raised here, where ``next_batch`` would have."""
        err, self._held_error = self._held_error, None
        if err is not None:
            raise err
        batch, self._held = self._held, None
        return batch

    def _assemble_ahead(self, replica: Replica) -> None:
        """The one batch of look-ahead: :meth:`Replica.forward` calls
        this between a tier's return of an answer that is not on the
        host yet and the fetch of it, so it runs while the batch's
        program does.  Assembles the next due batch exactly as the
        pump's loop would — into the geometry's OTHER staging buffer,
        at the ladder's rung of now — holds it for the loop to dispatch
        next, and starts its transfer if the tier offers ``place``.
        Holds at most one batch, so a failover's second forward of the
        same batch finds it held and assembles no third.  Never raises
        into the forward it runs under: what the assembly raises the
        loop raises when it comes for the batch, and a transfer that
        fails is left to the batch's own forward."""
        if self._held is not None or self._held_error is not None:
            return
        try:
            batch = self.batcher.next_batch(self._tier_arg(),
                                            force=self._force, ahead=True)
        except Exception as err:
            self._held_error = err
            return
        if batch is None:
            return
        self._held = batch
        self.metrics.registry.counter("serve/assembled_ahead").inc()
        tier = replica.tier_objs[batch.model][batch.tier]
        if tier.place is None:
            return
        try:
            batch.placed = (tier, tier.place(batch.batch))
        except Exception:
            logger.warning("serving: transfer ahead of a batch of model "
                           "%r failed; its forward sends it again",
                           batch.model, exc_info=True)

    def _depth(self) -> int:
        """Requests that wait for a dispatch: the queue's, and those of
        the batch assembled ahead (out of the queue, not yet served) —
        the load the ladder and the metrics saw before the look-ahead."""
        held = len(self._held.requests) if self._held is not None else 0
        return self.queue.depth + held

    def next_event_t(self) -> Optional[float]:
        """Parallel mode: the next virtual instant the pool changes
        state (a replica frees / restarts / finishes pre-warming) — an
        event-driven load loop advances the clock to ``min(this, next
        arrival)`` when :meth:`pump` has nothing to do."""
        return self.pool.next_event_t(self.clock.now())

    def drain(self, max_batches: int = 10_000_000) -> None:
        """Force-flush everything still queued (shutdown / end of drill):
        every pending request reaches a terminal state."""
        for _ in range(max_batches):
            if self.pump(force=True) == 0 and len(self.queue) == 0:
                return
        raise RuntimeError("drain did not converge")

    # -- live weights: hot-swap with canary + rollback (ISSUE 18) ------------
    def hot_swap(self, checkpoint_path: str,
                 model: Optional[str] = None, *,
                 canary_fraction: float = 0.25,
                 canary_min: int = 32,
                 divergence_budget: float = 1e-3,
                 latency_budget_s: Optional[float] = None,
                 canary_seed: int = 0,
                 lkg_after: int = 2,
                 warm_s: Optional[float] = None) -> Dict[str, Any]:
        """Start a zero-downtime weight rollout from a published
        checkpoint snapshot:

        1. **verify + load + place** — the snapshot's sha256 manifest is
           verified, the pytree restored, and placed through the
           pipeline's declared :class:`~analytics_zoo_tpu.parallel.
           specs.SpecSet` (``place_state``) so the swap is mesh-correct
           by construction;
        2. **canary** — a seeded ``canary_fraction`` of this model's
           live requests is MIRRORED to the new weights (one extra
           forward per touched batch; the mirror never enters
           ``accounting()``), per-row output divergence and modeled
           latency land in rollout-labeled ``serve/canary/*`` metrics,
           and a dedicated :class:`~analytics_zoo_tpu.obs.slo.
           SloEvaluator` trips the stage the moment either crosses its
           budget;
        3. **rollout** — after ``canary_min`` clean mirrored requests
           the pool's one-replica-at-a-time drain → install → re-warm →
           rejoin machine takes over (session-pinned replicas last);
        4. **rollback** — a tripped canary or a mid-rollout SLO trip
           reverts to the previous weights (the ``serve-lkg`` tier's
           content) EXACTLY once; a fully-healthy rollout instead
           promotes this snapshot to ``serve-lkg`` after ``lkg_after``
           clean decision windows (PR-3's hysteresis, serving twin).

        Returns the rollout record (also appended to the swap log).
        Raises :class:`CheckpointCorrupt` on a bad manifest — a
        truncated publish never drains a replica."""
        from analytics_zoo_tpu.parallel import checkpoint as ckpt
        from analytics_zoo_tpu.resilience.errors import CheckpointCorrupt

        cfg = self._resolve_model(model)
        if cfg.weights_to_tiers is None:
            raise ValueError(
                f"model {cfg.name!r} declares no weights_to_tiers — the "
                f"runtime cannot build its tier stack from a checkpoint")
        if self._swap_ctl is not None \
                and self._swap_ctl["phase"] in ("canary", "rolling"):
            raise RuntimeError(
                f"hot_swap: rollout of "
                f"{self._swap_ctl['checkpoint']!r} still in progress")
        now = self.clock.now()
        try:
            ckpt.verify_snapshot(checkpoint_path)
            state = ckpt.load(checkpoint_path, verify=True)
        except CheckpointCorrupt as e:
            if self.obs is not None:
                self.obs.recorder.note(
                    "swap_rejected", checkpoint=checkpoint_path,
                    error=str(e)[:160], t=round(now, 6))
            raise
        placed = self.specs.place_state(state) \
            if self.specs is not None else state
        mirror = list(cfg.weights_to_tiers(placed, -1))
        if len(mirror) != len(cfg.tiers):
            raise ValueError(
                f"model {cfg.name!r}: weights_to_tiers built "
                f"{len(mirror)} tiers, template declares "
                f"{len(cfg.tiers)}")
        k = self._swap_counter
        self._swap_counter += 1
        from analytics_zoo_tpu.obs.slo import SloEvaluator, canary_slos

        window_params = {key: v for key, v in self._slo_params.items()
                         if key in ("fast_window_s", "slow_window_s",
                                    "time_scale", "timeline_cap")}
        evaluator = SloEvaluator(
            slos=canary_slos(cfg.name, divergence_budget,
                             latency_budget_s, rollout=k),
            registry=self.metrics.registry,
            fast_burn=1.0, slow_burn=1.0, **window_params)
        self._lkg = None   # a new rollout supersedes a pending promotion
        self._swap_ctl = {
            "phase": "canary", "model": cfg.name, "rollout": k,
            "checkpoint": checkpoint_path, "placed": placed,
            "mirror": mirror, "fraction": float(canary_fraction),
            "min": int(canary_min), "seed": int(canary_seed),
            "mirrored": 0, "evaluator": evaluator,
            "lkg_after": int(lkg_after), "warm_s": warm_s,
            "rolled_back": False, "stash": {}, "t_started": now,
        }
        self.metrics.registry.counter("serve/swap/rollouts").inc()
        if self.autoscaler is not None:
            # canary verdicts must not be masked by fresh capacity —
            # the loop keeps observing but actuations are swallowed
            self.autoscaler.hold = True
        if self.obs is not None:
            self.obs.recorder.note(
                "swap_started", model=cfg.name, rollout=k,
                checkpoint=checkpoint_path,
                canary_fraction=float(canary_fraction),
                canary_min=int(canary_min),
                divergence_budget=divergence_budget, t=round(now, 6))
        record = {"rollout": k, "model": cfg.name,
                  "checkpoint": checkpoint_path, "outcome": None,
                  "t_started": round(now, 6)}
        self._swap_log.append(record)
        if canary_fraction <= 0 or canary_min <= 0:
            self._begin_roll()   # canary explicitly disabled
        return record

    @property
    def swap_active(self) -> bool:
        """Whether a rollout is in flight (canary or rolling) — the
        gate a checkpoint-watching driver checks before starting the
        next ``hot_swap`` (one rollout at a time; a newly-published
        snapshot waits its turn)."""
        return (self._swap_ctl is not None
                and self._swap_ctl["phase"] in ("canary", "rolling"))

    @property
    def lkg_pending(self) -> bool:
        """Whether a completed rollout is still inside its serve-LKG
        hysteresis (clean decision windows not yet accumulated).  A
        driver that starts the next ``hot_swap`` now supersedes the
        pending promotion — waiting for this to clear is how each
        fully-healthy rollout actually lands in the ``serve-lkg``
        tier."""
        return self._lkg is not None

    def _swap_install(self, replica: Replica) -> None:
        """The pool rollout's install hook: stash the replica's live
        tier stack for this model (the rollback inventory — still
        jit-warm), then mount the new-weights tiers built for THIS
        rid (per-replica stores stay per-replica)."""
        ctl = self._swap_ctl
        name = ctl["model"]
        ctl["stash"][replica.rid] = (replica.forward_fns.get(name),
                                     replica.tier_objs.get(name))
        tiers = list(self.models[name].weights_to_tiers(
            ctl["placed"], replica.rid))
        replica.forward_fns[name] = [t.forward for t in tiers]
        replica.tier_objs[name] = tiers
        self.metrics.registry.counter("serve/swap/replicas_swapped").inc()

    def _begin_roll(self) -> None:
        ctl = self._swap_ctl
        ctl["phase"] = "rolling"
        self.pool.swap_defer = set(self._session_rids())
        self.pool.hot_swap(ctl["checkpoint"], install=self._swap_install,
                           warm_s=ctl["warm_s"],
                           last=sorted(self._session_rids()))
        if self.autoscaler is not None:
            self.autoscaler.hold = False
        if self.obs is not None:
            self.obs.recorder.note(
                "swap_rolling", model=ctl["model"],
                rollout=ctl["rollout"], mirrored=ctl["mirrored"],
                t=round(self.clock.now(), 6))

    def _swap_tick(self) -> None:
        """Advance swap bookkeeping once per pump: refresh the deferred
        (session-pinned) rid set, let the pool machine step, and detect
        rollout completion (which arms the serve-LKG hysteresis)."""
        ctl = self._swap_ctl
        if ctl is None or ctl["phase"] != "rolling":
            return
        self.pool.swap_defer = set(self._session_rids())
        self.pool.healthy()          # runs _revive → _step_rollout
        if self.pool.rollout_active:
            return
        ctl["phase"] = "complete"
        ctl["stash"] = {}            # old weights no longer needed
        self._swap_stats["completed"] += 1
        self._swap_log[-1]["outcome"] = "complete"
        swapped = (self.pool.last_rollout or {}).get("swapped", [])
        self._lkg = {"ctl": ctl, "clean": 0,
                     "after": ctl["lkg_after"]}
        if self.obs is not None:
            self.obs.recorder.note(
                "swap_complete", model=ctl["model"],
                rollout=ctl["rollout"], replicas=list(swapped),
                t=round(self.clock.now(), 6))

    def _maybe_canary(self, batch: AssembledBatch, rows,
                      now: float) -> None:
        """Canary mirroring on the live dispatch path: a seeded
        fraction of this model's requests also runs on the new-weights
        mirror tier; per-row divergence + modeled latency land in the
        rollout-labeled registry names and the canary evaluator trips
        the stage on budget.  The mirror NEVER touches the request
        lifecycle — ``accounting()`` is conserved by construction."""
        ctl = self._swap_ctl
        if ctl is None or ctl["phase"] != "canary" \
                or batch.model != ctl["model"]:
            return
        gate = int(ctl["fraction"] * 1000)
        sel = [i for i, r in enumerate(batch.requests)
               if not r.finished
               and (r.rid * 1_000_003 + ctl["seed"]) % 1000 < gate]
        if not sel:
            return
        m, k = ctl["model"], ctl["rollout"]
        reg = self.metrics.registry
        reg.counter(f"serve/canary/mirrored/model={m}").inc(len(sel))
        ctl["mirrored"] += len(sel)
        div_h = reg.histogram(
            f"serve/canary/divergence/model={m}/swap={k}")
        mirror_tier = ctl["mirror"][batch.tier]
        try:
            mrows = np.asarray(mirror_tier.forward(batch.batch))
            for i in sel:
                a, b = rows[i], mrows[i]
                if isinstance(a, (str, bytes, np.str_)):
                    div = 0.0 if a == b else 1.0
                else:
                    d = np.abs(np.asarray(a, dtype=np.float64)
                               - np.asarray(b, dtype=np.float64))
                    div = float(np.max(d)) if d.size else 0.0
                div_h.observe(div)
        except Exception as err:
            # a crashing canary forward is itself a tripworthy signal
            div_h.observe(float("inf"))
            if self.obs is not None:
                self.obs.recorder.note(
                    "canary_error", model=m, rollout=k,
                    error=f"{type(err).__name__}: {err}"[:160],
                    t=round(now, 6))
        if self._service_time is not None:
            live = float(self._service_hook(batch, -1))
            template = self.models[m].tiers[batch.tier]
            ratio = (template.speed / mirror_tier.speed
                     if getattr(mirror_tier, "speed", 0) else 1.0)
            reg.histogram(
                f"serve/canary/latency_s/model={m}/swap={k}"
            ).observe(live * ratio)
        ev = ctl["evaluator"]
        ev.observe_registry(reg, now)
        decision = ev.decide(now)
        if decision.burning:
            self._swap_stats["trips"] += 1
            reg.counter("serve/canary/trips").inc()
            if self.obs is not None:
                self.obs.recorder.note(
                    "canary_trip", model=m, rollout=k,
                    burning=list(decision.burning),
                    mirrored=ctl["mirrored"], t=round(now, 6))
            self._swap_rollback("canary_trip: "
                                + ",".join(decision.burning))
        elif ctl["mirrored"] >= ctl["min"]:
            self._begin_roll()

    def _swap_rollback(self, reason: str) -> None:
        """Revert the rollout to the previous weights (the content of
        the ``serve-lkg`` tier) EXACTLY once — the ``rolled_back``
        latch makes a canary trip racing a mid-rollout anomaly
        idempotent.  Already-swapped replicas get their stashed (still
        jit-warm) tier stacks back instantly; a replica with no stash
        (grown mid-rollout) is rebuilt from the verified ``serve-lkg``
        snapshot when one exists."""
        ctl = self._swap_ctl
        if ctl is None or ctl["rolled_back"]:
            return
        ctl["rolled_back"] = True
        now = self.clock.now()
        swapped = self.pool.abort_rollout()
        missing: List[int] = []
        for rid in swapped:
            r = self.pool.replica_by_rid(rid)
            if r is None:
                continue
            stash = ctl["stash"].get(rid)
            if stash is not None and stash[0] is not None:
                r.forward_fns[ctl["model"]] = stash[0]
                r.tier_objs[ctl["model"]] = stash[1]
            else:
                missing.append(rid)
        lkg_path = None
        if missing:
            from analytics_zoo_tpu.parallel import checkpoint as ckpt

            base = os.path.dirname(os.path.abspath(ctl["checkpoint"]))
            found = ckpt.tier_snapshot(base, "serve-lkg")
            if found is not None:
                lkg_path = found[0]
                state = ckpt.load(lkg_path, verify=False)
                placed = self.specs.place_state(state) \
                    if self.specs is not None else state
                for rid in missing:
                    r = self.pool.replica_by_rid(rid)
                    tiers = list(self.models[ctl["model"]]
                                 .weights_to_tiers(placed, rid))
                    r.forward_fns[ctl["model"]] = [t.forward
                                                   for t in tiers]
                    r.tier_objs[ctl["model"]] = tiers
        ctl["phase"] = "rolled_back"
        ctl["stash"] = {}
        self._swap_stats["rollbacks"] += 1
        self._swap_log[-1]["outcome"] = "rolled_back"
        self._swap_log[-1]["reason"] = reason[:160]
        self.metrics.registry.counter("serve/swap/rollbacks").inc()
        self._lkg = None
        if self.autoscaler is not None:
            self.autoscaler.hold = False
        if self.obs is not None:
            self.obs.recorder.note(
                "swap_rollback", model=ctl["model"],
                rollout=ctl["rollout"], reason=reason[:160],
                reverted=list(swapped), lkg=lkg_path,
                t=round(now, 6))
            if self.obs.dump_path:
                self.obs.dump("swap_rollback")

    def _maybe_promote_lkg(self, decision) -> None:
        """Serve-LKG hysteresis (the PR-3 pattern): after a completed
        rollout, ``lkg_after`` consecutive clean decision windows
        promote the swapped snapshot into the ``serve-lkg`` tier; a
        trip resets the streak (and a mid-rollout trip rolls back via
        ``_decide_window`` before ever reaching here)."""
        pend = self._lkg
        if pend is None:
            return
        model = pend["ctl"]["model"]
        dirty = any(self._slo_model.get(s) == model
                    for s in decision.burning)
        if dirty:
            pend["clean"] = 0
            return
        pend["clean"] += 1
        if pend["clean"] < pend["after"]:
            return
        from analytics_zoo_tpu.parallel import checkpoint as ckpt
        from analytics_zoo_tpu.resilience.errors import CheckpointCorrupt

        snap = pend["ctl"]["checkpoint"]
        base = os.path.dirname(os.path.abspath(snap))
        self._lkg = None
        try:
            target = ckpt.promote_tier(base, snap, "serve-lkg")
        except (CheckpointCorrupt, OSError) as e:
            # the trainer may have GC'd the step snapshot already —
            # a missed promotion is not a serving fault
            if self.obs is not None:
                self.obs.recorder.note(
                    "swap_lkg_failed", checkpoint=snap,
                    error=str(e)[:160],
                    t=round(self.clock.now(), 6))
            return
        self._swap_stats["lkg_promotions"] += 1
        self.metrics.registry.counter("serve/swap/lkg_promotions").inc()
        if self.obs is not None:
            self.obs.recorder.note(
                "swap_lkg_promoted", checkpoint=snap, tier=target,
                rollout=pend["ctl"]["rollout"],
                t=round(self.clock.now(), 6))

    # -- internals -----------------------------------------------------------
    def _fault_for(self, replica: Replica) -> Optional[Callable]:
        """Compose the chaos hooks targeting ``replica`` at the current
        dispatch index (None when nothing is due)."""
        if self.chaos is None:
            return None
        idx = self._dispatch_idx
        hooks: List[Callable] = []
        spec = self.chaos.serving_active("slow_forward", idx, consume=False)
        if spec is not None and spec.detail.get(
                "replica", replica.rid) == replica.rid:
            self.chaos.serving_active("slow_forward", idx)  # record+consume
            delay = float(spec.detail.get("delay_s", 2.0))
            # the wedge advances time THROUGH the replica's budget guard:
            # with a fence budget armed the pool observes the wedge at
            # the fence instant; without one this is a plain sleep (the
            # PR-5 return-then-check path, byte-identical)
            hooks.append(lambda r: r.sleep_guarded(delay))
        spec = self.chaos.serving_active("replica_crash", idx, consume=False)
        if spec is not None and spec.detail.get(
                "replica", replica.rid) == replica.rid:
            self.chaos.serving_active("replica_crash", idx)

            def crash(r):
                from analytics_zoo_tpu.resilience.errors import InjectedFault

                raise InjectedFault(
                    f"chaos: replica {r.rid} killed mid-batch")

            hooks.append(crash)
        if not hooks:
            return None

        def fault(r):
            for h in hooks:
                h(r)

        return fault

    def _note_fill(self, batch: AssembledBatch) -> None:
        """Per-model batch-fill EWMA — the autoscaler's width-vs-count
        saturation signal: sustained fill ≈ 1.0 means the model is
        batch-saturated and count-growth would split full batches below
        the occupancy knee (see :meth:`_width_speedup`)."""
        cap = max(self.batcher.model_batch(batch.model), 1)
        fill = min(1.0, batch.n_valid / cap)
        prev = self._fill_ewma.get(batch.model)
        self._fill_ewma[batch.model] = (
            fill if prev is None else 0.8 * prev + 0.2 * fill)

    def _count_staging(self, batch: AssembledBatch) -> None:
        if not batch.staging_reused:
            self.metrics.registry.counter("serve/staging_alloc").inc()

    def _answer_rows(self, batch: AssembledBatch, out: Any) -> np.ndarray:
        """The tier's answer as rows to hand out.  The batch's input is
        one of the batcher's staging buffers, which the next batch but
        one of the geometry overwrites (:class:`AssembledBatch`): an
        answer that
        shares memory with it — a tier that returns its input, or a
        view of it — is copied before a request retains a row of it."""
        rows = np.asarray(out)
        if np.shares_memory(
                rows, batch.batch[self.models[batch.model].pad_key]):
            rows = rows.copy()
        return rows

    def _dispatch(self, batch: AssembledBatch) -> None:
        self._scrub_dead_session_rows(batch)
        self._count_staging(batch)
        if self.parallel:
            self._dispatch_parallel(batch)
            return
        self._dispatch_idx += 1
        self.metrics.on_batch(batch.n_valid,
                              self.batcher.model_batch(batch.model),
                              self._depth())
        self._note_fill(batch)
        model_label = batch.model if self._multi else None
        t0 = self.clock.now()
        batch_span = None
        if self.obs is not None:
            # the batch gets its own trace (it belongs to N requests at
            # once); each member request's queue span closes here and a
            # per-request dispatch child opens under its root
            batch_span = self.obs.tracer.start(
                "batch", f"batch-{self._dispatch_idx}",
                requests=[r.rid for r in batch.requests],
                edge=str(batch.edge), n_valid=batch.n_valid,
                tier=batch.tier)
            for req in batch.requests:
                spans = self._spans.get(req.rid)
                if spans is None:
                    continue
                q = spans.pop("queue", None)
                if q is not None:
                    q.end(status="assembled", edge=str(batch.edge))
                spans["dispatch"] = self.obs.tracer.start(
                    "dispatch", spans["root"].trace_id,
                    parent=spans["root"], tier=batch.tier,
                    batch=self._dispatch_idx)
        try:
            with stage("az/serve/forward"):
                out = self.pool.dispatch(batch, fault_for=self._fault_for,
                                         meanwhile=self._assemble_ahead)
        except ReplicaWedged as err:
            with stage("az/serve/handout"):
                now = self.clock.now()
                for req in batch.requests:
                    if req.finished:        # scrubbed dead-session row
                        continue
                    req.finish("failed", now, error=err)
                    self._account_terminal(req)
                    self.metrics.on_fail(model=model_label)
                    self._end_request_spans(req, "failed",
                                            attempts=req.attempts)
                    if req.session is not None:
                        # affine dispatch lost its replica (or wedged):
                        # the session's carry state is gone — honest
                        # state loss
                        self._kill_session(req, str(err))
                if batch_span is not None:
                    batch_span.end(status="failed",
                                   redispatched=batch.redispatched)
                self._after_dispatch(batch, t0, failed=True)
            return
        with stage("az/serve/handout"):
            now = self.clock.now()
            rows = self._answer_rows(batch, out)
            self._maybe_canary(batch, rows, now)
            for i, req in enumerate(batch.requests):
                if req.finished:            # scrubbed dead-session row
                    continue
                req.tier = batch.tier
                req.finish("done", now, result=rows[i])
                self._account_terminal(req)
                missed = now > req.deadline_t
                self.metrics.on_complete(now - req.arrival_t, batch.tier,
                                         missed=missed, model=model_label)
                self._end_request_spans(req, "done", attempts=req.attempts,
                                        missed=missed)
                if req.final and req.session is not None:
                    self._release_session(req.session)
            if batch_span is not None:
                batch_span.end(status="done",
                               redispatched=batch.redispatched)
            self._after_dispatch(batch, t0, failed=False)

    def _parallel_fault(self, replica: Replica) -> Tuple[bool, float, float]:
        """Chaos windows for the current dispatch index against
        ``replica`` under the parallel service model: ``(crash,
        delay_s, slow_x)``.  The windows are the same ``serving_active``
        queries the serial ``_fault_for`` composes; here the effects are
        applied to the replica's OWN busy horizon instead of the shared
        clock.  ``slow_x`` (the ``slow_device`` kind) multiplies the
        SERVICE time — a persistently slow-but-correct device, which
        deliberately does NOT count as chaotic: it must slip past the
        wedge/fence checks, because catching it is the straggler
        detector's job, not the watchdog's."""
        if self.chaos is None:
            return False, 0.0, 1.0
        idx = self._dispatch_idx
        delay = 0.0
        spec = self.chaos.serving_active("slow_forward", idx, consume=False)
        if spec is not None and spec.detail.get(
                "replica", replica.rid) == replica.rid:
            self.chaos.serving_active("slow_forward", idx)  # record+consume
            delay = float(spec.detail.get("delay_s", 2.0))
        crash = False
        spec = self.chaos.serving_active("replica_crash", idx, consume=False)
        if spec is not None and spec.detail.get(
                "replica", replica.rid) == replica.rid:
            self.chaos.serving_active("replica_crash", idx)
            crash = True
        slow_x = 1.0
        spec = self.chaos.serving_active("slow_device", idx, consume=False)
        if spec is not None and spec.detail.get(
                "replica", replica.rid) == replica.rid:
            self.chaos.serving_active("slow_device", idx)
            slow_x = float(spec.detail.get("slow_x", 4.0))
        return crash, delay, slow_x

    def _dispatch_parallel(self, batch: AssembledBatch) -> None:
        """Parallel-service dispatch: assign the batch to a free (or,
        for sessions/force-drain, the pinned/least-busy) replica; its
        completion lands at ``start + cold_tax + service`` on THAT
        replica's busy horizon while the shared clock stands still —
        replicas serve concurrently, so resizing the pool really
        changes capacity (what the fleet drill measures).

        Chaos + failover compose here too (ISSUE 18): an injected crash
        fences the replica at the instant the batch would have started
        on its horizon, a ``slow_forward`` wedge is detected at the
        fence budget (or, without one, when the slow forward returns) —
        and the batch re-dispatches EXACTLY once through the same
        ``redispatched`` latch as serial mode.  Request spans thread
        through unchanged: dispatch/root spans end AT the computed
        completion instant (``Span.end(at=)``), so az-trace tail
        attribution covers fleet drills."""
        self._dispatch_idx += 1
        self.metrics.on_batch(batch.n_valid,
                              self.batcher.model_batch(batch.model),
                              self.queue.depth)
        self._note_fill(batch)
        now = self.clock.now()
        model_label = batch.model if self._multi else None
        batch_span = None
        if self.obs is not None:
            batch_span = self.obs.tracer.start(
                "batch", f"batch-{self._dispatch_idx}",
                requests=[r.rid for r in batch.requests],
                edge=str(batch.edge), n_valid=batch.n_valid,
                tier=batch.tier)
            for req in batch.requests:
                spans = self._spans.get(req.rid)
                if spans is None:
                    continue
                q = spans.pop("queue", None)
                if q is not None:
                    q.end(status="assembled", edge=str(batch.edge))
                spans["dispatch"] = self.obs.tracer.start(
                    "dispatch", spans["root"].trace_id,
                    parent=spans["root"], tier=batch.tier,
                    batch=self._dispatch_idx)

        def fail_batch(err: BaseException, at: float) -> None:
            for req in batch.requests:
                if req.finished:        # scrubbed dead-session row
                    continue
                req.finish("failed", at, error=err)
                self._account_terminal(req)
                self.metrics.on_fail(model=model_label)
                self._end_request_spans(req, "failed", at=at,
                                        attempts=req.attempts)
                if req.session is not None:
                    self._kill_session(req, str(err))
            if batch_span is not None:
                batch_span.end(status="failed", at=at,
                               redispatched=batch.redispatched)
            if batch.redispatched:
                self.metrics.redispatches += 1
            self._since_decision += 1
            if self._since_decision >= self.decision_every:
                self._decide_window()

        def complete(replica: Replica, out: Any, start: float,
                     elapsed: float, service: float) -> None:
            completion = start + elapsed
            replica.busy_until = completion
            if self.health is not None:
                # only the SERVICE component feeds the straggler EWMA:
                # injected slow_forward delay and cold-start warm tax
                # are not the silicon's speed, and eviction is
                # irreversible — a replica paying warm taxes for new
                # (model, edge, tier) keys must not be flagged for it
                self._note_device_health(replica, service)
            rows = self._answer_rows(batch, out)
            self._maybe_canary(batch, rows, now)
            for i, req in enumerate(batch.requests):
                if req.finished:        # scrubbed dead-session row
                    continue
                req.tier = batch.tier
                req.finish("done", completion, result=rows[i])
                self._account_terminal(req)
                missed = completion > req.deadline_t
                self.metrics.on_complete(completion - req.arrival_t,
                                         batch.tier, missed=missed,
                                         model=model_label)
                self._end_request_spans(req, "done", at=completion,
                                        attempts=req.attempts,
                                        missed=missed)
                if req.final and req.session is not None:
                    self._release_session(req.session)
            if batch_span is not None:
                batch_span.end(status="done", at=completion,
                               redispatched=batch.redispatched)
            if batch.redispatched:
                self.metrics.redispatches += 1
            self._since_decision += 1
            if self._since_decision >= self.decision_every:
                self._decide_window()

        def wedge(replica: Replica, err: ReplicaWedged, at: float,
                  is_backup: bool) -> None:
            replica.busy_until = at
            self.pool._fence(replica, err, at=at)
            failover(replica, err, at, is_backup)

        def serve_on(replica: Replica, t_avail: float,
                     is_backup: bool) -> None:
            """One service attempt on ``replica``'s busy horizon,
            mirroring the serial ``Replica.forward`` time order —
            injected delay, cold compile, model fn, service — with the
            fence budget cutting the cumulative elapsed exactly where
            ``sleep_guarded`` would.  A chaos crash/wedge fences the
            replica at the computed instant and (for the primary, on a
            non-affine batch) falls through to failover."""
            for req in batch.requests:
                req.attempts += 1
            replica.dispatches += 1
            crash, delay, slow_x = self._parallel_fault(replica)
            start = max(t_avail, replica.busy_until)
            budget = replica.fence_budget_s
            chaotic = crash or delay > 0
            if chaotic and budget is not None and delay > budget:
                # the injected stall alone crosses the budget: fenced
                # mid-delay, before compile/fn would even run
                wedge(replica, ReplicaWedged(
                    f"replica {replica.rid}: forward wedged mid-flight "
                    f"— fenced at the {budget:.3f}s fence budget"),
                    start + budget, is_backup)
                return
            if crash:
                # serial ordering: the slow_forward hook sleeps first,
                # then the crash hook raises — the kill lands at
                # start + delay on this replica's horizon
                wedge(replica, ReplicaWedged(
                    f"replica {replica.rid}: forward crashed mid-batch "
                    f"(InjectedFault: chaos: replica {replica.rid} "
                    f"killed mid-batch)"), start + delay, is_backup)
                return
            try:
                out = replica._fn_for(batch)(batch.batch)
            except Exception as e:
                err = e if isinstance(e, ReplicaWedged) else ReplicaWedged(
                    f"replica {replica.rid}: forward crashed mid-batch "
                    f"({type(e).__name__}: {e})")
                fail_batch(err, start)
                return
            tax = replica.cold_tax(batch, mark=False)
            if chaotic and budget is not None and delay + tax > budget:
                # fenced mid-compile: the geometry stays COLD for the
                # restarted replica (mirrors _maybe_cold_compile)
                wedge(replica, ReplicaWedged(
                    f"replica {replica.rid}: forward wedged mid-flight "
                    f"— fenced at the {budget:.3f}s fence budget"),
                    start + budget, is_backup)
                return
            if tax > 0 and replica.warm_keys is not None:
                replica.warm_keys.add((batch.model, batch.edge,
                                       batch.tier))
            # slow_device stretches the service itself (the device
            # computes correctly, just slowly) and stays OUT of
            # `chaotic`: no wedge, no fence — only the straggler EWMA
            # sees it, through the health feed in complete()
            service = float(self._service_hook(batch, replica.rid)) * slow_x
            elapsed = delay + tax + service
            if chaotic and budget is not None and elapsed > budget:
                # fence-budget semantics on the replica's OWN busy
                # horizon: the wedge is observed at start + budget
                wedge(replica, ReplicaWedged(
                    f"replica {replica.rid}: forward wedged mid-flight "
                    f"— fenced at the {budget:.3f}s fence budget"),
                    start + budget, is_backup)
                return
            if chaotic and elapsed > replica.watchdog.timeout_s:
                # no budget: return-then-check — the wedge rides out
                # the whole stall before it is observed
                wedge(replica, ReplicaWedged(
                    f"replica {replica.rid}: forward wedged "
                    f"({elapsed:.3f}s > "
                    f"{replica.watchdog.timeout_s:.3f}s deadline)"),
                    start + elapsed, is_backup)
                return
            complete(replica, out, start, elapsed, service)

        def failover(failed: Replica, err: ReplicaWedged,
                     t_detect: float, is_backup: bool) -> None:
            if is_backup or batch.redispatched \
                    or batch.affinity is not None:
                # latch spent, or a session batch (its carry lives on
                # the failed replica — honest state loss)
                fail_batch(err, t_detect)
                return
            batch.redispatched = True
            backup = self.pool.pick_free(t_detect, exclude=failed.rid)
            if backup is None:
                backup = self.pool.least_busy()
            if backup is None:
                fail_batch(ReplicaWedged(
                    f"batch failover from replica {failed.rid}: no "
                    f"healthy replica left"), t_detect)
                return
            self.pool._event({"kind": "failover", "from": failed.rid,
                              "to": backup.rid,
                              "t": round(t_detect, 6),
                              "requests": [r.rid
                                           for r in batch.requests]})
            serve_on(backup, t_detect, is_backup=True)

        if batch.affinity is not None:
            self.pool._revive()
            replica = self.pool.replica_by_rid(batch.affinity)
            if replica is None or replica.state != "healthy":
                replica = None
        else:
            replica = self.pool.pick_free(now)
            if replica is None:
                # force-drain path: queue the batch on the least-busy
                # replica (starts when it frees)
                replica = self.pool.least_busy()
        if replica is None:
            fail_batch(ReplicaWedged(
                f"no replica available for model {batch.model!r}"
                + (f" (session pinned to {batch.affinity})"
                   if batch.affinity is not None else "")), now)
            return
        serve_on(replica, now, is_backup=False)

    def _note_device_health(self, replica: Replica, elapsed: float) -> None:
        """Feed one completed dispatch's per-replica SERVICE time (the
        post-``slow_x`` compute component only — excluding injected
        ``slow_forward`` delay and cold-start warm tax, which would
        falsely flag healthy silicon) into the straggler EWMA ladder;
        when the ladder flags the replica
        (persistently over ``straggler_factor`` × the fleet median for
        ``flag_after`` windows), quarantine it: drain-then-retire with
        ``device_budget`` decremented, so capacity recovers on healthy
        silicon and nothing re-seats on the slow device."""
        flagged = self.health.observe_step_time(replica.rid, float(elapsed))
        if flagged is None:
            return
        pol = self.health.policy
        if not (pol.evict and self.health.eviction_budget_left):
            logger.warning("health: replica %d flagged as straggler but "
                           "eviction is %s — serving continues degraded",
                           flagged,
                           "off" if not pol.evict else "budget-exhausted")
            return
        victim = self.pool.replica_by_rid(flagged)
        width = victim.width if victim is not None else 1
        if self.pool.quarantine(flagged, reason="straggler"):
            self.health.note_quarantine(flagged, "straggler")
            if self.autoscaler is not None:
                self.autoscaler.note_quarantine(flagged, width)

    def _after_dispatch(self, batch: AssembledBatch, t0: float,
                        failed: bool) -> None:
        """``t0`` to now is THIS batch's service, launch to answer, for
        the batcher's EWMA; where the next batch was assembled ahead it
        contains that batch's collate, which ran under this program."""
        dt = self.clock.now() - t0
        if not failed and self.batcher.service_time is None:
            # the EWMA is only ever read when no explicit service model
            # is configured — don't maintain it for nobody
            self.batcher.observe_service_s(batch.edge, dt, tier=batch.tier,
                                           model=batch.model)
        if batch.redispatched:
            self.metrics.redispatches += 1
        self._since_decision += 1
        if self._since_decision >= self.decision_every:
            self._decide_window()

    def _decide_window(self) -> None:
        depth = self._depth()
        detail = {"shed_in_window": self._window_shed,
                  "queue_depth": depth}
        if self.slo is not None:
            # SLO-driven path: window verdicts come from multi-window
            # burn rates over registry snapshots, not the raw flag —
            # the decision itself lands in the black box (Clockwork:
            # the action log explains the action)
            now = self.clock.now()
            self.slo.observe_registry(self.metrics.registry, now)
            decision = self.slo.decide(now)
            if self.obs is not None:
                self.obs.recorder.note(
                    "slo_decision", t=round(now, 6),
                    overloaded=decision.overloaded,
                    burning=list(decision.burning),
                    new_trips=list(decision.new_trips),
                    recovered=list(decision.recovered),
                    scale_hint=decision.scale_hint)
            if self._multi:
                self._observe_multi(decision, detail)
            else:
                self.ladder.observe_decision(decision, detail=detail)
            # mid-rollout anomaly: a fresh trip of the swapped model's
            # SLOs while replicas are still being swapped rolls back
            ctl = self._swap_ctl
            if ctl is not None and ctl["phase"] == "rolling" \
                    and decision.new_trips:
                hit = [s for s in decision.new_trips
                       if self._slo_model.get(s) == ctl["model"]]
                if hit:
                    self._swap_rollback(
                        "mid_rollout_anomaly: " + ",".join(hit))
            self._maybe_promote_lkg(decision)
            if self.autoscaler is not None:
                self._actuate(decision)
        else:
            if self._multi:
                for name, ladder in self.ladders.items():
                    depth_high = ladder.policy.depth_high * self.max_batch
                    overloaded = (
                        self._window_shed_by.get(name, 0) > 0
                        or depth > depth_high)
                    ladder.observe_window(overloaded, detail=dict(detail))
            else:
                depth_high = self.ladder.policy.depth_high * self.max_batch
                overloaded = (self._window_shed > 0
                              or depth > depth_high)
                self.ladder.observe_window(overloaded, detail=detail)
        self._window_shed = 0
        self._window_shed_by = {}
        self._since_decision = 0

    def _observe_multi(self, decision, detail: Dict[str, Any]) -> None:
        """Fan one SLO decision out to the per-model ladders and refresh
        the weighted-EDF weights: each model's ladder sees only ITS
        SLOs' burn, and its dispatch weight follows its worst
        fast-window burn (capped) — deadline weighted by how fast that
        model's error budget is being spent."""
        burning_by_model: Dict[str, List[str]] = {}
        for slo_name in decision.burning:
            m = self._slo_model.get(slo_name)
            if m is not None:
                burning_by_model.setdefault(m, []).append(slo_name)
        for name, ladder in self.ladders.items():
            cfg = self.models[name]
            if cfg.slos:
                burning = burning_by_model.get(name, [])
                d = {"slo_burning": burning,
                     "scale_hint": decision.scale_hint, **detail}
                ladder.observe_window(bool(burning), detail=d)
            else:
                # a model with no declared SLOs falls back to its raw
                # per-model shed flag
                ladder.observe_window(
                    self._window_shed_by.get(name, 0) > 0,
                    detail=dict(detail))
            if cfg.slos:
                worst = max((decision.per_slo[s.name]["fast"]["burn"]
                             for s in cfg.slos
                             if s.name in decision.per_slo),
                            default=0.0)
                w = min(max(1.0, 1.0 + worst), self.weight_cap)
                self.batcher.set_model_weight(name, w)
                self.metrics.registry.gauge(
                    f"serve/model_weight/model={name}").set(w)

    def _actuate(self, decision) -> None:
        """The autoscaler's policy loop, then the ACTUATION: a due
        target resizes the pool — growth pre-warms compiled geometries
        before the replica joins dispatch, shrink drains-then-retires
        (session-pinned replicas protected).  A :class:`Reshape`
        decision (the width-vs-count path, armed by
        ``policy.reshape_width``) instead swaps the saturated model's
        ladder onto wider slices — pool COUNT unchanged."""
        target = self.autoscaler.observe_decision(
            decision, self.pool.size,
            saturation=dict(self._fill_ewma) or None,
            widths=dict(self._model_width))
        if target is None:
            return
        if isinstance(target, Reshape):
            self._do_reshape(target)
            return
        protected = self._session_rids()
        if self.pool._swap is not None \
                and self.pool._swap["current"] is not None:
            # the rollout's current victim is mid-drain/warm: retiring
            # it would silently skip its swap step
            protected.add(self.pool._swap["current"])
        actions = self.pool.resize(target,
                                   prewarm=self.autoscaler.policy.prewarm,
                                   protected=sorted(protected))
        if self.obs is not None:
            self.obs.recorder.note(
                "autoscale", t=round(self.clock.now(), 6),
                target=target, grown=actions["grown"],
                drained=actions["drained"],
                burning=list(decision.burning))

    def _do_reshape(self, decision: Reshape) -> None:
        """Actuate a width reshape: the model's service model moves to
        ``to_width``-way sharded slices, and every replica's warm keys
        for that model are DROPPED — wider geometry means new compiled
        programs, so the next dispatch per geometry pays the cold-
        compile tax on the hot path (a reshape must not hide its
        recompile cost the way pre-warm hides growth's)."""
        self._model_width[decision.model] = decision.to_width
        dropped = 0
        for r in self.pool.replicas:
            if r.warm_keys:
                before = len(r.warm_keys)
                r.warm_keys = {k for k in r.warm_keys
                               if k[0] != decision.model}
                dropped += before - len(r.warm_keys)
        ev = {"kind": "autoscale_reshape", "model": decision.model,
              "from_width": decision.from_width,
              "to_width": decision.to_width,
              "fill": round(decision.fill, 6),
              "geometries_dropped": dropped,
              "t": round(self.clock.now(), 6),
              "rationale": decision.rationale}
        self._reshape_log.append(ev)
        self.pool._event(ev)
        if self.obs is not None:
            self.obs.recorder.note(
                "autoscale", t=round(self.clock.now(), 6),
                reshape=decision.model, to_width=decision.to_width,
                fill=round(decision.fill, 6),
                burning=list(decision.burning)
                if hasattr(decision, "burning") else [])

    # -- observability -------------------------------------------------------
    def accounting(self) -> Dict[str, Any]:
        """Request-conservation check: every submitted request is in
        exactly one terminal state once the runtime is drained —
        ``unaccounted == 0`` is the drill's hard invariant.  Exact in
        both retention modes: with ``retain_requests`` the states are
        recounted from the objects; without, the incrementally
        maintained terminal counters ARE the ledger (every terminal
        transition flows through the runtime)."""
        if self.retain_requests:
            by_state: Dict[str, int] = {}
            for r in self.requests:
                by_state[r.state] = by_state.get(r.state, 0) + 1
        else:
            by_state = dict(sorted(self._by_state.items()))
        terminal = sum(v for k, v in by_state.items()
                       if k in ("done", "shed", "timeout", "failed"))
        return {"submitted": self._submitted, "by_state": by_state,
                "terminal": terminal,
                "unaccounted": self._submitted - terminal}

    def snapshot(self) -> Dict[str, Any]:
        mesh_info = None
        if self.specs is not None:
            mesh_info = {
                "axes": dict(self.specs.mesh.shape),
                "data_axis_size": self.specs.data_axis_size,
            }
        out = {
            "mesh": mesh_info,
            "metrics": self.metrics.snapshot(),
            "queue": self.queue.snapshot(),
            "replicas": self.pool.snapshot(),
            "accounting": self.accounting(),
        }
        if self._multi:
            out["models"] = {
                name: {
                    "ladder": self.ladders[name].snapshot(),
                    "weight": self.batcher.model_weight(name),
                    "outcomes": self.metrics.model_snapshot(name),
                    "tiers": [{"name": t.name, "speed": t.speed}
                              for t in cfg.tiers],
                }
                for name, cfg in self.models.items()}
            out["sessions"] = {
                "opened": self._sessions_opened,
                "open": self._open_sessions,
                "failed": self._sessions_failed,
            }
            if self.autoscaler is not None:
                out["autoscale"] = self.autoscaler.snapshot()
                out["pool_size"] = self.pool.size
                out["cold_compiles"] = self.pool.cold_compiles
        else:
            out["ladder"] = self.ladder.snapshot()
            out["tiers"] = [{"name": t.name, "speed": t.speed,
                             "quality_note": t.quality_note}
                            for t in self.tiers]
        if self.slice_width > 1 or self._reshape_log:
            # keyed in only when replicas are slices or a reshape fired
            # (legacy snapshots byte-identical)
            out["slices"] = {
                "slice_width": self.slice_width,
                "devices_used": self.pool.devices_used,
                "device_budget": self.pool.device_budget,
                "model_width": dict(sorted(self._model_width.items())),
                "reshapes": [dict(e) for e in self._reshape_log],
            }
        if self.slo is not None:
            # keyed in only when armed, so pre-PR-11 snapshots (and the
            # banked RESILIENCE_r03/OBS_r01 replays) are byte-unchanged
            r = self.slo.report()
            out["slo"] = {k: r[k] for k in
                          ("slos", "windows", "decisions", "trips",
                           "peak_burns")}
        if self._swap_counter:
            # keyed in only once hot_swap was used (legacy snapshots
            # byte-identical)
            out["swap"] = {
                "rollouts": self._swap_counter,
                "completed": self._swap_stats["completed"],
                "rollbacks": self._swap_stats["rollbacks"],
                "trips": self._swap_stats["trips"],
                "lkg_promotions": self._swap_stats["lkg_promotions"],
                "history": [dict(h) for h in self._swap_log],
            }
        return out
