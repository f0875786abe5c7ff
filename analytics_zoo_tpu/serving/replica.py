"""Replica health supervision, exactly-once batch failover, and the
elastic pool the autoscaler actuates.

A serving cell runs N replicas of the model (N devices, or N mesh
shards each presenting as one replica).  A replica can fail two ways
mid-batch: its forward *raises* (device lost, injected crash), or it
*wedges* — makes no progress past its
:class:`~analytics_zoo_tpu.resilience.watchdog.StallWatchdog` deadline
(the PR-1 failure mode that otherwise blocks the host loop silently).
Either way the pool

1. **fences** the replica — state ``fenced``, no further dispatches;
2. **re-dispatches** the in-flight batch to a healthy replica EXACTLY
   once (``AssembledBatch.redispatched`` latch — a batch that fails its
   second replica fails its requests with
   :class:`~analytics_zoo_tpu.resilience.errors.ReplicaWedged` rather
   than ping-ponging through the whole pool and amplifying overload);
3. **restarts** the fenced replica in the background — modeled as a
   ``restart_s`` cooldown on the runtime clock; once it elapses the
   next dispatch cycle re-admits the replica (and its jit cache is
   assumed cold, which is why restarts must not be free).

**Fence budget** (ISSUE 14 satellite — the OBS_r02 p99 fix): by default
a wedged forward is only *observed* when it finally returns, so its
batch rides out the whole stall before re-dispatch — exactly the
``failover_redispatch`` segment the banked tail attribution blamed for
95 % of the p99 cohort gap.  ``ReplicaPool(fence_budget_s=...)`` bounds
that: every virtual sleep inside a supervised forward goes through the
budget guard, and the moment the forward's elapsed time would cross the
budget the replica raises :class:`ReplicaWedged` *at the fence instant*
— the pool fences and re-dispatches **on the fence**, not on the wedged
forward's eventual return, so the redispatch segment is bounded by the
knob.  ``None`` keeps the PR-5 return-then-check behavior (the banked
RESILIENCE_r03 / OBS_r01 / OBS_r02 replays are byte-identical).

**Elasticity** (ISSUE 14 tentpole): :meth:`ReplicaPool.resize` is the
autoscaler's actuator.  Growth builds replicas through the pool's
``replica_factory`` and — when compiled-geometry modeling is armed
(``compile_s`` > 0 with a ``prewarm_keys`` plan) — **pre-warms** them:
the new replica sits in state ``warming`` while its per-(model, edge,
tier) programs compile, joining dispatch only once every planned
geometry is resident, so a burst-driven scale-up never serves a cold
jit cache.  With ``prewarm=False`` the replica joins immediately cold
and its first dispatch of each geometry pays the ``compile_s`` tax on
the hot path (a ``cold_compile`` event per geometry) — the serving-
scale drill banks exactly that delta.  Shrink is **drain-then-retire**:
the victim stops receiving batches (state ``draining``), any in-flight
batch finishes or re-dispatches exactly once through the ordinary
failover latch, and the replica is removed once idle — never with work
on it.

Supervision is PULL-mode :class:`StallWatchdog` on the runtime's clock:
``beat`` when the forward starts, ``check`` when it returns.  A forward
whose (possibly virtual) duration exceeds ``wedge_timeout_s`` is a
wedge even though it eventually returned — in production the push-mode
monitor thread would have interrupted it mid-flight; on the virtual
clock the pull check observes the same deadline deterministically (and
the fence budget models the push-mode interrupt itself).
"""

from __future__ import annotations

import logging
from typing import (Any, Callable, Dict, List, Optional, Sequence, Set,
                    Tuple)

import numpy as np

from analytics_zoo_tpu.obs.span import stage
from analytics_zoo_tpu.resilience.errors import (ReplicaWedged, StallError,
                                                 is_retryable)
from analytics_zoo_tpu.resilience.watchdog import StallWatchdog
from analytics_zoo_tpu.serving.batcher import AssembledBatch
from analytics_zoo_tpu.serving.request import DEFAULT_MODEL

logger = logging.getLogger("analytics_zoo_tpu")

#: a (model, edge, tier) compiled-geometry key — what pre-warm plans
#: enumerate and ``warm_keys`` tracks
GeometryKey = Tuple[str, Any, int]


def _on_host(out: Any) -> bool:
    """Whether a tier's answer is on the host already: a numpy array or
    scalar, or anything that is no array at all (a list of strings).
    What else offers ``__array__`` — a jax array the tier handed back
    without waiting for it — is still on its way."""
    return isinstance(out, (np.ndarray, np.generic)) \
        or not hasattr(out, "__array__")


class Replica:
    """One supervised model replica.

    ``forward_fns`` maps degradation-tier index → callable
    ``batch_dict -> outputs`` (every tier's geometry pre-compiled on
    this replica's device) — a list for single-model runtimes, or a
    ``{model: [tier fns]}`` dict for a multiplexed one (ISSUE 14).
    ``service_hook`` (optional) returns the simulated service seconds
    for a dispatch — the virtual-clock path; when ``None`` the real
    forward's wall time is what the watchdog sees.

    ``warm_keys``: the compiled-geometry set this replica holds.
    ``None`` (default) disables compile modeling — everything is warm,
    the PR-5 behavior.  A set (possibly empty) arms it: dispatching a
    (model, edge, tier) not in the set pays ``compile_s`` on the hot
    path first (a *cold compile*), exactly the latency cliff pre-warm
    exists to delete.
    """

    #: devices this replica occupies — a plain replica is one device;
    #: :class:`ReplicaSlice` overrides it with its sub-mesh width.  The
    #: pool's ``device_budget`` clamp and the autoscaler's slice-unit
    #: bounds both reason in these units (ISSUE 19).
    width: int = 1

    def __init__(self, rid: int, forward_fns, clock,
                 wedge_timeout_s: float,
                 service_hook: Optional[Callable[..., float]] = None,
                 fence_budget_s: Optional[float] = None,
                 compile_s: float = 0.0,
                 warm_keys: Optional[Set[GeometryKey]] = None):
        self.rid = rid
        if isinstance(forward_fns, dict):
            self.forward_fns: Dict[str, List[Callable]] = {
                m: list(fns) for m, fns in forward_fns.items()}
        else:
            self.forward_fns = {DEFAULT_MODEL: list(forward_fns)}
        self.clock = clock
        self.service_hook = service_hook
        self.fence_budget_s = fence_budget_s
        self.compile_s = float(compile_s)
        self.warm_keys = warm_keys
        self.state = "healthy"       # healthy|fenced|warming|draining
        #: draining for a live-weight swap, NOT for retirement — the
        #: rollout machine re-admits this replica after installing the
        #: new weights instead of ``_revive`` removing it
        self.swap_drain = False
        self.restart_at: Optional[float] = None
        self.warm_ready_at: Optional[float] = None
        self._warm_plan: Sequence[GeometryKey] = ()
        self.dispatches = 0
        self.wedges = 0
        self.cold_compiles = 0
        self.inflight = 0            # batches currently on this replica
        #: parallel-service mode (ISSUE 14, the fleet drill's capacity
        #: model): the virtual instant this replica's last assigned
        #: batch completes — replicas serve CONCURRENTLY, each
        #: sequentially, and the runtime only assigns to free ones
        self.busy_until = 0.0
        self.observer: Optional[Callable[[Dict[str, Any]], None]] = None
        #: per-model ServingTier instances this replica serves (set by
        #: the runtime) — how session state eviction reaches the tier's
        #: per-replica store (``ServingTier.evict_session``)
        self.tier_objs: Dict[str, List[Any]] = {}
        self._fence_t: Optional[float] = None
        #: geometries whose forward has COMPLETED here at least once.  A
        #: geometry's first forward traces, lowers and compiles it, so
        #: its failure is the program's (a lowering/Mosaic error every
        #: replica would repeat), not this replica's
        self._ran: Set[GeometryKey] = set()
        # one time-source convention (utils.clock): the watchdog takes
        # the Clock object itself since PR 7, no .now unwrapping
        self.watchdog = StallWatchdog(
            timeout_s=wedge_timeout_s, name=f"replica-{rid}",
            clock=clock)

    def _fn_for(self, batch: AssembledBatch) -> Callable:
        try:
            return self.forward_fns[batch.model][batch.tier]
        except (KeyError, IndexError):
            raise ReplicaWedged(
                f"replica {self.rid}: no forward for model "
                f"{batch.model!r} tier {batch.tier}") from None

    def sleep_guarded(self, seconds: float) -> None:
        """Advance virtual time inside a supervised forward, bounded by
        the fence budget: crossing it sleeps only UP TO the fence
        instant and raises :class:`ReplicaWedged` there — the push-mode
        monitor interrupting the wedge mid-flight, modeled exactly on
        the pull-mode clock.  With no budget this is a plain sleep (the
        PR-5 return-then-check path, byte-identical)."""
        if self._fence_t is None:
            self.clock.sleep(seconds)
            return
        now = self.clock.now()
        if now + seconds > self._fence_t:
            self.clock.sleep(max(self._fence_t - now, 0.0))
            raise ReplicaWedged(
                f"replica {self.rid}: forward wedged mid-flight — fenced "
                f"at the {self.fence_budget_s:.3f}s fence budget")
        self.clock.sleep(seconds)

    def cold_tax(self, batch: AssembledBatch, mark: bool = True) -> float:
        """The cold-compile tax this dispatch pays: ``compile_s`` when
        the replica has never compiled the batch's geometry (pre-warm's
        counterfactual), else 0.  Records the ``cold_compile`` event and
        (with ``mark``) the now-resident key."""
        if self.warm_keys is None or self.compile_s <= 0:
            return 0.0
        key = (batch.model, batch.edge, batch.tier)
        if key in self.warm_keys:
            return 0.0
        self.cold_compiles += 1
        if self.observer is not None:
            self.observer({"kind": "cold_compile", "replica": self.rid,
                           "model": batch.model, "edge": str(batch.edge),
                           "tier": batch.tier,
                           "t": round(self.clock.now(), 6)})
        if mark:
            self.warm_keys.add(key)
        return self.compile_s

    def _maybe_cold_compile(self, batch: AssembledBatch) -> None:
        tax = self.cold_tax(batch, mark=False)
        if tax <= 0:
            return
        self.sleep_guarded(tax)
        # marked warm only once the compile completed (a fence mid-
        # compile leaves the geometry cold for the restarted replica)
        self.warm_keys.add((batch.model, batch.edge, batch.tier))

    def warm(self, batch: AssembledBatch) -> float:
        """Run one geometry OFF the dispatch path, so its compile happens
        before traffic does: no watchdog, deadline or fence (a compile is
        not a stall) and nothing caught (a compile error is the
        caller's to see).  Returns the wall seconds, compile included."""
        t0 = self.clock.now()
        out = self._fn_for(batch)(batch.batch)
        if not _on_host(out):
            # the program has run, and read its input, only when its
            # answer is here: the next warm batch may then be assembled
            np.asarray(out)
        self._ran.add((batch.model, batch.edge, batch.tier))
        return self.clock.now() - t0

    def _inputs(self, batch: AssembledBatch) -> Dict[str, Any]:
        """What the tier is called with: the host batch, with the leaves
        the runtime placed ahead in their device form if THIS replica
        serves the tier that placed them.  Taken once: a failover's
        second forward re-sends the host buffer."""
        placed, batch.placed = batch.placed, None
        if placed is not None \
                and self.tier_objs[batch.model][batch.tier] is placed[0]:
            return {**batch.batch, **placed[1]}
        return batch.batch

    def forward(self, batch: AssembledBatch,
                fault: Optional[Callable[["Replica"], None]] = None,
                meanwhile: Optional[Callable[["Replica"], None]] = None
                ) -> Any:
        """Run one batch under stall supervision.  ``fault`` (chaos) runs
        just before the model fn — it may raise (crash) or advance the
        virtual clock (slow forward).  A tier may hand back its answer
        before it is on the host (a device array whose program is still
        running): ``meanwhile(self)`` then runs — the runtime's one
        batch of look-ahead, which must not raise — and the answer is
        fetched here, under ``az/serve/result_wait`` and the same
        supervision as the call, so what leaves the replica is always a
        host answer.  An answer that is on the host already skips both.
        Raises :class:`ReplicaWedged` on
        a retryable crash or deadline overrun; the POOL owns
        fencing/failover.  A PROGRAM error is not a replica fault and
        propagates as itself: anything the failure classification calls fatal,
        and anything the model fn or the fetch of its answer raises the
        first time a geometry runs here (that call compiles it, and an
        asynchronous program's first run ends at the fetch) — fencing and
        failing over would only repeat the same error on the next
        replica."""
        self.watchdog.beat()
        self.dispatches += 1
        self.inflight += 1
        t0 = self.clock.now()
        self._fence_t = (t0 + self.fence_budget_s
                         if self.fence_budget_s is not None else None)
        key = (batch.model, batch.edge, batch.tier)
        compiling = False
        try:
            if fault is not None:
                fault(self)
            self._maybe_cold_compile(batch)
            fn = self._fn_for(batch)
            compiling = key not in self._ran
            out = fn(self._inputs(batch))
            if not _on_host(out):
                if meanwhile is not None:
                    meanwhile(self)
                with stage("az/serve/result_wait"):
                    out = np.asarray(out)
            compiling = False
            self._ran.add(key)
            if self.service_hook is not None:
                # virtual time: the hook says how long this forward took
                self.sleep_guarded(float(self.service_hook(batch,
                                                           self.rid)))
        except ReplicaWedged:
            raise
        except Exception as e:
            if compiling or not is_retryable(e):
                raise
            raise ReplicaWedged(
                f"replica {self.rid}: forward crashed mid-batch "
                f"({type(e).__name__}: {e})") from e
        finally:
            self.inflight -= 1
            self._fence_t = None
        try:
            self.watchdog.check()
        except StallError as e:
            raise ReplicaWedged(
                f"replica {self.rid}: forward wedged "
                f"({self.clock.now() - t0:.3f}s > "
                f"{self.watchdog.timeout_s:.3f}s deadline)") from e
        return out

    # -- lifecycle ----------------------------------------------------------
    def fence(self, restart_at: float) -> None:
        self.state = "fenced"
        self.wedges += 1
        self.restart_at = restart_at

    def maybe_restart(self, now: float) -> bool:
        """Re-admit the replica once its background restart completed."""
        if self.state == "fenced" and self.restart_at is not None \
                and now >= self.restart_at:
            self.state = "healthy"
            self.restart_at = None
            # clear the latched stall verdict + the age accumulated while
            # fenced, or the revived replica would instantly re-wedge
            self.watchdog.reset()
            return True
        return False

    def begin_warming(self, plan: Sequence[GeometryKey],
                      ready_at: float) -> None:
        """Enter the pre-warm phase: compile every planned geometry OFF
        the dispatch path; :meth:`maybe_warm` admits the replica once
        they are all resident."""
        self.state = "warming"
        self._warm_plan = tuple(plan)
        self.warm_ready_at = ready_at
        self.warm_keys = set()

    def maybe_warm(self, now: float) -> bool:
        """Join dispatch once the pre-warm compiles completed — the
        replica becomes eligible with every planned geometry warm."""
        if self.state == "warming" and self.warm_ready_at is not None \
                and now >= self.warm_ready_at:
            self.state = "healthy"
            self.warm_ready_at = None
            self.warm_keys = set(self._warm_plan)
            self._warm_plan = ()
            self.watchdog.reset()
            return True
        return False


class ReplicaSlice(Replica):
    """A replica that IS a mesh slice (ISSUE 19 tentpole): its tier
    programs are jitted against a width-``w`` sub-mesh rather than a
    single device, so one pool entry occupies ``w`` devices and serves
    each batch with ``w``-way sharded compute.

    ``specs`` is the tier ladder's
    :class:`~analytics_zoo_tpu.parallel.specs.SpecSet` rebased onto the
    slice's sub-mesh (``SpecSet.replace_mesh``) — the same declaration
    the training side elastically re-places, which is what makes a
    serving replica and a training shard the same artifact.  The
    runtime's replica factory jits the tier forwards under
    ``specs.mesh``; this class only carries the width (for the pool's
    device accounting) and the specs (for audit/debug surfaces).  A
    width-1 slice is behaviorally a plain :class:`Replica`.
    """

    def __init__(self, rid: int, forward_fns, clock,
                 wedge_timeout_s: float, width: int = 1,
                 specs: Optional[Any] = None, **kwargs):
        if width < 1:
            raise ValueError(f"slice width must be >= 1, got {width}")
        super().__init__(rid, forward_fns, clock, wedge_timeout_s,
                         **kwargs)
        self.width = int(width)
        self.specs = specs


class ReplicaPool:
    """Round-robin dispatch over healthy replicas with fence + exactly-
    once failover, plus the resize actuator the autoscaler drives.
    ``events`` is the deterministic log the drill banks (no wall-clock
    entries beyond the runtime clock's virtual time).  ``observer``
    (optional, set by the runtime) sees every event as it is appended —
    the telemetry spine's flight recorder hangs off it, and a fence
    event is one of the black box's dump triggers.

    ``fence_budget_s``: the wedge-detection bound (see the module
    docstring) — assigned to every replica that doesn't carry its own.
    ``replica_factory(rid) -> Replica``: how :meth:`resize` builds
    growth replicas (the runtime wires one that mirrors its own replica
    construction).  ``prewarm_keys``/``compile_s``: the compiled-
    geometry plan and per-program compile cost the pre-warm/cold-
    compile modeling uses (``compile_s == 0`` disables it — the PR-5
    behavior)."""

    def __init__(self, replicas: Sequence[Replica], clock,
                 restart_s: float = 5.0,
                 observer: Optional[Callable[[Dict[str, Any]], None]] = None,
                 fence_budget_s: Optional[float] = None,
                 replica_factory: Optional[Callable[[int], Replica]] = None,
                 prewarm_keys: Optional[Sequence[GeometryKey]] = None,
                 compile_s: float = 0.0,
                 device_budget: Optional[int] = None):
        if not replicas:
            raise ValueError("need at least one replica")
        self.replicas = list(replicas)
        self.clock = clock
        self.restart_s = float(restart_s)
        self.events: List[Dict[str, Any]] = []
        self.observer = observer
        self.fence_budget_s = fence_budget_s
        self.replica_factory = replica_factory
        self.prewarm_keys = tuple(prewarm_keys) if prewarm_keys else ()
        self.compile_s = float(compile_s)
        #: hard device ceiling (ISSUE 19 satellite): replica growth is
        #: clamped so Σ width over non-draining replicas never exceeds
        #: it — a width-4 slice grow can't silently over-subscribe the
        #: fleet the way a replica-count bound alone would allow.
        self.device_budget = device_budget
        self._rr = 0
        self._rid_counter = max(r.rid for r in self.replicas) + 1
        #: active hot-swap rollout (None between rollouts) — see hot_swap
        self._swap: Optional[Dict[str, Any]] = None
        #: rids the rollout must NOT drain yet (the runtime refreshes
        #: this with the session-pinned set every pump: session-affine
        #: replicas are swapped LAST, after their sessions close)
        self.swap_defer: Set[int] = set()
        self.swaps_completed = 0
        self.swaps_started = 0
        self.last_rollout: Optional[Dict[str, Any]] = None
        for r in self.replicas:
            self._adopt(r)

    def _adopt(self, r: Replica) -> None:
        if r.fence_budget_s is None:
            r.fence_budget_s = self.fence_budget_s
        r.observer = self._event

    def _event(self, ev: Dict[str, Any]) -> None:
        self.events.append(ev)
        if self.observer is not None:
            self.observer(ev)

    # -- selection -----------------------------------------------------------
    def _revive(self) -> None:
        now = self.clock.now()
        retired: List[Replica] = []
        for r in self.replicas:
            if r.maybe_restart(now):
                self._event({"kind": "replica_restarted",
                             "replica": r.rid, "t": round(now, 6)})
            elif r.maybe_warm(now):
                self._event({"kind": "replica_prewarmed",
                             "replica": r.rid, "t": round(now, 6),
                             "geometries": len(r.warm_keys or ())})
            elif r.state == "draining" and not r.swap_drain \
                    and r.inflight == 0 and r.busy_until <= now:
                retired.append(r)
        for r in retired:
            self.replicas.remove(r)
            self._event({"kind": "replica_retired", "replica": r.rid,
                         "t": round(now, 6)})
        self._step_rollout(now)

    def healthy(self) -> List[Replica]:
        self._revive()
        return [r for r in self.replicas if r.state == "healthy"]

    @property
    def size(self) -> int:
        """Pool size the autoscaler reasons about: every replica that
        is, or will come back as, dispatchable (healthy, fenced-with-
        restart-pending, warming) — draining replicas are already on
        their way out."""
        return sum(r.state != "draining" for r in self.replicas)

    @property
    def devices_used(self) -> int:
        """Devices occupied by non-draining replicas — Σ ``width``, the
        unit the ``device_budget`` clamp and the autoscaler's slice-unit
        bounds reason in (a plain replica is width 1)."""
        return sum(r.width for r in self.replicas
                   if r.state != "draining")

    @property
    def cold_compiles(self) -> int:
        return sum(r.cold_compiles for r in self.replicas)

    def pick(self, exclude: Optional[int] = None) -> Optional[Replica]:
        """Deterministic round-robin over healthy replicas (skipping
        ``exclude`` — the replica that just failed this batch)."""
        ready = [r for r in self.healthy() if r.rid != exclude]
        if not ready:
            return None
        r = ready[self._rr % len(ready)]
        self._rr += 1
        return r

    def replica_by_rid(self, rid: int) -> Optional[Replica]:
        for r in self.replicas:
            if r.rid == rid:
                return r
        return None

    def quarantine(self, rid: int, reason: str = "device_health") -> bool:
        """Evict one replica's devices from the fleet: drain-then-retire
        (the ordinary ``resize`` shrink path — in-flight work finishes or
        re-dispatches once through the failover latch) AND decrement
        ``device_budget`` by the replica's width, so neither a later
        ``resize`` grow nor the autoscaler can re-seat anything on the
        quarantined silicon.  Returns False when ``rid`` is unknown or
        already draining (idempotent — the health sentinel may flag the
        same device from several windows)."""
        r = self.replica_by_rid(rid)
        if r is None or r.state == "draining":
            return False
        width = r.width
        r.state = "draining"
        if self.device_budget is not None:
            self.device_budget = max(self.device_budget - width, 0)
        self._event({"kind": "replica_quarantined", "replica": rid,
                     "reason": reason, "width": width,
                     "device_budget": self.device_budget,
                     "t": round(self.clock.now(), 6)})
        logger.warning("pool: replica %d quarantined (%s) — draining; "
                       "device budget now %s", rid, reason,
                       self.device_budget)
        return True

    # -- parallel service (the fleet capacity model) --------------------------
    def any_free(self, now: float) -> bool:
        return any(r.busy_until <= now for r in self.healthy())

    def pick_free(self, now: float,
                  exclude: Optional[int] = None) -> Optional[Replica]:
        """Round-robin over healthy replicas that are FREE at ``now`` —
        parallel-service mode's assignment rule (a busy replica is
        serving its previous batch concurrently)."""
        ready = [r for r in self.healthy()
                 if r.busy_until <= now and r.rid != exclude]
        if not ready:
            return None
        r = ready[self._rr % len(ready)]
        self._rr += 1
        return r

    def least_busy(self) -> Optional[Replica]:
        """Healthy replica with the earliest busy horizon — the force-
        drain path queues work there when nobody is free."""
        ready = self.healthy()
        if not ready:
            return None
        return min(ready, key=lambda r: (r.busy_until, r.rid))

    def next_event_t(self, now: float) -> Optional[float]:
        """The next virtual instant pool state changes (a busy replica
        frees, a restart completes, a pre-warm finishes) — what an
        event-driven load loop advances the clock to."""
        ts: List[float] = []
        for r in self.replicas:
            if r.busy_until > now:
                ts.append(r.busy_until)
            if r.state == "fenced" and r.restart_at is not None \
                    and r.restart_at > now:
                ts.append(r.restart_at)
            if r.state == "warming" and r.warm_ready_at is not None \
                    and r.warm_ready_at > now:
                ts.append(r.warm_ready_at)
        return min(ts) if ts else None

    # -- resize (the autoscaler's actuator) ----------------------------------
    def resize(self, n: int, prewarm: bool = True,
               protected: Sequence[int] = ()) -> Dict[str, List[int]]:
        """Grow or shrink the pool to ``n`` non-draining replicas.

        Growth builds replicas through ``replica_factory``; with
        compile modeling armed they **pre-warm** first (state
        ``warming`` for ``compile_s × len(prewarm_keys)`` of clock
        time, then join with every planned geometry warm) unless
        ``prewarm=False`` — then they join immediately cold and pay the
        tax per first dispatch.  Shrink is drain-then-retire: victims
        (fenced first, then the highest-rid healthy replica not in
        ``protected`` — session-pinned replicas are never drained while
        an alternative exists) stop receiving batches at once and are
        removed when idle; in-flight work finishes or re-dispatches
        exactly once through the ordinary failover latch.  Returns the
        rids acted on."""
        if n < 1:
            raise ValueError(f"pool size must be >= 1, got {n}")
        self._revive()
        protected_set = set(protected)
        actions: Dict[str, List[int]] = {"grown": [], "drained": []}
        while self.size < n:
            if self.replica_factory is None:
                raise RuntimeError("pool growth needs a replica_factory")
            rid = self._rid_counter
            self._rid_counter += 1
            r = self.replica_factory(rid)
            if self.device_budget is not None \
                    and self.devices_used + r.width > self.device_budget:
                # grow clamped AT THE ACTUATOR: the pool refuses to
                # over-subscribe devices even if a policy bug asks it to
                self._rid_counter -= 1
                self._event({"kind": "resize_budget_clamped",
                             "t": round(self.clock.now(), 6),
                             "requested": int(n), "size": self.size,
                             "devices_used": self.devices_used,
                             "width": r.width,
                             "device_budget": self.device_budget})
                break
            r.compile_s = self.compile_s
            self._adopt(r)
            now = self.clock.now()
            modeled = self.compile_s > 0 and self.prewarm_keys
            if modeled and prewarm:
                r.begin_warming(
                    self.prewarm_keys,
                    now + self.compile_s * len(self.prewarm_keys))
            elif modeled:
                r.warm_keys = set()     # joins cold: pays per-dispatch tax
            self.replicas.append(r)
            if self._swap is not None:
                # growth mid-rollout joins with the NEW weights already
                # installed — it must not serve the retiring checkpoint,
                # and the rollout must not re-drain it
                self._swap["install"](r)
                self._swap["swapped"].append(rid)
                self._event({"kind": "swap_installed", "replica": rid,
                             "t": round(now, 6),
                             "checkpoint": self._swap["checkpoint"],
                             "grown": True})
            self._event({"kind": "replica_joined", "replica": rid,
                         "t": round(now, 6), "prewarm": bool(prewarm),
                         "state": r.state})
            actions["grown"].append(rid)
        while self.size > n:
            # a fenced replica is the cheapest victim — UNLESS sessions
            # are pinned to it: it restarts with their state intact,
            # while retiring it would lose them permanently
            victims = [r for r in self.replicas if r.state == "fenced"
                       and r.rid not in protected_set]
            if not victims:
                victims = sorted(
                    (r for r in self.replicas
                     if r.state in ("healthy", "warming")
                     and r.rid not in protected_set),
                    key=lambda r: -r.rid)
            if not victims:
                break                   # everything left is protected
            victim = victims[0]
            victim.state = "draining"
            self._event({"kind": "replica_draining",
                         "replica": victim.rid,
                         "t": round(self.clock.now(), 6),
                         "inflight": victim.inflight})
            actions["drained"].append(victim.rid)
        self._revive()                  # idle victims retire immediately
        return actions

    # -- live-weight hot-swap (the rollout state machine) ---------------------
    @property
    def rollout_active(self) -> bool:
        return self._swap is not None

    def hot_swap(self, checkpoint: str,
                 install: Callable[[Replica], None],
                 warm_s: Optional[float] = None,
                 last: Sequence[int] = ()) -> Dict[str, Any]:
        """Start a zero-downtime weight rollout: one replica at a time is
        drained (state ``draining`` with the ``swap_drain`` mark — never
        retired), ``install(replica)`` swaps its weights once idle, the
        replica re-warms its compiled geometries (when compile modeling
        is armed) and rejoins dispatch before the next victim drains.
        The rollout advances from :meth:`_revive`, i.e. on every ordinary
        dispatch cycle — no extra driver needed.

        ``checkpoint`` is the snapshot directory the new weights came
        from; its sha256 manifest is verified HERE too (defense in depth
        — the runtime already verified at load), so a truncated publish
        can never start draining replicas.  ``last`` rids are queued at
        the tail (session-pinned replicas swap last); rids in
        ``swap_defer`` are additionally held until the runtime clears
        them.  In-flight batches on the draining replica finish or ride
        the ordinary exactly-once failover latch — ``accounting()``
        conserves every request across the rollout."""
        if self._swap is not None:
            raise RuntimeError(
                f"hot_swap: rollout of {self._swap['checkpoint']!r} "
                f"still in progress")
        from analytics_zoo_tpu.parallel import checkpoint as ckpt

        ckpt.verify_snapshot(checkpoint)
        last_set = set(last)
        order = sorted(r.rid for r in self.replicas
                       if r.state != "draining" and r.rid not in last_set)
        order += sorted(r.rid for r in self.replicas
                        if r.state != "draining" and r.rid in last_set)
        self._swap = {"checkpoint": checkpoint, "install": install,
                      "warm_s": warm_s, "pending": order,
                      "current": None, "phase": None, "swapped": []}
        self.swaps_started += 1
        self._event({"kind": "swap_rollout_started",
                     "checkpoint": checkpoint, "order": list(order),
                     "t": round(self.clock.now(), 6)})
        self._step_rollout(self.clock.now())
        return dict(self._swap, install=None)

    def _step_rollout(self, now: float) -> None:
        """Advance the active rollout one step.  Idempotent; called from
        ``_revive`` so the machine moves whenever pool state is read."""
        sw = self._swap
        if sw is None:
            return
        cur = self.replica_by_rid(sw["current"]) \
            if sw["current"] is not None else None
        if sw["current"] is not None and cur is None:
            sw["current"] = None     # victim retired mid-drain (resize)
        if cur is not None:
            if sw["phase"] == "drain":
                if cur.state == "healthy":
                    # fenced mid-drain and restarted: resume the drain
                    cur.state = "draining"
                if cur.state == "draining" and cur.inflight == 0 \
                        and cur.busy_until <= now:
                    sw["install"](cur)
                    cur.swap_drain = False
                    sw["swapped"].append(cur.rid)
                    self._event({"kind": "swap_installed",
                                 "replica": cur.rid, "t": round(now, 6),
                                 "checkpoint": sw["checkpoint"]})
                    if self.compile_s > 0 and self.prewarm_keys:
                        warm = sw["warm_s"] if sw["warm_s"] is not None \
                            else self.compile_s * len(self.prewarm_keys)
                        cur.begin_warming(self.prewarm_keys, now + warm)
                        sw["phase"] = "warm"
                    else:
                        cur.state = "healthy"
                        cur.watchdog.reset()
                        self._event({"kind": "swap_rejoined",
                                     "replica": cur.rid,
                                     "t": round(now, 6)})
                        sw["current"] = None
                return  # one replica at a time: wait for drain/warm
            if sw["phase"] == "warm":
                if cur.state == "warming":
                    return
                self._event({"kind": "swap_rejoined", "replica": cur.rid,
                             "t": round(now, 6)})
                sw["current"] = None
        # pick the next victim (deferred/retired rids skipped or dropped)
        while sw["pending"]:
            rid = sw["pending"][0]
            r = self.replica_by_rid(rid)
            if r is None or (r.state == "draining" and not r.swap_drain):
                sw["pending"].pop(0)    # retired or retiring: nothing to swap
                continue
            if rid in self.swap_defer:
                # deferred (session-pinned): try a later non-deferred rid
                later = [x for x in sw["pending"]
                         if x not in self.swap_defer
                         and self.replica_by_rid(x) is not None]
                if not later:
                    return              # everything left is deferred: wait
                rid = later[0]
                r = self.replica_by_rid(rid)
                sw["pending"].remove(rid)
            else:
                sw["pending"].pop(0)
            if r.state != "healthy":
                # fenced/warming: queue it back and wait for this cycle
                sw["pending"].insert(0, rid)
                return
            r.state = "draining"
            r.swap_drain = True
            sw["current"], sw["phase"] = rid, "drain"
            self._event({"kind": "swap_drain", "replica": rid,
                         "t": round(now, 6), "inflight": r.inflight})
            self._step_rollout(now)      # an idle victim installs at once
            return
        # pending empty and no current: the rollout is complete
        self.swaps_completed += 1
        self.last_rollout = {"checkpoint": sw["checkpoint"],
                             "swapped": list(sw["swapped"])}
        self._event({"kind": "swap_rollout_complete",
                     "checkpoint": sw["checkpoint"],
                     "swapped": list(sw["swapped"]),
                     "t": round(now, 6)})
        self._swap = None

    def abort_rollout(self) -> List[int]:
        """Stop an in-progress rollout (the rollback path): the
        currently-draining victim is re-admitted un-swapped, and the
        rids that already received new weights are returned so the
        caller can reinstall the rollback tier on them.  No-op (empty
        list) when no rollout is active — the exactly-once rollback
        latch lives in the runtime, this is just the actuator."""
        sw = self._swap
        if sw is None:
            return []
        cur = self.replica_by_rid(sw["current"]) \
            if sw["current"] is not None else None
        if cur is not None and cur.swap_drain:
            cur.swap_drain = False
            if cur.state == "draining":
                cur.state = "healthy"
                cur.watchdog.reset()
        swapped = list(sw["swapped"])
        self._event({"kind": "swap_rollout_aborted",
                     "checkpoint": sw["checkpoint"],
                     "swapped": swapped,
                     "t": round(self.clock.now(), 6)})
        self._swap = None
        return swapped

    # -- dispatch with failover ----------------------------------------------
    def _fence(self, replica: Replica, err: ReplicaWedged,
               at: Optional[float] = None) -> None:
        """Fence ``replica``.  ``at`` pins the fence instant explicitly —
        the parallel service model detects a crash/wedge at an instant it
        computed on the replica's busy horizon, which the shared clock
        has not reached yet."""
        t = self.clock.now() if at is None else float(at)
        restart_at = t + self.restart_s
        replica.fence(restart_at)
        self._event({"kind": "replica_fenced", "replica": replica.rid,
                     "t": round(t, 6),
                     "restart_at": round(restart_at, 6),
                     "error": str(err).split("\n")[0][:160]})
        logger.warning("serving: fenced replica %d (%s); restart at t=%.3f",
                       replica.rid, err, restart_at)

    def dispatch(self, batch: AssembledBatch,
                 fault_for: Optional[Callable[[Replica], Optional[
                     Callable[[Replica], None]]]] = None,
                 meanwhile: Optional[Callable[[Replica], None]] = None
                 ) -> Any:
        """Run ``batch`` on a healthy replica; on :class:`ReplicaWedged`
        fence the replica and re-dispatch EXACTLY once.  Returns the
        forward outputs; raises :class:`ReplicaWedged` when the retry is
        spent or no healthy replica remains (the runtime fails the
        batch's requests — retryable from the client's side).
        ``meanwhile`` goes to every :meth:`Replica.forward` of the batch,
        the failover's too, so it is the caller's to make idempotent.

        A batch with ``affinity`` set (a streaming-session batch) MUST
        run on that replica — its RNN carry lives there, so failover to
        another replica would silently decode from zeroed state; if the
        pinned replica is gone or unhealthy the batch fails instead
        (honest state loss, the runtime fails its requests)."""
        if batch.affinity is not None:
            self._revive()
            replica = self.replica_by_rid(batch.affinity)
            if replica is None or replica.state != "healthy":
                raise ReplicaWedged(
                    f"session replica {batch.affinity} unavailable "
                    f"(state: "
                    f"{replica.state if replica else 'retired'}) — "
                    f"session state lost")
            fault = fault_for(replica) if fault_for is not None else None
            try:
                return self.dispatch_on(replica, batch, fault, meanwhile)
            except ReplicaWedged as err:
                self._fence(replica, err)
                raise
        replica = self.pick()
        if replica is None:
            raise ReplicaWedged("no healthy replica available")
        try:
            fault = fault_for(replica) if fault_for is not None else None
            return self.dispatch_on(replica, batch, fault, meanwhile)
        except ReplicaWedged as err:
            self._fence(replica, err)
            if batch.redispatched:
                raise
            batch.redispatched = True
            backup = self.pick(exclude=replica.rid)
            if backup is None:
                raise ReplicaWedged(
                    f"batch failover from replica {replica.rid}: no healthy "
                    f"replica left") from err
            self._event({"kind": "failover", "from": replica.rid,
                         "to": backup.rid,
                         "t": round(self.clock.now(), 6),
                         "requests": [r.rid for r in batch.requests]})
            fault = fault_for(backup) if fault_for is not None else None
            try:
                return self.dispatch_on(backup, batch, fault, meanwhile)
            except ReplicaWedged as err2:
                self._fence(backup, err2)
                raise

    def dispatch_on(self, replica: Replica, batch: AssembledBatch,
                    fault: Optional[Callable[[Replica], None]],
                    meanwhile: Optional[Callable[[Replica], None]] = None
                    ) -> Any:
        for req in batch.requests:
            req.attempts += 1
        return replica.forward(batch, fault=fault, meanwhile=meanwhile)

    def snapshot(self) -> Dict[str, Any]:
        out = {
            "replicas": [{"rid": r.rid, "state": r.state,
                          "dispatches": r.dispatches, "wedges": r.wedges}
                         for r in self.replicas],
            "healthy": sum(r.state == "healthy" for r in self.replicas),
        }
        if self.swaps_started:    # legacy snapshots stay byte-identical
            out["rollouts"] = {
                "started": self.swaps_started,
                "completed": self.swaps_completed,
                "active": self._swap is not None,
                "last": dict(self.last_rollout) if self.last_rollout
                else None,
            }
        return out
