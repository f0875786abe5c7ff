"""Graceful degradation ladder: trade answer quality for throughput
under sustained overload, then climb back.

Load shedding keeps the queue honest but every shed is a lost answer.
Before shedding hard, a serving cell can buy capacity by serving a
CHEAPER variant of the same model — the degradation tiers:

- tier 0: full quality (bf16/fp32 weights, full NMS top-K / beam);
- tier 1: int8 weights via the existing ``utils.quantize.
  quantize_params`` path (~4× fewer parameter bytes, mAP delta
  +0.0001 — ``INT8_MAP_PARITY.json``);
- tier 2+: int8 plus reduced post-processing work (NMS ``keep_topk``,
  beam width) — bounded, explicit quality cuts.

Transitions use the SAME hysteresis discipline as the PR-3 anomaly
ladder's promote-after-M-clean-steps: ``down_after`` consecutive
overloaded decision windows step one tier down; ``up_after`` consecutive
clean windows step one tier up.  Asymmetric on purpose (``up_after`` >
``down_after`` by default): stepping down is cheap and urgent, stepping
up into still-marginal load re-creates the overload and makes the tier
oscillate — exactly the flapping the clean-window count suppresses.

The ladder is pure host state driven by ``observe_window``; what a tier
*means* (which forward fn, which top-K) is the runtime's business
(``ServingTier`` descriptors, built e.g. by
``pipelines.ssd.ssd_serving_tiers``).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Dict, List, Optional

logger = logging.getLogger("analytics_zoo_tpu")


@dataclasses.dataclass
class ServingTier:
    """Descriptor for one rung: a human-readable name, the per-replica
    forward callable factory's product (bound by the runtime), and the
    relative speed the batcher's service-time model may consult
    (1.0 = tier-0 time; int8 < 1).

    ``forward(batch) -> answer``: rows on the host (a numpy array, a
    list), or a DEVICE array whose program may still be running.  The
    replica fetches the second kind itself
    (:meth:`~analytics_zoo_tpu.serving.replica.Replica.forward`), and
    while its program runs the runtime assembles the next due batch — so
    a tier that launches and returns gets the overlap, and one that
    fetches before it returns runs as it always did.  Either way the
    batch's input must have been read when the answer is on the host
    (:class:`~analytics_zoo_tpu.serving.batcher.AssembledBatch`).

    ``place`` (optional, for a tier that returns device arrays):
    ``batch dict -> {leaf: device array}``, the start of the transfer of
    the leaves the forward would send itself.  The runtime calls it on
    the batch it assembled ahead, and the batch's forward is then called
    with a copy of the dict that holds those leaves in their device form
    (never with the host batch changed: a failover re-sends that).

    ``device_program`` (optional): a zero-arg thunk returning ``(fn,
    example_args, static_argnums)`` for the tier's underlying jitted
    device program — what ``az_analyze --program`` traces, so the
    static audit covers exactly the program this tier dispatches (the
    ``forward`` callable itself is a host closure with decode loops and
    cannot be traced).

    ``device_program_for`` (optional): ``(edge, rows) -> thunk`` of the
    same contract, for ONE geometry of a runtime's plan: the program the
    tier dispatches for a batch of ``rows`` rows padded to ``edge`` —
    same function, shapes, dtypes and static arguments — which is what a
    map of a device trace's instructions has to be made from
    (``obs/device_scopes.py``; ``device_program`` alone builds the
    audit's smallest batch).  ``rows=None``: the tier's own batch.

    ``evict_session`` (streaming session tiers, ISSUE 14): drop one
    session's carry state from this tier instance's store — the
    runtime calls it on the pinned replica when a session dies without
    its final chunk ever being served (killed, shed, replica loss), so
    failed sessions don't leak their state on the replica.

    ``pads_session_rows`` (streaming session tiers): this tier's forward
    takes a row whose session is −1 for padding — it runs its program of
    that geometry and reads and writes no session's state.  Only then has
    a session model a dry run, and ``ServingRuntime.warm`` (whose batch is
    all such rows) compiles it; a tier that steps whatever state its rows
    name must leave it False."""

    name: str
    forward: Callable[[Dict[str, Any]], Any]
    speed: float = 1.0
    quality_note: str = ""
    device_program: Optional[Callable[[], tuple]] = None
    device_program_for: Optional[Callable[[Any, Optional[int]],
                                          Callable[[], tuple]]] = None
    evict_session: Optional[Callable[[int], None]] = None
    pads_session_rows: bool = False
    place: Optional[Callable[[Dict[str, Any]], Dict[str, Any]]] = None


@dataclasses.dataclass
class LadderPolicy:
    """``down_after`` consecutive overloaded windows → one tier down
    (toward cheaper); ``up_after`` consecutive clean windows → one tier
    up.  A window is overloaded when the runtime observed any shed in it
    or its end-of-window queue depth exceeded ``depth_high`` batches'
    worth of work."""

    down_after: int = 2
    up_after: int = 4
    depth_high: int = 2     # in units of max_batch

    def __post_init__(self):
        if self.down_after < 1 or self.up_after < 1:
            raise ValueError("down_after/up_after must be >= 1")


class DegradationLadder:
    """Hysteresis state machine over overload observations.

    ``tier`` is the current rung index (0 = full quality, rising =
    cheaper).  ``events`` logs every transition with its window index —
    the drill pins engage/disengage against the configured hysteresis.
    """

    def __init__(self, n_tiers: int, policy: Optional[LadderPolicy] = None):
        if n_tiers < 1:
            raise ValueError("need at least one tier")
        self.n_tiers = int(n_tiers)
        self.policy = policy or LadderPolicy()
        self.tier = 0
        self.overloaded_streak = 0
        self.clean_streak = 0
        self.windows = 0
        self.events: List[Dict[str, Any]] = []

    def observe_window(self, overloaded: bool,
                       detail: Optional[Dict[str, Any]] = None) -> str:
        """Feed one decision window; returns ``"down"``, ``"up"`` or
        ``"hold"``.  Streaks reset on every transition so each further
        step needs a FULL fresh streak (step-at-a-time, like the anomaly
        ladder's rollback budget)."""
        self.windows += 1
        action = "hold"
        if overloaded:
            self.clean_streak = 0
            self.overloaded_streak += 1
            if (self.overloaded_streak >= self.policy.down_after
                    and self.tier < self.n_tiers - 1):
                self.tier += 1
                self.overloaded_streak = 0
                action = "down"
        else:
            self.overloaded_streak = 0
            self.clean_streak += 1
            if (self.clean_streak >= self.policy.up_after
                    and self.tier > 0):
                self.tier -= 1
                self.clean_streak = 0
                action = "up"
        if action != "hold":
            ev = {"kind": f"tier_{action}", "window": self.windows,
                  "tier": self.tier, **(detail or {})}
            self.events.append(ev)
            logger.warning("serving ladder: tier %s to %d (window %d)",
                           action, self.tier, self.windows)
        return action

    def observe_decision(self, decision,
                         detail: Optional[Dict[str, Any]] = None) -> str:
        """Feed one :class:`~analytics_zoo_tpu.obs.slo.SloDecision`
        instead of a raw overloaded flag — the SLO-driven decision
        input (PR 11): a window is overloaded when an SLO is *burning*
        on both burn-rate windows, not merely when a shed happened.
        The transition event records which SLOs drove it, so a banked
        drill can show the step-down was SLO-attributed."""
        d = {"slo_burning": list(decision.burning),
             "scale_hint": decision.scale_hint, **(detail or {})}
        return self.observe_window(decision.overloaded, detail=d)

    def snapshot(self) -> Dict[str, Any]:
        return {"tier": self.tier, "windows": self.windows,
                "overloaded_streak": self.overloaded_streak,
                "clean_streak": self.clean_streak,
                "transitions": list(self.events)}
