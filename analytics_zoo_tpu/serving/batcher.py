"""Deadline-aware dynamic batch assembly over compiled geometries.

Batching amortizes the fixed cost of a dispatch and fills the MXU, but
waiting to fill a batch spends the queued requests' deadline slack.
The classic dynamic-batching compromise (Clipper's adaptive batch
sizing): flush a bucket when it is FULL, or when its most urgent request can no longer afford
to wait for more arrivals.

Geometry discipline: an online path must never hand XLA a shape it has
not compiled — a surprise compile is a multi-second latency cliff that
blows every deadline in the queue.  So assembled batches only ever use

- a time axis from the configured ``bucket_edges`` (the same
  :func:`analytics_zoo_tpu.data.bucket.edge_for` rule the train-side
  ``BucketBatcher`` uses, so serving reuses training's compiled
  geometries), and
- a batch axis of exactly ``max_batch`` — partial flushes are padded
  with zero rows and carry ``n_valid`` (the ``Uint8ToBatch`` convention;
  the runtime slices outputs back).

Flush rule per bucket: let ``t_est`` be the estimated service time of
that bucket's geometry at the current tier.  Flush when
``len(bucket) >= max_batch``, or when the earliest deadline in the
bucket satisfies ``deadline - now <= t_est + slack_margin`` — i.e. the
urgent request would miss if we waited any longer.  Estimation comes
from ``service_time(edge, n, tier)``, the same model the drill uses, or
from an online EWMA of observed service times when none is given.

Multiplexing (ISSUE 14, the Clipper frontend pattern): a batcher given
``plans`` (one :class:`ModelPlan` per registered model) keeps a bucket
per **(model, affinity, edge)** — models never share a batch, a
streaming session's chunks only group with chunks pinned to the same
replica — and the service-time EWMA keys per **(model, edge, tier)**
with the PR-5 always-urgent cold seed *per key*, so one model's learned
estimate never flushes (or starves) another model's batches.  Flush-
ready buckets are drained in **weighted-EDF** order: the runtime feeds
per-model weights from the SLO burn rates (``set_model_weight``) and a
burning model's slack is divided by its weight, so its buckets win the
next dispatch — deadline-weighted by how fast that model's error
budget is being spent.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.data.bucket import edge_for
from analytics_zoo_tpu.obs.span import stage
from analytics_zoo_tpu.serving.request import (DEFAULT_MODEL,
                                               AdmissionQueue, Request)

#: bucket key for fixed-shape models (no variable axis)
FIXED = "fixed"


@dataclasses.dataclass
class ModelPlan:
    """Per-model batching geometry for a multiplexed runtime.

    ``bucket_edges``: variable-axis edges (``None`` = fixed shape);
    ``pad_key``/``length_key``: the payload leaf padded to the edge and
    the per-row valid-length vector's batch key; ``max_batch``: per-
    model batch axis (``None`` = the batcher's global ``max_batch``);
    ``streaming``: session-type model — assembled batches additionally
    carry ``session`` (int64, padding rows −1) and ``final`` (int8)
    vectors so the stateful forward can route each row to its session
    carry and flush on the last chunk.
    """

    bucket_edges: Optional[Sequence[int]] = None
    pad_key: str = "input"
    length_key: Optional[str] = "n_frames"
    max_batch: Optional[int] = None
    streaming: bool = False


@dataclasses.dataclass
class AssembledBatch:
    """One device-ready batch: ``requests`` in EDF order, padded
    ``batch`` dict, the geometry it compiled under, and the dispatch
    bookkeeping the failover path reads (``redispatched``).  ``model``
    keys the replica's per-model forward table; ``affinity`` (set for
    session batches) pins the dispatch to one replica.

    **Who owns the bytes.**  ``batch[pad_key]`` is one of the PAIR of
    staging buffers the batcher keeps for this ``(model, edge)``, filled
    in place and in turn: it is valid until the next batch BUT ONE of
    that geometry is assembled, and no longer.  A forward must copy
    whatever it keeps (a row, a view, a zero-copy device alias).  The
    runtime keeps its side: it assembles at most ONE batch ahead — the
    next batch while this one's program runs, into the other buffer —
    and the batch after that only once this one's answers are handed
    out, and the hand-out copies an answer that shares memory with the
    buffer before a request retains it.  Who fences what: on the chip
    the device may read the host buffer until the transfer
    ``jnp.asarray`` started completes, and on a CPU backend
    ``jnp.asarray`` of an aligned array may alias it with no copy at all
    — either way the program that consumed the input has run when its
    answer is on the host, and that ONE fence, the fetch of the answer
    (``az/serve/result_wait``), is the only one: the tiers that fetch
    before they return make it themselves, and for a tier that hands
    back a device array (the SSD tiers) :meth:`Replica.forward
    <analytics_zoo_tpu.serving.replica.Replica.forward>` makes it before
    the answer leaves the replica.  Nobody waits for the transfer by
    itself.  The small vectors (``length_key``, ``session``, ``final``)
    are new for every batch.  ``staging_reused`` is False when the pair
    was allocated or replaced for this batch (``serve/staging_alloc``
    counts those: one a geometry).

    ``placed`` rides BESIDE the host batch, never in its place: ``(the
    tier that placed, {leaf: device array})`` once the runtime started
    the batch's transfer ahead (``ServingTier.place``).  The first
    forward of the batch on a replica that serves that very tier takes
    it; a failover's second forward re-sends the host buffer."""

    requests: List[Request]
    batch: Dict[str, Any]
    edge: Any                       # bucket edge or FIXED
    n_valid: int
    tier: int = 0
    redispatched: bool = False      # exactly-once failover latch
    model: str = DEFAULT_MODEL
    affinity: Optional[int] = None
    staging_reused: bool = True
    placed: Optional[Tuple[Any, Dict[str, Any]]] = None

    @property
    def earliest_deadline(self) -> float:
        return min(r.deadline_t for r in self.requests)


@dataclasses.dataclass
class _StagingPair:
    """A geometry's two staging buffers, ONE allocation of shape
    ``(2, cap) + row shape``: ``dirty[k]`` rows of ``pair[k]`` were left
    non-zero by the last batch assembled in it, and ``pair[turn]`` is the
    one the next batch fills."""

    pair: np.ndarray
    dirty: List[int] = dataclasses.field(default_factory=lambda: [0, 0])
    turn: int = 0


class DeadlineBatcher:
    """Assemble :class:`AssembledBatch` es from an :class:`AdmissionQueue`.

    ``pad_key`` names the payload leaf padded to the bucket edge; other
    payload leaves must share a shape within a bucket and are stacked
    as-is.  ``length_key`` (when set) adds the per-row valid-length
    vector to the batch — the same contract ``BucketBatcher`` gives the
    train step.

    ``plans`` (multiplexed mode): model name → :class:`ModelPlan`; the
    legacy ``bucket_edges``/``pad_key``/``length_key`` arguments then
    only seed the ``DEFAULT_MODEL`` plan when none is declared.  With
    plans, ``service_time`` takes ``(model, edge, n, tier)``; without,
    the PR-5 ``(edge, n, tier)`` signature is unchanged.
    """

    def __init__(self, queue: AdmissionQueue, max_batch: int,
                 bucket_edges: Optional[Sequence[int]] = None,
                 pad_key: str = "input",
                 length_key: Optional[str] = "n_frames",
                 service_time: Optional[Callable[..., float]] = None,
                 slack_margin_s: float = 0.0,
                 plans: Optional[Dict[str, ModelPlan]] = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.queue = queue
        self.max_batch = int(max_batch)
        self.multiplexed = plans is not None
        if plans is None:
            plans = {DEFAULT_MODEL: ModelPlan(
                bucket_edges=bucket_edges, pad_key=pad_key,
                length_key=length_key)}
        self.plans: Dict[str, ModelPlan] = {}
        for name, plan in plans.items():
            edges = (sorted(int(e) for e in plan.bucket_edges)
                     if plan.bucket_edges else None)
            self.plans[name] = dataclasses.replace(plan, bucket_edges=edges)
        self.service_time = service_time
        self.slack_margin_s = float(slack_margin_s)
        # online EWMA of observed per-(model, geometry, tier) service
        # time, used when no explicit model is configured; a key with no
        # observation yet estimates +inf ⇒ always-urgent, so a cold
        # runtime flushes the first (possibly singleton) batch at once
        # and bootstraps the estimate from its observed service time.
        # The MODEL dimension is load-bearing under multiplexing: a
        # freshly registered model must re-earn its own estimate instead
        # of inheriting another model's service time (ISSUE 14 satellite
        # — the cold-start seed applies PER KEY).
        self._ewma: Dict[Tuple[str, Any, int], float] = {}
        #: per-model weighted-EDF weights (1.0 = plain EDF); the runtime
        #: feeds these from the SLO burn rates each decision window
        self._weights: Dict[str, float] = {}
        self._weighted = False
        #: per (model, edge): the geometry's pair of staging buffers.
        #: Tier is no part of the key (every tier of a model takes the
        #: same input), and a batch of another row shape or dtype
        #: REPLACES its geometry's pair, so what is held is bounded by
        #: the geometries, never by traffic.
        self._staging: Dict[Tuple[str, Any], _StagingPair] = {}

    def _plan(self, model: str) -> ModelPlan:
        try:
            return self.plans[model]
        except KeyError:
            raise KeyError(f"no batching plan for model {model!r} "
                           f"(registered: {sorted(self.plans)})") from None

    def model_batch(self, model: str) -> int:
        plan = self._plan(model)
        return plan.max_batch if plan.max_batch else self.max_batch

    # -- weighted EDF ------------------------------------------------------
    def set_model_weight(self, model: str, weight: float) -> None:
        """Set ``model``'s dispatch weight (≥ 1 boosts, the runtime
        derives it from the model's SLO burn rate).  Slack is divided
        by the weight in the ready-bucket ordering, so a burning
        model's buckets win the next dispatch."""
        if weight <= 0:
            raise ValueError(f"weight must be > 0, got {weight}")
        self._weights[model] = float(weight)
        self._weighted = any(w != 1.0 for w in self._weights.values())

    def model_weight(self, model: str) -> float:
        return self._weights.get(model, 1.0)

    # -- service-time estimate --------------------------------------------
    def estimate_s(self, edge: Any, n: int, tier: int,
                   model: str = DEFAULT_MODEL) -> float:
        if self.service_time is not None:
            if self.multiplexed:
                return float(self.service_time(model, edge, n, tier))
            return float(self.service_time(edge, n, tier))
        return self._ewma.get((model, edge, tier), float("inf"))

    def observe_service_s(self, edge: Any, seconds: float, tier: int = 0,
                          model: str = DEFAULT_MODEL,
                          alpha: float = 0.3) -> None:
        key = (model, edge, tier)
        prev = self._ewma.get(key)
        self._ewma[key] = (seconds if prev is None
                           else (1 - alpha) * prev + alpha * seconds)

    # -- bucket assignment -------------------------------------------------
    def bucket_of(self, req: Request) -> Any:
        plan = self._plan(req.model)
        if plan.bucket_edges is None or req.length is None:
            return FIXED
        return edge_for(int(req.length), plan.bucket_edges)

    # -- assembly ----------------------------------------------------------
    def _group_stats(self) -> Dict[Tuple[str, Optional[int], Any],
                                   Tuple[int, float]]:
        """One O(Q) pass over the queued requests: per (model, affinity,
        edge) group → (count, earliest deadline).  The flush decision
        needs nothing else, so the heap is neither sorted nor mutated —
        this is the scan the million-request drill pays per pump."""
        stats: Dict[Tuple[str, Optional[int], Any], Tuple[int, float]] = {}
        for r in self.queue.iter_queued():
            key = (r.model, r.affinity, self.bucket_of(r))
            cur = stats.get(key)
            if cur is None:
                stats[key] = (1, r.deadline_t)
            else:
                stats[key] = (cur[0] + 1, min(cur[1], r.deadline_t))
        return stats

    def next_batch(self, tier, force: bool = False, ahead: bool = False
                   ) -> Optional[AssembledBatch]:
        """Assemble the most urgent flush-ready batch, or ``None`` when
        every bucket can still afford to wait.  ``tier`` is the current
        degradation rung — an int, or a ``{model: tier}`` map in
        multiplexed mode (each model rides its own ladder).
        ``force=True`` (drain) flushes the most urgent non-empty bucket
        regardless of slack.  Expired requests are shed first — never
        dispatched.  ``ahead`` only tags the ``az/serve/collate`` stage:
        the runtime assembles this batch while another's program runs."""
        self.queue.expire()
        stats = self._group_stats()
        if not stats:
            return None
        tiers = tier if isinstance(tier, dict) else None
        now = self.queue.clock.now()
        ready: List[Tuple[float, str, Tuple[str, Optional[int], Any]]] = []
        for key, (count, earliest) in stats.items():
            model, _affinity, edge = key
            cap = self.model_batch(model)
            m_tier = (tiers.get(model, 0) if tiers is not None
                      else int(tier))
            full = count >= cap
            est = self.estimate_s(edge, min(count, cap), m_tier,
                                  model=model)
            urgent = earliest - now <= est + self.slack_margin_s
            if full or urgent or force:
                if self._weighted:
                    # weighted EDF: positive slack shrinks by the
                    # model's burn-rate weight; NEGATIVE slack (an
                    # overdue bucket — possible under
                    # shed_expired=False) grows in magnitude instead,
                    # so a burning model ranks more urgent in both
                    # regimes (division would invert it exactly when
                    # the bucket is latest).  Equal weights reduce to
                    # plain EDF either way.
                    slack = earliest - now
                    w = self.model_weight(model)
                    rank = slack / w if slack >= 0 else slack * w
                else:
                    rank = earliest
                ready.append((rank, f"{model}/{_affinity}/{edge}", key))
        if not ready:
            return None
        _, _, key = min(ready, key=lambda t: (t[0], t[1]))
        model, affinity, edge = key
        taken = self.queue.pop_edf(
            predicate=lambda r: (r.model == model
                                 and r.affinity == affinity
                                 and self.bucket_of(r) == edge),
            limit=self.model_batch(model))
        m_tier = tiers.get(model, 0) if tiers is not None else int(tier)
        with stage("az/serve/collate") as collate:
            batch = self._collate(taken, edge, m_tier, model=model,
                                  affinity=affinity)
            collate.attrs["reused"] = batch.staging_reused
            collate.attrs["ahead"] = ahead
        return batch

    def _collate(self, reqs: List[Request], edge: Any, tier: int,
                 model: str = DEFAULT_MODEL,
                 affinity: Optional[int] = None) -> AssembledBatch:
        """Pad rows to the bucket edge and the batch axis to the model's
        batch size — both geometries already compiled — in one of the two
        staging buffers this batcher keeps for ``(model, edge)``, the one
        the batch before last used: a new array of a batch's size costs
        its page faults again for every batch, a kept one only the copy,
        and the batch before this one may still be read out of the other
        (a transfer, a program under way).  The two are allocated
        together the first time the geometry is seen.  The bytes are
        those ``np.stack`` of the
        padded rows gave (its promoted dtype, its ``ValueError`` for
        rows of different shapes); see :class:`AssembledBatch` for how
        long they stay valid."""
        plan = self._plan(model)
        cap = self.model_batch(model)
        arrs = [np.asarray(r.payload[plan.pad_key]
                           if isinstance(r.payload, dict) else r.payload)
                for r in reqs]
        # everything that can refuse the rows comes before the first
        # write, so a refused batch leaves the buffer as it was; a row of
        # another shape would broadcast into its slot silently
        skip = 0 if edge is FIXED else 1
        row_shape = arrs[0].shape[skip:]
        if any(a.shape[skip:] != row_shape for a in arrs):
            raise ValueError("all input arrays must have the same shape")
        lengths = []
        if edge is not FIXED:
            row_shape = (int(edge),) + row_shape
            lengths = [min(int(r.length if r.length is not None
                               else arr.shape[0]), int(edge), arr.shape[0])
                       for r, arr in zip(reqs, arrs)]
        shape = (cap,) + row_shape
        dtype = np.result_type(*dict.fromkeys(a.dtype for a in arrs))
        kept = self._staging.get((model, edge))
        reused = kept is not None and kept.pair.shape[1:] == shape \
            and kept.pair.dtype == dtype
        if not reused:      # zeroed, so no row of either is dirty
            kept = self._staging[(model, edge)] = _StagingPair(
                np.zeros((2,) + shape, dtype))
        turn = kept.turn
        buf, dirty = kept.pair[turn], kept.dirty[turn]
        zero = np.zeros((), dtype)      # '' for strings, where 0 reads '0'
        if edge is FIXED:
            for i, arr in enumerate(arrs):
                buf[i] = arr
        else:
            for i, (arr, n) in enumerate(zip(arrs, lengths)):
                buf[i, :n] = arr[:n]
                buf[i, n:] = zero
        n_valid = len(arrs)
        pad = cap - n_valid
        if dirty > n_valid:
            buf[n_valid:dirty] = zero
        kept.dirty[turn] = n_valid
        kept.turn = 1 - turn
        batch: Dict[str, Any] = {plan.pad_key: buf}
        if edge is not FIXED and plan.length_key:
            batch[plan.length_key] = np.asarray(lengths + [0] * pad,
                                                np.int32)
        if plan.streaming:
            sess = [(-1 if r.session is None else int(r.session))
                    for r in reqs] + [-1] * pad
            fin = [int(bool(r.final)) for r in reqs] + [0] * pad
            batch["session"] = np.asarray(sess, np.int64)
            batch["final"] = np.asarray(fin, np.int8)
        return AssembledBatch(requests=reqs, batch=batch, edge=edge,
                              n_valid=n_valid, tier=tier, model=model,
                              affinity=affinity, staging_reused=reused)
