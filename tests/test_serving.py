"""Online serving resilience runtime — tier-1 virtual-clock smoke.

Everything here runs on the VirtualClock with a synthetic service-time
model and a tiny pure-numpy model fn, so the full overload/failover
story executes in milliseconds of real CPU and is bit-deterministic
(the committed drill artifact RESILIENCE_r03.json is the full-size
version of these scenarios).  Covered: batch assembly determinism over
bucket geometries, EDF ordering + shed-before-dispatch + bounded-queue
rejection, failover-exactly-once re-dispatch, and degradation-ladder
hysteresis in both directions.
"""

import numpy as np
import pytest

from analytics_zoo_tpu.resilience.chaos import ChaosMonkey, FaultSpec
from analytics_zoo_tpu.resilience.errors import (ReplicaWedged,
                                                 RequestTimeout,
                                                 ServerOverloaded,
                                                 is_retryable)
from analytics_zoo_tpu.serving import (FIXED, AdmissionQueue,
                                       DeadlineBatcher, DegradationLadder,
                                       LadderPolicy, Request,
                                       ServingRuntime, ServingTier,
                                       VirtualClock)


def _fwd(batch):
    # rows summed over all trailing axes -> (B,) readback
    x = batch["input"]
    return x.reshape(x.shape[0], -1).sum(axis=1)


def _tiers(n=2):
    speeds = [1.0, 0.6, 0.45]
    return [ServingTier(name, _fwd, speed)
            for name, speed in zip(["fp", "int8", "int8_lowk"][:n],
                                   speeds[:n])]


def _drive_load(rt, clock, n, gap_s, payload_fn=None):
    """Submit ``n`` requests on a fixed arrival schedule (``gap_s``
    apart in virtual time), pumping the scheduler as time passes.  When
    a dispatch's service time carries the clock past several arrival
    instants, those requests are submitted as the burst they are — the
    single-server queueing behavior a serial virtual-clock harness can
    model honestly."""
    t_next = clock.now()
    submitted = 0
    while submitted < n:
        if clock.now() < t_next:
            if rt.pump() == 0:
                clock.advance(t_next - clock.now())
            continue
        # submit EVERY arrival whose instant has passed before giving the
        # scheduler a turn — a long dispatch surfaces the requests that
        # arrived during it as the burst they are
        while submitted < n and clock.now() >= t_next:
            try:
                rt.submit(payload_fn(submitted) if payload_fn
                          else {"input": np.ones((1, 2), np.float32)})
            except ServerOverloaded:
                pass
            submitted += 1
            t_next += gap_s
        rt.pump()


def _runtime(clock, *, tiers=None, chaos=None, **kw):
    kw.setdefault("queue_capacity", 32)
    kw.setdefault("max_batch", 4)
    kw.setdefault("default_deadline_s", 10.0)
    kw.setdefault("wedge_timeout_s", 1.0)
    kw.setdefault("restart_s", 3.0)
    kw.setdefault("service_time", lambda edge, n, tier: 0.05)
    return ServingRuntime(tiers or _tiers(), n_replicas=2, clock=clock,
                          chaos=chaos, **kw)


class TestBatchAssembly:
    def _drive(self):
        """One fixed submission script → the sequence of dispatched
        batches (edge, n_valid, request ids)."""
        clock = VirtualClock()
        seen = []
        edges = [8, 16]

        def spy(batch):
            return _fwd(batch)

        rt = ServingRuntime([ServingTier("fp", spy)], n_replicas=1,
                            clock=clock, queue_capacity=32, max_batch=3,
                            bucket_edges=edges, default_deadline_s=5.0,
                            wedge_timeout_s=5.0,
                            service_time=lambda e, n, t: 0.01)
        orig = rt._dispatch

        def record(batch):
            seen.append((batch.edge, batch.n_valid,
                         tuple(r.rid for r in batch.requests)))
            orig(batch)

        rt._dispatch = record
        lengths = [3, 12, 7, 15, 5, 9, 2, 14, 6]
        for i, n in enumerate(lengths):
            rt.submit({"input": np.ones((n, 2), np.float32)},
                      length=n, deadline_s=2.0 + 0.1 * i)
            clock.advance(0.05)
            rt.pump()
        rt.drain()
        assert rt.accounting()["unaccounted"] == 0
        return seen

    def test_assembly_deterministic_and_bucketed(self):
        a = self._drive()
        b = self._drive()
        assert a == b                       # same script → same batches
        # every batch uses a configured geometry, never an ad-hoc shape
        assert {e for e, _, _ in a} <= {8, 16}
        # full buckets flush at max_batch
        assert any(n == 3 for _, n, _ in a)

    def test_rows_padded_to_edge_and_batch(self):
        clock = VirtualClock()
        shapes = []

        def spy(batch):
            shapes.append((batch["input"].shape,
                           tuple(batch["n_frames"])))
            return _fwd(batch)

        rt = ServingRuntime([ServingTier("fp", spy)], n_replicas=1,
                            clock=clock, queue_capacity=8, max_batch=4,
                            bucket_edges=[8], default_deadline_s=1.0,
                            wedge_timeout_s=5.0,
                            service_time=lambda e, n, t: 0.01)
        rt.submit({"input": np.ones((5, 3), np.float32)}, length=5)
        rt.submit({"input": np.ones((2, 3), np.float32)}, length=2)
        rt.drain()
        # one batch: rows padded to edge 8, batch axis padded to 4,
        # true lengths carried for the first n_valid rows
        assert shapes == [((4, 8, 3), (5, 2, 0, 0))]
        assert all(r.state == "done" for r in rt.requests)


class TestEdfShedding:
    def test_edf_order_and_expiry(self):
        clock = VirtualClock()
        shed = []
        q = AdmissionQueue(8, clock, on_shed=lambda r, c: shed.append(
            (r.rid, c)))
        # submit out of deadline order
        for rid, dl in [(0, 5.0), (1, 1.0), (2, 3.0)]:
            q.submit(Request(rid=rid, payload=None, arrival_t=0.0,
                             deadline_t=dl))
        clock.advance(1.5)          # request 1's deadline passes queued
        assert q.expire() == 1
        assert shed == [(1, "deadline")]
        popped = q.pop_edf()
        assert [r.rid for r in popped] == [2, 0]    # EDF order
        # the expired request carries the retryable timeout error
        # (terminal state is "timeout")

    def test_queue_full_is_explicit_retryable_signal(self):
        clock = VirtualClock()
        rt = _runtime(clock, queue_capacity=2, max_batch=8,
                      default_deadline_s=100.0)
        rt.submit({"input": np.ones((1, 2), np.float32)})
        rt.submit({"input": np.ones((1, 2), np.float32)})
        with pytest.raises(ServerOverloaded) as ei:
            rt.submit({"input": np.ones((1, 2), np.float32)})
        assert is_retryable(ei.value)
        # the rejected request is still accounted (state "shed"), and
        # the metrics name the cause
        acct = rt.accounting()
        assert acct["by_state"]["shed"] == 1
        assert rt.metrics.shed_by_cause == {"queue_full": 1}
        rt.drain()
        assert rt.accounting()["unaccounted"] == 0

    def test_expired_shed_before_dispatch_never_reach_device(self):
        clock = VirtualClock()
        served_values = []

        def spy(batch):
            served_values.extend(batch["input"][:, 0, 0].tolist())
            return _fwd(batch)

        rt = ServingRuntime([ServingTier("fp", spy)], n_replicas=1,
                            clock=clock, queue_capacity=16, max_batch=4,
                            default_deadline_s=1.0, wedge_timeout_s=5.0,
                            service_time=lambda e, n, t: 0.01)
        for i in range(3):
            # request 0 carries a poison value 7.0 and a short deadline
            rt.submit({"input": np.full((1, 2), 7.0 if i == 0 else 1.0,
                                        np.float32)},
                      deadline_s=0.5 if i == 0 else 5.0)
        clock.advance(1.0)          # request 0 expires while queued
        rt.drain()
        timed_out = [r for r in rt.requests if r.state == "timeout"]
        assert [r.rid for r in timed_out] == [0]
        assert isinstance(timed_out[0].error, RequestTimeout)
        assert is_retryable(timed_out[0].error)
        # the expired request's payload never reached a model fn
        assert 7.0 not in served_values
        done = {r.rid for r in rt.requests if r.state == "done"}
        assert done == {1, 2}
        assert rt.metrics.shed_by_cause == {"deadline": 1}


class TestFailover:
    def test_crash_fences_redispatches_exactly_once_and_restarts(self):
        clock = VirtualClock()
        monkey = ChaosMonkey([FaultSpec("replica_crash", 1,
                                        detail={"replica": 0})])
        rt = _runtime(clock, chaos=monkey)
        for i in range(16):
            rt.submit({"input": np.ones((2, 2), np.float32)})
            clock.advance(0.2)
            rt.pump()
        rt.drain()
        # every request completed despite the mid-batch kill
        assert rt.accounting()["by_state"] == {"done": 16}
        fences = [e for e in rt.pool.events if e["kind"] == "replica_fenced"]
        fails = [e for e in rt.pool.events if e["kind"] == "failover"]
        assert len(fences) == 1 and fences[0]["replica"] == 0
        assert len(fails) == 1 and fails[0]["from"] == 0
        # the failed batch's requests were dispatched exactly twice
        # (original + one re-dispatch), everyone else exactly once
        redone = set(fails[0]["requests"])
        for r in rt.requests:
            assert r.attempts == (2 if r.rid in redone else 1)
        # background restart re-admits the replica once its cooldown
        # elapses on the runtime clock
        clock.advance(rt.pool.restart_s + 10.0)
        assert rt.pool.healthy() and rt.pool.snapshot()["healthy"] == 2
        restarts = [e for e in rt.pool.events
                    if e["kind"] == "replica_restarted"]
        assert restarts and restarts[0]["replica"] == 0

    def test_second_failure_fails_batch_not_infinite_ping_pong(self):
        clock = VirtualClock()
        # both replicas crash the same batch: dispatch 1 on whichever
        # replica is picked, then the failover dispatch also crashes
        monkey = ChaosMonkey([
            FaultSpec("replica_crash", 1, batches=1, detail={}),
            FaultSpec("replica_crash", 1, batches=1, detail={}),
        ])
        rt = _runtime(clock, chaos=monkey)
        for i in range(4):
            rt.submit({"input": np.ones((2, 2), np.float32)})
        rt.drain()
        failed = [r for r in rt.requests if r.state == "failed"]
        assert len(failed) == 4
        assert all(isinstance(r.error, ReplicaWedged) for r in failed)
        assert all(r.attempts == 2 for r in failed)     # exactly once
        assert rt.accounting()["unaccounted"] == 0

    def test_wedged_forward_detected_by_watchdog(self):
        clock = VirtualClock()
        monkey = ChaosMonkey([FaultSpec("slow_forward", 1,
                                        detail={"replica": 0,
                                                "delay_s": 9.0})])
        rt = _runtime(clock, chaos=monkey, default_deadline_s=30.0)
        for i in range(8):
            rt.submit({"input": np.ones((2, 2), np.float32)})
            clock.advance(0.2)
            rt.pump()
        rt.drain()
        fences = [e for e in rt.pool.events if e["kind"] == "replica_fenced"]
        assert len(fences) == 1 and "wedged" in fences[0]["error"]
        assert rt.accounting()["by_state"] == {"done": 8}


class TestWarmAndProgramErrors:
    """The real-clock seams (ISSUE 21): geometries compile before traffic
    through ``ServingRuntime.warm``, and a program error — the failure of
    a geometry's FIRST forward on a replica, or anything the failure
    classification calls fatal — propagates as itself instead of being
    laundered into ``ReplicaWedged`` + fence + failover."""

    def test_warm_runs_every_geometry_on_every_replica_off_the_books(self):
        calls = []

        def fwd(tag):
            def forward(batch):
                calls.append((tag, batch["input"].shape))
                return _fwd(batch)
            return forward

        tiers = [ServingTier("fp", fwd("fp"), 1.0),
                 ServingTier("int8", fwd("int8"), 0.6)]
        rt = _runtime(VirtualClock(), tiers=tiers, bucket_edges=[4, 8])
        took = rt.warm({"input": np.ones((3, 2), np.float32)})
        # 2 edges x 2 tiers, each padded to its compiled geometry
        # (max_batch rows, edge frames), on both replicas
        assert sorted(took) == [("default", 4, 0), ("default", 4, 1),
                                ("default", 8, 0), ("default", 8, 1)]
        assert sorted(set(calls)) == [("fp", (4, 4, 2)), ("fp", (4, 8, 2)),
                                      ("int8", (4, 4, 2)),
                                      ("int8", (4, 8, 2))]
        assert len(calls) == 8
        # nothing was submitted, dispatched or supervised
        assert rt.accounting()["submitted"] == 0
        assert all(r.dispatches == 0 for r in rt.pool.replicas)

    def test_warm_refuses_streaming_models(self):
        from analytics_zoo_tpu.serving.runtime import ModelConfig

        cfg = ModelConfig(name="asr", tiers=_tiers(1), streaming=True,
                          tier_factory=lambda rid: _tiers(1))
        rt = ServingRuntime(models=[cfg], n_replicas=1,
                            clock=VirtualClock(),
                            service_time=lambda m, e, n, t: 0.05)
        with pytest.raises(ValueError, match="streaming"):
            rt.warm({"input": np.ones((1, 2), np.float32)})

    def test_first_forward_failure_of_a_geometry_is_not_a_wedge(self):
        """A compile error surfaces at a geometry's first forward; here a
        retryable-CLASS error stands in for it (Mosaic failures raise
        ``JaxRuntimeError``).  No fence, no failover: it propagates."""
        import jax

        def broken(batch):
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: Mosaic failed to compile TPU kernel")

        rt = _runtime(VirtualClock(), tiers=[ServingTier("fp", broken, 1.0)])
        with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
            rt.warm({"input": np.ones((1, 2), np.float32)})
        rt.submit({"input": np.ones((1, 2), np.float32)})
        with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
            rt.drain()
        assert not [e for e in rt.pool.events
                    if e["kind"] in ("replica_fenced", "failover")]

    def test_runtime_error_after_a_geometry_ran_is_a_replica_fault(self):
        """Once a geometry has completed a forward on a replica, a
        retryable error from it is the replica's: fence + fail over."""
        import jax

        state = {"calls": 0}

        def flaky(batch):
            state["calls"] += 1
            if state["calls"] == 3:        # after both replicas warmed
                raise jax.errors.JaxRuntimeError("device lost")
            return _fwd(batch)

        rt = _runtime(VirtualClock(), tiers=[ServingTier("fp", flaky, 1.0)])
        rt.warm({"input": np.ones((1, 2), np.float32)})
        rt.submit({"input": np.ones((1, 2), np.float32)})
        rt.drain()
        assert rt.accounting()["by_state"] == {"done": 1}
        assert [e["kind"] for e in rt.pool.events
                if e["kind"] in ("replica_fenced", "failover")] \
            == ["replica_fenced", "failover"]

    def test_fatal_class_errors_always_propagate(self):
        """A TypeError is a bug in the program, whenever it happens."""
        state = {"calls": 0}

        def buggy(batch):
            state["calls"] += 1
            if state["calls"] > 2:
                raise TypeError("unsupported operand")
            return _fwd(batch)

        rt = _runtime(VirtualClock(), tiers=[ServingTier("fp", buggy, 1.0)])
        rt.warm({"input": np.ones((1, 2), np.float32)})
        rt.submit({"input": np.ones((1, 2), np.float32)})
        with pytest.raises(TypeError, match="unsupported operand"):
            rt.drain()


class TestDegradationLadder:
    def test_hysteresis_down_and_up(self):
        ladder = DegradationLadder(3, LadderPolicy(down_after=2,
                                                   up_after=3))
        assert ladder.observe_window(True) == "hold"
        assert ladder.observe_window(True) == "down"
        assert ladder.tier == 1
        # streak reset: next step down needs a FULL fresh streak
        assert ladder.observe_window(True) == "hold"
        assert ladder.observe_window(True) == "down"
        assert ladder.tier == 2
        # floor: cannot go below the cheapest tier
        ladder.observe_window(True)
        ladder.observe_window(True)
        assert ladder.tier == 2
        # recovery needs up_after consecutive clean windows
        assert ladder.observe_window(False) == "hold"
        assert ladder.observe_window(False) == "hold"
        assert ladder.observe_window(False) == "up"
        assert ladder.tier == 1
        # a single overloaded window resets the clean streak
        ladder.observe_window(False)
        ladder.observe_window(True)
        for _ in range(2):
            assert ladder.observe_window(False) == "hold"
        assert ladder.observe_window(False) == "up"
        assert ladder.tier == 0

    def test_runtime_degrades_under_shed_and_recovers(self):
        clock = VirtualClock()
        rt = _runtime(clock, tiers=_tiers(2), queue_capacity=8,
                      max_batch=2, default_deadline_s=0.4,
                      service_time=lambda e, n, t: 0.15 if t == 0 else 0.06,
                      decision_every=2,
                      ladder_policy=LadderPolicy(down_after=2, up_after=3))
        tiers_seen = []
        orig = rt._dispatch

        def record(batch):
            tiers_seen.append(batch.tier)
            orig(batch)

        rt._dispatch = record
        # overload: arrivals well above the tier-0 service rate
        _drive_load(rt, clock, 40, gap_s=0.05)
        assert rt.metrics.shed_total > 0
        down = [e for e in rt.ladder.events if e["kind"] == "tier_down"]
        assert down                        # engaged the int8 tier
        assert max(tiers_seen) == 1        # ... and actually served on it
        # calm: arrivals well under the service rate -> clean windows
        _drive_load(rt, clock, 30, gap_s=0.2)
        rt.drain()
        assert rt.ladder.tier == 0          # recovered with hysteresis
        ups = [e for e in rt.ladder.events if e["kind"] == "tier_up"]
        assert len(ups) >= 1
        # both tiers actually served traffic
        assert {0, 1} <= set(tiers_seen)
        assert rt.accounting()["unaccounted"] == 0
        # per-tier latency recorded separately
        snap = rt.metrics.snapshot()
        assert set(snap["latency_by_tier"]) == {"0", "1"}


class TestMetricsSnapshot:
    def test_latency_memory_bounded_by_reservoir(self):
        """PR 7 satellite: per-tier latency used to be an unbounded list
        full-sorted per snapshot; it is now a bounded reservoir in the
        central registry — O(1) memory per tier at any request count,
        exact below capacity, honest ``sampled`` flag past it."""
        from analytics_zoo_tpu.serving import ServingMetrics

        m = ServingMetrics(reservoir=64)
        for i in range(10_000):
            m.on_complete(i * 1e-4, tier=0, missed=False)
        h = m.registry.histogram("serve/latency_s/tier=0", max_samples=64)
        assert len(h.samples) == 64 and h.count == 10_000
        snap = m.snapshot()["latency_by_tier"]["0"]
        assert snap["n"] == 10_000 and snap["sampled"] is True
        assert snap["max_s"] == pytest.approx(0.9999)
        # exact (not sampled) below reservoir capacity
        m2 = ServingMetrics(reservoir=64)
        for v in (0.3, 0.1, 0.2):
            m2.on_complete(v, tier=1, missed=False)
        s2 = m2.snapshot()["latency_by_tier"]["1"]
        assert s2 == {"n": 3, "p50_s": 0.2, "p99_s": 0.3, "max_s": 0.3,
                      "sampled": False}

    def test_snapshot_shape(self):
        clock = VirtualClock()
        rt = _runtime(clock)
        for i in range(6):
            rt.submit({"input": np.ones((1, 2), np.float32)})
            clock.advance(0.1)
            rt.pump()
        rt.drain()
        snap = rt.snapshot()
        m = snap["metrics"]
        assert m["submitted"] == 6 and m["completed"] == 6
        assert m["deadline_miss_rate"] == 0.0
        assert m["latency_by_tier"]["0"]["p99_s"] is not None
        assert snap["accounting"]["unaccounted"] == 0
        assert snap["replicas"]["healthy"] == 2
        assert snap["ladder"]["tier"] == 0


@pytest.fixture(scope="module")
def tiny_ds2_model():
    from analytics_zoo_tpu.pipelines.deepspeech2 import make_ds2_model

    return make_ds2_model(hidden=16, n_rnn_layers=1, utt_length=16,
                          rnn_block=4)


class TestPipelineTiers:
    """The pipelines-side tier hooks: real predictors behind the
    runtime's request API (the SSD hook shares the same shape; its
    predictor stack is exercised by test_quantize/test_pipelines)."""

    def test_ds2_tiers_serve_real_model_on_bucketed_geometry(
            self, tiny_ds2_model):
        from analytics_zoo_tpu.pipelines.deepspeech2 import (
            DS2Param, ds2_serving_tiers)

        tiers = ds2_serving_tiers(tiny_ds2_model,
                                  DS2Param(decoder="beam", beam_width=8))
        # beam ladder: full beam -> reduced beam -> greedy, cheapest last
        assert [t.name for t in tiers] == ["beam8", "beam4", "greedy"]
        assert tiers[0].speed >= tiers[1].speed >= tiers[2].speed

        clock = VirtualClock()
        rt = ServingRuntime(tiers, n_replicas=1, clock=clock,
                            queue_capacity=8, max_batch=2,
                            bucket_edges=[16], default_deadline_s=5.0,
                            wedge_timeout_s=60.0,
                            service_time=lambda e, n, t: 0.01)
        rng = np.random.RandomState(0)
        for n in (10, 3):
            feats = rng.randn(n, 13).astype(np.float32)
            rt.submit({"input": feats}, length=n)
        rt.drain()
        assert rt.accounting()["by_state"] == {"done": 2}
        # real forward + beam decode ran: every result is a transcript
        # string decoded from only the row's valid frames
        assert all(isinstance(r.result, str) for r in rt.requests)

    def test_ds2_greedy_param_collapses_ladder(self, tiny_ds2_model):
        from analytics_zoo_tpu.pipelines.deepspeech2 import (
            DS2Param, ds2_serving_tiers)

        tiers = ds2_serving_tiers(tiny_ds2_model, DS2Param(decoder="greedy"))
        # no decode quality to shed -> single greedy rung
        assert [t.name for t in tiers] == ["greedy"]
