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


def _reference_batch(reqs, edge, cap, plan):
    """What ``_collate`` built before it kept a staging buffer, kept as
    the reference: every row padded into a new array of its own, pad
    rows of zeros, ``np.stack`` of them all."""
    rows, lengths = [], []
    for r in reqs:
        arr = np.asarray(r.payload[plan.pad_key])
        if edge is FIXED:
            rows.append(arr)
            continue
        n = min(int(r.length if r.length is not None else arr.shape[0]),
                int(edge), arr.shape[0])
        padded = np.zeros((int(edge),) + arr.shape[1:], arr.dtype)
        padded[:n] = arr[:n]
        rows.append(padded)
        lengths.append(n)
    pad = cap - len(rows)
    rows.extend(np.zeros_like(rows[0]) for _ in range(pad))
    batch = {plan.pad_key: np.stack(rows)}
    if edge is not FIXED and plan.length_key:
        batch[plan.length_key] = np.asarray(lengths + [0] * pad, np.int32)
    if plan.streaming:
        batch["session"] = np.asarray(
            [r.session for r in reqs] + [-1] * pad, np.int64)
        batch["final"] = np.asarray(
            [int(r.final) for r in reqs] + [0] * pad, np.int8)
    return batch


def _rows(rng, shapes, dtype=np.float32, **request):
    """One request a shape, payloads without a zero in them (so a stale
    byte of an earlier batch cannot pass for padding)."""
    none = [None] * len(shapes)
    return [Request(rid=i, arrival_t=0.0, deadline_t=1.0,
                    payload={"input": np.asarray(rng.rand(*shape) + 1,
                                                 dtype)},
                    length=request.get("lengths", none)[i],
                    session=request.get("sessions", none)[i],
                    final=bool(request.get("finals", none)[i]))
            for i, shape in enumerate(shapes)]


#: name → (plan, [(edge, request shapes, request keywords), ...]): the
#: batches of one geometry in the order one batcher assembles them
STAGING_SCRIPTS = {
    "fixed_full": (
        {}, [(FIXED, [(5, 3)] * 4, {})]),
    "fixed_short_after_full": (
        {}, [(FIXED, [(5, 3)] * 4, {}), (FIXED, [(5, 3)], {}),
             (FIXED, [(5, 3)] * 3, {}), (FIXED, [(5, 3)] * 2, {})]),
    "fixed_both_of_the_pair_shrink": (
        {}, [(FIXED, [(5, 3)] * 4, {}), (FIXED, [(5, 3)] * 4, {}),
             (FIXED, [(5, 3)], {}), (FIXED, [(5, 3)] * 2, {}),
             (FIXED, [(5, 3)] * 3, {}), (FIXED, [(5, 3)], {})]),
    "bucketed_lengths_shrink": (
        {"bucket_edges": [8]},
        [(8, [(8, 2), (7, 2), (8, 2)], {"lengths": [8, 7, 8]}),
         (8, [(3, 2), (6, 2), (1, 2)], {"lengths": [3, 6, 1]}),
         # the declared length cuts a longer payload; one longer than
         # the edge is cut to the edge
         (8, [(6, 2), (11, 2)], {"lengths": [2, 11]})]),
    "streaming": (
        {"bucket_edges": [6], "length_key": "n_samples", "streaming": True},
        [(6, [(6,), (6,), (4,)], {"lengths": [6, 6, 4],
                                  "sessions": [7, 9, 11],
                                  "finals": [False, False, True]}),
         (6, [(2,)], {"lengths": [2], "sessions": [9], "finals": [True]})]),
    "scalar_rows": (
        {}, [(FIXED, [()] * 3, {}), (FIXED, [()], {})]),
}


class TestStagingBuffer:
    """ISSUE 27, ISSUE 36: ``_collate`` fills, in turn, the two staging
    buffers the batcher keeps for the geometry.  The bytes are those
    ``np.stack`` gave; the pair is one allocation a geometry; a batch's
    bytes stand until the next batch but one; nobody who kept an answer
    sees a later batch."""

    CAP = 4

    def _batcher(self, **plan):
        from analytics_zoo_tpu.serving.batcher import ModelPlan

        clock = VirtualClock()
        b = DeadlineBatcher(AdmissionQueue(64, clock), max_batch=self.CAP,
                            plans={"default": ModelPlan(**plan)})
        return b, b.plans["default"]

    @pytest.mark.parametrize("script", sorted(STAGING_SCRIPTS))
    def test_bytes_are_those_np_stack_gave(self, script):
        plan_kw, batches = STAGING_SCRIPTS[script]
        b, plan = self._batcher(**plan_kw)
        rng = np.random.RandomState(27)
        seen = []
        for k, (edge, shapes, kw) in enumerate(batches):
            reqs = _rows(rng, shapes, **kw)
            got = b._collate(reqs, edge, 0)
            want = _reference_batch(reqs, edge, self.CAP, plan)
            assert sorted(got.batch) == sorted(want), (script, k)
            for key, ref in want.items():
                assert got.batch[key].dtype == ref.dtype, (script, k, key)
                assert got.batch[key].tobytes() == ref.tobytes(), (
                    script, k, key)
            assert got.n_valid == len(reqs)
            assert got.staging_reused == (k > 0)
            # filled in turn: the batch before this one stands as it
            # was, the one before that gave its buffer
            if seen:
                assert not np.shares_memory(got.batch["input"], seen[-1][0])
                assert seen[-1][0].tobytes() == seen[-1][1], (script, k)
            if len(seen) > 1:
                assert np.shares_memory(got.batch["input"], seen[-2][0])
            seen.append((got.batch["input"], want["input"].tobytes()))

    @pytest.mark.parametrize("dtypes,promoted", [
        ((np.float32, np.float64, np.float32), np.float64),
        ((np.int8, np.uint8), np.int16),
        ((np.int32, np.float32), np.float64),
        ((np.float16,), np.float16),
    ])
    def test_rows_of_mixed_dtype_take_the_promoted_one(self, dtypes,
                                                       promoted):
        b, plan = self._batcher()
        rng = np.random.RandomState(3)
        reqs = [r for d in dtypes for r in _rows(rng, [(2, 3)], d)]
        got = b._collate(reqs, FIXED, 0).batch["input"]
        want = _reference_batch(reqs, FIXED, self.CAP, plan)["input"]
        assert got.dtype == want.dtype == promoted
        assert got.tobytes() == want.tobytes()
        # the same rows in one dtype next: the buffer is replaced, not
        # filled through a cast
        again = b._collate(_rows(rng, [(2, 3)] * 2, dtypes[0]), FIXED, 0)
        assert again.batch["input"].dtype == dtypes[0]
        assert again.staging_reused == (dtypes[0] == promoted)

    @pytest.mark.parametrize("edge,shapes", [
        (FIXED, [(5, 3), (5, 1)]),        # would broadcast silently
        (FIXED, [(5, 3), (3,)]),          # would broadcast silently
        (FIXED, [(5, 3), (4, 3)]),
        (8, [(6, 2), (6, 1)]),
        (8, [(6, 2), (6,)]),
    ])
    def test_a_row_of_another_shape_is_refused_as_np_stack_does(
            self, edge, shapes):
        b, plan = self._batcher(bucket_edges=None if edge is FIXED else [8])
        rng = np.random.RandomState(5)
        full = _rows(rng, [shapes[0]] * self.CAP)
        b._collate(full, edge, 0)
        kept = b._staging[("default", edge)]
        before = kept.pair.copy()
        reqs = _rows(rng, shapes)
        with pytest.raises(ValueError, match="same shape") as theirs:
            _reference_batch(reqs, edge, self.CAP, plan)
        with pytest.raises(ValueError, match="same shape") as ours:
            b._collate(reqs, edge, 0)
        assert str(ours.value) == str(theirs.value)
        # refused before a byte was written or the turn passed: both
        # buffers' zero-padding bookkeeping still holds for the next batch
        assert b._staging[("default", edge)] is kept
        np.testing.assert_array_equal(kept.pair, before)
        assert (kept.dirty, kept.turn) == ([self.CAP, 0], 1)
        ok = _rows(rng, [shapes[0]])
        assert (b._collate(ok, edge, 0).batch["input"].tobytes()
                == _reference_batch(ok, edge, self.CAP, plan)["input"]
                .tobytes())

    def test_one_pair_a_geometry_replaced_when_the_rows_change(self):
        from analytics_zoo_tpu.serving.batcher import ModelPlan

        clock = VirtualClock()
        plans = {"a": ModelPlan(bucket_edges=[4, 8]),
                 "b": ModelPlan(max_batch=2)}
        b = DeadlineBatcher(AdmissionQueue(64, clock), max_batch=self.CAP,
                            plans=plans)
        geometries = [("a", 4), ("a", 8), ("b", FIXED)]
        rng = np.random.RandomState(11)
        pairs, last = {}, {}
        for round_ in range(3):
            for tier in (0, 1):                 # tier is no part of the key
                for model, edge in geometries:
                    shape = (3, 2) if model == "a" else (5,)
                    batch = b._collate(_rows(rng, [shape] * 2), edge, tier,
                                       model=model)
                    got = batch.batch["input"]
                    pair = pairs.setdefault((model, edge),
                                            b._staging[(model, edge)].pair)
                    # the two were allocated together, once
                    assert b._staging[(model, edge)].pair is pair
                    assert batch.staging_reused == ((model, edge) in last)
                    assert got.base is pair and len(pair) == 2
                    if (model, edge) in last:
                        assert not np.shares_memory(got, last[(model, edge)])
                    last[(model, edge)] = got
                    assert len(b._staging) <= len(geometries)
        assert sorted(b._staging, key=str) == sorted(geometries, key=str)
        assert pairs[("b", FIXED)].shape == (2, 2, 5)
        # rows of another shape: the pair of that geometry alone goes
        wider = b._collate(_rows(rng, [(6,)]), FIXED, 0, model="b")
        assert wider.staging_reused is False
        assert wider.batch["input"].shape == (2, 6)
        assert not np.shares_memory(wider.batch["input"],
                                    pairs[("b", FIXED)])
        assert b._staging[("a", 4)].pair is pairs[("a", 4)]
        assert len(b._staging) == len(geometries)
        again = b._collate(_rows(rng, [(6,)]), FIXED, 1, model="b")
        assert again.staging_reused is True
        assert again.batch["input"].base is wider.batch["input"].base
        assert not np.shares_memory(again.batch["input"],
                                    wider.batch["input"])

    @pytest.mark.parametrize("parallel", [False, True],
                             ids=["serial", "parallel"])
    @pytest.mark.parametrize("answer", [
        lambda batch: batch["input"],
        lambda batch: batch["input"][:, :1],
        lambda batch: batch["input"].reshape(-1, 6)[:, ::2],
    ], ids=["input", "view", "strided"])
    def test_a_tier_that_returns_its_input_cannot_leak_the_buffer(
            self, answer, parallel):
        clock = VirtualClock()
        rt = ServingRuntime([ServingTier("echo", answer)], n_replicas=1,
                            clock=clock, queue_capacity=16, max_batch=2,
                            default_deadline_s=10.0, parallel_replicas=parallel,
                            service_time=lambda e, n, t: 0.01)
        rng = np.random.RandomState(2)
        pictures = [rng.rand(3, 2).astype(np.float32) for _ in range(6)]
        rounds, before = [], []
        for k in range(3):      # the third batch fills the first's buffer
            rounds.append([rt.submit({"input": p})
                           for p in pictures[2 * k:2 * k + 2]])
            rt.pump()
            clock.advance(0.05)
            rt.drain()
            assert all(r.state == "done" for r in rounds[k])
            before.append([np.array(r.result) for r in rounds[k]])
        staging = rt.batcher._staging[("default", FIXED)].pair
        for k, reqs in enumerate(rounds):
            for req, was, pic in zip(reqs, before[k], pictures[2 * k:]):
                np.testing.assert_array_equal(req.result, was)
                np.testing.assert_array_equal(
                    req.result, answer({"input": pic[None]})[0])
                assert not np.shares_memory(req.result, staging)
        # the third batch did overwrite the rows the first one used
        np.testing.assert_array_equal(staging[0, 0], pictures[4])
        np.testing.assert_array_equal(staging[1, 0], pictures[2])

    def test_collate_stage_says_reused_and_the_registry_counts_allocs(self):
        import time

        from analytics_zoo_tpu import obs

        clock = VirtualClock()
        rt = _runtime(clock)
        t0 = time.monotonic()
        for _ in range(2):
            for _ in range(4):
                rt.submit({"input": np.ones((1, 2), np.float32)})
            assert rt.pump() == 1
        collates = [r for r in obs.stages(since=t0)
                    if r.name == "az/serve/collate"]
        assert [r.attrs for r in collates] == [
            {"reused": False, "ahead": False},
            {"reused": True, "ahead": False}]
        reg = rt.metrics.registry
        assert reg.counter("serve/staging_alloc").value == 1
        # warm() allocates off the books of no one: a warmed runtime's
        # first batch reuses, and the counter says how many it took
        warmed = _runtime(VirtualClock())
        warmed.warm({"input": np.ones((1, 2), np.float32)})
        for _ in range(4):
            warmed.submit({"input": np.ones((1, 2), np.float32)})
        t1 = time.monotonic()
        assert warmed.pump() == 1
        assert [r.attrs["reused"] for r in obs.stages(since=t1)
                if r.name == "az/serve/collate"] == [True]
        assert warmed.metrics.registry.counter(
            "serve/staging_alloc").value == 1

    def test_streaming_session_through_kept_rows_matches_offline(self):
        """Chunks of shrinking lengths from two sessions share the rows
        of one kept pair of buffers over four batches: what ``StreamingDS2``
        buffers across chunks must be its own (the tier hands it a view
        of the staging row), and a row's tail the zeros of THIS chunk."""
        import jax.numpy as jnp

        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.models import DeepSpeech2
        from analytics_zoo_tpu.pipelines.deepspeech2 import (
            StreamingDS2, ds2_streaming_tiers)
        from analytics_zoo_tpu.serving import ModelConfig

        model = Model(DeepSpeech2(hidden=16, n_rnn_layers=1,
                                  bidirectional=False))
        model.build(0, jnp.zeros((1, 50, 13), jnp.float32))
        EDGE = 6000
        cfg = ModelConfig(
            name="ds2-stream", streaming=True,
            tiers=ds2_streaming_tiers(model, chunk_frames=20),
            tier_factory=lambda rid: ds2_streaming_tiers(
                model, chunk_frames=20),
            pad_key="input", length_key="n_samples",
            bucket_edges=[EDGE], chunk_deadline_s=2.0)
        clock = VirtualClock()
        rt = ServingRuntime(models=[cfg], n_replicas=1, clock=clock,
                            queue_capacity=32, max_batch=2,
                            service_time=lambda m, e, n, t: 0.02)
        rng = np.random.RandomState(4)
        cuts = {0: [6000, 4100, 2900, 700], 1: [5000, 6000, 1300, 3300]}
        utts = {s: (rng.randn(sum(c)) * 0.1).astype(np.float32)
                for s, c in cuts.items()}
        sids = {s: rt.open_session("ds2-stream") for s in cuts}
        reqs = {s: [] for s in cuts}
        buffers = set()
        for k in range(4):
            for s, c in cuts.items():
                chunk = utts[s][sum(c[:k]):sum(c[:k + 1])]
                reqs[s].append(rt.submit_chunk(
                    sids[s], {"input": chunk}, length=len(chunk),
                    final=(k == 3)))
            clock.advance(0.1)
            assert rt.pump() == 1
            buffers.add(id(rt.batcher._staging[("ds2-stream", EDGE)].pair))
        assert len(buffers) == 1
        assert rt.accounting()["by_state"] == {"done": 8}
        for s, c in cuts.items():
            direct = StreamingDS2(model, chunk_frames=20)
            pieces = [direct.accept(utts[s][sum(c[:k]):sum(c[:k + 1])])
                      for k in range(4)]
            pieces.append(direct.flush())
            assert "".join(str(r.result) for r in reqs[s]) \
                == "".join(pieces), s


class _Later:
    """A tier's answer that is not on the host yet, as a device array
    whose program is still running: the rows are computed from the input
    only when ``__array__`` fetches them (so an input overwritten in
    between shows in the answer), and the fetch is stamped in ``log``."""

    def __init__(self, rows, log, tag):
        self.rows, self.log, self.tag = rows, log, tag

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.tag))
        return self.rows()


class _Placed(np.ndarray):
    """What a test tier's ``place`` makes of a leaf: the same bytes,
    recognisable."""


class TestLookAhead:
    """ISSUE 36: a tier may hand back an answer that is not on the host
    yet; the replica fetches it, and in between the runtime assembles the
    next due batch (and starts its transfer).  One batch ahead, one
    thread, ``pump()`` answers all it dispatched."""

    CAP = 4

    def _runtime(self, log, *, later=True, fail=None, n_tiers=1,
                 n_replicas=1, place=False, warm=False, **kw):
        """A runtime over tiers that log ``("forward", first row's value,
        kind of input)`` and answer with a :class:`_Later` (or, with
        ``later=False``, with host rows); ``fail`` maps the n-th fetch
        (after ``warm()``, if ``warm``) to the exception it raises; the
        batcher's ``_collate`` logs ``("collate", first row's value)``
        and checks the pair's contract."""
        clock = VirtualClock()
        fetches = [-1000 if warm else 0]    # nothing fails in warm()

        def forward(batch):
            x = batch["input"]
            tag = float(np.asarray(x)[0].ravel()[0])
            log.append(("forward", tag, type(x).__name__))
            if not later:
                return _fwd(batch)

            def rows():
                fetches[0] += 1
                err = (fail or {}).get(fetches[0])
                if err is not None:
                    raise err
                return _fwd({"input": np.asarray(x)})

            return _Later(rows, log, tag)

        def ahead(batch):
            log.append(("place", float(batch["input"][0].ravel()[0])))
            return {"input": batch["input"].view(_Placed)}

        tiers = [ServingTier(f"t{i}", forward, speed,
                             place=ahead if place else None)
                 for i, speed in enumerate([1.0, 0.6, 0.45][:n_tiers])]
        kw.setdefault("queue_capacity", 32)
        kw.setdefault("service_time", lambda e, n, t: 0.05)
        rt = ServingRuntime(tiers, n_replicas=n_replicas, clock=clock,
                            max_batch=self.CAP, default_deadline_s=10.0,
                            wedge_timeout_s=1.0, restart_s=30.0, **kw)
        collate = rt.batcher._collate
        owners = {}

        def logged(reqs, *a, **k):
            batch = collate(reqs, *a, **k)
            buf = batch.batch["input"]
            log.append(("collate", float(buf[0].ravel()[0])))
            # a buffer is written again only once the batch that used it
            # last was handed out
            at = buf.__array_interface__["data"][0]
            assert all(r.finished for r in owners.get(at, ()))
            owners[at] = list(reqs)
            return batch

        rt.batcher._collate = logged
        if warm:        # a geometry's first run is the program's own
            rt.warm({"input": np.zeros((1, 2), np.float32)})
            del log[:]
            owners.clear()
            fetches[0] = 0
        return rt, clock

    def _submit(self, rt, n_batches, first=1.0):
        """``n_batches`` full batches; every row of batch k holds
        ``first + k`` and one more in its own first cell, so a request's
        answer names its batch AND its row."""
        reqs = []
        for k in range(n_batches):
            for i in range(self.CAP):
                x = np.full((1, 2), first + k, np.float32)
                x[0, 1] = i
                reqs.append(rt.submit({"input": x}))
        return reqs

    def _check_answers(self, reqs, first=1.0):
        for j, r in enumerate(reqs):
            assert r.state == "done", (j, r.state, r.error)
            assert float(r.result) == first + j // self.CAP + j % self.CAP

    def test_the_next_batch_is_assembled_between_the_call_and_the_fetch(
            self):
        import time

        from analytics_zoo_tpu import obs

        log = []
        rt, _ = self._runtime(log)
        reqs = self._submit(rt, 3)
        t0 = time.monotonic()
        assert rt.pump() == 3
        assert log == [
            ("collate", 1.0), ("forward", 1.0, "ndarray"),
            ("collate", 2.0), ("fetch", 1.0),
            ("forward", 2.0, "ndarray"), ("collate", 3.0), ("fetch", 2.0),
            ("forward", 3.0, "ndarray"), ("fetch", 3.0)]
        self._check_answers(reqs)
        done = [r.completed_t for r in reqs]
        assert max(done[:4]) <= min(done[4:8]) <= max(done[4:8]) \
            <= min(done[8:])
        records = [r for r in obs.stages(since=t0)
                   if r.name.startswith("az/serve/")]
        assert [r.attrs for r in records
                if r.name == "az/serve/collate"] == [
            {"reused": False, "ahead": False},
            {"reused": True, "ahead": True},
            {"reused": True, "ahead": True}]
        # the replica's fetch is the batch's result_wait, inside its
        # forward; the collate ahead lies inside the forward before it
        by = {n: [r for r in records if r.name == "az/serve/" + n]
              for n in ("forward", "collate", "result_wait", "handout")}
        assert [len(v) for v in by.values()] == [3, 3, 3, 3]
        for k, (fwd, wait) in enumerate(zip(by["forward"],
                                            by["result_wait"])):
            assert fwd.t0 <= wait.t0 <= wait.t1 <= fwd.t1
            if k < 2:
                ahead = by["collate"][k + 1]
                assert fwd.t0 <= ahead.t0 <= ahead.t1 <= wait.t0
        reg = rt.metrics.registry
        assert reg.counter("serve/assembled_ahead").value == 2
        assert reg.counter("serve/staging_alloc").value == 1
        assert rt.accounting()["by_state"] == {"done": 12}
        assert rt._held is None

    def test_a_tier_whose_answer_is_on_the_host_runs_as_it_did(self):
        import time

        from analytics_zoo_tpu import obs

        log = []
        rt, _ = self._runtime(log, later=False, place=True)
        reqs = self._submit(rt, 2)
        t0 = time.monotonic()
        assert rt.pump() == 2
        assert log == [("collate", 1.0), ("forward", 1.0, "ndarray"),
                       ("collate", 2.0), ("forward", 2.0, "ndarray")]
        self._check_answers(reqs)
        names = [r.name for r in sorted(obs.stages(since=t0),
                                        key=lambda r: r.t0)
                 if r.name.startswith("az/serve/")]
        assert names == ["az/serve/pump"] + [
            "az/serve/collate", "az/serve/forward", "az/serve/handout"] * 2
        assert [r.attrs for r in obs.stages(since=t0)
                if r.name == "az/serve/collate"] == [
            {"reused": False, "ahead": False},
            {"reused": True, "ahead": False}]
        assert "serve/assembled_ahead" not in \
            rt.metrics.registry.snapshot()["counters"]
        assert rt.metrics.registry.counter("serve/staging_alloc").value == 1

    def test_the_batch_ahead_is_placed_beside_its_host_buffer(self):
        log = []
        rt, _ = self._runtime(log, place=True)
        seen = []
        dispatch = rt._dispatch

        def spy(batch):
            seen.append((type(batch.batch["input"]).__name__,
                         batch.placed is not None))
            dispatch(batch)
            assert batch.placed is None         # taken, once

        rt._dispatch = spy
        reqs = self._submit(rt, 2)
        assert rt.pump() == 2
        # the first batch of a pump goes up inside its own forward; the
        # second was placed while the first ran, and its forward got the
        # placed leaf while the batch kept the host buffer
        assert log == [
            ("collate", 1.0), ("forward", 1.0, "ndarray"),
            ("collate", 2.0), ("place", 2.0), ("fetch", 1.0),
            ("forward", 2.0, "_Placed"), ("fetch", 2.0)]
        assert seen == [("ndarray", False), ("ndarray", True)]
        self._check_answers(reqs)

    def test_warm_fetches_an_answer_that_is_not_on_the_host(self):
        log = []
        rt, _ = self._runtime(log, n_tiers=2)
        rt.warm({"input": np.full((1, 2), 7.0, np.float32)})
        assert log == [("collate", 7.0), ("forward", 7.0, "ndarray"),
                       ("fetch", 7.0)] * 2
        assert rt.metrics.registry.counter("serve/staging_alloc").value == 1

    def test_an_error_at_the_fetch_fails_over_once_and_the_held_batch_follows(
            self):
        from analytics_zoo_tpu.resilience.errors import InjectedFault

        log = []
        rt, _ = self._runtime(
            log, n_replicas=2, place=True, warm=True,
            fail={1: InjectedFault("device lost at the fetch")})
        reqs = self._submit(rt, 2)
        assert rt.pump() == 2
        # the second forward of the first batch assembles no third, and
        # gets the host buffer, whose bytes the batch ahead left alone
        assert log == [
            ("collate", 1.0), ("forward", 1.0, "ndarray"),
            ("collate", 2.0), ("place", 2.0), ("fetch", 1.0),
            ("forward", 1.0, "ndarray"), ("fetch", 1.0),
            ("forward", 2.0, "_Placed"), ("fetch", 2.0)]
        self._check_answers(reqs)
        assert [r.attempts for r in reqs] == [2] * 4 + [1] * 4
        kinds = [e["kind"] for e in rt.pool.events]
        assert kinds == ["replica_fenced", "failover"]
        assert [r.state for r in rt.pool.replicas] == ["fenced", "healthy"]
        assert rt.metrics.redispatches == 1
        assert rt.metrics.registry.counter(
            "serve/assembled_ahead").value == 1

    def test_with_no_replica_left_both_batches_end_in_a_terminal_state(self):
        from analytics_zoo_tpu.resilience.errors import InjectedFault

        log = []
        rt, _ = self._runtime(
            log, warm=True,
            fail={1: InjectedFault("device lost at the fetch")})
        reqs = self._submit(rt, 2)
        rt.drain()
        assert log == [("collate", 1.0), ("forward", 1.0, "ndarray"),
                       ("collate", 2.0), ("fetch", 1.0)]
        assert [r.state for r in reqs] == ["failed"] * 8
        assert all(isinstance(r.error, ReplicaWedged) for r in reqs)
        assert rt.accounting()["unaccounted"] == 0 and rt._held is None

    @pytest.mark.parametrize("warm,error", [
        (True, ValueError("a program error")),
        # retryable, but at a geometry's first run: the program's own
        (False, RuntimeError("first run of the program")),
    ], ids=["fatal", "first_run"])
    def test_an_exception_out_of_a_forward_leaves_the_held_batch_to_the_next_pump(
            self, warm, error):
        from analytics_zoo_tpu.resilience.errors import InjectedFault

        log = []
        if not warm:
            error = InjectedFault(str(error))
        rt, _ = self._runtime(log, warm=warm, fail={1: error})
        reqs = self._submit(rt, 2)
        with pytest.raises(type(error), match=str(error)):
            rt.pump()
        assert [r.state for r in rt.pool.replicas] == ["healthy"]
        assert [r.state for r in reqs[4:]] == ["pending"] * 4
        assert rt._held is not None and len(rt.queue) == 0
        rt.drain()
        self._check_answers(reqs[4:], first=2.0)
        assert rt._held is None

    def test_what_the_assembly_ahead_raises_the_pump_raises_after_the_answer(
            self):
        log = []
        rt, _ = self._runtime(log)
        reqs = self._submit(rt, 1)
        odd = [rt.submit({"input": np.ones(shape, np.float32)})
               for shape in [(1, 2)] * 3 + [(1, 3)]]
        with pytest.raises(ValueError, match="same shape"):
            rt.pump()
        # as without the look-ahead: the batch in flight is answered
        # first, and the next pump goes on
        self._check_answers(reqs)
        assert all(r.state == "pending" for r in odd)
        assert rt.pump() == 0 and rt._held is None

    def test_a_held_batch_rides_the_rung_of_its_assembly_and_counts_as_depth(
            self):
        log = []
        rt, _ = self._runtime(
            log, n_tiers=3, decision_every=1,
            ladder_policy=LadderPolicy(down_after=1, up_after=99,
                                       depth_high=1))
        # 9 requests: while the first batch runs the second is held and
        # ONE request is queued.  1 is no overload (depth_high is one
        # batch), 1 + 4 held is: the ladder sees the load it saw before
        reqs = self._submit(rt, 2) + [rt.submit(
            {"input": np.full((1, 2), 3.0, np.float32)})]
        assert rt.pump() == 2
        down = rt.ladder.snapshot()["transitions"]
        assert [(e["kind"], e["window"], e["queue_depth"])
                for e in down] == [("tier_down", 1, 5)]
        # the step the first batch's completion decided applies from the
        # batch AFTER the one that was already assembled
        assert [r.tier for r in reqs[:8]] == [0] * 8
        assert rt.ladder.tier == 1
        rt.drain()
        assert reqs[8].tier == 1
        assert rt.accounting()["by_state"] == {"done": 9}


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
