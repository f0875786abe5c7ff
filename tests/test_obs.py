"""Telemetry spine (analytics_zoo_tpu.obs): registry, recorder, spans,
exporters, probe, and the end-to-end wiring into serving + training.

Everything deterministic: virtual clocks, seeded reservoirs, counted
span ids — the same properties the committed ``OBS_r01.json`` flight
recording pins at drill scale.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

from analytics_zoo_tpu.obs import (FlightRecorder, MetricRegistry,
                                   Observability, Tracer,
                                   render_prometheus, run_metadata,
                                   span_conservation)
from analytics_zoo_tpu.obs.registry import ReservoirHistogram, nearest_rank
from analytics_zoo_tpu.utils.clock import (MonotonicClock, VirtualClock,
                                           as_now_fn)


class TestRegistry:
    def test_counter_gauge_histogram_snapshot_schema(self):
        r = MetricRegistry()
        r.counter("a/n").inc(3)
        r.gauge("b/depth").set(7)
        h = r.histogram("c/lat_s")
        for v in (0.1, 0.3, 0.2):
            h.observe(v)
        snap = r.snapshot()
        assert set(snap) == {"counters", "gauges", "histograms"}
        assert snap["counters"] == {"a/n": 3}
        assert snap["gauges"] == {"b/depth": 7.0}
        hs = snap["histograms"]["c/lat_s"]
        assert hs["count"] == 3 and hs["min"] == 0.1 and hs["max"] == 0.3
        assert hs["p50"] == 0.2 and hs["sampled"] is False

    def test_get_or_create_is_idempotent_but_type_mismatch_raises(self):
        r = MetricRegistry()
        assert r.counter("x") is r.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            r.gauge("x")

    def test_histogram_bound_conflict_raises(self):
        r = MetricRegistry()
        r.histogram("h", max_samples=64)
        assert r.histogram("h", max_samples=64).max_samples == 64
        with pytest.raises(ValueError, match="max_samples=64"):
            r.histogram("h", max_samples=128)

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            MetricRegistry().counter("x").inc(-1)

    def test_reservoir_bounded_and_exact_below_capacity(self):
        h = ReservoirHistogram("h", max_samples=8)
        for v in range(6):
            h.observe(float(v))
        # below capacity: the reservoir IS the stream, percentiles exact
        assert sorted(h.samples) == [0, 1, 2, 3, 4, 5]
        assert h.percentile(50) == 2.0 and not h.saturated
        for v in range(6, 10_000):
            h.observe(float(v))
        # bounded memory, exact moments
        assert len(h.samples) == 8 and h.saturated
        assert h.count == 10_000 and h.max == 9999.0 and h.min == 0.0

    def test_reservoir_deterministic_from_name_seed(self):
        def run():
            h = ReservoirHistogram("same-name", max_samples=16)
            for v in range(1000):
                h.observe(float(v % 97))
            return h.snapshot()

        assert run() == run()

    def test_nearest_rank_matches_reference_formula(self):
        xs = sorted([5.0, 1.0, 9.0, 3.0, 7.0])
        assert nearest_rank(xs, 50) == 5.0
        assert nearest_rank(xs, 99) == 9.0
        assert nearest_rank(xs, 0) == 1.0
        assert nearest_rank([], 50) is None


class TestFlightRecorder:
    def test_ring_bound_and_dropped_count(self):
        rec = FlightRecorder(capacity=4, clock=VirtualClock())
        for i in range(7):
            rec.note("tick", i=i)
        assert len(rec) == 4 and rec.dropped == 3
        # oldest evicted, seq monotone
        assert [e["i"] for e in rec.events()] == [3, 4, 5, 6]
        assert [e["seq"] for e in rec.events()] == [3, 4, 5, 6]

    def test_dump_writes_deterministic_jsonl(self, tmp_path):
        clock = VirtualClock()
        rec = FlightRecorder(capacity=8, clock=clock,
                             dump_path=str(tmp_path / "box.jsonl"))
        rec.note("a", x=1)
        clock.advance(0.5)
        rec.note("b", y=[1, 2])
        text = rec.dump("test_reason")
        assert (tmp_path / "box.jsonl").read_text() == text
        lines = [json.loads(ln) for ln in text.splitlines()]
        assert [e["kind"] for e in lines] == ["a", "b"]
        assert lines[1]["t"] == 0.5
        assert rec.dumps[0]["reason"] == "test_reason"
        # sorted keys => byte-stable serialization
        assert text == "".join(json.dumps(e, sort_keys=True) + "\n"
                               for e in lines)


class TestSpans:
    def test_parenting_and_conservation(self):
        clock = VirtualClock()
        rec = FlightRecorder(clock=clock)
        t = Tracer(clock=clock, recorder=rec)
        root = t.start("request", "req-1", rid=1)
        clock.advance(0.1)
        child = t.start("queue", "req-1", parent=root)
        clock.advance(0.2)
        child.end(status="assembled")
        root.end(status="done")
        cons = span_conservation(rec.events())
        assert cons["ok"] and cons["traces"] == 1 and cons["spans"] == 2
        assert cons["roots_by_status"] == {"done": 1}

    def test_cross_trace_parent_rejected(self):
        t = Tracer(clock=VirtualClock())
        a = t.start("x", "req-1")
        with pytest.raises(ValueError, match="belongs to trace"):
            t.start("y", "req-2", parent=a)

    def test_end_idempotent_first_writer_wins(self):
        rec = FlightRecorder(clock=VirtualClock())
        t = Tracer(clock=VirtualClock(), recorder=rec)
        s = t.start("x", "req-0")
        s.end(status="done")
        s.end(status="failed")      # no-op
        evs = rec.events("span")
        assert len(evs) == 1 and evs[0]["status"] == "done"

    def test_context_manager_marks_errors(self):
        rec = FlightRecorder(clock=VirtualClock())
        t = Tracer(clock=VirtualClock(), recorder=rec)
        with pytest.raises(RuntimeError):
            with t.span("boom", "req-0"):
                raise RuntimeError("kaput")
        ev = rec.events("span")[0]
        assert ev["status"] == "error"
        assert "RuntimeError" in ev["attrs"]["error"]

    def test_conservation_flags_orphans_and_unended(self):
        rec = FlightRecorder(clock=VirtualClock())
        t = Tracer(clock=VirtualClock(), recorder=rec)
        s = t.start("child", "req-5", )
        s.parent_id = 999           # orphan: parent not in trace
        s.end()
        cons = span_conservation(rec.events())
        assert not cons["ok"] and "0 roots" in cons["violations"][0]


class TestExporters:
    def test_prometheus_rendering(self):
        r = MetricRegistry()
        r.counter("serve/shed/cause=deadline").inc(2)
        r.gauge("queue/depth").set(3)
        h = r.histogram("serve/latency_s/tier=0")
        for v in (0.1, 0.2):
            h.observe(v)
        text = render_prometheus(r)
        assert 'serve_shed_total{cause="deadline"} 2' in text
        assert "queue_depth 3.0" in text
        assert 'serve_latency_s{tier="0",quantile="0.5"}' in text
        assert 'serve_latency_s_count{tier="0"} 2' in text

    def test_summary_bridge_respects_trigger_gating(self):
        from analytics_zoo_tpu.obs import SummaryBridge
        from analytics_zoo_tpu.parallel import Trigger
        from analytics_zoo_tpu.parallel.summary import TrainSummary

        class FakeWriter:
            def __init__(self):
                self.scalars = []

            def add_scalar(self, tag, value, it):
                self.scalars.append((tag, float(value), it))

        summary = TrainSummary("unused", "app")
        summary._writer = FakeWriter()
        summary.set_summary_trigger("train/steps",
                                    Trigger.several_iteration(10))
        r = MetricRegistry()
        r.counter("train/steps").inc(5)
        r.gauge("lr").set(0.1)
        bridge = SummaryBridge(summary)
        bridge.export(r, iteration=3)    # gated tag withheld
        tags = [t for t, _, _ in summary._writer.scalars]
        assert "lr" in tags and "train/steps" not in tags
        bridge.export(r, iteration=10)   # trigger fires
        tags = [t for t, _, _ in summary._writer.scalars]
        assert "train/steps" in tags


class TestReadStatsPublish:
    def test_publishes_gauges_idempotently(self):
        from analytics_zoo_tpu.data.records import ReadStats

        reg = MetricRegistry()
        stats = ReadStats(records=10, retries=2, skipped_records=1)
        stats.publish(reg)
        stats.publish(reg)      # repeat must not double count (gauges)
        g = reg.snapshot()["gauges"]
        assert g == {"data/read/records": 10.0, "data/read/retries": 2.0,
                     "data/read/skipped_records": 1.0,
                     "data/read/skipped_shards": 0.0}

    def test_shard_read_drill_carries_registry_snapshot(self, tmp_path):
        import random

        from tools.chaos_drill import shard_read_drill

        out = shard_read_drill(str(tmp_path), random.Random(0))
        assert out["survived"] is True
        g = out["registry"]["gauges"]
        assert g["data/read/retries"] == out["retries"]
        assert g["data/read/skipped_records"] == out["skipped_records"]


class TestRunMetadata:
    def test_required_keys_present(self):
        from analytics_zoo_tpu.obs.runmeta import REQUIRED_KEYS

        meta = run_metadata("test_tool", seed=7, extra={"smoke": True})
        for k in REQUIRED_KEYS:
            assert k in meta
        assert meta["tool"] == "test_tool" and meta["seed"] == 7
        assert meta["smoke"] is True
        assert meta["backend"] == "cpu"


class TestObservabilityBundle:
    def test_adopt_clock_follows_runtime_unless_pinned(self):
        obs = Observability()
        vc = VirtualClock(start=5.0)
        obs.adopt_clock(vc)
        assert obs.tracer.now() == 5.0 and obs.recorder.now() == 5.0
        pinned = Observability(clock=VirtualClock(start=1.0))
        pinned.adopt_clock(vc)
        assert pinned.tracer.now() == 1.0    # explicit clock wins

    def test_clock_normalization_helpers(self):
        assert as_now_fn(None)() <= MonotonicClock().now()
        vc = VirtualClock(start=2.0)
        assert as_now_fn(vc)() == 2.0
        assert as_now_fn(lambda: 9.0)() == 9.0
        # serving.clock keeps re-exporting the moved classes
        from analytics_zoo_tpu.serving.clock import VirtualClock as VC2
        assert VC2 is VirtualClock


class TestServingIntegration:
    def _runtime(self, clock, obs, chaos=None, n_replicas=2):
        from analytics_zoo_tpu.serving import ServingRuntime, ServingTier

        def fwd(batch):
            x = batch["input"]
            return x.reshape(x.shape[0], -1).sum(axis=1)

        return ServingRuntime(
            [ServingTier("fp", fwd)], n_replicas=n_replicas, clock=clock,
            queue_capacity=8, max_batch=2, default_deadline_s=0.5,
            wedge_timeout_s=5.0, service_time=lambda e, n, t: 0.05,
            chaos=chaos, obs=obs)

    def test_request_traces_reconcile_with_accounting(self):
        clock = VirtualClock()
        obs = Observability(capacity=512)
        rt = self._runtime(clock, obs)
        for i in range(9):
            try:
                rt.submit({"input": np.ones((1, 2), np.float32)})
            except Exception:
                pass
            clock.advance(0.02 if i % 3 else 0.4)
            rt.pump()
        clock.advance(2.0)
        rt.drain()
        acct = rt.accounting()
        cons = span_conservation(obs.recorder.events())
        assert cons["ok"], cons["violations"]
        assert cons["traces"] == acct["submitted"]
        assert cons["roots_by_status"] == acct["by_state"]
        # metrics landed in the SAME registry the spans' runtime owns
        assert "serve/submitted" in obs.registry
        assert obs.registry.counter("serve/submitted").value == \
            acct["submitted"]

    def test_replica_fence_trips_black_box_dump(self, tmp_path):
        from analytics_zoo_tpu.resilience.chaos import ChaosMonkey, FaultSpec

        clock = VirtualClock()
        box = str(tmp_path / "flight.jsonl")
        obs = Observability(capacity=512, dump_path=box)
        monkey = ChaosMonkey([FaultSpec("replica_crash", 1,
                                        detail={"replica": 0})])
        rt = self._runtime(clock, obs, chaos=monkey)
        for i in range(8):
            rt.submit({"input": np.ones((1, 2), np.float32)})
            clock.advance(0.2)
            rt.pump()
        rt.drain()
        assert rt.accounting()["by_state"] == {"done": 8}
        # the fence event is in the ring AND tripped a dump to the box
        assert obs.recorder.events("replica_fenced")
        assert any(d["reason"] == "replica_fenced"
                   for d in obs.recorder.dumps)
        dumped = [json.loads(ln) for ln in
                  open(box).read().splitlines()]
        assert any(e.get("kind") == "replica_fenced" for e in dumped)


class TestTrainingIntegration:
    def _fit(self, obs, n_batches=4, epochs=2, ckpt=None, nan_batch=None,
             anomaly=None):
        import jax.numpy as jnp
        from flax import linen as nn

        from analytics_zoo_tpu.core.criterion import MSECriterion
        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger

        rng = np.random.RandomState(0)
        X = rng.randn(8 * n_batches, 4).astype(np.float32)
        W = rng.randn(4, 1).astype(np.float32)
        data = []
        for i in range(n_batches):
            x = X[i * 8:(i + 1) * 8].copy()
            if i == nan_batch:
                x[0, 0] = np.nan
            data.append({"input": x, "target": X[i * 8:(i + 1) * 8] @ W})
        m = Model(nn.Dense(1))
        m.build(0, jnp.zeros((1, 4), jnp.float32))
        opt = (Optimizer(m, data, MSECriterion())
               .set_optim_method(SGD(0.05))
               .set_observability(obs)
               .set_end_when(Trigger.max_epoch(epochs)))
        if ckpt:
            opt.set_checkpoint(ckpt, Trigger.every_epoch())
        if anomaly is not None:
            opt.set_anomaly_policy(anomaly)
        opt.optimize()
        return opt

    def test_step_and_checkpoint_spans_with_loader_coordinates(
            self, tmp_path):
        obs = Observability(capacity=512)
        self._fit(obs, ckpt=str(tmp_path / "ck"))
        spans = obs.recorder.events("span")
        steps = [s for s in spans if s["name"] == "train_step"]
        saves = [s for s in spans if s["name"] == "checkpoint_save"]
        assert len(steps) == 8 and len(saves) == 2
        # trace ids ARE the loader coordinates
        assert steps[0]["trace"] == "train-e0-b0"
        assert steps[-1]["trace"] == "train-e1-b3"
        assert all(s["status"] == "ok" for s in steps)
        snap = obs.registry.snapshot()
        assert snap["counters"]["train/dispatch/steps"] == 8
        assert snap["counters"]["train/dispatch/records"] == 64
        assert snap["histograms"]["train/dispatch/step_s"]["count"] == 8
        assert snap["histograms"]["checkpoint/save_s"]["count"] == 2

    def test_step_span_closed_when_train_step_raises(self):
        """An exception escaping the step call must still close the
        span — the crashed step is the event the black box exists to
        capture."""
        import jax.numpy as jnp
        from flax import linen as nn

        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger

        def bad_criterion(output, batch):
            raise ValueError("boom in criterion")

        obs = Observability(capacity=64)
        m = Model(nn.Dense(1))
        m.build(0, jnp.zeros((1, 4), jnp.float32))
        data = [{"input": np.ones((8, 4), np.float32),
                 "target": np.ones((8, 1), np.float32)}]
        opt = (Optimizer(m, data, bad_criterion)
               .set_optim_method(SGD(0.05))
               .set_observability(obs)
               .set_end_when(Trigger.max_epoch(1)))
        with pytest.raises(ValueError, match="boom"):
            opt.optimize()
        steps = [s for s in obs.recorder.events("span")
                 if s["name"] == "train_step"]
        assert len(steps) == 1 and steps[0]["status"] == "error"
        assert "ValueError" in steps[0]["attrs"]["error"]

    def test_failure_detector_divergence_dumps_black_box(self, tmp_path):
        """The black-box contract covers BOTH divergence paths: the
        legacy DivergenceDetector raise must dump the ring just like
        the anomaly ladder's."""
        from analytics_zoo_tpu.parallel.elastic import DivergenceDetector
        from analytics_zoo_tpu.resilience.errors import TrainingDiverged

        box = str(tmp_path / "flight.jsonl")
        obs = Observability(capacity=256, dump_path=box)
        import jax.numpy as jnp
        from flax import linen as nn

        from analytics_zoo_tpu.core.criterion import MSECriterion
        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger

        x = np.ones((8, 4), np.float32)
        data = [{"input": x, "target": np.full((8, 1), np.nan, np.float32)}]
        m = Model(nn.Dense(1))
        m.build(0, jnp.zeros((1, 4), jnp.float32))
        opt = (Optimizer(m, data * 4, MSECriterion())
               .set_optim_method(SGD(0.05))
               .set_observability(obs)
               .set_failure_detector(DivergenceDetector(check_every=1,
                                                        max_bad_checks=2))
               .set_end_when(Trigger.max_epoch(3)))
        with pytest.raises(TrainingDiverged):
            opt.optimize()
        assert any(d["reason"] == "training_diverged"
                   for d in obs.recorder.dumps)
        assert os.path.exists(box)
        assert obs.recorder.events("training_diverged")

    def test_unhealthy_step_named_in_trace_and_counted(self, tmp_path):
        from analytics_zoo_tpu.resilience.anomaly import AnomalyPolicy

        obs = Observability(capacity=512)
        self._fit(obs, epochs=1, nan_batch=1,
                  anomaly=AnomalyPolicy(rollback_after=100,
                                        promote_initial=False,
                                        forensics_dir=str(tmp_path)))
        bad = [s for s in obs.recorder.events("span")
               if s["name"] == "train_step" and s["status"] == "unhealthy"]
        assert len(bad) == 1 and bad[0]["trace"] == "train-e0-b1"
        assert bad[0]["attrs"]["action"] == "skipped"
        assert obs.registry.counter("train/anomaly/bad_steps").value == 1


class TestPrometheusEdgeCases:
    """render_prometheus must survive the exposition format's sharp
    edges: label escaping, lossy name sanitization, empty reservoirs."""

    def test_label_values_needing_escaping(self):
        reg = MetricRegistry()
        reg.counter('serve/shed/cause=say "no" to back\\slash').inc(2)
        text = render_prometheus(reg)
        # prometheus text format: \\ then \" inside the quoted value
        assert 'cause="say \\"no\\" to back\\\\slash"' in text
        assert text.count("# TYPE serve_shed_total counter") == 1

    def test_newline_in_label_value_escaped(self):
        reg = MetricRegistry()
        reg.counter("serve/shed/cause=two\nlines").inc()
        text = render_prometheus(reg)
        assert 'cause="two\\nlines"' in text
        # the rendered exposition must stay one sample per line
        lines = [ln for ln in text.splitlines() if "cause=" in ln]
        assert len(lines) == 1

    def test_sanitization_collision_must_not_silently_merge(self):
        """Two registry names that sanitize to the same Prometheus
        name (`-` and `_` both become `_`) are an error, not a silent
        double-sample the scrape side would merge."""
        reg = MetricRegistry()
        reg.counter("serve/lat-s").inc()
        reg.counter("serve/lat_s").inc()
        with pytest.raises(ValueError, match="collision"):
            render_prometheus(reg)

    def test_label_variants_of_one_family_do_not_collide(self):
        reg = MetricRegistry()
        reg.histogram("serve/latency_s/tier=0").observe(0.1)
        reg.histogram("serve/latency_s/tier=1").observe(0.2)
        text = render_prometheus(reg)
        assert text.count("# TYPE serve_latency_s summary") == 1
        assert 'tier="0"' in text and 'tier="1"' in text

    def test_empty_reservoir_histogram_renders_nan_quantiles(self):
        reg = MetricRegistry()
        reg.histogram("train/dispatch/step_s")     # never observed
        text = render_prometheus(reg)
        assert 'quantile="0.5"} NaN' in text
        assert 'quantile="0.99"} NaN' in text
        assert "train_dispatch_step_s_count 0" in text
        assert "train_dispatch_step_s_sum 0.0" in text


class TestMetricCatalog:
    """obs/names.py is the one declaration of the registry namespace:
    the docs table pins against it, and every name the live subsystems
    register resolves in it."""

    def _doc_names(self):
        import re

        path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "docs", "OBSERVABILITY.md")
        with open(path, encoding="utf-8") as f:
            doc = f.read()
        names = set()
        for line in doc.splitlines():
            if not line.lstrip().startswith("|"):
                continue
            for tok in re.findall(r"`([^`]+)`", line):
                if "/" in tok and " " not in tok \
                        and not tok.endswith((".py", ".md")):
                    names.add(tok)
        return names

    def test_docs_names_table_matches_the_catalog_exactly(self):
        from analytics_zoo_tpu.obs.names import CATALOG, SCOPES, STAGES

        doc = self._doc_names()
        cat = set(CATALOG) | set(STAGES) | set(SCOPES)
        assert doc - cat == set(), \
            f"documented but undeclared: {sorted(doc - cat)}"
        assert cat - doc == set(), \
            f"declared but undocumented: {sorted(cat - doc)}"

    def test_catalog_entries_are_well_formed(self):
        import re

        from analytics_zoo_tpu.obs.names import CATALOG

        for name, doc in CATALOG.items():
            assert re.fullmatch(r"[a-z][a-z0-9_/=*.-]*", name), name
            assert "/" in name, f"{name}: no subsystem prefix"
            kind = doc.split("·")[0].strip()
            assert kind in ("counter", "gauge", "histogram"), (name, doc)

    def test_live_serving_and_slo_names_resolve_in_catalog(self):
        from analytics_zoo_tpu.obs.names import lookup
        from analytics_zoo_tpu.obs.slo import SloEvaluator, shed_rate_slo
        from analytics_zoo_tpu.serving.metrics import ServingMetrics

        reg = MetricRegistry()
        m = ServingMetrics(registry=reg)
        m.on_submit()
        m.on_shed("deadline")
        m.on_complete(0.1, tier=1, missed=True)
        m.on_fail()
        m.on_batch(2, 4, 1)
        m.redispatches = 1
        ev = SloEvaluator([shed_rate_slo(0.1)], fast_window_s=1,
                          slow_window_s=10, registry=reg)
        ev.observe(reg.snapshot(), t=0.0)
        ev.decide(t=0.0)
        for name in reg.metrics():
            assert lookup(name), f"unregistered metric name: {name}"

    def test_lookup_covers_exact_and_family_names(self):
        from analytics_zoo_tpu.obs.names import lookup

        assert lookup("serve/submitted")
        assert lookup("serve/shed/cause=queue_full")      # family
        assert not lookup("serve/submittedx")
        assert not lookup("made/up")


class TestPrometheusSuffixCollisions:
    def test_counter_total_suffix_collision_with_gauge_raises(self):
        """Review fix: collisions are checked on EMITTED series names —
        counter 'train/steps' renders train_steps_total, which a gauge
        named 'train/steps_total' would silently duplicate."""
        reg = MetricRegistry()
        reg.counter("train/steps").inc()
        reg.gauge("train/steps_total").set(1)
        with pytest.raises(ValueError, match="collision"):
            render_prometheus(reg)

    def test_histogram_sum_suffix_collision_raises(self):
        reg = MetricRegistry()
        reg.histogram("x/y").observe(1.0)
        reg.gauge("x/y_sum").set(2)
        with pytest.raises(ValueError, match="collision"):
            render_prometheus(reg)

    def test_distinct_suffixed_names_still_render(self):
        reg = MetricRegistry()
        reg.counter("train/steps").inc()
        reg.gauge("train/steps_now").set(1)
        text = render_prometheus(reg)
        assert "train_steps_total 1" in text
        assert "train_steps_now 1.0" in text
