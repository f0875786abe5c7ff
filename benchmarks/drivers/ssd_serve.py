"""SSD serving through ``ServingRuntime``: ``ssd_serving_tiers`` as
shipped, one replica, the real clock (the benchmark's own object, so every
stamp of a request is the benchmark's), ``warm()`` before the window.

The traffic mix is a CLOSED loop: ``callers`` callers, each of which
submits its next request when its last is answered.  The runtime is
synchronous — ``pump()`` returns when every batch that was due has been
answered — so the loop is: every idle caller submits, ``pump()``, collect.
A request is timed from the benchmark's stamp just before ``submit`` to
the runtime's ``completed_t`` (read off the benchmark's clock after the
answer is a host array).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import datagen
from benchmarks.reference import ssd as ref

ANNOTATE_PUMP = "bench/pump"
ANNOTATE_SUBMIT = "bench/submit"
ANNOTATE_FORWARD = "bench/forward"


class BenchClock:
    """The clock handed to the runtime (``now``/``sleep``)."""

    def now(self) -> float:
        return time.monotonic()

    def sleep(self, seconds: float) -> None:
        time.sleep(max(0.0, seconds))


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(np.ceil(q * len(s))) - 1))]


def compare_detections(served: np.ndarray, ref_scores, ref_boxes,
                       ref_set) -> Dict[str, float]:
    """One request's answer against the reference's tables for its
    picture.  ``served`` (K, 6) rows (class, score, x1, y1, x2, y2), class
    −1 where empty; ``ref_scores`` (P, C), ``ref_boxes`` (P, 4);
    ``ref_set`` the reference's own answer as a set of (prior, class).
    Each served detection is traced back to the prior whose reference box
    lies nearest: ``box_gap`` is that distance, ``score_gap`` the distance
    of its score from the reference's score of that prior and class,
    ``set_miss`` the share of (prior, class) pairs only one side kept;
    ``score_rel`` the served scores' relative distances (for their rms)."""
    live = served[:, 0] >= 0
    if not live.any():
        return {"box_gap": 0.0, "score_gap": 0.0, "score_rel": np.zeros(0),
                "set_miss": 1.0 if ref_set else 0.0}
    boxes, cls = served[live, 2:6], served[live, 0].astype(np.int64)
    dist = np.abs(boxes[:, None, :] - ref_boxes[None, :, :]).max(-1)
    prior = dist.argmin(1)
    got_set = set(zip(prior.tolist(), cls.tolist()))
    want = ref_scores[prior, cls]
    return {"box_gap": float(dist[np.arange(len(prior)), prior].max()),
            "score_gap": float(np.abs(served[live, 1] - want).max()),
            "score_rel": (served[live, 1] - want) / np.maximum(want, 1e-6),
            "set_miss": len(got_set ^ ref_set)
            / max(1, len(got_set) + len(ref_set))}


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int, workdir: str,
                 toy: Any = None, tier: Optional[int] = None):
        """``toy`` (tests only): see ``ssd_train.Driver``.  ``tier``: pin
        the degradation ladder to that rung (the control: the program's
        own int8 path switched on)."""
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.workdir, self.toy, self.tier = workdir, toy, tier
        self.res = int(config["resolution"])
        self.classes = int(config["num_classes"])
        self.forwards: List[tuple] = []       # (t0, t1, tier) per batch
        # a fault planted by the tests: rows -> rows, applied where the
        # answer is produced
        self.sabotage = None

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        import jax

        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.models.ssd import SSDVgg
        from analytics_zoo_tpu.pipelines.ssd import (PreProcessParam,
                                                     ssd_serving_tiers)
        from analytics_zoo_tpu.serving import ServingRuntime

        cfg, mix, a = self.config, self.traffic, self.config["assumed"]
        bias = float(a["background_bias"])
        # the model and the picture set are the same in every run: how long
        # DetectionOutput takes depends on how many candidates the weights
        # put over its threshold, and with weights from --seed the cell's
        # throughput read 47 to 140 requests/s from seed to seed (PR 25).
        # --seed draws the order in which callers walk the pictures and
        # the sample that the check compares.
        wseed = int(a["weights_seed"])
        if self.toy is None:
            module = SSDVgg(num_classes=self.classes, resolution=self.res)
            self.weights = ref.make_weights(wseed, self.res, self.classes,
                                            background_bias=bias)
        else:
            module = self.toy.module
            self.weights = self.toy.weights(wseed, background_bias=bias)
        self.weights0 = jax.tree_util.tree_map(np.asarray, self.weights)
        model = Model(module, {"params": self.weights})
        self.max_batch = int(mix["max_batch"])
        tiers = ssd_serving_tiers(
            model, PreProcessParam(batch_size=self.max_batch,
                                   resolution=self.res),
            compute_dtype=cfg["compute_dtype"])
        self.tier_names = [t.name for t in tiers]
        tiers = [dataclasses.replace(t, forward=self._spanned(t.forward, i))
                 for i, t in enumerate(tiers)]
        self.runtime = ServingRuntime(
            tiers, n_replicas=1, max_batch=self.max_batch,
            queue_capacity=int(mix["queue_capacity"]),
            default_deadline_s=float(mix["deadline_s"]), clock=BenchClock())
        rng = np.random.RandomState(datagen.numpy_seed(mix["pictures_seed"]))
        means = np.asarray(ref.BGR_MEANS, np.float32)
        self.pictures = [
            datagen.render_shapes_image(rng, self.res, int(mix["max_shapes"]))
            [0].astype(np.float32) - means
            for _ in range(int(mix["pictures"]))]
        self.order = np.random.RandomState(
            datagen.numpy_seed(self.seed)).permutation(len(self.pictures))
        self.runtime.warm({"input": self.pictures[0]})
        if self.tier is not None:
            self.runtime.ladder.tier = self.tier

    def _spanned(self, forward, tier: int):
        """The benchmark's span around the call into the serve program."""
        def spanned(batch):
            import jax

            t0 = time.monotonic()
            with jax.profiler.TraceAnnotation(ANNOTATE_FORWARD):
                rows = forward(batch)
            if self.sabotage is not None:
                rows = self.sabotage(rows)
            self.forwards.append((t0, time.monotonic(), tier))
            return rows
        return spanned

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, tracer) -> Dict:
        import jax

        from analytics_zoo_tpu.resilience.errors import ServerOverloaded

        mix, rt = self.traffic, self.runtime
        callers = int(mix["callers"])
        trace_from = int(mix["trace_after_cycles"])
        trace_to = trace_from + int(mix["trace_cycles"])
        sent = [0] * callers
        pending: Dict[int, tuple] = {}
        self.done: List[tuple] = []       # (submit_t, request, picture)
        refused = cycle = 0
        t_open = time.monotonic()
        t_close = t_open + seconds
        while time.monotonic() < t_close or tracer.running:
            if cycle == trace_from:
                tracer.start()
            with jax.profiler.TraceAnnotation(ANNOTATE_SUBMIT):
                for c in range(callers):
                    if c in pending:
                        continue
                    pic = int(self.order[(c + sent[c])
                                         % len(self.order)])
                    sent[c] += 1
                    t = time.monotonic()
                    try:
                        req = rt.submit({"input": self.pictures[pic]})
                    except ServerOverloaded:
                        refused += 1
                        continue
                    pending[c] = (t, req, pic)
            with jax.profiler.TraceAnnotation(ANNOTATE_PUMP):
                rt.pump()
            for c in [c for c, (_, r, _) in pending.items() if r.finished]:
                self.done.append(pending.pop(c))
            cycle += 1
            if cycle == trace_to:
                tracer.stop()
        rt.drain()      # nothing a caller sent is left unanswered
        self.done.extend(pending.values())

        answered = [(t, r) for t, r, _ in self.done if r.state == "done"]
        inside = [r.completed_t for _, r in answered
                  if r.completed_t <= t_close]
        failed = refused + sum(1 for _, r, _ in self.done
                               if r.state != "done")
        lat = [r.completed_t - t for t, r in answered]
        snap = rt.snapshot()["metrics"]
        tiers_answered: Dict[str, int] = {}
        for _, r in answered:
            name = self.tier_names[r.tier]
            tiers_answered[name] = tiers_answered.get(name, 0) + 1
        return {
            "t_open": t_open,
            "attempted": len(self.done) + refused, "failed": failed,
            "end_to_end": {
                "serve_throughput": (len(inside) / (max(inside) - t_open)
                                     if inside else 0.0),
                "serve_latency_p95": (1e3 * percentile(lat, 0.95)
                                      if lat else 0.0)},
            "batch": self.max_batch, "resolution": self.res,
            "num_classes": self.classes,
            "batches": len(self.forwards),
            "tiers_answered": tiers_answered,
            "latency_p50_ms": 1e3 * percentile(lat, 0.5) if lat else None,
            "counters": {"mean_batch_fill": snap["mean_batch_fill"],
                         "batches": snap["batches"],
                         "shed_total": snap["shed_total"],
                         "failed": snap["failed"]},
        }

    def free(self) -> None:
        """Drop the program's state before the reference runs."""
        self.runtime = None

    # -- correct -----------------------------------------------------------
    def sample(self) -> List[tuple]:
        """The answered requests to compare, drawn from the seed."""
        answered = [(r, pic) for _, r, pic in self.done if r.state == "done"]
        rng = np.random.RandomState(datagen.numpy_seed(self.seed + 1))
        n = min(len(answered), int(self.traffic["check_requests"]))
        return [answered[i] for i in
                rng.choice(len(answered), size=n, replace=False)]

    def reference_tables(self, pictures: List[int], mode: str = "f32",
                         nms_thresh: Optional[float] = None):
        """{picture: (scores (P,C), boxes (P,4), answer set, answer)} in
        blocks of ``reference_block`` pictures; ``nms_thresh`` overrides
        the configuration's (the control that leaves suppression out)."""
        import jax

        net = (self.toy.net if self.toy is not None
               else ref.vgg_net(self.res, self.classes))
        params = jax.tree_util.tree_map(jax.numpy.asarray, self.weights0)
        post = self.config["post"]

        @jax.jit
        def block(p, x):
            scores, boxes = ref.scores_and_boxes(p, x, self.res, net, mode)
            idx, cls, best = jax.vmap(lambda s, b: ref.detection_output(
                s, b, post["conf_thresh"],
                post["nms_thresh"] if nms_thresh is None else nms_thresh,
                post["nms_topk"], post["keep_topk"]))(scores, boxes)
            return scores, boxes, idx, cls, best

        out, step = {}, int(self.traffic["reference_block"])
        for lo in range(0, len(pictures), step):
            chunk = pictures[lo:lo + step]
            pad = chunk + [chunk[-1]] * (step - len(chunk))
            res = jax.tree_util.tree_map(np.asarray, block(
                params, np.stack([self.pictures[i] for i in pad])))
            for j, pic in enumerate(chunk):
                scores, boxes, idx, cls, best = (t[j] for t in res)
                keep = cls >= 0
                out[pic] = (scores, boxes,
                            set(zip(idx[keep].tolist(), cls[keep].tolist())),
                            (idx, cls, best))
        return out

    def readings(self, answers: List[tuple], tables: Dict) -> Dict[str, float]:
        """The numbers compared over ``answers`` [(rows (K,6), picture)]:
        the widest box and score gaps, the mean share of the answer sets
        that differs."""
        per = [compare_detections(np.asarray(rows, np.float32),
                                  *tables[pic][:3]) for rows, pic in answers]
        rel = np.concatenate([p["score_rel"] for p in per])
        return {"box_gap": max(p["box_gap"] for p in per),
                "score_gap": max(p["score_gap"] for p in per),
                "score_rel_rms": float(np.sqrt(np.mean(rel ** 2)))
                if len(rel) else 0.0,
                "set_miss": float(np.mean([p["set_miss"] for p in per]))}

    def control_answers(self, tables: Dict, mode: str = "int8",
                        nms_thresh: Optional[float] = None) -> List[tuple]:
        """The reference put in the program's place, one precision down or
        with the suppression left out: its own answers for the same
        pictures, as (K, 6) rows."""
        low = self.reference_tables(sorted(tables), mode, nms_thresh)
        out = []
        for pic, (_, boxes, _, (idx, cls, best)) in low.items():
            rows = np.concatenate(
                [cls[:, None].astype(np.float32), best[:, None],
                 np.where((cls >= 0)[:, None], boxes[np.maximum(idx, 0)],
                          0.0)], 1)
            out.append((rows, pic))
        return out

    def check(self) -> Dict[str, Dict[str, float]]:
        sample = self.sample()
        self.tables = self.reference_tables(sorted({p for _, p in sample}))
        self.numbers = self.readings([(r.result, p) for r, p in sample],
                                     self.tables)
        return {k: {"value": self.numbers[k], "limit": float(limit)}
                for k, limit in self.traffic["limits"].items()}

    def control_readings(self) -> Dict[str, Dict[str, float]]:
        """After ``check()``: the same numbers for the reference put in
        the program's place, in the precision the configuration states (a
        second witness of the lower reading), one precision down (the
        control, which has to fail), and in float32 with the per-class
        suppression left out (a guarantee of the configuration broken:
        precision hardly moves which detections are kept, this does)."""
        return {
            "reference_bf16": self.readings(
                self.control_answers(self.tables, "bf16"), self.tables),
            "control_int8": self.readings(
                self.control_answers(self.tables, "int8"), self.tables),
            "control_no_nms": self.readings(
                self.control_answers(self.tables, "f32", nms_thresh=2.0),
                self.tables)}
