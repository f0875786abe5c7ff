"""``obs.stage``: the always-on host stages — the primitive itself, and
what the prefetch thread, the train loop and the forked loader leave in
the ring (the serve side, which needs the toy SSD, is in
``tests/benchmark/test_stages_cpu.py``)."""

import ast
import collections
import inspect
import threading
import time

import numpy as np
import pytest

from analytics_zoo_tpu import obs
from analytics_zoo_tpu.obs import names, span


def since(t0):
    return collections.Counter(r.name for r in obs.stages(since=t0))


class TestStage:
    def test_nests_and_records_in_closing_order(self):
        t0 = time.monotonic()
        with obs.stage("az/test/outer", k=1):
            with obs.stage("az/test/inner"):
                time.sleep(0.002)
        inner, outer = obs.stages(since=t0)[-2:]
        assert (inner.name, outer.name) == ("az/test/inner", "az/test/outer")
        assert outer.t0 <= inner.t0 < inner.t1 <= outer.t1
        assert inner.t1 - inner.t0 >= 0.002
        assert inner.thread == outer.thread == threading.get_ident()
        assert outer.attrs == {"k": 1} and inner.attrs == {}

    def test_records_on_an_exception_and_lets_it_through(self):
        t0 = time.monotonic()
        with pytest.raises(ZeroDivisionError):
            with obs.stage("az/test/raises"):
                1 / 0
        assert since(t0)["az/test/raises"] == 1

    def test_since_cuts_to_the_stages_that_began_after_it(self):
        with obs.stage("az/test/before"):
            cut = time.monotonic()
        with obs.stage("az/test/after"):
            pass
        got = since(cut)
        assert got["az/test/after"] == 1 and "az/test/before" not in got
        assert any(r.name == "az/test/before" for r in obs.stages())

    def test_each_thread_nests_on_its_own(self):
        t0 = time.monotonic()
        inside = threading.Barrier(2, timeout=10)

        def work():
            with obs.stage("az/test/thread"):
                inside.wait()           # both stages are open at once

        threads = [threading.Thread(target=work) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        got = [r for r in obs.stages(since=t0) if r.name == "az/test/thread"]
        assert len({r.thread for r in got}) == 2
        assert max(r.t0 for r in got) < min(r.t1 for r in got)

    def test_ring_is_bounded(self):
        assert span._STAGES.maxlen == 1 << 16
        for _ in range(span._STAGES.maxlen + 10):
            span.record_stage("az/test/fill", 0.0, 0.0)
        assert len(obs.stages()) == span._STAGES.maxlen

    def test_every_stage_in_the_program_is_declared(self):
        """The names the program opens (``stage``) or writes
        (``record_stage``) are the names ``obs/names.py`` declares."""
        import analytics_zoo_tpu
        import os
        import re

        root = os.path.dirname(analytics_zoo_tpu.__file__)
        used = set()
        for folder, _, files in os.walk(root):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(folder, f)) as fh:
                        used |= set(re.findall(
                            r"stage\(\s*\"(az/[a-z0-9_/]+)\"", fh.read()))
        assert used == set(names.STAGES)


class TestTrainLoopStages:
    def run(self, summary, steps=5):
        import jax.numpy as jnp
        from flax import linen as nn

        from analytics_zoo_tpu.core.criterion import MSECriterion
        from analytics_zoo_tpu.core.module import Model
        from analytics_zoo_tpu.parallel import SGD, Optimizer, Trigger

        rng = np.random.RandomState(0)
        data = [{"input": rng.randn(8, 4).astype(np.float32),
                 "target": rng.randn(8, 1).astype(np.float32)}
                for _ in range(steps)]
        m = Model(nn.Dense(1))
        m.build(0, jnp.zeros((1, 4), jnp.float32))
        opt = (Optimizer(m, data, MSECriterion(), prefetch=2)
               .set_optim_method(SGD(0.05))
               .set_end_when(Trigger.max_epoch(1)))
        if summary is not None:
            opt.set_train_summary(summary)
        t0 = time.monotonic()
        opt.optimize()
        return obs.stages(since=t0)

    def test_one_of_each_a_step_and_no_summary_without_one(self):
        records = self.run(None)
        got = collections.Counter(r.name for r in records)
        # the epoch's end costs the producer one more next() and the
        # consumer one more get (the stop sentinel)
        assert got["az/input/next"] == got["az/input/get_wait"] == 5 + 1
        assert got["az/input/place"] == got["az/input/put_wait"] == 5
        assert got["az/train/dispatch"] == got["az/train/boundary"] == 5
        assert got["az/train/prepare"] == 5
        assert "az/train/summary" not in got
        by = {}
        for r in records:
            by.setdefault(r.name, set()).add(r.thread)
        main = threading.get_ident()
        assert by["az/train/dispatch"] == by["az/input/get_wait"] == {main}
        assert by["az/input/next"] == by["az/input/place"] != {main}

    def test_summary_stage_only_with_a_summary(self, tmp_path):
        from analytics_zoo_tpu.parallel.summary import TrainSummary

        summary = TrainSummary(str(tmp_path), "t")
        try:
            got = collections.Counter(r.name for r in self.run(summary))
        finally:
            summary.close()
        assert got["az/train/summary"] == got["az/train/dispatch"] == 5

    def test_loop_stages_tile_the_step(self):
        """get_wait, prepare, dispatch, boundary follow one another on
        the main thread with nothing of the loop between them."""
        records = [r for r in self.run(None, steps=8)
                   if r.thread == threading.get_ident()]
        covered = sum(r.t1 - r.t0 for r in records)
        assert covered >= 0.9 * (records[-1].t1 - records[0].t0)


class TestLoaderWorkerCounters:
    def loader(self, n=24, workers=2):
        from analytics_zoo_tpu.data import (DataSet, FnTransformer,
                                            ParallelLoader)

        ds = DataSet.from_arrays(
            x=np.arange(n * 4, dtype=np.float32).reshape(n, 4))

        def fn(s):
            time.sleep(0.002)
            return {"x": s["x"] * 2}

        return ParallelLoader(ds.transform(FnTransformer(fn)).batch(4),
                              workers)

    def test_a_pool_once_an_epoch_start_and_a_worker_record_an_epoch(self):
        """Three epochs from one pool: ``az/input/pool_start`` once,
        ``az/input/epoch_start`` once an epoch with ``kept`` false then
        true, ``az/input/worker`` once a worker and epoch, written as
        the parent reads that worker's end marker."""
        loader = self.loader()
        t0 = time.monotonic()
        batches = [list(loader) for _ in range(3)]
        records = obs.stages(since=t0)
        workers = [r for r in records if r.name == "az/input/worker"]
        assert (sorted((r.attrs["epoch"], r.attrs["worker"]) for r in workers)
                == [(e, w) for e in range(3) for w in range(2)])
        for e in range(3):
            assert (sum(r.attrs["groups"] for r in workers
                        if r.attrs["epoch"] == e) == len(batches[e]) == 6)
        for r in workers:
            a = r.attrs
            # 12 samples of its own at 2 ms each
            assert a["chain_s"] >= 12 * 0.002
            assert a["put_s"] > 0 and a["walk_s"] > 0 and a["spills"] == 0
            assert a["chain_s"] + a["put_s"] + a["walk_s"] <= r.t1 - r.t0
        starts = [r for r in records if r.name == "az/input/pool_start"]
        assert len(starts) == 1 and starts[0].attrs == {"workers": 2}
        assert starts[0].t0 <= min(r.t0 for r in workers)
        epochs = [r for r in records if r.name == "az/input/epoch_start"]
        assert [r.attrs for r in epochs] == [{"kept": False}, {"kept": True},
                                            {"kept": True}]
        # the fork is inside the epoch's start that needed it
        assert epochs[0].t0 <= starts[0].t0 < starts[0].t1 <= epochs[0].t1
        assert epochs[0].thread == starts[0].thread

    def test_a_stopped_pool_records_each_workers_unfinished_epoch(self):
        """A window that closes no pool has the records of the epochs
        that ended in it; the pool's stop adds one a worker for the epoch
        it was in, up to the stop."""
        from analytics_zoo_tpu.data import parallel

        loader = self.loader()
        assert len(list(loader)) == 6
        # a kept worker runs ahead until its ring is full: epoch 1's three
        # groups and end marker take the four slots, so it stands in
        # epoch 2 with a group decoded that it cannot put
        deadline = time.monotonic() + 10
        while (any(r.counters[parallel._CHAIN_S] < 4 * 0.002
                   or r.counters[parallel._EPOCH] < 2
                   for r in loader._pool.rings)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        t0 = time.monotonic()
        loader.close()
        workers = [r for r in obs.stages(since=t0 - 60)
                   if r.name == "az/input/worker"][-2:]
        assert sorted(r.attrs["worker"] for r in workers) == [0, 1]
        for r in workers:
            assert (r.attrs["epoch"], r.attrs["groups"]) == (2, 0)
            assert r.attrs["chain_s"] >= 4 * 0.002 and r.t1 >= t0 > r.t0

    def test_an_epoch_closed_early_forks_the_next_ones_pool(self):
        loader = self.loader()
        t0 = time.monotonic()
        it = iter(loader)
        next(it)
        it.close()
        assert len(list(loader)) == 6 and len(list(loader)) == 6
        got = [(r.name, r.attrs.get("kept")) for r in obs.stages(since=t0)
               if r.name in ("az/input/pool_start", "az/input/epoch_start")]
        assert got == [("az/input/pool_start", None),
                       ("az/input/epoch_start", False),
                       ("az/input/pool_start", None),
                       ("az/input/epoch_start", False),
                       ("az/input/epoch_start", True)]
        loader.close()

    def test_pool_and_epoch_start_close_inside_the_first_next(self):
        it = iter(self.loader())
        t0 = time.monotonic()
        next(it)
        got = since(t0)
        assert got["az/input/pool_start"] == got["az/input/epoch_start"] == 1
        it.close()

    def test_spilled_groups_are_counted_on_their_worker(self):
        from analytics_zoo_tpu.data import (DataSet, FnTransformer,
                                            ParallelLoader)

        ds = DataSet.from_arrays(x=np.zeros((8, 4), np.float32))
        big = FnTransformer(lambda s: {"x": np.zeros(4096, np.float32)})
        loader = ParallelLoader(ds.transform(big).batch(4), 2,
                                slot_bytes=8192)
        t0 = time.monotonic()
        assert len(list(loader)) == 2
        workers = [r for r in obs.stages(since=t0)
                   if r.name == "az/input/worker"]
        assert [r.attrs["spills"] for r in workers] == [1, 1]
        assert loader.spills == 2

    def test_the_worker_module_imports_no_jax_and_opens_no_stage(self):
        from analytics_zoo_tpu.data import parallel

        tree = ast.parse(inspect.getsource(parallel))
        imported = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported |= {a.name.split(".")[0] for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                imported.add((node.module or "").split(".")[0])
        assert "jax" not in imported
        worker = inspect.getsource(parallel._worker_main)
        assert "obs_span" not in worker
