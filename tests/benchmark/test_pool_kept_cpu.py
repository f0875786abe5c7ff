"""The train cell's window over epoch boundaries, at the toy trunk on the
CPU: the loader's pool is forked in set-up and kept, so no
``az/input/pool_start`` lies inside the window and every
``az/input/epoch_start`` of it carries ``kept`` true — which the reader
the benchmark has, ``stage_attr_share``, reads as 100 %.

``pool_kept.train`` is NOT in ``BENCHMARK.json``: an accepted test of the
harness counts the cell's ring metrics (PERF.md section 7 has the entry
and the file for the ``benchmark`` PR that adds it).  ``PARAMS`` are what
that file's would be."""

import os
import time

import pytest

import toy
from analytics_zoo_tpu.obs import span
from benchmarks import harness, program_spans
from benchmarks.drivers import ssd_train
from benchmarks.readers import stage_attr_share

CELL = "ssd300-train-b64"
PARAMS = {"span": "az/input/epoch_start", "attr": "kept"}


class Slice:
    """The harness's tracer without a profiler: a traced run's window
    lasts until the driver has stopped its slice."""

    running = False

    def start(self):
        self.running = True

    def stop(self):
        self.running = False


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """16 images at batch 4: an epoch of 4 steps, a window of 10 traced
    steps that two or three epochs begin in."""
    config = harness.load_json(harness.HERE, "configs", "ssd300-vgg16.json")
    config.update(resolution=toy.RES, num_priors=8732)
    traffic = dict(
        harness.load_json(harness.HERE, "traffic", "train-shapes-b64.json"),
        global_batch=4, images=16, shards=2, warm_steps=2,
        worker_processes=2, trace_after_steps=0, trace_steps=10)
    driver = ssd_train.Driver(config, traffic, 2 ** 31 + 34,
                              str(tmp_path_factory.mktemp("train")),
                              toy=toy.Toy)
    began = time.monotonic()
    driver.setup()
    loader = driver.feed.dataset
    window = driver.window(0.0, Slice())
    ctx = {"trace": None, "config": config, "traffic": traffic,
           "window": window, "counters": window["counters"], "peaks": None,
           "began": began}
    return ctx, loader


def test_no_pool_is_forked_inside_the_window_and_every_epoch_is_kept(
        trained):
    ctx, _ = trained
    assert ctx["window"]["traced_steps"] == 10
    inside = program_spans.ring(ctx)
    assert not [r for r in inside if r.name == program_spans.POOL_START]
    epochs = [r for r in inside if r.name == PARAMS["span"]]
    assert len(epochs) >= 2 and all(r.attrs == {"kept": True} for r in epochs)
    assert stage_attr_share.read(ctx, PARAMS) == 100.0
    # set-up forked three pools (the feed closes its first two epochs
    # after one batch and two), each for an epoch that was not kept
    before = [r for r in program_spans.ring(ctx, reach_back=True)
              if ctx["began"] <= r.t0 < ctx["window"]["t_open"]]
    assert ([r.attrs["kept"] for r in before if r.name == PARAMS["span"]]
            == [False] * 3)
    assert len([r for r in before
                if r.name == program_spans.POOL_START]) == 3


def test_the_cells_once_an_epoch_metrics_still_have_their_readings(trained):
    """A window that closes no pool has the workers' records of the epochs
    that ended in it; the pool that fed it was forked before it."""
    ctx, _ = trained
    got = harness.read_per_layer(harness.load_benchmark(), CELL, ctx)
    assert 0 < got["worker_busy_share.train"]["value"] <= 100
    assert got["pool_start_ms.train"]["value"] > 0
    workers = [r for r in program_spans.ring(ctx)
               if r.name == program_spans.WORKER]
    assert len({(r.attrs["epoch"], r.attrs["worker"])
                for r in workers}) == len(workers) >= 4


def test_what_the_run_leaves_alive_close_releases(trained):
    """The window's end closes the feed's open epoch, which stops the
    pool — unless the prefetch thread had already read that epoch to its
    end: then the pool is kept, and the feed exposes no ``close``, so it
    lives until the loader's own ``close()``, its collection or the
    interpreter's exit."""
    _, loader = trained
    pids = loader.worker_pids()
    assert len(pids) in (0, 2)
    loader.close()
    assert loader._pool is None and not loader.worker_pids()
    for pid in pids:                    # joined, so reaped: no such process
        with pytest.raises(ProcessLookupError):
            os.kill(pid, 0)


@pytest.mark.parametrize("flags, want", [
    ([True, True, True], 100.0),        # a ring of kept epochs
    ([False, False], 0.0),              # every epoch forked its pool
    ([False, True, True, True], 75.0),
    ([], None),                         # a program without the stage
])
def test_the_share_of_kept_epochs_as_the_reader_reads_it(flags, want):
    later = {"window": {"t_open": time.monotonic()}}
    for kept in flags:
        span.record_stage(PARAMS["span"], time.monotonic(), time.monotonic(),
                          kept=kept)
    # the parent of this stage wrote pool starts alone
    span.record_stage(program_spans.POOL_START, time.monotonic(),
                      time.monotonic(), workers=2)
    got = stage_attr_share.read(later, PARAMS)
    assert got == (want if want is None else pytest.approx(want))
